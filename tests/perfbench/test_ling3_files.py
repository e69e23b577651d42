"""What PR 48 adds to the benchmark for the Ling-3.0-flash configuration:
the configuration file against the catalog row key by key, its cuts and
floors, the builder's arithmetic against the issue's numbers, the readers
of the new names (perfbench/latent_shares.py) held to a synthetic program
and to the recorded dense capture. Everything here asserts by membership,
never by position: the contract has every later cell and metric appended
behind these. (The cell's rehearsal through run.py is test_rehearse.py's,
which runs every cell of BENCHMARK.json.)"""
import importlib
import json
import os

import pytest

from perfbench_helpers import PERFBENCH, ROOT

CELL = "ling-3.0-flash.train-s8k"
CONFIG = "ling-3.0-flash"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
LAYER = "jit(step_fn)/jvp(view0)/net0_solaropen2layer%d"
MLA = LAYER % 5 + "/net0_solaropen2layer5_multiheadlatentattention0"
MLA_BACK = MLA.replace("jvp(view0)", "transpose(jvp(view0))")
MOE = LAYER % 5 + "/net0_solaropen2layer5_sharedexpertmoe0"
KDA = LAYER % 1 + "/net0_solaropen2layer1_kimideltaattention0"

#: an optimised module with an instruction for each thing the readers tell
#: apart; the Pallas calls carry the names a TPU compile gives them
TEXT = """HloModule jit_step_fn, is_scheduled=true

ENTRY %main.9 (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0), metadata={op_name="t_datas[0]"}
  %jvp_flash_fwd_.1 = f32[8,8]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="MLA/flash_fwd/pallas_call"}
  %flash_bwd_dkvq.2 = f32[8,8]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="MLA_BACK/flash_bwd_dkvq/pallas_call"}
  %multiply.3 = f32[8,8]{1,0} multiply(%a, %a), metadata={op_name="MLA/mla_rope/mul"}
  %dot.4 = f32[8,8]{1,0} dot(%a, %a), metadata={op_name="MLA/mla_up/net0_solaropen2layer5_multiheadlatentattention0_dense2/dot_general"}
  %sort.5 = f32[8,8]{1,0} sort(%a), dimensions={0}, metadata={op_name="MOE/net0_solaropen2layer5_sharedexpertmoe0_moelayer0/router/top_k"}
  %compare.6 = f32[8,8]{1,0} multiply(%a, %a), metadata={op_name="MOE/net0_solaropen2layer5_sharedexpertmoe0_moelayer0/router/router_groups/gt"}
  %sort.7 = f32[8,8]{1,0} sort(%a), dimensions={0}, metadata={op_name="MOE/net0_solaropen2layer5_sharedexpertmoe0_moelayer0/moe_dispatch/sort"}
  %delta_rule_fwd.8 = f32[8,8]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="KDA/delta_rule/pallas_call"}
  %multiply.9 = f32[8,8]{1,0} multiply(%a, %a), metadata={op_name="jit(step_fn)/optimizer/mul"}
  ROOT %copy.10 = f32[8,8]{1,0} copy(%a)
}
""".replace("MLA_BACK", MLA_BACK).replace("MLA", MLA).replace("MOE", MOE) \
    .replace("KDA", KDA)

_CALL = "custom-call(f32[8,8]{1,0} %a), custom_call_target=\"tpu_custom_call\""
_MUL = "multiply(f32[8,8]{1,0} %a, f32[8,8]{1,0} %a)"
#: (event text as the profiler names it, seconds, the keys it is booked to)
EVENTS = [
    ("%jvp_flash_fwd_.1 = f32[8,8]{1,0} " + _CALL, 1.0,
     ("latent_attn_block", "latent_flash")),
    ("%flash_bwd_dkvq.2 = f32[8,8]{1,0} " + _CALL, 2.0,
     ("latent_attn_block", "latent_flash")),
    ("%multiply.3 = f32[8,8]{1,0} " + _MUL, 4.0, ("latent_attn_block",)),
    ("%dot.4 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %a, f32[8,8]{1,0} %a)", 8.0,
     ("latent_attn_block",)),
    ("%sort.5 = f32[8,8]{1,0} sort(f32[8,8]{1,0} %a), dimensions={0}", 16.0,
     ("group_router",)),
    ("%compare.6 = f32[8,8]{1,0} " + _MUL, 32.0, ("group_router",)),
    # the dispatch's sort, the delta rule's kernel (a custom call too), the
    # optimizer, an op the program lacks: none of the three
    ("%sort.7 = f32[8,8]{1,0} sort(f32[8,8]{1,0} %a), dimensions={0}", 64.0,
     ()),
    ("%delta_rule_fwd.8 = f32[8,8]{1,0} " + _CALL, 128.0, ()),
    ("%multiply.9 = f32[8,8]{1,0} " + _MUL, 256.0, ()),
    ("%fusion.99 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %a), kind=kLoop, "
     "calls=%fused_computation.77", 512.0, ()),
]
BUSY = 1023.0
WANT = {"latent_attn_block": 15.0, "latent_flash": 3.0, "group_router": 48.0}
NEW_METRICS = ("latent_attn_block_time_share", "latent_flash_time_share",
               "latent_flash_roofline", "group_router_time_share")
JOINED = ("delta_rule_time_share", "delta_rule_roofline",
          "linear_attn_block_time_share", "shared_moe_block_time_share",
          "train_dispatch_ms_per_step", "optimizer_time_share",
          "update_fused_matmul_time_share", "unscoped_time_share")


@pytest.fixture(scope="module")
def latent_shares():
    """As the layer metrics import it (perfbench/ is on sys.path)."""
    return importlib.import_module("latent_shares")


@pytest.fixture(scope="module")
def cell(harness, bench):
    _, workload, config = harness.resolve(bench, CELL, rehearse=False)
    return workload, config, harness.load_module("builders",
                                                 config["builder"])


def _ops(events=EVENTS):
    return [[text, "other", seconds] for text, seconds, _ in events]


# ------------------------------------------------------- the configuration
def test_the_file_holds_every_published_key(cell, bench):
    _, config, _ = cell
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ling-3.0-flash")
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["source"] == config["source"] == row["source_url"]
    assert entry["reduced"] == config["reduced"]
    assert set(config["reduced"]) == {
        "num_layers", "first_k_dense_replace", "num_experts", "vocab_size",
        "num_nextn_predict_layers"}
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["reduced_from"][key] == value, key
            assert config[key] != value, key
        else:
            assert config[key] == value, key
    assert config["reduced_from"]["num_layers"] \
        == row["config"]["num_hidden_layers"] == config["num_hidden_layers"]
    assert len(entry["why"]) <= 200
    (listed,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert listed["chips"] == 1 and listed["config"] == CONFIG
    assert len(listed["why"]) <= 200


def test_the_cut_keeps_the_guides_floors(cell):
    _, config, _ = cell
    pattern = config["layer_pattern_run"]
    period = config["layer_group_size"]
    assert len(pattern) == config["num_layers"] == period     # a whole one
    assert all((letter == "M") == ((i + 1) % period == 0)
               for i, letter in enumerate(pattern))
    assert len(pattern) - config["first_k_dense_replace"] >= 4
    assert config["num_experts"] >= 8
    assert config["first_held_expert"] + config["num_experts"] \
        <= config["reduced_from"]["num_experts"]
    assert 8 * config["vocab_size"] == config["reduced_from"]["vocab_size"]
    # one of the router's groups a host of 8 ranks of 8 experts
    assert config["reduced_from"]["num_experts"] \
        == config["n_group"] * 8 * config["num_experts"]
    assert "EP 64" in config["cut"] and "DISTORTS" in config["cut"]
    for reading in ("kda_full_rank", "kda_safe_gate", "kda_heads",
                    "kda_beta", "mla_qk_norm", "mla_rope", "router",
                    "aux_losses", "layer_pattern", "init_head_scale",
                    "mla_init"):
        assert len(config["assumed"][reading]) > 40, reading
    for reading in ("kda_full_rank", "kda_safe_gate", "mla_qk_norm"):
        assert "alternative" in config["assumed"][reading], reading


def test_the_traffic_is_the_issues(cell):
    workload, _, _ = cell
    assert workload["driver"] == "train_step"
    assert workload["traffic"] == {
        "generator": "token_batches", "objective": "next_token", "batch": 1,
        "seq_len": 8192, "zipf_a": 1.0}
    check = workload["check"]
    assert check["sequences"] == 1 and check["tail_positions"] == 256
    assert 0 < check["outputs_rel_rms"] < 0.1
    assert 0 < check["loss_rel"] <= 1e-3
    assert 0.5 < check["update_agreement"] < 1
    assert len(check["why"]) > 1000


def test_builder_arithmetic_is_the_issues(cell):
    """767.0 M parameters held, 10.74 GB of arguments at 14 bytes each,
    480.5 M matmul weights a token always visits, 83.9 MFLOP of causal
    scores forward at 8192, from the configuration's keys alone."""
    workload, config, builder = cell
    seq_len = workload["traffic"]["seq_len"]
    assert builder.matmul_params(config) == {
        "K": 62996480, "M": 31965184, "dense": 47185920, "experts": 7208960,
        "expert": 5898240, "head": 50298880}
    count = builder.parameter_count(config)
    assert count == 767009312 and abs(count - 767.0e6) < 0.1e6
    assert round(14 * count / 1e9, 2) == 10.74
    assert builder.always_visited_params(config) == 480477184
    assert builder.latent_attention_flops_per_token(config, seq_len, 1) \
        == 83886080 == 2 * (192 + 128) * 4096 * 32
    # the issue's 294 MFLOP counts the backward's recomputed scores too
    assert builder.latent_attention_flops_per_token(
        config, seq_len, 3.5) == 293601280
    assert builder.attention_flops_per_token(config, seq_len) \
        == 3 * 83886080
    assert builder.delta_rule_flops_per_token(config) \
        == 3 * 5 * 32 * 182954
    assert builder.held_expert_flops_per_token(config) \
        == 6 * 5898240 * 5 // 8
    assert builder.model_flops_per_token(config, seq_len) == 3244457664
    assert builder.latent_attention_bytes_per_token(config) == 32 * 3848
    # a whole model by the same formulas is the row's "~125 B"
    whole = dict(config, num_experts=512, vocab_size=157184,
                 first_k_dense_replace=2, layer_pattern_run="KKKKKM" * 7)
    assert round(builder.parameter_count(whole) / 1e9, 1) == 124.4


# --------------------------------------------------------------- the readers
def test_seconds_by_block_on_the_synthetic_program(latent_shares):
    program = latent_shares.scopes.program_from_text(TEXT)
    assert latent_shares.seconds_by_block(program, _ops()) == WANT
    assert WANT == {key: sum(s for _, s, keys in EVENTS if key in keys)
                    for key in latent_shares.KEYS}
    # no latent attention block ran: absent, not zero (another model's
    # SharedExpertMoE has a router too)
    other = [e for e in EVENTS if "latent_attn_block" not in e[2]]
    assert latent_shares.seconds_by_block(program, _ops(other)) is None


def _context(harness, cell, seconds):
    workload, config, _ = cell
    traffic = workload["traffic"]
    return {"trace": {"busy_s": BUSY, "ops": _ops()}, "config": config,
            "workload": workload, "chips": 1, "steps": 3,
            "tokens_per_step": traffic["batch"] * traffic["seq_len"],
            "peaks": harness.load_json(PERFBENCH, "peaks.json")
            ["device_kinds"]["TPU v5 lite"], "latent_seconds": seconds}


def test_the_four_metrics_read_the_names(harness, cell):
    context = _context(harness, cell, WANT)
    read = {name: harness.load_module("layer_metrics", name).compute(context)
            for name in NEW_METRICS}
    assert read["latent_attn_block_time_share"] \
        == pytest.approx(100 * 15 / BUSY)
    assert read["latent_flash_time_share"] == pytest.approx(100 * 3 / BUSY)
    assert read["group_router_time_share"] == pytest.approx(100 * 48 / BUSY)
    # 251.7 MFLOP a token at 197e12 FLOP/s: more than 123 kB at 819e9 B/s,
    # so the operations bound it
    assert 3 * 83886080 / 197e12 > 32 * 3848 / 819e9
    assert read["latent_flash_roofline"] == pytest.approx(
        100 * 3 * 8192 * 3 * 83886080 / 197e12 / 3.0)
    # nothing to read: the line leaves all four out
    empty = _context(harness, cell, None)
    assert all(harness.load_module("layer_metrics", name).compute(empty)
               is None for name in NEW_METRICS)
    for context in ({"trace": None},
                    {"trace": {"busy_s": 0.0, "ops": []}}):
        assert all(harness.load_module("layer_metrics", name).compute(
            dict(context)) is None for name in NEW_METRICS)


def test_a_dense_capture_has_none_of_the_names(latent_shares, reducer):
    """The GPT cell's recorded capture: its program names its scopes and
    none is a Ling block's, so the readers return None, as they must on
    every program of a parent."""
    capture = os.path.join(PERFBENCH, "trace", "scope_fixtures",
                           "cerebras-gpt-1.3b.train-s16k.xplane.pb.gz")
    reduced = reducer.reduce_capture(capture)
    programs = latent_shares.scopes.programs_from_capture(
        latent_shares.scopes.read_capture_bytes(capture))
    program = latent_shares.scopes.pick_program(programs, reduced["ops"])
    assert program is not None
    assert latent_shares.seconds_by_block(program, reduced["ops"]) is None


def test_the_new_entries_are_there_by_name(bench, harness):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    layers = {"latent_attn_block_time_share": "models",
              "latent_flash_time_share": "attention kernels",
              "latent_flash_roofline": "attention kernels",
              "group_router_time_share": "parallel"}
    for name in NEW_METRICS:
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["source"] == "device_trace"
        assert by_name[name]["moves"] == "train_tok_per_s"
        assert by_name[name]["unit"] == "%"
        assert by_name[name]["layer"] == layers[name]
        assert callable(harness.load_module("layer_metrics", name).compute)
    assert by_name["latent_flash_roofline"]["better"] == "higher"
    for name in JOINED:
        assert CELL in by_name[name]["workloads"], name
    # every custom call of this program is not an attention kernel: the
    # cell stays off the lists that read them all
    for name in ("flash_roofline", "flash_bwd_time_share"):
        assert CELL not in by_name[name]["workloads"], name
    assert sum(CELL in m.get("workloads", ()) for m in bench["per_layer"]) \
        == len(NEW_METRICS) + len(JOINED)


def test_the_solar_cells_entries_hold_where_this_cell_joined_their_lists(
        bench):
    """tests/perfbench/test_solar_open2_files.py::
    test_the_new_entries_are_there_by_name stands red since this cell
    joined four of its metrics' lists (it asserts the lists EQUAL the Solar
    cell alone; only a `benchmark` PR may edit that file). Everything else
    it asserts is held here, by membership, so that a regression of the
    Solar entries does not hide behind the red."""
    solar, config = "solar-open2.train-s8k", "solar-open2-250b"
    by_name = {m["name"]: m for m in bench["per_layer"]}
    layers = {"delta_rule_time_share": "scan ops",
              "delta_rule_roofline": "scan ops",
              "linear_attn_block_time_share": "models",
              "gated_attn_block_time_share": "models",
              "shared_moe_block_time_share": "parallel"}
    for name, layer in layers.items():
        entry = by_name[name]
        assert solar in entry["workloads"], name
        assert (entry["source"], entry["moves"], entry["unit"],
                entry["layer"]) == ("device_trace", "train_tok_per_s", "%",
                                    layer), name
    assert by_name["delta_rule_roofline"]["better"] == "higher"
    (listed,) = [w for w in bench["workloads"] if w["name"] == solar]
    assert (listed["chips"], listed["config"], listed["traffic"]) \
        == (1, config, "train-s8k")
    assert solar in by_name["train_dispatch_ms_per_step"]["workloads"]
    assert sum(solar in m.get("workloads", ()) for m in bench["per_layer"]) \
        == len(layers) + 1
