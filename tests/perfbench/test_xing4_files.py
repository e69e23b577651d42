"""What PR 53 adds to the benchmark for the Xing4.0-29B-A4B configuration:
the configuration file against the catalog row key by key, its cuts and
floors, the builder's arithmetic against the issue's numbers, the readers
of the new names (perfbench/hyper_shares.py) held to a synthetic program
and to the recorded dense capture, and the accepted latent readers on this
model's paths. Everything here asserts by membership, never by position or
count: the contract has every later cell and metric appended behind these.
(The cell's rehearsal through run.py is test_rehearse.py's, which runs
every cell of BENCHMARK.json.)"""
import importlib
import json
import os

import pytest

from perfbench_helpers import PERFBENCH

CELL = "xing4.0-29b-a4b.train-s8k"
CONFIG = "xing4.0-29b-a4b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
LAYER = "jit(step_fn)/jvp(view0)/net0_xing4layer%d"
HC = LAYER % 4 + "/net0_xing4layer4_hyperconnection1"
HC_BACK = HC.replace("jvp(view0)", "transpose(jvp(view0))")
MLA = LAYER % 4 + "/net0_xing4layer4_multiheadlatentattention0"
MOE = LAYER % 4 + "/net0_xing4layer4_sharedexpertmoe0"

#: an optimised module with an instruction for each thing the readers tell
#: apart; the Pallas calls carry the names a TPU compile gives them
TEXT = """HloModule jit_step_fn, is_scheduled=true

ENTRY %main.9 (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0), metadata={op_name="t_datas[0]"}
  %multiply.1 = f32[8,8]{1,0} multiply(%a, %a), metadata={op_name="HC/hc_pre/mul"}
  %add.2 = f32[8,8]{1,0} add(%a, %a), metadata={op_name="HC/hc_post/add"}
  %multiply.3 = f32[8,8]{1,0} multiply(%a, %a), metadata={op_name="HC_BACK/hc_post/mul"}
  %divide.4 = f32[8,8]{1,0} divide(%a, %a), metadata={op_name="HC/hc_sinkhorn/div"}
  %dot.5 = f32[8,8]{1,0} dot(%a, %a), metadata={op_name="HC/hc_maps/dot_general"}
  %jvp_flash_fwd_.6 = f32[8,8]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="MLA/flash_fwd/pallas_call"}
  %dot.7 = f32[8,8]{1,0} dot(%a, %a), metadata={op_name="MLA/mla_q_down/net0_xing4layer4_multiheadlatentattention0_dense0/dot_general"}
  %sort.8 = f32[8,8]{1,0} sort(%a), dimensions={0}, metadata={op_name="MOE/net0_xing4layer4_sharedexpertmoe0_moelayer0/router/top_k"}
  %multiply.9 = f32[8,8]{1,0} multiply(%a, %a), metadata={op_name="jit(step_fn)/optimizer/mul"}
  ROOT %copy.10 = f32[8,8]{1,0} copy(%a)
}
""".replace("HC_BACK", HC_BACK).replace("HC", HC).replace("MLA", MLA) \
    .replace("MOE", MOE)

_CALL = "custom-call(f32[8,8]{1,0} %a), custom_call_target=\"tpu_custom_call\""
_MUL = "multiply(f32[8,8]{1,0} %a, f32[8,8]{1,0} %a)"
_DOT = "dot(f32[8,8]{1,0} %a, f32[8,8]{1,0} %a)"
#: (event text as the profiler names it, seconds, the keys it is booked to)
EVENTS = [
    ("%multiply.1 = f32[8,8]{1,0} " + _MUL, 1.0, ("hyper_conn", "hyper_mix")),
    ("%add.2 = f32[8,8]{1,0} add(f32[8,8]{1,0} %a, f32[8,8]{1,0} %a)", 2.0,
     ("hyper_conn", "hyper_mix")),
    ("%multiply.3 = f32[8,8]{1,0} " + _MUL, 4.0, ("hyper_conn", "hyper_mix")),
    ("%divide.4 = f32[8,8]{1,0} divide(f32[8,8]{1,0} %a, f32[8,8]{1,0} %a)",
     8.0, ("hyper_conn",)),
    ("%dot.5 = f32[8,8]{1,0} " + _DOT, 16.0, ("hyper_conn",)),
    # the mixer's kernel and its low-rank query, the router, the optimizer,
    # an op the program lacks: none of the two
    ("%jvp_flash_fwd_.6 = f32[8,8]{1,0} " + _CALL, 32.0,
     ("latent_attn_block", "latent_flash")),
    ("%dot.7 = f32[8,8]{1,0} " + _DOT, 64.0, ("latent_attn_block",)),
    ("%sort.8 = f32[8,8]{1,0} sort(f32[8,8]{1,0} %a), dimensions={0}", 128.0,
     ("group_router",)),
    ("%multiply.9 = f32[8,8]{1,0} " + _MUL, 256.0, ()),
    ("%fusion.99 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %a), kind=kLoop, "
     "calls=%fused_computation.77", 512.0, ()),
]
BUSY = 1023.0
WANT = {"hyper_conn": 31.0, "hyper_mix": 7.0}
NEW_METRICS = ("hyper_conn_time_share", "hyper_conn_roofline")
JOINED = ("latent_attn_block_time_share", "latent_flash_time_share",
          "latent_flash_roofline", "shared_moe_block_time_share",
          "train_dispatch_ms_per_step", "optimizer_time_share",
          "update_fused_matmul_time_share", "unscoped_time_share")
#: the lists this cell could NOT join: tests/perfbench/test_expert_load_files
#: .py holds them by set equality and only a `benchmark` PR may edit it
#: (ROADMAP.md R0), and the Ling cell's group step is not in this program
NOT_JOINED = ("held_window_fill", "held_windows_per_pass",
              "expert_load_min_share", "held_expert_us_per_live_row",
              "group_router_time_share", "flash_roofline",
              "flash_bwd_time_share")


@pytest.fixture(scope="module")
def hyper_shares():
    """As the layer metrics import it (perfbench/ is on sys.path)."""
    return importlib.import_module("hyper_shares")


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
                   if r["name"] == "Xing4.0-29B-A4B")
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["source"] == config["source"] == row["source_url"]
    assert entry["reduced"] == config["reduced"]
    assert set(config["reduced"]) == {
        "num_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"}
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["reduced_from"][key] == value, key
            assert config[key] != value, key
        else:
            assert config[key] == value, key
    assert config["reduced_from"]["num_layers"] \
        == row["config"]["num_hidden_layers"] == config["num_hidden_layers"]
    assert len(entry["why"]) <= 200 and "drawn" in entry["why"]
    (listed,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert listed["chips"] == 1 and listed["config"] == CONFIG
    assert len(listed["why"]) <= 200 and "1/8" in listed["why"]


def test_the_cut_keeps_the_guides_floors(cell):
    _, config, _ = cell
    assert config["num_layers"] - config["first_k_dense_replace"] == 4
    assert config["first_k_dense_replace"] == 1      # leading layers once
    assert config["moe_layer_freq"] == 1             # the period: one layer
    assert config["n_routed_experts"] == 8
    assert config["first_held_expert"] + config["n_routed_experts"] \
        <= config["reduced_from"]["n_routed_experts"]
    assert 8 * config["vocab_size"] == config["reduced_from"]["vocab_size"]
    # EP 8: the ranks that share a layer hold all of its experts
    assert 8 * config["n_routed_experts"] \
        == config["reduced_from"]["n_routed_experts"]
    for said in ("EP 8", "DISTORTS", "8 ways", "pipeline stage", "1/8"):
        assert said in config["cut"], said
    for reading in ("hc_ends", "hc_sublayers", "hc_norm", "hc_eps",
                    "hc_maps", "hc_sinkhorn", "hc_mix", "hc_init", "mla_q",
                    "mla_rope", "router", "aux_losses", "init_head_scale",
                    "num_nextn_predict_layers"):
        assert len(config["assumed"][reading]) > 40, reading
    for reading in ("hc_ends", "hc_sublayers", "hc_norm", "hc_eps",
                    "hc_maps", "hc_sinkhorn", "hc_mix", "mla_q", "mla_rope",
                    "aux_losses"):
        assert "alternative" in config["assumed"][reading], reading
    init = config["hc_init"]
    assert set(init) == {"weight_std_units", "b_res_diagonal", "a"}
    assert len(init["a"]) == 3


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
    """759.3 M parameters held, 10.63 GB of arguments at 14 bytes each,
    348.3 M matmul weights a token always visits + 22.0 M of its held
    experts, 83.9 MFLOP of causal scores forward a layer at 8192, 179.2 kB
    of streams a sublayer, from the configuration's keys alone."""
    workload, config, builder = cell
    seq_len = workload["traffic"]["seq_len"]
    assert builder.matmul_params(config) == {
        "mla": 28409856, "hyper": 344064, "dense": 99090432,
        "experts": 11239424, "expert": 11010048, "head": 58720256}
    count = builder.parameter_count(config)
    assert count == 759346446 and abs(count - 759.3e6) < 0.1e6
    assert round(14 * count / 1e9, 2) == 10.63
    assert builder.always_visited_params(config) == 348258304
    assert round(builder.always_visited_params(config) / 1e6, 1) == 348.3
    assert builder.held_expert_flops_per_token(config) \
        == 6 * 11010048 * 4 // 2 == 6 * 22020096
    assert builder.latent_attention_flops_per_token(config, seq_len, 1) \
        == 5 * 83886080 == 5 * 2 * (192 + 128) * 4096 * 32
    assert builder.attention_flops_per_token(config, seq_len) \
        == 3 * 5 * 83886080
    # 2222 + 1258 MFLOP a token, 28.5 TFLOP a step
    flops = builder.model_flops_per_token(config, seq_len)
    assert flops == 6 * (348258304 + 22020096) + 3 * 5 * 83886080
    assert round(flops / 1e9, 2) == 3.48
    assert round(flops * seq_len / 1e12, 1) == 28.5
    assert builder.latent_attention_bytes_per_token(config) == 5 * 32 * 3848
    assert builder.hyper_connection_bytes_per_token(config) \
        == 10 * 179200 == 10 * (5 * 4 + 5) * 3584 * 2
    # 14.7 GB a step, 17.9 ms at the chip's 819 GB/s
    assert round(1792000 * seq_len / 819e9 * 1e3, 1) == 17.9
    # a whole model by the same formulas is the row's "29 B"
    whole = dict(config, n_routed_experts=64, vocab_size=131072,
                 first_k_dense_replace=2, num_layers=40)
    assert round(builder.parameter_count(whole) / 1e9, 1) == 29.5


# --------------------------------------------------------------- the readers
def test_seconds_by_block_on_the_synthetic_program(hyper_shares):
    program = hyper_shares.scopes.program_from_text(TEXT)
    assert hyper_shares.seconds_by_block(program, _ops()) == WANT
    assert WANT == {key: sum(s for _, s, keys in EVENTS if key in keys)
                    for key in hyper_shares.KEYS}
    # no hyper-connection ran: absent, not zero
    other = [e for e in EVENTS if "hyper_conn" not in e[2]]
    assert hyper_shares.seconds_by_block(program, _ops(other)) is None


def test_the_accepted_latent_readers_read_this_models_paths():
    """latent_shares.py goes by the stems `multiheadlatentattention` and
    `sharedexpertmoe` and the kernels' names, whatever layer class stands
    above them: the low-rank query's matmul and the kernel are the
    block's, the router is the expert block's."""
    latent_shares = importlib.import_module("latent_shares")
    program = latent_shares.scopes.program_from_text(TEXT)
    assert latent_shares.seconds_by_block(program, _ops()) == {
        key: sum(s for _, s, keys in EVENTS if key in keys)
        for key in latent_shares.KEYS}


def _context(harness, cell, seconds):
    workload, config, _ = cell
    traffic = workload["traffic"]
    return {"trace": {"busy_s": BUSY, "ops": _ops()}, "config": config,
            "workload": workload, "chips": 1, "steps": 3,
            "tokens_per_step": traffic["batch"] * traffic["seq_len"],
            "peaks": harness.load_json(PERFBENCH, "peaks.json")
            ["device_kinds"]["TPU v5 lite"], "hyper_seconds": seconds}


def test_the_two_metrics_read_the_names(harness, cell):
    context = _context(harness, cell, WANT)
    read = {name: harness.load_module("layer_metrics", name).compute(context)
            for name in NEW_METRICS}
    assert read["hyper_conn_time_share"] == pytest.approx(100 * 31 / BUSY)
    # 1.792 MB a token at 819 GB/s over the 7 s of the two mixes
    assert read["hyper_conn_roofline"] == pytest.approx(
        100 * 3 * 8192 * 1792000 / 819e9 / 7.0)
    # nothing to read: the line leaves both out
    empty = _context(harness, cell, None)
    assert all(harness.load_module("layer_metrics", name).compute(empty)
               is None for name in NEW_METRICS)
    for context in ({"trace": None},
                    {"trace": {"busy_s": 0.0, "ops": []}}):
        assert all(harness.load_module("layer_metrics", name).compute(
            dict(context)) is None for name in NEW_METRICS)
    # the maps ran and no mix is named: a share, no roofline
    context = _context(harness, cell, {"hyper_conn": 24.0, "hyper_mix": 0.0})
    assert harness.load_module("layer_metrics", "hyper_conn_roofline") \
        .compute(context) is None
    # a builder without the count (any parent's): no roofline, no error
    context = _context(harness, cell, WANT)
    context["config"] = dict(context["config"], builder="ling3_lm")
    assert harness.load_module("layer_metrics", "hyper_conn_roofline") \
        .compute(context) is None


def test_a_dense_capture_has_none_of_the_names(hyper_shares, reducer):
    """The GPT cell's recorded capture: its program names its scopes and
    none is a hyper-connection's, so the readers return None, as they must
    on every program of a parent."""
    capture = os.path.join(PERFBENCH, "trace", "scope_fixtures",
                           "cerebras-gpt-1.3b.train-s16k.xplane.pb.gz")
    reduced = reducer.reduce_capture(capture)
    programs = hyper_shares.scopes.programs_from_capture(
        hyper_shares.scopes.read_capture_bytes(capture))
    program = hyper_shares.scopes.pick_program(programs, reduced["ops"])
    assert program is not None
    assert hyper_shares.seconds_by_block(program, reduced["ops"]) is None


def test_the_new_entries_are_there_by_name(bench, harness):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["source"] == "device_trace"
        assert by_name[name]["moves"] == "train_tok_per_s"
        assert by_name[name]["unit"] == "%"
        assert by_name[name]["layer"] == "models"
        assert callable(harness.load_module("layer_metrics", name).compute)
    assert by_name["hyper_conn_roofline"]["better"] == "higher"
    assert by_name["hyper_conn_time_share"]["better"] == "lower"
    for name in JOINED:
        assert CELL in by_name[name]["workloads"], name
    for name in NOT_JOINED:
        assert CELL not in by_name[name]["workloads"], name
    assert {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())} \
        == set(NEW_METRICS) | set(JOINED)
