"""What PR 38 adds to the benchmark for the Solar-Open2-250B configuration:
the builder's arithmetic against the issue's numbers, the configuration
file against the catalog row, the readers of the new names
(perfbench/delta_shares.py) held to a synthetic program and to the recorded
dense capture, and the names the tiny model's train step really carries.
Everything here asserts by membership, never by position: the contract has
every later cell and metric appended behind these."""
import importlib
import json
import os

import numpy as np
import pytest

from perfbench_helpers import PERFBENCH, ROOT

CELL = "solar-open2.train-s8k"
CONFIG = "solar-open2-250b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
LAYER = "jit(step_fn)/jvp(view0)/net0_solaropen2layer%d"
KDA = LAYER % 1 + "/net0_solaropen2layer1_kimideltaattention0"
KDA_AGAIN = KDA.replace(
    "jvp(view0)", "transpose(jvp(view0))/jvp(view0)/checkpoint/"
    "rematted_computation")
GQA = LAYER % 0 + "/net0_solaropen2layer0_gatedgroupedqueryattention0"
MOE = LAYER % 1 + "/net0_solaropen2layer1_sharedexpertmoe0"

#: an optimised module with an instruction for each thing the readers tell
#: apart; the Pallas and grouped-matmul calls carry the names a TPU compile
#: gives them
TEXT = """HloModule jit_step_fn, is_scheduled=true

%fused_computation.1 (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %exp.1 = f32[8,8]{1,0} exponential(%p0), metadata={op_name="KDA/delta_rule/checkpoint/exp"}
  ROOT %multiply.1 = f32[8,8]{1,0} multiply(%exp.1, %p0), metadata={op_name="KDA/delta_rule/checkpoint/mul"}
}

ENTRY %main.9 (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0), metadata={op_name="t_datas[0]"}
  %fusion.1 = f32[8,8]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="KDA/delta_rule/checkpoint/mul"}
  %dot.2 = f32[8,8]{1,0} dot(%a, %a), metadata={op_name="KDA_AGAIN/delta_rule/while/body/dot_general"}
  %multiply.3 = f32[8,8]{1,0} multiply(%a, %a), metadata={op_name="KDA/kda_gate_norm/mul"}
  %dot.4 = f32[8,8]{1,0} dot(%a, %a), metadata={op_name="KDA/net0_solaropen2layer1_kimideltaattention0_dense0/dot_general"}
  %flash_fwd.5 = f32[8,8]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="GQA/flash_fwd/pallas_call"}
  %multiply.6 = f32[8,8]{1,0} multiply(%a, %a), metadata={op_name="GQA/gqa_gate/mul"}
  %ragged-dot-none.7 = f32[8,8]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %dot.8 = f32[8,8]{1,0} dot(%a, %a), metadata={op_name="MOE/net0_solaropen2layer1_sharedexpertmoe0_swiglu0/ffn/net0_solaropen2layer1_sharedexpertmoe0_swiglu0_dense0/dot_general"}
  %sort.9 = f32[8,8]{1,0} sort(%a), dimensions={0}, metadata={op_name="MOE/net0_solaropen2layer1_sharedexpertmoe0_moelayer0/moe_dispatch/sort"}
  %add.10 = f32[8,8]{1,0} add(%a, %a), metadata={op_name="jit(step_fn)/jvp(view0)/net0_solaropen2layer1/add"}
  %multiply.11 = f32[8,8]{1,0} multiply(%a, %a), metadata={op_name="jit(step_fn)/optimizer/mul"}
  ROOT %copy.12 = f32[8,8]{1,0} copy(%a)
}
""".replace("KDA_AGAIN", KDA_AGAIN).replace("KDA", KDA) \
    .replace("GQA", GQA).replace("MOE", MOE)

#: (event text as the profiler names it, seconds, the keys it is booked to)
EVENTS = [
    ("%fusion.1 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %a), kind=kLoop, "
     "calls=%fused_computation.1", 1.0, ("linear_attn_block", "delta_rule")),
    ("%dot.2 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %a, f32[8,8]{1,0} %a)", 2.0,
     ("linear_attn_block", "delta_rule")),
    ("%multiply.3 = f32[8,8]{1,0} multiply(f32[8,8]{1,0} %a, "
     "f32[8,8]{1,0} %a)", 4.0, ("linear_attn_block",)),
    ("%dot.4 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %a, f32[8,8]{1,0} %a)", 8.0,
     ("linear_attn_block",)),
    ("%flash_fwd.5 = f32[8,8]{1,0} custom-call(f32[8,8]{1,0} %a), "
     "custom_call_target=\"tpu_custom_call\"", 16.0, ("gated_attn_block",)),
    ("%multiply.6 = f32[8,8]{1,0} multiply(f32[8,8]{1,0} %a, "
     "f32[8,8]{1,0} %a)", 32.0, ("gated_attn_block",)),
    ("%ragged-dot-none.7 = f32[8,8]{1,0} custom-call(f32[8,8]{1,0} %a), "
     "custom_call_target=\"tpu_custom_call\"", 64.0, ("shared_moe_block",)),
    ("%dot.8 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %a, f32[8,8]{1,0} %a)", 128.0,
     ("shared_moe_block",)),
    ("%sort.9 = f32[8,8]{1,0} sort(f32[8,8]{1,0} %a), dimensions={0}", 256.0,
     ("shared_moe_block",)),
    # the layer's residual add, the optimizer, an op the program lacks:
    # none of the blocks
    ("%add.10 = f32[8,8]{1,0} add(f32[8,8]{1,0} %a, f32[8,8]{1,0} %a)",
     512.0, ()),
    ("%multiply.11 = f32[8,8]{1,0} multiply(f32[8,8]{1,0} %a, "
     "f32[8,8]{1,0} %a)", 1024.0, ()),
    ("%fusion.99 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %a), kind=kLoop, "
     "calls=%fused_computation.77", 2048.0, ()),
]
BUSY = 4095.0
WANT = {"linear_attn_block": 15.0, "delta_rule": 3.0,
        "gated_attn_block": 48.0, "shared_moe_block": 448.0}
METRICS = ("delta_rule_time_share", "delta_rule_roofline",
           "linear_attn_block_time_share", "gated_attn_block_time_share",
           "shared_moe_block_time_share")


@pytest.fixture(scope="module")
def delta_shares():
    """As the layer metrics import it (perfbench/ is on sys.path)."""
    return importlib.import_module("delta_shares")


@pytest.fixture(scope="module")
def cell(harness, bench):
    _, workload, config = harness.resolve(bench, CELL, rehearse=False)
    return workload, config, harness.load_module("builders",
                                                 config["builder"])


def _ops(events=EVENTS):
    return [[text, "other", seconds] for text, seconds, _ in events]


def test_seconds_by_block_on_the_synthetic_program(delta_shares):
    program = delta_shares.scopes.program_from_text(TEXT)
    assert delta_shares.seconds_by_block(program, _ops()) == WANT
    assert WANT == {key: sum(s for _, s, keys in EVENTS if key in keys)
                    for key in delta_shares.KEYS}
    # none of the blocks ran (ragged dots alone are another model's
    # MoELayer): absent, not zero
    other = [e for e in EVENTS if not e[2] or "ragged" in e[0]]
    assert delta_shares.seconds_by_block(program, _ops(other)) is None


def _context(harness, cell, seconds):
    workload, config, _ = cell
    traffic = workload["traffic"]
    return {"trace": {"busy_s": BUSY, "ops": _ops()}, "config": config,
            "workload": workload, "chips": 1, "steps": 3,
            "tokens_per_step": traffic["batch"] * traffic["seq_len"],
            "peaks": harness.load_json(PERFBENCH, "peaks.json")
            ["device_kinds"]["TPU v5 lite"], "delta_seconds": seconds}


def test_the_five_metrics_read_the_names(harness, cell):
    context = _context(harness, cell, WANT)
    read = {name: harness.load_module("layer_metrics", name).compute(context)
            for name in METRICS}
    assert read["delta_rule_time_share"] == pytest.approx(100 * 3 / BUSY)
    assert read["linear_attn_block_time_share"] \
        == pytest.approx(100 * 15 / BUSY)
    assert read["gated_attn_block_time_share"] \
        == pytest.approx(100 * 48 / BUSY)
    assert read["shared_moe_block_time_share"] \
        == pytest.approx(100 * 448 / BUSY)
    # 3 K layers x 8 heads x (2 forwards of 12 d + 4, a backward of
    # 22 d + 8) = 141 696 B a token at 819e9 B/s: more than the 4 passes'
    # 17.56 MFLOP at 197e12 FLOP/s, so the bytes bound it
    assert 141696 / 819e9 > 17563584 / 197e12
    assert read["delta_rule_roofline"] == pytest.approx(
        100 * 3 * 8192 * 141696 / 819e9 / 3.0)
    # nothing to read: the line leaves all five out
    empty = _context(harness, cell, None)
    assert all(harness.load_module("layer_metrics", name).compute(empty)
               is None for name in METRICS)
    for context in ({"trace": None},
                    {"trace": {"busy_s": 0.0, "ops": []}}):
        assert all(harness.load_module("layer_metrics", name).compute(
            dict(context)) is None for name in METRICS)


def test_a_dense_capture_has_none_of_the_names(delta_shares, reducer):
    """The GPT cell's recorded capture: its program names its scopes and
    none is a Solar block's, so the readers return None, as they must on
    every program of a parent."""
    capture = os.path.join(PERFBENCH, "trace", "scope_fixtures",
                           "cerebras-gpt-1.3b.train-s16k.xplane.pb.gz")
    reduced = reducer.reduce_capture(capture)
    programs = delta_shares.scopes.programs_from_capture(
        delta_shares.scopes.read_capture_bytes(capture))
    program = delta_shares.scopes.pick_program(programs, reduced["ops"])
    assert program is not None
    assert delta_shares.seconds_by_block(program, reduced["ops"]) is None


def test_the_new_entries_are_there_by_name(bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    layers = {"delta_rule_time_share": "scan ops",
              "delta_rule_roofline": "scan ops",
              "linear_attn_block_time_share": "models",
              "gated_attn_block_time_share": "models",
              "shared_moe_block_time_share": "parallel"}
    for name in METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["source"] == "device_trace"
        assert by_name[name]["moves"] == "train_tok_per_s"
        assert by_name[name]["unit"] == "%"
        assert by_name[name]["layer"] == layers[name]
    assert by_name["delta_rule_roofline"]["better"] == "higher"
    (listed,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert listed["chips"] == 1 and listed["config"] == CONFIG
    assert listed["traffic"] == "train-s8k"
    # the one accepted list the cell joins: the program's own span, which
    # every TrainStep carries
    assert CELL in by_name["train_dispatch_ms_per_step"]["workloads"]
    assert sum(CELL in m.get("workloads", ()) for m in bench["per_layer"]) \
        == len(METRICS) + 1
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= len(bench["workloads"]) // 4


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


def test_builder_arithmetic_is_the_issues(cell):
    """840.9 M parameters held, 11.77 GB of arguments at 14 bytes each, and
    the FLOPs a token, from the configuration's keys alone."""
    workload, config, builder = cell
    seq_len = workload["traffic"]["seq_len"]
    matmul = builder.matmul_params(config)
    assert matmul == {"K": 18120704, "G": 13631488, "experts": 17039360,
                      "expert": 15728640, "head": 100663296}
    assert builder.parameter_count(config) == 840872600
    assert 11.77e9 < 14 * builder.parameter_count(config) < 11.78e9
    # whole mixers would not fit: 8 x the heads
    whole = 3 * (matmul["K"] - 4096 * 256) * 8 + matmul["G"] * 8
    assert 14 * (builder.parameter_count(config) + whole
                 - 3 * matmul["K"] - matmul["G"]) > 16.9e9
    assert builder.attention_flops_per_token(config, seq_len) \
        == 6 * seq_len * 8 * 128 == 50331648
    assert builder.delta_rule_forward_flops(64, 128, 128) \
        == 10 * 64 * 128 + 6 * 128 * 128 + 2 * 64 * 64 // 3 == 182954
    assert builder.delta_rule_flops_per_token(config) \
        == 3 * 3 * 8 * 182954 == 13172688
    assert builder.delta_rule_flops_per_token(config, passes=4) == 17563584
    assert builder.delta_rule_bytes_per_token(config) \
        == 3 * 8 * (2 * (12 * 128 + 4) + 22 * 128 + 8) == 141696
    # 8 choices a token land on the 8 of 320 held a fifth of a time each
    assert builder.held_expert_flops_per_token(config) \
        == 6 * 4 * 15728640 // 5 == 75497472
    visited = matmul["G"] + 3 * matmul["K"] + 4 * matmul["experts"] \
        + matmul["head"]
    assert visited == 236814336
    assert builder.model_flops_per_token(config, seq_len) \
        == 6 * visited + 75497472 + 13172688 + 50331648 == 1559887824
    # the nominal chunk is the benchmark's, not the program's
    assert builder.NOMINAL_CHUNK == 64
    assert builder.delta_rule_flops_per_token(
        dict(config, delta_rule_chunk=16)) == 13172688


def test_the_model_that_is_built_has_the_counted_parameters(harness, bench):
    """The count is of the blocks the builder really builds: at the tiny
    preset every parameter of the model is one the arithmetic counts, and
    the routers come out of the build balanced."""
    _, _, config = harness.resolve(bench, CELL, rehearse=True)
    builder = harness.load_module("builders", config["builder"])
    built = builder.build(config, 0, 256)
    held = sum(int(np.prod(p.shape))
               for p in built["model"].collect_params().values())
    assert held == builder.parameter_count(config)
    model = built["model"]
    assert model.pattern == "GKKK"
    assert all(np.abs(l.experts.moe.router_bias.data().asnumpy()).max() > 0
               for l in model.layers)
    # the builder's own rule, Nemotron's at this router's slope
    nemotron = harness.load_module("builders", "nemotron_h_lm")
    assert builder.BALANCE_ROUNDS == nemotron.BALANCE_ROUNDS
    assert builder.BALANCE_STEP < nemotron.BALANCE_STEP
    # and the rule goes on in every train step, a round of the builder's a
    # step
    assert config["router_bias_rate"] == pytest.approx(
        builder.BALANCE_STEP, rel=1e-5)
    assert all(l.experts.moe._bias_rate == config["router_bias_rate"]
               for l in model.layers)


def test_the_configuration_is_the_catalog_row_but_for_what_it_lists(bench):
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog beside the guides here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Solar-Open2-250B")
    assert entry["source"] == config["source"] == row["source_url"]
    assert entry["reduced"] == config["reduced"] == [
        "num_layers", "n_routed_experts", "vocab_size", "mixer_shards"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["reduced_from"][key] == value != config[key]
        else:
            assert config[key] == value, key
    assert config["reduced_from"] == {
        "num_layers": row["layers"], "n_routed_experts": 320,
        "vocab_size": row["vocab_size"], "mixer_shards": 1}
    assert config["num_hidden_layers"] == row["layers"] == 48
    # the floors: one whole period of four layers, 8 experts, an eighth of
    # the rows; the run is layers 0-3 as published
    run = config["layer_pattern_run"]
    whole = "".join("G" if i in config["gqa_layers"] else "K"
                    for i in range(48))
    assert whole.startswith(run) and len(run) == config["num_layers"] == 4
    assert whole.count("G") == 12 and run.count("G") * 12 == len(run) * 3
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= row["vocab_size"]
    for said in ("router", "gqa_gate", "low_rank", "init", "conv_activation",
                 "q_scale", "qk_norm", "l2_eps", "positions", "num_layers",
                 "mixer_shards", "n_routed_experts", "delta_rule_chunk"):
        assert config["assumed"][said], said
    assert "DISTORTS" in config["cut"] and "TP 8 x EP 40" in config["cut"]
    assert len(entry["why"]) <= 200 and "drawn" in entry["why"]


def test_the_tiny_steps_names_are_the_ones_the_readers_know(
        delta_shares, harness, bench, monkeypatch):
    """Lower the rehearsal-sized train step here and read its own text: the
    op's scope and the three blocks' names are there on forward, recomputed
    and backward ops, and the readers' keys find them."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    from incubator_mxnet_tpu import gluon, jit, nd
    _, workload, config = harness.resolve(bench, CELL, rehearse=True)
    builder = harness.load_module("builders", config["builder"])
    built = builder.build(config, 0, 128)
    trainer = gluon.Trainer(built["train_net"].collect_params(), "adam",
                            {"learning_rate": 1e-4, "multi_precision": True})
    step = jit.TrainStep(built["train_net"], built["loss"], trainer)
    tokens = nd.array(np.zeros((1, 128), "int32"))
    step(tokens, tokens)
    (text,) = [t for model_id, t in jit.compiled_train_programs()
               if model_id == step._model_id]
    program = delta_shares.scopes.program_from_text(text)
    seen = {}
    names = (delta_shares.RULE_SCOPE, delta_shares.KDA_STEM,
             delta_shares.GQA_STEM, delta_shares.MOE_STEM)
    for instr in program.instrs.values():
        parts, _ = delta_shares.scopes.components(instr.op_name)
        kind = "again" if "rematted_computation" in parts else \
            "back" if "transpose(" in instr.op_name else "forward"
        for name in names:
            if any(name in p for p in parts):
                seen.setdefault(name, set()).add(kind)
    assert set(seen) == set(names)
    for where in seen.values():
        assert where == {"forward", "again", "back"}
    ops = [["%%%s = f32[1]{0} add()" % name, "other", 1.0]
           for name in program.instrs]
    seconds = delta_shares.seconds_by_block(program, ops)
    assert all(seconds[key] > 0 for key in delta_shares.KEYS)
    assert seconds["delta_rule"] < seconds["linear_attn_block"]
