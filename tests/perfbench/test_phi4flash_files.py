"""What PR 34 adds to the benchmark for the Phi-4-mini-flash-reasoning
configuration: the builder's arithmetic against the issue's numbers, the
configuration file against the catalog row, the pattern rule at the
published depth, the readers of the new names (perfbench/sambay_shares.py)
held to a synthetic program and to the recorded dense capture, and the
names the tiny model's train step really carries."""
import importlib
import json
import os

import numpy as np
import pytest

from perfbench_helpers import PERFBENCH, ROOT

CELL = "phi-4-mini-flash.train-s16k"
CONFIG = "phi-4-mini-flash-reasoning"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
LAYER = "jit(step_fn)/jvp(view0)/net0_sambaylayer%d"
MAMBA = LAYER % 0 + "/net0_sambaylayer0_mamba1mixer0"
MAMBA_AGAIN = MAMBA.replace(
    "jvp(view0)", "transpose(jvp(view0))/jvp(view0)/checkpoint/"
    "rematted_computation")
WINDOW = LAYER % 1 + "/net0_sambaylayer1_differentialattention0"
GMU = LAYER % 4 + "/net0_sambaylayer4_gatedmemoryunit0/gmu"
CROSS = LAYER % 5 + "/net0_sambaylayer5_differentialattention0/" \
    "cross_attention"

#: an optimised module with an instruction for each thing the readers tell
#: apart; the Pallas calls carry their kernel's name, as a TPU compile
#: names them
TEXT = """HloModule jit_step_fn, is_scheduled=true

%fused_computation.1 (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %exp.1 = f32[8,8]{1,0} exponential(%p0), metadata={op_name="MAMBA/selective_scan/while/body/checkpoint/exp"}
  ROOT %multiply.1 = f32[8,8]{1,0} multiply(%exp.1, %p0), metadata={op_name="MAMBA/selective_scan/while/body/checkpoint/mul"}
}

ENTRY %main.9 (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0), metadata={op_name="t_datas[0]"}
  %fusion.1 = f32[8,8]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="MAMBA/selective_scan/while/body/checkpoint/mul"}
  %reduce.2 = f32[8]{0} reduce(%a, %a), dimensions={1}, metadata={op_name="MAMBA_AGAIN/selective_scan/reduce_sum"}
  %multiply.3 = f32[8,8]{1,0} multiply(%a, %a), metadata={op_name="MAMBA/mamba_gate/mul"}
  %flash_window_fwd.4 = f32[8,8]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="WINDOW/diff_attention/flash_window_fwd/pallas_call"}
  %flash_window_bwd.5 = f32[8,8]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="WINDOW/diff_attention/flash_window_bwd/pallas_call"}
  %flash_fwd.6 = f32[8,8]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="CROSS/diff_attention/flash_fwd/pallas_call"}
  %dot.7 = f32[8,8]{1,0} dot(%a, %a), metadata={op_name="GMU/net0_sambaylayer4_gatedmemoryunit0_dense0/dot_general"}
  %subtract.8 = f32[8,8]{1,0} subtract(%a, %a), metadata={op_name="WINDOW/diff_attention/sub"}
  %flash_fwd.9 = f32[8,8]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/jvp(view0)/net0_sambaylayer3/net0_sambaylayer3_differentialattention0/diff_attention/flash_fwd/pallas_call"}
  ROOT %copy.10 = f32[8,8]{1,0} copy(%a)
}
""".replace("MAMBA_AGAIN", MAMBA_AGAIN).replace("MAMBA", MAMBA) \
    .replace("WINDOW", WINDOW).replace("GMU", GMU).replace("CROSS", CROSS)

#: (event text as the profiler names it, seconds, the key it is booked to)
EVENTS = [
    ("%fusion.1 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %a), kind=kLoop, "
     "calls=%fused_computation.1", 1.0, "scan"),
    ("%reduce.2 = f32[8]{0} reduce(f32[8,8]{1,0} %a, f32[8,8]{1,0} %a), "
     "dimensions={1}", 2.0, "scan"),
    ("%multiply.3 = f32[8,8]{1,0} multiply(f32[8,8]{1,0} %a, "
     "f32[8,8]{1,0} %a)", 4.0, None),
    ("%flash_window_fwd.4 = f32[8,8]{1,0} custom-call(f32[8,8]{1,0} %a), "
     "custom_call_target=\"tpu_custom_call\"", 8.0, "window_kernels"),
    ("%flash_window_bwd.5 = f32[8,8]{1,0} custom-call(f32[8,8]{1,0} %a), "
     "custom_call_target=\"tpu_custom_call\"", 16.0, "window_kernels"),
    # the cross-decoder's full-causal kernel: under `cross_attention`
    ("%flash_fwd.6 = f32[8,8]{1,0} custom-call(f32[8,8]{1,0} %a), "
     "custom_call_target=\"tpu_custom_call\"", 32.0, "cross_decoder"),
    ("%dot.7 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %a, f32[8,8]{1,0} %a)", 64.0,
     "cross_decoder"),
    # the window layer's own element-wise work, F's kernel, an op the
    # program lacks: none of the three
    ("%subtract.8 = f32[8,8]{1,0} subtract(f32[8,8]{1,0} %a, "
     "f32[8,8]{1,0} %a)", 128.0, None),
    ("%flash_fwd.9 = f32[8,8]{1,0} custom-call(f32[8,8]{1,0} %a), "
     "custom_call_target=\"tpu_custom_call\"", 256.0, None),
    ("%fusion.99 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %a), kind=kLoop, "
     "calls=%fused_computation.77", 512.0, None),
]
BUSY = 1023.0
WANT = {"scan": 3.0, "window_kernels": 24.0, "cross_decoder": 96.0}
METRICS = ("selective_scan_time_share", "selective_scan_roofline",
           "window_flash_roofline", "window_attn_time_share",
           "cross_decoder_time_share")


@pytest.fixture(scope="module")
def sambay_shares():
    """As the layer metrics import it (perfbench/ is on sys.path)."""
    return importlib.import_module("sambay_shares")


@pytest.fixture(scope="module")
def cell(harness, bench):
    _, workload, config = harness.resolve(bench, CELL, rehearse=False)
    return workload, config, harness.load_module("builders",
                                                 config["builder"])


def _ops():
    return [[text, "other", seconds] for text, seconds, _ in EVENTS]


def test_seconds_by_name_on_the_synthetic_program(sambay_shares):
    program = sambay_shares.scopes.program_from_text(TEXT)
    assert sambay_shares.seconds_by_name(program, _ops()) == WANT
    assert WANT == {key: sum(s for _, s, k in EVENTS if k == key)
                    for key in sambay_shares.KEYS}
    # none of the names ran: absent, not zero
    other = [row for row, (_, _, key) in zip(_ops(), EVENTS) if key is None]
    assert sambay_shares.seconds_by_name(program, other) is None


def _context(harness, cell, seconds):
    workload, config, _ = cell
    traffic = workload["traffic"]
    return {"trace": {"busy_s": BUSY, "ops": _ops()}, "config": config,
            "workload": workload, "chips": 1, "steps": 3,
            "tokens_per_step": traffic["batch"] * traffic["seq_len"],
            "peaks": harness.load_json(PERFBENCH, "peaks.json")
            ["device_kinds"]["TPU v5 lite"], "sambay_seconds": seconds}


def test_the_five_metrics_read_the_names(harness, cell):
    context = _context(harness, cell, WANT)
    read = {name: harness.load_module("layer_metrics", name).compute(context)
            for name in METRICS}
    assert read["selective_scan_time_share"] == pytest.approx(100 * 3 / BUSY)
    assert read["window_attn_time_share"] == pytest.approx(100 * 24 / BUSY)
    assert read["cross_decoder_time_share"] == pytest.approx(100 * 96 / BUSY)
    # 2 Mamba layers x (forward 2 x (5120 + 160 + 32 + 5120), backward
    # twice the inputs + dy) = 104 704 B a token, at 819e9 B/s
    assert read["selective_scan_roofline"] == pytest.approx(
        100 * 3 * 16384 * 104704 / 819e9 / 3.0)
    # the live band of 16 384 queries under a window of 512, 3 sequences,
    # one S layer, 3 x 40 x (2 x 64 + 4 x 64) FLOP a (query, key)
    keys = (16384 - 512) * 512 + 512 * 513 // 2
    assert read["window_flash_roofline"] == pytest.approx(
        100 * 3 * keys * 46080 / 197e12 / 24.0)
    # nothing to read: the line leaves all five out
    empty = _context(harness, cell, None)
    assert all(harness.load_module("layer_metrics", name).compute(empty)
               is None for name in METRICS)
    for context in ({"trace": None},
                    {"trace": {"busy_s": 0.0, "ops": []}}):
        assert all(harness.load_module("layer_metrics", name).compute(
            dict(context)) is None for name in METRICS)


def test_a_dense_capture_has_none_of_the_names(sambay_shares, reducer):
    """The GPT cell's recorded capture: its program names its scopes and
    none is the scan's, the window kernels' or the cross-decoder's, so the
    readers return None, as they must on every program of a parent."""
    capture = os.path.join(PERFBENCH, "trace", "scope_fixtures",
                           "cerebras-gpt-1.3b.train-s16k.xplane.pb.gz")
    reduced = reducer.reduce_capture(capture)
    programs = sambay_shares.scopes.programs_from_capture(
        sambay_shares.scopes.read_capture_bytes(capture))
    program = sambay_shares.scopes.pick_program(programs, reduced["ops"])
    assert program is not None
    assert sambay_shares.seconds_by_name(program, reduced["ops"]) is None


#: accepted metrics whose readers find this cell's time by a name it
#: carries: a kernel's (`flash_bwd_dkvq` of F and C; every custom call,
#: over the builder's `attention_flops_per_token`) or a `TrainStep` scope
#: (`optimizer`; what lies under no scope). The by-scope classes of
#: trace/scopes.py that want a `Transformer*Layer` stem (attn, mlp,
#: norm_residual, embed_head_loss) cannot tell a `SambaYLayer` (PERF.md 7)
SHARED_METRICS = ("train_dispatch_ms_per_step", "flash_bwd_time_share",
                  "flash_roofline", "optimizer_time_share",
                  "update_fused_matmul_time_share", "unscoped_time_share")


def test_the_new_metrics_are_listed_for_the_new_cell(bench):
    """By membership, never by position: the contract has every later cell
    and metric appended behind these."""
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["source"] == "device_trace"
        assert by_name[name]["moves"] == "train_tok_per_s"
    (listed,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert listed["chips"] == 1


@pytest.mark.parametrize("name", SHARED_METRICS)
def test_accepted_metrics_that_can_read_the_cell_list_it(bench, name):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert CELL in by_name[name]["workloads"]


def test_builder_arithmetic_is_the_issues(cell):
    """697.09 M parameters held, 3.85 B whole from the published keys, and
    the FLOPs a token, from the configuration's keys alone."""
    workload, config, builder = cell
    seq_len = workload["traffic"]["seq_len"]
    assert builder.parameter_count(config) == 697094272
    whole = builder.parameter_count(
        config, "MS" * 8 + "MF" + "GC" * 7,
        config["reduced_from"]["vocab_size"])
    assert whole == 3852562944 and round(whole / 1e9, 2) == 3.85
    matmul = builder.matmul_params(config)
    other = builder.other_params(config)
    assert {c: matmul[c] + other[c] for c in "MSFGC"} == {
        "M": 119895040, "S": 98322304, "F": 98322304, "G": 104867840,
        "C": 91766144}
    assert sum(matmul[c] for c in config["layer_pattern_run"]) \
        + matmul["head"] == 696770560
    assert builder.attention_flops_per_token(config, seq_len) \
        == 2 * 3 * 7680 * seq_len == 754974720
    assert builder.window_flops_per_token(config) == 23592960
    assert builder.model_flops_per_token(config, seq_len) \
        == 6 * 696770560 + 754974720 + 23592960 == 4959191040
    assert builder.scan_bytes_per_token(config) \
        == 2 * (3 * 2 * (5120 + 160 + 32) + 2 * 2 * 5120) == 104704
    assert builder.window_keys(seq_len, 512) \
        == sum(min(i + 1, 512) for i in range(seq_len))
    assert builder.window_keys(100, 512) == 100 * 101 // 2
    assert 14 * builder.parameter_count(config) < 9.76e9


def test_the_model_that_is_built_has_the_counted_parameters(harness, bench):
    """The count is of the blocks the builder really builds: at the tiny
    preset every parameter of the model is one the arithmetic counts."""
    _, _, config = harness.resolve(bench, CELL, rehearse=True)
    builder = harness.load_module("builders", config["builder"])
    built = builder.build(config, 0, 64)
    held = sum(int(np.prod(p.shape))
               for p in built["model"].collect_params().values())
    assert held == builder.parameter_count(config)


def test_the_pattern_rule_gives_the_published_order(harness, bench):
    from incubator_mxnet_tpu.models.phi4flash import sambay_pattern
    _, _, config = harness.resolve(bench, CELL, rehearse=False)
    whole = sambay_pattern(config["num_hidden_layers"],
                           config["mb_per_layer"])
    assert whole == "MSMSMSMSMSMSMSMSMFGCGCGCGCGCGCGC"
    assert (whole[16], whole[17]) == ("M", "F")
    # the cut: one pair of each of the model's three kinds
    run = config["layer_pattern_run"]
    assert run == "MSMFGC" and len(run) == config["num_layers"]
    assert all(pair in whole for pair in ("MS", "MF", "GC"))


def test_the_configuration_is_the_catalog_row_but_for_what_it_lists(bench):
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog beside the guides here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Phi-4-mini-flash-reasoning")
    assert entry["source"] == config["source"] == row["source_url"]
    assert entry["reduced"] == config["reduced"] \
        == ["num_layers", "vocab_size"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["reduced_from"][key] == value != config[key]
        else:
            assert config[key] == value, key
    assert config["reduced_from"] == {"num_layers": row["layers"],
                                      "vocab_size": row["vocab_size"]}
    # the floors: a whole period and four layers more, an eighth of the rows
    assert config["num_layers"] >= config["mb_per_layer"] + 4
    assert config["vocab_size"] * 8 >= row["vocab_size"]
    for said in ("mamba", "attention_biases", "lambda_depth", "init",
                 "position_embedding"):
        assert config["assumed"][said]
    assert "DISTORTS" in config["cut"] and "8-stage pipeline" in config["cut"]


def test_the_tiny_steps_names_are_the_ones_the_readers_know(
        sambay_shares, harness, bench, monkeypatch):
    """Lower the rehearsal-sized train step here and read its own text: the
    scan's and the cross-decoder's scopes are there on forward, recomputed
    and backward ops, and the readers' keys find them (the window kernels
    are found by a name only a TPU compile gives them)."""
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
    program = sambay_shares.scopes.program_from_text(text)
    seen = {}
    for instr in program.instrs.values():
        parts, _ = sambay_shares.scopes.components(instr.op_name)
        kind = "again" if "rematted_computation" in parts else \
            "back" if "transpose(" in instr.op_name else "forward"
        for scope in (sambay_shares.SCAN_SCOPE,) \
                + sambay_shares.CROSS_SCOPES:
            if scope in parts:
                seen.setdefault(scope, set()).add(kind)
    assert set(seen) == {"selective_scan", "gmu", "cross_attention"}
    for where in seen.values():
        assert where == {"forward", "again", "back"}
    ops = [["%%%s = f32[1]{0} add()" % name, "other", 1.0]
           for name in program.instrs]
    seconds = sambay_shares.seconds_by_name(program, ops)
    assert seconds["scan"] > 0 and seconds["cross_decoder"] > 0
