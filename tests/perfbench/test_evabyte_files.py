"""What PR 45 adds to the benchmark for the EvaByte configuration: the
builder's arithmetic against the issue's numbers, the configuration file
against the catalog row, the readers of the new names
(perfbench/eva_shares.py) held to a synthetic program and to the recorded
dense capture, and the names the tiny model's train step really carries.
Everything here asserts by membership, never by position: the contract has
every later cell and metric appended behind these."""
import importlib
import json
import os

import numpy as np
import pytest

from perfbench_helpers import PERFBENCH, ROOT

CELL = "evabyte.train-long"
CONFIG = "evabyte"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
LAYER = "jit(step_fn)/jvp(view0)/net0_evabytelayer1"
ATT = LAYER + "/net0_evabytelayer1_evaattention0"
ATT_BACK = ATT.replace("jvp(view0)", "transpose(jvp(view0))/jvp(view0)/"
                       "checkpoint")
MLP = LAYER + "/net0_evabytelayer1_rowblockedswiglu0"

#: an optimised module with an instruction for each thing the readers tell
#: apart; the Pallas calls carry the names a TPU compile gives them
TEXT = """HloModule jit_step_fn, is_scheduled=true

%fused_computation.1 (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %exp.1 = f32[8,8]{1,0} exponential(%p0), metadata={op_name="ATT/checkpoint/eva_remote/exp"}
  ROOT %multiply.1 = f32[8,8]{1,0} multiply(%exp.1, %p0), metadata={op_name="ATT/checkpoint/eva_remote/reduce_sum"}
}

ENTRY %main.9 (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0), metadata={op_name="t_datas[0]"}
  %fusion.1 = f32[8,8]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="ATT/checkpoint/eva_remote/reduce_sum"}
  %flash_fwd.2 = f32[8,8]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="ATT/eva_local/flash_fwd/pallas_call"}
  %flash_bwd_dkvq.3 = f32[8,8]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="ATT_BACK/eva_local/flash_bwd_dkvq/pallas_call"}
  %multiply.4 = f32[8,8]{1,0} multiply(%a, %a), metadata={op_name="ATT/eva_pool/checkpoint/mul"}
  %dot.5 = f32[8,8]{1,0} dot(%a, %a), metadata={op_name="ATT_BACK/checkpoint/rematted_computation/eva_remote/dot_general"}
  %add.6 = f32[8,8]{1,0} add(%a, %a), metadata={op_name="ATT/checkpoint/eva_merge/add"}
  %dot.7 = f32[8,8]{1,0} dot(%a, %a), metadata={op_name="ATT/net0_evabytelayer1_evaattention0_dense0/dot_general"}
  %multiply.8 = f32[8,8]{1,0} multiply(%a, %a), metadata={op_name="ATT/rope/mul"}
  %dot.9 = f32[8,8]{1,0} dot(%a, %a), metadata={op_name="MLP/ffn/checkpoint/dot_general"}
  %dot.10 = f32[8,8]{1,0} dot(%a, %a), metadata={op_name="jit(step_fn)/jvp(loss)/multibyte_head/while/body/checkpoint/dot_general"}
  %add.11 = f32[8,8]{1,0} add(%a, %a), metadata={op_name="jit(step_fn)/jvp(view0)/net0_evabytelayer1/add"}
  %multiply.12 = f32[8,8]{1,0} multiply(%a, %a), metadata={op_name="jit(step_fn)/optimizer/mul"}
  ROOT %copy.13 = f32[8,8]{1,0} copy(%a)
}
""".replace("ATT_BACK", ATT_BACK).replace("ATT", ATT).replace("MLP", MLP)


def _call(name):
    return ("%%%s = f32[8,8]{1,0} custom-call(f32[8,8]{1,0} %%a), "
            "custom_call_target=\"tpu_custom_call\"" % name)


def _op(name, opcode):
    return ("%%%s = f32[8,8]{1,0} %s(f32[8,8]{1,0} %%a, f32[8,8]{1,0} %%a)"
            % (name, opcode))


#: (event text as the profiler names it, seconds, the keys it is booked to)
EVENTS = [
    ("%fusion.1 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %a), kind=kLoop, "
     "calls=%fused_computation.1", 1.0, ("eva_attn_block", "eva_remote")),
    (_call("flash_fwd.2"), 2.0, ("eva_attn_block", "eva_local")),
    (_call("flash_bwd_dkvq.3"), 4.0, ("eva_attn_block", "eva_local")),
    (_op("multiply.4", "multiply"), 8.0, ("eva_attn_block", "eva_pool")),
    (_op("dot.5", "dot"), 16.0, ("eva_attn_block", "eva_remote")),
    (_op("add.6", "add"), 32.0, ("eva_attn_block", "eva_merge")),
    (_op("dot.7", "dot"), 64.0, ("eva_attn_block",)),
    (_op("multiply.8", "multiply"), 128.0, ("eva_attn_block",)),
    # the MLP, the layer's residual add, the optimizer, an op the program
    # lacks: not the attention block; the head is its own key
    (_op("dot.9", "dot"), 256.0, ()),
    (_op("dot.10", "dot"), 512.0, ("multibyte_head",)),
    (_op("add.11", "add"), 1024.0, ()),
    (_op("multiply.12", "multiply"), 2048.0, ()),
    ("%fusion.99 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %a), kind=kLoop, "
     "calls=%fused_computation.77", 4096.0, ()),
]
BUSY = 8191.0
WANT = {"eva_attn_block": 255.0, "eva_pool": 8.0, "eva_local": 6.0,
        "eva_remote": 17.0, "eva_merge": 32.0, "multibyte_head": 512.0}
METRICS = ("eva_attn_block_time_share", "eva_local_time_share",
           "eva_remote_time_share", "eva_attention_roofline",
           "multibyte_head_time_share")


@pytest.fixture(scope="module")
def eva_shares():
    """As the layer metrics import it (perfbench/ is on sys.path)."""
    return importlib.import_module("eva_shares")


@pytest.fixture(scope="module")
def cell(harness, bench):
    _, workload, config = harness.resolve(bench, CELL, rehearse=False)
    return workload, config, harness.load_module("builders",
                                                 config["builder"])


def _ops(events=EVENTS):
    return [[text, "other", seconds] for text, seconds, _ in events]


def test_seconds_by_scope_on_the_synthetic_program(eva_shares):
    program = eva_shares.scopes.program_from_text(TEXT)
    assert eva_shares.seconds_by_scope(program, _ops()) == WANT
    assert WANT == {key: sum(s for _, s, keys in EVENTS if key in keys)
                    for key in eva_shares.KEYS}
    # nothing ran under the block or the head: absent, not zero
    other = [e for e in EVENTS if not e[2]]
    assert eva_shares.seconds_by_scope(program, _ops(other)) is None


def _context(harness, cell, seconds):
    workload, config, _ = cell
    traffic = workload["traffic"]
    return {"trace": {"busy_s": BUSY, "ops": _ops()}, "config": config,
            "workload": workload, "chips": 1, "steps": 3,
            "tokens_per_step": traffic["batch"] * traffic["seq_len"],
            "peaks": harness.load_json(PERFBENCH, "peaks.json")
            ["device_kinds"]["TPU v5 lite"], "eva_seconds": seconds}


def test_the_five_metrics_read_the_names(harness, cell):
    context = _context(harness, cell, WANT)
    read = {name: harness.load_module("layer_metrics", name).compute(context)
            for name in METRICS}
    assert read["eva_attn_block_time_share"] == pytest.approx(
        100 * 255.0 / BUSY)
    assert read["eva_local_time_share"] == pytest.approx(100 * 6.0 / BUSY)
    # what the summaries cost: pool + strips + merge
    assert read["eva_remote_time_share"] == pytest.approx(
        100 * (8.0 + 17.0 + 32.0) / BUSY)
    assert read["multibyte_head_time_share"] == pytest.approx(
        100 * 512.0 / BUSY)
    # a layer a sequence: the 6 matmuls nothing can do without (2 forward,
    # 4 backward) of 2 x 32 x 128 FLOP over the 16 785 408 exact and
    # 7 340 032 summary pairs at 197e12 FLOP/s, against 1.61 GB at
    # 819e9 B/s: the operations bound it; over all four scopes' time
    flops = 6 * 2 * 32 * 128 * (16785408 + 7340032)
    assert flops / 197e12 > 1610612736 / 819e9
    assert read["eva_attention_roofline"] == pytest.approx(
        100 * 3 * 4 * flops / 197e12 / (8.0 + 6.0 + 17.0 + 32.0))
    # nothing to read: the line leaves all five out
    empty = _context(harness, cell, None)
    assert all(harness.load_module("layer_metrics", name).compute(empty)
               is None for name in METRICS)
    for context in ({"trace": None},
                    {"trace": {"busy_s": 0.0, "ops": []}}):
        assert all(harness.load_module("layer_metrics", name).compute(
            dict(context)) is None for name in METRICS)


def test_a_dense_capture_has_none_of_the_names(eva_shares, reducer):
    """The GPT cell's recorded capture: its program names its scopes and
    none is this block's or this head's, so the readers return None, as
    they must on every program of a parent."""
    capture = os.path.join(PERFBENCH, "trace", "scope_fixtures",
                           "cerebras-gpt-1.3b.train-s16k.xplane.pb.gz")
    reduced = reducer.reduce_capture(capture)
    programs = eva_shares.scopes.programs_from_capture(
        eva_shares.scopes.read_capture_bytes(capture))
    program = eva_shares.scopes.pick_program(programs, reduced["ops"])
    assert program is not None
    assert eva_shares.seconds_by_scope(program, reduced["ops"]) is None


def test_the_new_entries_are_there_by_name(bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    layers = {"eva_attn_block_time_share": "models",
              "eva_local_time_share": "attention kernels",
              "eva_remote_time_share": "attention kernels",
              "eva_attention_roofline": "attention kernels",
              "multibyte_head_time_share": "models"}
    for name in METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["source"] == "device_trace"
        assert by_name[name]["moves"] == "train_tok_per_s"
        assert by_name[name]["unit"] == "%"
        assert by_name[name]["layer"] == layers[name]
        assert by_name[name]["better"] == (
            "higher" if name.endswith("roofline") else "lower")
    (listed,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert listed["chips"] == 1 and listed["config"] == CONFIG
    assert listed["traffic"] == "train-long" and len(listed["why"]) <= 200
    # the accepted lists the cell joins: the program's own span, which
    # every TrainStep carries, and the readers that find `TrainStep`'s
    # `optimizer` by name. NOT flash_roofline: it reads every custom call
    # against one causal count (PERF.md section 7)
    joined = ("train_dispatch_ms_per_step", "optimizer_time_share",
              "update_fused_matmul_time_share", "unscoped_time_share")
    for name in joined:
        assert CELL in by_name[name]["workloads"], name
    assert sum(CELL in m.get("workloads", ()) for m in bench["per_layer"]) \
        == len(METRICS) + len(joined)
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert len(entry["why"]) <= 200 and "drawn" in entry["why"]


def test_the_traffic_is_the_issues(cell):
    workload, _, _ = cell
    assert workload["driver"] == "train_step"
    assert workload["traffic"] == {
        "generator": "token_batches", "objective": "next_token", "batch": 1,
        "seq_len": 16384, "zipf_a": 1.0}
    check = workload["check"]
    assert check["sequences"] == 1 and check["tail_positions"] == 256
    assert 0 < check["outputs_rel_rms"] < 0.1
    assert 0 < check["loss_rel"] <= 1e-3
    assert 0.5 < check["update_agreement"] < 1


def test_builder_arithmetic_is_the_issues(cell):
    """821.4 M parameters held, 11.50 GB of arguments at 14 bytes each, and
    the FLOPs a token, from the configuration's keys alone."""
    workload, config, builder = cell
    seq_len = workload["traffic"]["seq_len"]
    assert builder.matmul_params(config) == {
        "attention": 67108864, "mlp": 135266304, "head": 10485760}
    assert builder.parameter_count(config) == 821366784
    assert 11.49e9 < 14 * builder.parameter_count(config) < 11.50e9
    # a query sees 1024.5 exact keys and 448 summaries on average at 16k
    exact, remote = builder.seen_pairs(config, seq_len)
    assert (exact, remote) == (16785408, 7340032)
    assert exact / seq_len == 1024.5 and remote / seq_len == 448
    assert builder.seen_pairs(config, 8192)[1] / 8192 == 192
    assert builder.seen_pairs(config, 32768)[1] / 32768 == 960
    assert builder.seen_pairs(config, 2048) == (2048 * 2049 // 2, 0)
    # MFLOP a token a layer, forward: projections 134.2, MLP 270.5, exact
    # pairs 16.8, summary pairs 7.3
    assert 2 * 67108864 == pytest.approx(134.2e6, rel=1e-3)
    assert 2 * 135266304 == pytest.approx(270.5e6, rel=1e-3)
    assert 2 * 2 * 32 * 128 * exact / seq_len == pytest.approx(16.8e6,
                                                               rel=2e-3)
    assert 2 * 2 * 32 * 128 * remote / seq_len == pytest.approx(7.34e6,
                                                                rel=1e-3)
    assert builder.eva_attention_flops(config, seq_len, 2) \
        == 2 * 2 * 32 * 128 * (exact + remote)
    assert builder.eva_attention_bytes(config, seq_len) == 1610612736
    # what doubles with the context: the summaries at S / 32 a query; the
    # exact part stays at (W + 1) / 2 keys a query
    assert builder.attention_flops_per_token(config, seq_len) \
        == 4 * 6 * 2 * 32 * 128 * seq_len // 32 == 100663296
    assert builder.model_flops_per_token(config, seq_len) == 4 * (
        6 * (67108864 + 135266304) + 6 * 32 * 128 * 2049) \
        + 6 * 10485760 + 100663296 == 5222006784


def test_the_roofline_counts_the_work_the_model_flops_count(cell, eva_shares):
    """One count of the attention's required work: what the roofline
    divides by a pair is what `model_flops_per_token` counts a seen key
    (the summaries at their asymptote), with no second forward in it."""
    _, config, builder = cell
    seq_len = 1 << 20
    a_summary = builder.attention_flops_per_token(config, seq_len) \
        / config["num_layers"] / (seq_len // 32)
    assert a_summary == eva_shares.MATMULS_A_PAIR * 2 * 32 * 128
    exact = (builder.model_flops_per_token(config, seq_len)
             - builder.model_flops_per_token(dict(config, window_size=-1),
                                             seq_len)) \
        / config["num_layers"] / (2049 / 2)
    assert exact == a_summary and eva_shares.MATMULS_A_PAIR == 6


def test_the_model_that_is_built_has_the_counted_parameters(harness, bench):
    """The count is of the blocks the builder really builds: at the tiny
    preset every parameter of the model is one the arithmetic counts."""
    _, _, config = harness.resolve(bench, CELL, rehearse=True)
    builder = harness.load_module("builders", config["builder"])
    built = builder.build(config, 0, 512)
    model = built["model"]
    held = sum(int(np.prod(p.shape))
               for p in model.collect_params().values())
    assert held == builder.parameter_count(config)
    assert model._remat and model.num_pred_heads == 8
    assert len(model.layers) == config["num_layers"]

    def rms(param):
        return float(np.sqrt(np.mean(np.square(
            param.data().asnumpy().astype("float32")))))

    units, hidden = config["hidden_size"], config["intermediate_size"]
    for layer in model.layers:
        assert rms(layer.mlp.down.weight) == pytest.approx(
            config["init_down_scale"] * (2 / (units + hidden)) ** 0.5,
            rel=0.1)
        assert rms(layer.mlp.up.weight) == pytest.approx(
            (2 / (units + hidden)) ** 0.5, rel=0.1)
        assert rms(layer.attn.phi) == pytest.approx(config["init_phi_std"],
                                                    rel=0.2)
        assert rms(layer.attn.mu) == pytest.approx(config["init_mu_std"],
                                                   rel=0.2)
        assert str(layer.attn.phi.dtype) == "float32"
        assert not np.any(layer.norm1.gamma.data().asnumpy()
                          .astype("float32"))


def test_the_configuration_is_the_catalog_row_but_for_what_it_lists(bench):
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog beside the guides here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "EvaByte")
    assert entry["source"] == config["source"] == row["source_url"]
    assert entry["reduced"] == config["reduced"] == ["num_layers"]
    # num_layers is a key of the file's own: every key of the row is kept
    for key, value in row["config"].items():
        assert config[key] == value, key
    assert config["reduced_from"] == {"num_layers": row["layers"]}
    assert config["num_hidden_layers"] == row["layers"] == 32
    # one stage of eight: four whole layers, the guide's floor; no width
    assert config["num_layers"] == 4 == row["layers"] // 8
    assert not any(word in key for key in config["reduced"]
                   for word in ("hidden", "intermediate", "dim", "head"))
    for said in ("pooling", "pooled_keys", "windows", "one_softmax",
                 "qk_norm", "rope", "pred_heads", "mixedp_attn", "init",
                 "num_chunks", "positions", "num_layers"):
        assert config["assumed"][said], said
    for said in ("pooling", "pooled_keys", "windows", "one_softmax"):
        assert "arXiv:2302.04542" in config["assumed"][said], said
    assert "DISTORTS" in config["cut"] and "PP 8" in config["cut"]
    assert "NOT SUPPORTED" in config["paper"]
    assert "float32 residual stream" in config["dtype"]


def test_the_tiny_steps_names_are_the_ones_the_readers_know(
        eva_shares, harness, bench, monkeypatch):
    """Lower the rehearsal-sized train step here and read its own text: the
    block's name, the op's four scopes and the head's are there on forward
    and backward ops, the kernels run (interpreted) under `eva_local`, and
    the readers' keys find them."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    from incubator_mxnet_tpu import gluon, jit, nd, telemetry
    _, workload, config = harness.resolve(bench, CELL, rehearse=True)
    builder = harness.load_module("builders", config["builder"])
    seq_len = workload["traffic"]["seq_len"]
    built = builder.build(config, 0, seq_len)
    trainer = gluon.Trainer(built["train_net"].collect_params(), "adam",
                            {"learning_rate": 1e-4, "multi_precision": True})
    step = jit.TrainStep(built["train_net"], built["loss"], trainer)
    calls = telemetry.REGISTRY.get("mxtpu_eva_attention_total")
    before = calls.value(local="streamed", remote="strips")
    tokens = nd.array(np.zeros((1, seq_len), "int32"))
    step(tokens, tokens)
    # a head of 128 in windows of 128: the streamed kernels, the strips
    assert calls.value(local="streamed", remote="strips") > before
    (text,) = [t for model_id, t in jit.compiled_train_programs()
               if model_id == step._model_id]
    program = eva_shares.scopes.program_from_text(text)
    seen = {}
    names = eva_shares.SCOPES + (eva_shares.BLOCK_STEM,
                                 eva_shares.HEAD_SCOPE)
    for instr in program.instrs.values():
        parts, _ = eva_shares.scopes.components(instr.op_name)
        kind = "back" if "transpose(" in instr.op_name else "forward"
        for name in names:
            if any(name in p for p in parts):
                seen.setdefault(name, set()).add(kind)
    assert set(seen) == set(names)
    for name, where in seen.items():
        assert where == {"forward", "back"}, name
    ops = [["%%%s = f32[1]{0} add()" % name, "other", 1.0]
           for name in program.instrs]
    seconds = eva_shares.seconds_by_scope(program, ops)
    assert all(seconds[key] > 0 for key in eva_shares.KEYS)
    assert sum(seconds[key] for key in eva_shares.SCOPES) \
        < seconds["eva_attn_block"]
