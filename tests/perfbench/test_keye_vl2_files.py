"""What PR 41 adds to the benchmark for the Keye-VL-2.0-30B-A3B
configuration: the builder's arithmetic against the issue's numbers, the
configuration file against the catalog row, the readers of the new names
(perfbench/sparse_shares.py) held to a synthetic program and to the recorded
dense capture, and the names the tiny model's train step really carries.
Everything here asserts by membership, never by position: the contract has
every later cell and metric appended behind these."""
import importlib
import json
import os

import numpy as np
import pytest

from perfbench_helpers import PERFBENCH, ROOT

CELL = "keye-vl-2.0.train-s16k"
CONFIG = "keye-vl-2.0-30b-a3b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
LAYER = "jit(step_fn)/jvp(view0)/net0_keyevl2layer%d"
ATT = LAYER % 1 + "/net0_keyevl2layer1_sparsegroupedqueryattention0"
ATT_BACK = ATT.replace("jvp(view0)", "transpose(jvp(view0))/jvp(view0)/"
                       "checkpoint")
MOE = LAYER % 1 + "/net0_keyevl2layer1_moelayer0"

#: an optimised module with an instruction for each thing the readers tell
#: apart; the Pallas calls carry the names a TPU compile gives them
TEXT = """HloModule jit_step_fn, is_scheduled=true

%fused_computation.1 (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %compare.1 = f32[8,8]{1,0} exponential(%p0), metadata={op_name="ATT/closed_call/while/body/closed_call/topk_select/while/body/ge"}
  ROOT %multiply.1 = f32[8,8]{1,0} multiply(%compare.1, %p0), metadata={op_name="ATT/closed_call/while/body/closed_call/topk_select/while/body/reduce_sum"}
}

ENTRY %main.9 (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0), metadata={op_name="t_datas[0]"}
  %fusion.1 = f32[8,8]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="ATT/closed_call/while/body/closed_call/topk_select/while/body/reduce_sum"}
  %sparse_index_fwd.2 = f32[8,8]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="ATT/closed_call/while/body/closed_call/indexer/sparse_index_fwd/pallas_call"}
  %sparse_index_bwd.3 = f32[8,8]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="ATT_BACK/while/body/closed_call/indexer/sparse_index_bwd/pallas_call"}
  %dot.4 = f32[8,8]{1,0} dot(%a, %a), metadata={op_name="ATT/indexer/net0_keyevl2layer1_sparsegroupedqueryattention0_dense4/dot_general"}
  %sparse_flash_fwd.5 = f32[8,8]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="ATT/while/body/closed_call/sparse_attention/sparse_flash_fwd/pallas_call"}
  %sparse_flash_bwd.6 = f32[8,8]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="ATT_BACK/while/body/closed_call/sparse_attention/sparse_flash_bwd/pallas_call"}
  %dot.7 = f32[8,8]{1,0} dot(%a, %a), metadata={op_name="ATT/net0_keyevl2layer1_sparsegroupedqueryattention0_dense0/dot_general"}
  %multiply.8 = f32[8,8]{1,0} multiply(%a, %a), metadata={op_name="ATT/rope/mul"}
  %ragged-dot-none.9 = f32[8,8]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %sort.10 = f32[8,8]{1,0} sort(%a), dimensions={0}, metadata={op_name="MOE/moe_dispatch/sort"}
  %add.11 = f32[8,8]{1,0} add(%a, %a), metadata={op_name="jit(step_fn)/jvp(view0)/net0_keyevl2layer1/add"}
  %multiply.12 = f32[8,8]{1,0} multiply(%a, %a), metadata={op_name="jit(step_fn)/optimizer/mul"}
  ROOT %copy.13 = f32[8,8]{1,0} copy(%a)
}
""".replace("ATT_BACK", ATT_BACK).replace("ATT", ATT).replace("MOE", MOE)


def _call(name):
    return ("%%%s = f32[8,8]{1,0} custom-call(f32[8,8]{1,0} %%a), "
            "custom_call_target=\"tpu_custom_call\"" % name)


def _op(name, opcode):
    return ("%%%s = f32[8,8]{1,0} %s(f32[8,8]{1,0} %%a, f32[8,8]{1,0} %%a)"
            % (name, opcode))


#: (event text as the profiler names it, seconds, the keys it is booked to)
EVENTS = [
    ("%fusion.1 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %a), kind=kLoop, "
     "calls=%fused_computation.1", 1.0, ("sparse_attn_block", "topk_select")),
    (_call("sparse_index_fwd.2"), 2.0, ("sparse_attn_block", "indexer")),
    (_call("sparse_index_bwd.3"), 4.0, ("sparse_attn_block", "indexer")),
    (_op("dot.4", "dot"), 8.0, ("sparse_attn_block", "indexer")),
    (_call("sparse_flash_fwd.5"), 16.0,
     ("sparse_attn_block", "sparse_attention")),
    (_call("sparse_flash_bwd.6"), 32.0,
     ("sparse_attn_block", "sparse_attention")),
    (_op("dot.7", "dot"), 64.0, ("sparse_attn_block",)),
    (_op("multiply.8", "multiply"), 128.0, ("sparse_attn_block",)),
    # the experts, the layer's residual add, the optimizer, an op the
    # program lacks: not the attention block
    (_call("ragged-dot-none.9"), 256.0, ()),
    ("%sort.10 = f32[8,8]{1,0} sort(f32[8,8]{1,0} %a), dimensions={0}",
     512.0, ()),
    (_op("add.11", "add"), 1024.0, ()),
    (_op("multiply.12", "multiply"), 2048.0, ()),
    ("%fusion.99 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %a), kind=kLoop, "
     "calls=%fused_computation.77", 4096.0, ()),
]
BUSY = 8191.0
WANT = {"sparse_attn_block": 255.0, "indexer": 14.0, "topk_select": 1.0,
        "sparse_attention": 48.0}
METRICS = ("sparse_attn_block_time_share", "indexer_time_share",
           "topk_select_time_share", "sparse_attention_time_share",
           "sparse_attention_roofline")


@pytest.fixture(scope="module")
def sparse_shares():
    """As the layer metrics import it (perfbench/ is on sys.path)."""
    return importlib.import_module("sparse_shares")


@pytest.fixture(scope="module")
def cell(harness, bench):
    _, workload, config = harness.resolve(bench, CELL, rehearse=False)
    return workload, config, harness.load_module("builders",
                                                 config["builder"])


def _ops(events=EVENTS):
    return [[text, "other", seconds] for text, seconds, _ in events]


def test_seconds_by_scope_on_the_synthetic_program(sparse_shares):
    program = sparse_shares.scopes.program_from_text(TEXT)
    assert sparse_shares.seconds_by_scope(program, _ops()) == WANT
    assert WANT == {key: sum(s for _, s, keys in EVENTS if key in keys)
                    for key in sparse_shares.KEYS}
    # nothing ran under the block: absent, not zero
    other = [e for e in EVENTS if not e[2]]
    assert sparse_shares.seconds_by_scope(program, _ops(other)) is None


def _context(harness, cell, seconds):
    workload, config, _ = cell
    traffic = workload["traffic"]
    return {"trace": {"busy_s": BUSY, "ops": _ops()}, "config": config,
            "workload": workload, "chips": 1, "steps": 3,
            "tokens_per_step": traffic["batch"] * traffic["seq_len"],
            "peaks": harness.load_json(PERFBENCH, "peaks.json")
            ["device_kinds"]["TPU v5 lite"], "sparse_seconds": seconds}


def test_the_five_metrics_read_the_names(harness, cell):
    context = _context(harness, cell, WANT)
    read = {name: harness.load_module("layer_metrics", name).compute(context)
            for name in METRICS}
    for name, key in (("sparse_attn_block_time_share", "sparse_attn_block"),
                      ("indexer_time_share", "indexer"),
                      ("topk_select_time_share", "topk_select"),
                      ("sparse_attention_time_share", "sparse_attention")):
        assert read[name] == pytest.approx(100 * WANT[key] / BUSY)
    # a layer a sequence: the 6 matmuls nothing can do without (2 forward,
    # 4 backward) of 2 x 32 x 128 FLOP over the 31 458 304 chosen pairs at
    # 197e12 FLOP/s, against 0.91 GB at 819e9 B/s: the operations bound it
    flops = 6 * 2 * 32 * 128 * 31458304
    assert flops / 197e12 > 905969664 / 819e9
    assert read["sparse_attention_roofline"] == pytest.approx(
        100 * 3 * 4 * flops / 197e12 / 48.0)
    # nothing to read: the line leaves all five out
    empty = _context(harness, cell, None)
    assert all(harness.load_module("layer_metrics", name).compute(empty)
               is None for name in METRICS)
    for context in ({"trace": None},
                    {"trace": {"busy_s": 0.0, "ops": []}}):
        assert all(harness.load_module("layer_metrics", name).compute(
            dict(context)) is None for name in METRICS)


def test_the_accepted_moe_roofline_reads_this_builder(harness, cell):
    """`moe_expert_matmul_roofline` (OLMoE's, PR 27) asks the builder for
    `expert_flops_per_token`: here the held experts', a token's 8 choices
    landing on this chip's 16 of 128 an eighth of the time."""
    context = dict(_context(harness, cell, None), moe_seconds={
        "block": 30.0, "router": 1.0, "moe_dispatch": 6.0,
        "moe_experts": 20.0, "moe_combine": 3.0})
    read = harness.load_module(
        "layer_metrics", "moe_expert_matmul_roofline").compute(context)
    assert read == pytest.approx(
        100 * 3 * 16384 * 113246208 / 197e12 / 20.0)
    assert harness.load_module(
        "layer_metrics", "moe_dispatch_time_share").compute(context) \
        == pytest.approx(100 * 10.0 / BUSY)


def test_a_dense_capture_has_none_of_the_names(sparse_shares, reducer):
    """The GPT cell's recorded capture: its program names its scopes and
    none is this block's, so the readers return None, as they must on
    every program of a parent."""
    capture = os.path.join(PERFBENCH, "trace", "scope_fixtures",
                           "cerebras-gpt-1.3b.train-s16k.xplane.pb.gz")
    reduced = reducer.reduce_capture(capture)
    programs = sparse_shares.scopes.programs_from_capture(
        sparse_shares.scopes.read_capture_bytes(capture))
    program = sparse_shares.scopes.pick_program(programs, reduced["ops"])
    assert program is not None
    assert sparse_shares.seconds_by_scope(program, reduced["ops"]) is None


def test_the_new_entries_are_there_by_name(bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    layers = {"sparse_attn_block_time_share": "models",
              "indexer_time_share": "attention kernels",
              "topk_select_time_share": "attention kernels",
              "sparse_attention_time_share": "attention kernels",
              "sparse_attention_roofline": "attention kernels"}
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
    assert listed["traffic"] == "train-s16k" and len(listed["why"]) <= 200
    # the accepted lists the cell joins: the program's own span, which
    # every TrainStep carries, and the readers that find its time by name
    # (a `MoELayer` and its scopes, `TrainStep`'s `optimizer`). The by-scope
    # model classes know a layer by two stems this model does not have
    joined = ("train_dispatch_ms_per_step", "moe_block_time_share",
              "moe_dispatch_time_share", "moe_expert_matmul_roofline",
              "optimizer_time_share", "update_fused_matmul_time_share",
              "unscoped_time_share")
    for name in joined:
        assert CELL in by_name[name]["workloads"], name
    assert sum(CELL in m.get("workloads", ()) for m in bench["per_layer"]) \
        == len(METRICS) + len(joined)
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)


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
    """465.4 M parameters held, 6.52 GB of arguments at 14 bytes each, and
    the FLOPs a token, from the configuration's keys alone."""
    workload, config, builder = cell
    seq_len = workload["traffic"]["seq_len"]
    matmul = builder.matmul_params(config)
    assert matmul == {"attention": 18874368, "indexer": 2260992,
                      "router": 262144, "expert": 4718592,
                      "head": 38895616}
    assert builder.parameter_count(config) == 465391104
    assert 6.51e9 < 14 * builder.parameter_count(config) < 6.52e9
    # a query past 2048 keeps 2048 keys; on average 1920 of 8192.5 seen
    assert builder.chosen_pairs(seq_len, 2048) == 31458304
    assert builder.chosen_pairs(100, 2048) == 100 * 101 // 2
    assert builder.chosen_pairs(seq_len, 2048) / seq_len \
        == pytest.approx(1920, abs=0.1)
    causal = seq_len * (seq_len + 1) // 2
    assert causal == 134225920
    # MFLOP a token a layer, forward: attention over the chosen 31.5
    # (dense causal 134.2), index scores 16.8
    assert builder.sparse_attention_flops(config, seq_len, 2) / seq_len \
        == pytest.approx(31.46e6, rel=1e-3)
    assert 2 * 2 * 32 * 128 * causal / seq_len \
        == pytest.approx(134.2e6, rel=1e-3)
    assert 2 * 16 * 64 * causal / seq_len == pytest.approx(16.78e6, rel=1e-3)
    assert builder.sparse_attention_bytes(config, seq_len) == 905969664
    # 8 choices a token land on the 16 of 128 held an eighth of a time each
    assert builder.held_expert_flops_per_token(config) \
        == builder.expert_flops_per_token(config) \
        == 6 * 4 * 4718592 == 113246208
    # what doubles with the context: the index scores (S / 2 pairs a token,
    # three passes); the attention proper stays at 2048 keys a query
    assert builder.attention_flops_per_token(config, seq_len) \
        == 4 * 3 * 2 * 16 * 64 * seq_len // 2 == 201326592
    assert builder.model_flops_per_token(config, seq_len) == 4 * (
        6 * (18874368 + 262144) + 4 * 2260992
        + 6 * 2 * 32 * 128 * 2048) + 6 * 38895616 + 113246208 + 201326592 \
        == 1446051840


def test_the_roofline_counts_the_work_the_model_flops_count(
        cell, sparse_shares):
    """One count of the attention's required work: what the roofline
    divides by a pair is what `model_flops_per_token` counts a chosen key
    (its asymptote of 2048 keys a query), with no second forward in it."""
    _, config, builder = cell
    seq_len, base = 1 << 20, dict(config, sa_config=dict(
        config["sa_config"]))
    base["sa_config"]["topk"] = 0
    a_key = (builder.model_flops_per_token(config, seq_len)
             - builder.model_flops_per_token(base, seq_len)) \
        / config["num_layers"] / 2048
    assert a_key == sparse_shares.MATMULS_A_PAIR * 2 * 32 * 128
    assert sparse_shares.MATMULS_A_PAIR == 6


def test_the_model_that_is_built_has_the_counted_parameters(harness, bench):
    """The count is of the blocks the builder really builds: at the tiny
    preset every parameter of the model is one the arithmetic counts."""
    _, _, config = harness.resolve(bench, CELL, rehearse=True)
    builder = harness.load_module("builders", config["builder"])
    built = builder.build(config, 0, 128)
    held = sum(int(np.prod(p.shape))
               for p in built["model"].collect_params().values())
    assert held == builder.parameter_count(config)
    model = built["model"]
    moe = model.layers[0].moe
    assert moe.held == (config["first_held_expert"], config["num_experts"])
    assert moe.gate_weight.shape[0] == config["reduced_from"]["num_experts"]
    assert model._remat
    # a token's own row carries the stream: the embedding is Xavier times
    # sqrt(2 x 48 x U), every other matrix plain Xavier

    def rms(param):
        return float(np.sqrt(np.mean(np.square(
            param.data().asnumpy().astype("float32")))))

    units, vocab = config["hidden_size"], config["vocab_size"]
    wide = config["num_attention_heads"] * config["head_dim"]
    assert rms(model.tok_embed.weight) == pytest.approx(
        (2 / (vocab + units)) ** 0.5 * (96 * units) ** 0.5, rel=0.1)
    for layer in model.layers:
        for matrix in (layer.attn.proj.weight, layer.attn.query.weight):
            assert rms(matrix) == pytest.approx(
                (2 / (units + wide)) ** 0.5, rel=0.1)
        assert rms(layer.moe.w2) == pytest.approx(rms(layer.moe.w1), rel=0.1)


def test_the_configuration_is_the_catalog_row_but_for_what_it_lists(bench):
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog beside the guides here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Keye-VL-2.0-30B-A3B")
    assert entry["source"] == config["source"] == row["source_url"]
    assert entry["reduced"] == config["reduced"] == [
        "num_layers", "num_experts", "vocab_size"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["reduced_from"][key] == value != config[key]
        else:
            assert config[key] == value, key
    assert config["reduced_from"] == {
        "num_layers": row["layers"], "num_experts": 128,
        "vocab_size": row["vocab_size"]}
    assert config["num_hidden_layers"] == row["layers"] == 48
    # the floors: four layers of the one-layer period, 16 >= 8 experts, an
    # eighth of the rows; no width is among the cuts
    assert config["num_layers"] == 4 and config["num_experts"] == 16
    assert config["vocab_size"] * 8 == row["vocab_size"]
    assert config["num_experts"] * 8 == row["config"]["num_experts"]
    assert not any(word in key for key in config["reduced"]
                   for word in ("hidden", "intermediate", "dim", "head"))
    for said in ("qk_norm", "mrope", "indexer_rope", "indexer_weights",
                 "indexer_key_norm", "indexer_training", "selection",
                 "router", "init", "positions", "vision", "num_layers",
                 "num_experts"):
        assert config["assumed"][said], said
    assert "DISTORTS" in config["cut"] and "EP 8" in config["cut"]
    assert "NOT SUPPORTED" in config["paper"]
    assert len(entry["why"]) <= 200 and "drawn" in entry["why"]


def test_the_tiny_steps_names_are_the_ones_the_readers_know(
        sparse_shares, harness, bench, monkeypatch):
    """Lower the rehearsal-sized train step here and read its own text: the
    block's name and the op's three scopes are there on forward and
    backward ops (the selection on forward ops alone: a recomputed layer
    keeps it), and the readers' keys find them."""
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
    program = sparse_shares.scopes.program_from_text(text)
    seen = {}
    names = sparse_shares.SCOPES + (sparse_shares.BLOCK_STEM,)
    for instr in program.instrs.values():
        parts, _ = sparse_shares.scopes.components(instr.op_name)
        kind = "back" if "transpose(" in instr.op_name else "forward"
        for name in names:
            if any(name in p for p in parts):
                seen.setdefault(name, set()).add(kind)
    assert set(seen) == set(names)
    assert seen.pop("topk_select") >= {"forward"}
    for where in seen.values():
        assert where == {"forward", "back"}
    ops = [["%%%s = f32[1]{0} add()" % name, "other", 1.0]
           for name in program.instrs]
    seconds = sparse_shares.seconds_by_scope(program, ops)
    assert all(seconds[key] > 0 for key in sparse_shares.KEYS)
    assert sum(seconds[key] for key in sparse_shares.SCOPES) \
        < seconds["sparse_attn_block"]
