"""What PR 27 adds to the benchmark for the OLMoE configuration: the two
copies of the float32 reference, the builder's arithmetic, the readers of
the MoE block's scopes (perfbench/moe_shares.py) held to a synthetic program
and to the recorded dense capture, and the scope names the tiny model's
train step really carries."""
import importlib
import os

import numpy as np
import pytest

from perfbench_helpers import PERFBENCH, ROOT

CELL = "olmoe-1b-7b.train-s4k"
MOE = "jit(step_fn)/jvp(view0)/net0_olmoetransformerdecoderlayer0/ffn/" \
    "net0_olmoetransformerdecoderlayer0_moelayer0"
MOE_BACK = MOE.replace("jvp(view0)", "transpose(jvp(view0))")

#: an optimised module with one instruction for each part of the MoE block,
#: one dense-layer op, and the grouped matmul's custom calls as a TPU
#: compile names them (no scope path)
TEXT = """HloModule jit_step_fn, is_scheduled=true

%fused_computation.1 (p0: bf16[8,8]) -> bf16[8,8] {
  %p0 = bf16[8,8]{1,0} parameter(0)
  %convolution.1 = bf16[8,8]{1,0} convolution(%p0, %p0), dim_labels=bf_io->bf, metadata={op_name="MOE/router/td,ed->te/dot_general"}
  ROOT %exp.1 = bf16[8,8]{1,0} exponential(%convolution.1), metadata={op_name="MOE/router/exp"}
}

ENTRY %main.9 (a: bf16[8,8]) -> bf16[8,8] {
  %a = bf16[8,8]{1,0} parameter(0), metadata={op_name="t_datas[0]"}
  %fusion.1 = bf16[8,8]{1,0} fusion(%a), kind=kOutput, calls=%fused_computation.1, metadata={op_name="MOE/router/exp"}
  %sort.2 = (s32[64]{0}, s32[64]{0}) sort(%a, %a), dimensions={0}, metadata={op_name="MOE/moe_dispatch/sort"}
  %gather.3 = bf16[8,8]{1,0} gather(%a, %a), metadata={op_name="MOE_BACK/moe_dispatch/gather"}
  %ragged-dot-none.4 = bf16[8,8]{1,0} custom-call(%a, %a), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %ragged-dot-metadata.1 = s32[9]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-metadata"}
  %multiply.5 = bf16[8,8]{1,0} multiply(%a, %a), metadata={op_name="MOE/moe_experts/jit(silu)/mul"}
  %reduce.6 = bf16[8,8]{1,0} reduce(%a, %a), dimensions={1}, metadata={op_name="MOE_BACK/moe_combine/reduce_sum"}
  %reshape.7 = bf16[8,8]{1,0} reshape(%a), metadata={op_name="MOE/reshape"}
  %add.8 = bf16[8,8]{1,0} add(%a, %a), metadata={op_name="jit(step_fn)/jvp(view0)/net0_olmoetransformerdecoderlayer0/add"}
  %flash_fwd.9 = bf16[8,8]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/jvp(view0)/net0_olmoetransformerdecoderlayer0/net0_rotarymultiheadattention0/flash_fwd/pallas_call"}
  ROOT %copy.11 = bf16[8,8]{1,0} copy(%a)
}
""".replace("MOE_BACK", MOE_BACK).replace("MOE", MOE)

#: (event text as the profiler names it, seconds, the part it is booked to)
EVENTS = [
    ("%fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %a), kind=kOutput, "
     "calls=%fused_computation.1", 1.0, "router"),
    ("%sort.2 = (s32[64]{0}, s32[64]{0}) sort(bf16[8,8]{1,0} %a, "
     "bf16[8,8]{1,0} %a), dimensions={0}", 2.0, "moe_dispatch"),
    ("%gather.3 = bf16[8,8]{1,0} gather(bf16[8,8]{1,0} %a, bf16[8,8]{1,0} "
     "%a)", 4.0, "moe_dispatch"),
    ("%ragged-dot-none.4 = bf16[8,8]{1,0} custom-call(bf16[8,8]{1,0} %a, "
     "bf16[8,8]{1,0} %a), custom_call_target=\"tpu_custom_call\"", 8.0,
     "moe_experts"),
    ("%ragged-dot-metadata.1 = s32[9]{0} custom-call(bf16[8,8]{1,0} %a), "
     "custom_call_target=\"tpu_custom_call\"", 0.5, "moe_experts"),
    ("%multiply.5 = bf16[8,8]{1,0} multiply(bf16[8,8]{1,0} %a, "
     "bf16[8,8]{1,0} %a)", 16.0, "moe_experts"),
    ("%reduce.6 = bf16[8,8]{1,0} reduce(bf16[8,8]{1,0} %a, bf16[8,8]{1,0} "
     "%a), dimensions={1}", 32.0, "moe_combine"),
    # in the block, in none of its four parts
    ("%reshape.7 = bf16[8,8]{1,0} reshape(bf16[8,8]{1,0} %a)", 64.0, None),
    # the layer's residual add, the attention kernel: outside the block
    ("%add.8 = bf16[8,8]{1,0} add(bf16[8,8]{1,0} %a, bf16[8,8]{1,0} %a)",
     128.0, "outside"),
    ("%flash_fwd.9 = bf16[8,8]{1,0} custom-call(bf16[8,8]{1,0} %a), "
     "custom_call_target=\"tpu_custom_call\"", 256.0, "outside"),
    ("%fusion.99 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %a), kind=kLoop, "
     "calls=%fused_computation.77", 512.0, "outside"),
]


@pytest.fixture(scope="module")
def moe_shares():
    """As the layer metrics import it (perfbench/ is on sys.path)."""
    return importlib.import_module("moe_shares")


def _ops():
    return [[text, "other", seconds] for text, seconds, _ in EVENTS]


def _want():
    want = dict.fromkeys(("block", "router", "moe_dispatch", "moe_experts",
                          "moe_combine"), 0.0)
    for _, seconds, part in EVENTS:
        if part != "outside":
            want["block"] += seconds
            if part:
                want[part] += seconds
    return want


def test_seconds_by_part_on_the_synthetic_program(moe_shares):
    program = moe_shares.scopes.program_from_text(TEXT)
    assert moe_shares.seconds_by_part(program, _ops()) == _want()
    # no MoE block ran: absent, not zero
    dense = [row for row, (_, _, part) in zip(_ops(), EVENTS)
             if part == "outside"]
    assert moe_shares.seconds_by_part(program, dense) is None


def _context(moe_shares, harness, bench, seconds):
    _, workload, config = harness.resolve(bench, CELL, rehearse=False)
    traffic = workload["traffic"]
    return {"trace": {"busy_s": 1023.5, "ops": _ops()}, "config": config,
            "workload": workload, "chips": 1, "steps": 3,
            "tokens_per_step": traffic["batch"] * traffic["seq_len"],
            "peaks": harness.load_json(PERFBENCH, "peaks.json")
            ["device_kinds"]["TPU v5 lite"], "moe_seconds": seconds}


def test_the_three_metrics_read_the_parts(moe_shares, harness, bench):
    context = _context(moe_shares, harness, bench, _want())
    read = {name: harness.load_module("layer_metrics", name).compute(context)
            for name in ("moe_block_time_share", "moe_dispatch_time_share",
                         "moe_expert_matmul_roofline")}
    assert read["moe_block_time_share"] == pytest.approx(
        100 * 127.5 / 1023.5)
    assert read["moe_dispatch_time_share"] == pytest.approx(
        100 * (1 + 6 + 32) / 1023.5)
    # 3 steps x 16384 tokens x 6 x 8 x 3 x 2048 x 1024 FLOP at 197e12,
    # over the 24.5 s under moe_experts
    needed_s = 3 * 16384 * 301989888 / 197e12
    assert read["moe_expert_matmul_roofline"] == pytest.approx(
        100 * needed_s / 24.5)
    # nothing to read: the line leaves all three out
    empty = _context(moe_shares, harness, bench, None)
    assert all(harness.load_module("layer_metrics", name).compute(empty)
               is None for name in read)
    untraced = dict(context, trace=None)
    untraced.pop("moe_seconds")
    assert moe_shares.moe_seconds(untraced) is None


def test_a_dense_capture_has_no_moe_time(moe_shares, reducer):
    """The GPT cell's recorded capture (trace/scope_fixtures): its program
    names its scopes and none is a MoELayer, so the readers return None,
    as they must on every program of a parent of PR 27."""
    capture = os.path.join(PERFBENCH, "trace", "scope_fixtures",
                           "cerebras-gpt-1.3b.train-s16k.xplane.pb.gz")
    reduced = reducer.reduce_capture(capture)
    programs = moe_shares.scopes.programs_from_capture(
        moe_shares.scopes.read_capture_bytes(capture))
    program = moe_shares.scopes.pick_program(programs, reduced["ops"])
    assert program is not None
    assert moe_shares.seconds_by_part(program, reduced["ops"]) is None


def test_builder_arithmetic_is_the_issues(harness, bench):
    _, workload, config = harness.resolve(bench, CELL, rehearse=False)
    builder = harness.load_module("builders", config["builder"])
    assert builder.model_flops_per_token(config, 4096) == 1071906816
    assert builder.expert_flops_per_token(config) \
        == 6 * 8 * 3 * 2048 * 1024 == 301989888
    assert builder.attention_flops_per_token(config, 4096) == 50331648
    # the depth that is run scales all three; the head is counted once
    deep = dict(config, num_layers=16)
    assert builder.expert_flops_per_token(deep) == 16 * 301989888
    # the cell is what ISSUE 27 names: 4 x 4096 Zipf tokens on one chip
    assert workload["traffic"] == {
        "generator": "token_batches", "objective": "next_token", "batch": 4,
        "seq_len": 4096, "zipf_a": 1.0}
    assert config["num_layers"] == 1 and config["num_hidden_layers"] == 16


def test_the_two_reference_copies_are_one(harness):
    """tests/olmoe_reference.py is what tier-1 compares the model with;
    the benchmark finds its copy by the configuration's name. Same text,
    and, loaded as the two sides load them, the same outputs."""
    mine = os.path.join(ROOT, "tests", "olmoe_reference.py")
    theirs = os.path.join(PERFBENCH, "reference", "olmoe-1b-7b-0125.py")
    with open(mine) as a, open(theirs) as b:
        assert a.read() == b.read()
    import jax
    import olmoe_reference as tests_copy
    bench_copy = harness.load_module("reference", "olmoe-1b-7b-0125")
    assert bench_copy is not tests_copy
    cfg = {"hidden_size": 32, "intermediate_size": 16, "num_layers": 2,
           "num_attention_heads": 2, "num_experts": 4,
           "num_experts_per_tok": 2, "norm_topk_prob": False,
           "rope_theta": 10000, "rms_norm_eps": 1e-5, "vocab_size": 64}
    rng = np.random.default_rng(0)

    def w(*shape):
        return rng.standard_normal(shape).astype("float32") / 4

    layer = lambda: {                                        # noqa: E731
        "n1": 1 + w(32), "n2": 1 + w(32), "q": w(32, 32), "k": w(32, 32),
        "v": w(32, 32), "o": w(32, 32), "q_norm": 1 + w(32),
        "k_norm": 1 + w(32), "router": w(4, 32), "gate": w(4, 32, 16),
        "up": w(4, 32, 16), "down": w(4, 16, 32)}
    params = {"tok_embed": w(64, 32), "layers": [layer(), layer()],
              "norm_f": 1 + w(32), "head": w(64, 32)}
    ids = rng.integers(0, 64, (2, 17)).astype("int32")
    tokens, labels = ids[:, :-1], ids[:, 1:]
    for name, args in (("forward", (params, cfg, tokens, labels, 4)),
                       ("checked_grads", (params, cfg, tokens, labels)),
                       ("routing", (params, cfg, tokens))):
        a, b = (getattr(m, name)(*args) for m in (tests_copy, bench_copy))
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert set(bench_copy.update_checked(params)) \
        == {"router", "gate", "up", "down"}


def test_the_tiny_steps_scopes_are_the_ones_the_readers_know(
        moe_shares, harness, bench, monkeypatch):
    """Lower the rehearsal-sized train step here and read its own text:
    every MoE part and `rope` are there, forward and backward; the class
    stems put the attention under `attn_block` and the MoE under
    `mlp_block` with trace/scopes.py unedited."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    import jax
    from incubator_mxnet_tpu import gluon, jit, nd
    _, workload, config = harness.resolve(bench, CELL, rehearse=True)
    builder = harness.load_module("builders", config["builder"])
    built = builder.build(config, 0, 128)
    trainer = gluon.Trainer(built["train_net"].collect_params(), "adam",
                            {"learning_rate": 1e-4, "multi_precision": True})
    step = jit.TrainStep(built["train_net"], built["loss"], trainer)
    tokens = nd.array(np.zeros((2, 128), "int32"))
    step(tokens, tokens)
    (text,) = [t for model_id, t in jit.compiled_train_programs()
               if model_id == step._model_id]
    program = moe_shares.scopes.program_from_text(text)
    seen = {}
    for instr in program.instrs.values():
        parts, _ = moe_shares.scopes.components(instr.op_name)
        back = "transpose(" in instr.op_name
        for part in moe_shares.PARTS + ("rope",):
            if part in parts:
                seen.setdefault(part, set()).add(back)
                inside = any(moe_shares.MOE_STEM in p for p in parts)
                assert inside == (part != "rope"), instr.op_name
                assert moe_shares.scopes.scope_class(instr.op_name) == (
                    "attn_block" if part == "rope" else "mlp_block")
    assert seen == {part: {False, True}
                    for part in moe_shares.PARTS + ("rope",)}
