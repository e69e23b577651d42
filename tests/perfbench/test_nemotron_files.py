"""What PR 31 adds to the benchmark for the Nemotron-3-Super configuration:
the two copies of the float32 reference, the builder's arithmetic, the
readers of the hybrid's scopes (perfbench/hybrid_shares.py) held to a
synthetic program and to the recorded dense capture, the configuration
file against the catalog row, and the scope names the tiny model's train
step really carries."""
import importlib
import json
import os

import numpy as np
import pytest

from perfbench_helpers import PERFBENCH, ROOT

CELL = "nemotron-3-super.train-s8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
LAYER = "jit(step_fn)/jvp(view0)/net0_nemotronhlayer0"
SSM = LAYER + "/net0_nemotronhlayer0_mamba2mixer0"
SSM_AGAIN = SSM.replace(
    "jvp(view0)", "transpose(jvp(view0))/jvp(view0)/checkpoint/"
    "rematted_computation")
MOE = LAYER.replace("layer0", "layer1") + "/net0_nemotronhlayer1_latentmoe0"
ROUTED = MOE + "/net0_nemotronhlayer1_latentmoe0_moelayer0"
ROUTED_BACK = ROUTED.replace("jvp(view0)",
                             "transpose(jvp(view0))/jvp(view0)/checkpoint")

#: an optimised module with an instruction for each thing the readers tell
#: apart, and the grouped matmul's custom calls as a TPU compile names them
TEXT = """HloModule jit_step_fn, is_scheduled=true

%fused_computation.1 (p0: bf16[8,8]) -> bf16[8,8] {
  %p0 = bf16[8,8]{1,0} parameter(0)
  %convolution.1 = bf16[8,8]{1,0} convolution(%p0, %p0), dim_labels=bf_io->bf, metadata={op_name="SSM/ssd_scan/bcgrij,bcjgrp->bcigrp/dot_general"}
  ROOT %add.1 = bf16[8,8]{1,0} add(%convolution.1, %p0), metadata={op_name="LAYER/add"}
}

ENTRY %main.9 (a: bf16[8,8]) -> bf16[8,8] {
  %a = bf16[8,8]{1,0} parameter(0), metadata={op_name="t_datas[0]"}
  %fusion.1 = bf16[8,8]{1,0} fusion(%a), kind=kOutput, calls=%fused_computation.1, metadata={op_name="LAYER/add"}
  %exp.2 = bf16[8,8]{1,0} exponential(%a), metadata={op_name="SSM_AGAIN/ssd_scan/exp"}
  %multiply.3 = bf16[8,8]{1,0} multiply(%a, %a), metadata={op_name="SSM/ssd_gate_norm/mul"}
  %dot.4 = bf16[8,8]{1,0} dot(%a, %a), metadata={op_name="SSM/net0_nemotronhlayer0_mamba2mixer0_dense0/dot_general"}
  %sort.5 = (s32[64]{0}, s32[64]{0}) sort(%a, %a), dimensions={0}, metadata={op_name="ROUTED/moe_dispatch/sort"}
  %ragged-dot-none.6 = bf16[8,8]{1,0} custom-call(%a, %a), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %multiply.7 = bf16[8,8]{1,0} multiply(%a, %a), metadata={op_name="ROUTED_BACK/moe_experts/mul"}
  %dot.8 = bf16[8,8]{1,0} dot(%a, %a), metadata={op_name="MOE/shared_expert/net0_nemotronhlayer1_latentmoe0_dense2/dot_general"}
  %add.9 = bf16[8,8]{1,0} add(%a, %a), metadata={op_name="LAYER/add"}
  %flash_fwd.10 = bf16[8,8]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/jvp(view0)/net0_nemotronhlayer7/net0_nemotronhlayer7_groupedqueryattention0/flash_fwd/pallas_call"}
  ROOT %copy.11 = bf16[8,8]{1,0} copy(%a)
}
""".replace("ROUTED_BACK", ROUTED_BACK).replace("ROUTED", ROUTED) \
    .replace("MOE", MOE).replace("SSM_AGAIN", SSM_AGAIN) \
    .replace("SSM", SSM).replace("LAYER", LAYER)

#: (event text as the profiler names it, seconds, the keys it is booked to)
EVENTS = [
    # a matmul-class fusion takes the dot inside it: the scan's, though its
    # own metadata is the residual add's
    ("%fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %a), kind=kOutput, "
     "calls=%fused_computation.1", 1.0, ("ssm_block", "ssm_scan")),
    ("%exp.2 = bf16[8,8]{1,0} exponential(bf16[8,8]{1,0} %a)", 2.0,
     ("ssm_block", "ssm_scan")),
    ("%multiply.3 = bf16[8,8]{1,0} multiply(bf16[8,8]{1,0} %a, "
     "bf16[8,8]{1,0} %a)", 4.0, ("ssm_block",)),
    ("%dot.4 = bf16[8,8]{1,0} dot(bf16[8,8]{1,0} %a, bf16[8,8]{1,0} %a)",
     8.0, ("ssm_block",)),
    ("%sort.5 = (s32[64]{0}, s32[64]{0}) sort(bf16[8,8]{1,0} %a, "
     "bf16[8,8]{1,0} %a), dimensions={0}", 16.0, ("latent_moe_block",)),
    ("%ragged-dot-none.6 = bf16[8,8]{1,0} custom-call(bf16[8,8]{1,0} %a, "
     "bf16[8,8]{1,0} %a), custom_call_target=\"tpu_custom_call\"", 32.0,
     ("latent_moe_block", "held_experts")),
    ("%multiply.7 = bf16[8,8]{1,0} multiply(bf16[8,8]{1,0} %a, "
     "bf16[8,8]{1,0} %a)", 64.0, ("latent_moe_block", "held_experts")),
    ("%dot.8 = bf16[8,8]{1,0} dot(bf16[8,8]{1,0} %a, bf16[8,8]{1,0} %a)",
     128.0, ("latent_moe_block",)),
    # the residual add, the attention kernel, an op the program lacks
    ("%add.9 = bf16[8,8]{1,0} add(bf16[8,8]{1,0} %a, bf16[8,8]{1,0} %a)",
     256.0, ()),
    ("%flash_fwd.10 = bf16[8,8]{1,0} custom-call(bf16[8,8]{1,0} %a), "
     "custom_call_target=\"tpu_custom_call\"", 512.0, ()),
    ("%fusion.99 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %a), kind=kLoop, "
     "calls=%fused_computation.77", 1024.0, ()),
]
BUSY = 2047.0
METRICS = ("ssm_block_time_share", "ssm_scan_time_share", "ssm_scan_roofline",
           "latent_moe_block_time_share", "held_expert_matmul_roofline")


@pytest.fixture(scope="module")
def hybrid_shares():
    """As the layer metrics import it (perfbench/ is on sys.path)."""
    return importlib.import_module("hybrid_shares")


def _ops():
    return [[text, "other", seconds] for text, seconds, _ in EVENTS]


def _want():
    want = dict.fromkeys(("ssm_block", "ssm_scan", "latent_moe_block",
                          "held_experts"), 0.0)
    for _, seconds, keys in EVENTS:
        for key in keys:
            want[key] += seconds
    return want


def test_seconds_by_block_on_the_synthetic_program(hybrid_shares):
    program = hybrid_shares.scopes.program_from_text(TEXT)
    assert hybrid_shares.seconds_by_block(program, _ops()) == _want()
    assert _want() == {"ssm_block": 15.0, "ssm_scan": 3.0,
                       "latent_moe_block": 240.0, "held_experts": 96.0}
    # neither block ran: absent, not zero
    other = [row for row, (_, _, keys) in zip(_ops(), EVENTS) if not keys]
    assert hybrid_shares.seconds_by_block(program, other) is None


def _context(harness, bench, seconds):
    _, workload, config = harness.resolve(bench, CELL, rehearse=False)
    traffic = workload["traffic"]
    return {"trace": {"busy_s": BUSY, "ops": _ops()}, "config": config,
            "workload": workload, "chips": 1, "steps": 3,
            "tokens_per_step": traffic["batch"] * traffic["seq_len"],
            "peaks": harness.load_json(PERFBENCH, "peaks.json")
            ["device_kinds"]["TPU v5 lite"], "hybrid_seconds": seconds}


def test_the_five_metrics_read_the_blocks(hybrid_shares, harness, bench):
    context = _context(harness, bench, _want())
    read = {name: harness.load_module("layer_metrics", name).compute(context)
            for name in METRICS}
    assert read["ssm_block_time_share"] == pytest.approx(100 * 15 / BUSY)
    assert read["ssm_scan_time_share"] == pytest.approx(100 * 3 / BUSY)
    assert read["latent_moe_block_time_share"] == pytest.approx(
        100 * 240 / BUSY)
    tokens = 3 * 8192
    # bytes bound the scan: 59 840 B a token at 819e9 B/s (73.1 ns)
    # against 12 318 720 FLOP at 197e12 FLOP/s (62.5 ns)
    assert read["ssm_scan_roofline"] == pytest.approx(
        100 * tokens * 59840 / 819e9 / 3.0)
    assert 59840 / 819e9 > 12318720 / 197e12
    assert read["held_expert_matmul_roofline"] == pytest.approx(
        100 * tokens * 56770560 / 197e12 / 96.0)
    # nothing to read: the line leaves all five out
    empty = _context(harness, bench, None)
    assert all(harness.load_module("layer_metrics", name).compute(empty)
               is None for name in METRICS)
    for context in ({"trace": None},
                    {"trace": {"busy_s": 0.0, "ops": []}}):
        assert all(harness.load_module("layer_metrics", name).compute(
            dict(context)) is None for name in METRICS)


def test_a_dense_capture_has_no_hybrid_time(hybrid_shares, reducer):
    """The GPT cell's recorded capture: its program names its scopes and
    none is a Mamba2Mixer or a LatentMoE, so the readers return None, as
    they must on every program of a parent of PR 31."""
    capture = os.path.join(PERFBENCH, "trace", "scope_fixtures",
                           "cerebras-gpt-1.3b.train-s16k.xplane.pb.gz")
    reduced = reducer.reduce_capture(capture)
    programs = hybrid_shares.scopes.programs_from_capture(
        hybrid_shares.scopes.read_capture_bytes(capture))
    program = hybrid_shares.scopes.pick_program(programs, reduced["ops"])
    assert program is not None
    assert hybrid_shares.seconds_by_block(program, reduced["ops"]) is None


def test_the_new_metrics_are_listed_for_the_new_cell_only(bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["source"] == "device_trace"
        assert by_name[name]["moves"] == "train_tok_per_s"
    assert [m["name"] for m in bench["per_layer"]][-5:] == list(METRICS)
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["workloads"][-1]["chips"] == 1
    assert CELL in by_name["train_dispatch_ms_per_step"]["workloads"]


def test_builder_arithmetic_is_the_issues(harness, bench):
    _, workload, config = harness.resolve(bench, CELL, rehearse=False)
    builder = harness.load_module("builders", config["builder"])
    assert builder.parameter_count(config) == 700865520      # 700.9 M
    assert builder.matmul_params(config) == {
        "M": 13697024, "*": 5242880, "E": 54525952, "expert": 5505024,
        "head": 67108864}
    # 422.93 M matmul weights a token visits, its held experts at their
    # expected 22 x 8 / 512
    visited = 5 * 13697024 + 5242880 + 5 * 54525952 + 67108864 \
        + 5 * 22 * 8 * 5505024 // 512
    assert visited == 422928384
    assert builder.held_expert_flops_per_token(config) \
        == 6 * 5 * 22 * 8 * 5505024 // 512 == 56770560
    assert builder.ssd_flops_per_token(config) \
        == 5 * 3 * (2 * 128 * 128 + 16 * (2 * 128 * 64 + 4 * 64 * 128 + 128)) \
        == 12318720
    assert builder.ssd_bytes_per_token(config) \
        == 5 * (3 * (2048 + 64 + 512) + 2 * 2048) == 59840
    assert builder.attention_flops_per_token(config, 8192) \
        == 6 * 8192 * 4 * 128 == 25165824
    assert builder.model_flops_per_token(config, 8192) \
        == 6 * visited + 12318720 + 25165824 == 2575054848
    # whole mixers (one shard) would not fit: the issue's 1 210.9 M
    whole = dict(config, mixer_shards=1)
    assert round(builder.parameter_count(whole) / 1e6, 1) == 1210.9
    # the cell is what ISSUE 31 names
    assert workload["traffic"] == {
        "generator": "token_batches", "objective": "next_token", "batch": 1,
        "seq_len": 8192, "zipf_a": 1.0}


def test_the_configuration_is_the_catalog_row_but_for_what_it_lists(bench):
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "nemotron-3-super-120b-a12b")
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    assert config["source"] == entry["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if config[k] != v}
    assert differs == {"n_routed_experts", "vocab_size",
                       "num_nextn_predict_layers"} <= set(config["reduced"])
    for key in differs:
        assert config["reduced_from"][key] == row["config"][key]
    assert (config["num_layers"], config["n_routed_experts"],
            config["vocab_size"], config["mixer_shards"],
            config["num_nextn_predict_layers"]) == (11, 8, 16384, 8, 0)
    assert config["layer_pattern_run"] \
        == row["config"]["hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    # the floors: a whole period, 8 experts a layer, an eighth of the rows
    assert config["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert {"cut", "assumed"} <= set(config)


def test_the_two_reference_copies_are_one(harness):
    """tests/nemotron_h_reference.py is what tier-1 compares the model
    with; the benchmark finds its copy by the configuration's name. Same
    text, and, loaded as the two sides load them, the same outputs."""
    mine = os.path.join(ROOT, "tests", "nemotron_h_reference.py")
    theirs = os.path.join(PERFBENCH, "reference",
                          "nemotron-3-super-120b-a12b.py")
    with open(mine) as a, open(theirs) as b:
        assert a.read() == b.read()
    import jax
    import nemotron_h_reference as tests_copy
    bench_copy = harness.load_module("reference",
                                     "nemotron-3-super-120b-a12b")
    assert bench_copy is not tests_copy
    cfg = {"layer_norm_epsilon": 1e-5, "ssm_state_size": 8,
           "mamba_head_dim": 4, "head_dim": 8, "num_experts_per_tok": 2,
           "norm_topk_prob": True, "routed_scaling_factor": 5,
           "layer_pattern_run": "M*E", "first_held_expert": 2}
    rng = np.random.default_rng(0)

    def w(*shape):
        return rng.standard_normal(shape).astype("float32") / 4

    u, inner, conv = 16, 8, 8 + 2 * 8
    params = {"tok_embed": w(32, u), "norm_f": 1 + w(u), "head": w(32, u),
              "layers": [
        {"norm": 1 + w(u), "in_proj": w(inner + conv + 2, u),
         "conv_w": w(conv, 4), "conv_b": w(conv), "A_log": w(2),
         "dt_bias": w(2), "D": 1 + w(2), "gate_norm": 1 + w(inner),
         "out_proj": w(u, inner)},
        {"norm": 1 + w(u), "q": w(16, u), "k": w(8, u), "v": w(8, u),
         "o": w(u, 16)},
        {"norm": 1 + w(u), "router": w(6, u), "router_bias": w(6),
         "latent_down": w(8, u), "latent_up": w(u, 8), "w1": w(3, 8, 12),
         "w2": w(3, 12, 8), "shared_up": w(20, u), "shared_down": w(u, 20)}]}
    ids = rng.integers(0, 32, (2, 13)).astype("int32")
    tokens, labels = ids[:, :-1], ids[:, 1:]
    for name, args in (("forward", (params, cfg, tokens, labels, 4)),
                       ("checked_grads", (params, cfg, tokens, labels))):
        a, b = (getattr(m, name)(*args) for m in (tests_copy, bench_copy))
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert set(bench_copy.update_checked(params)) == {
        "mamba_A_log", "mamba_dt_bias", "mamba_in_proj", "moe_router",
        "moe_latent_down", "moe_latent_up"} | {
        "moe_%s_e%d" % (n, i) for n in ("w1", "w2") for i in range(3)}


def test_the_tiny_steps_scopes_are_the_ones_the_readers_know(
        hybrid_shares, harness, bench, monkeypatch):
    """Lower the rehearsal-sized train step here and read its own text:
    both block stems and the scan's and the experts' scopes are there on
    forward, recomputed and backward ops, and the readers' keys find
    them."""
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
    program = hybrid_shares.scopes.program_from_text(text)
    seen = {}
    for instr in program.instrs.values():
        parts, _ = hybrid_shares.scopes.components(instr.op_name)
        kind = "again" if "rematted_computation" in parts else \
            "back" if "transpose(" in instr.op_name else "forward"
        for stem, scope in ((hybrid_shares.SSM_STEM,
                             hybrid_shares.SCAN_SCOPE),
                            (hybrid_shares.LATENT_MOE_STEM,
                             hybrid_shares.EXPERTS_SCOPE)):
            if any(stem in p for p in parts):
                seen.setdefault(stem, set()).add(kind)
                if scope in parts:
                    seen.setdefault(scope, set()).add(kind)
    assert set(seen) == {"mamba2mixer", "ssd_scan", "latentmoe",
                         "moe_experts"}
    for where in seen.values():
        assert where == {"forward", "again", "back"}
    # the CPU lowers ragged_dot to plain ops under `moe_experts`; a TPU
    # names them `ragged-dot-*`, which the readers book by name
    ops = [["%%%s = f32[1]{0} add()" % name, "other", 1.0]
           for name in program.instrs]
    seconds = hybrid_shares.seconds_by_block(program, ops)
    assert seconds["ssm_block"] > seconds["ssm_scan"] > 0
    assert seconds["latent_moe_block"] > seconds["held_experts"] > 0


def test_the_builder_balances_the_routers_loads(harness, bench, monkeypatch):
    """`balance_routers` at the tiny preset: on a fresh batch every expert
    layer's busiest expert is nearer the even load with the balancing than
    without it (an untrained router follows the activations' common
    component), and the bias stays float32 and untrained."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu import nd
    _, workload, config = harness.resolve(bench, CELL, rehearse=True)
    builder = harness.load_module("builders", config["builder"])
    tokens = np.random.default_rng(5).integers(
        0, config["vocab_size"], (1, 1024)).astype("int32")

    def busiest(net):
        """Per expert layer, the largest load over the even load."""
        out, x = [], net.tok_embed(nd.array(tokens))
        for layer in net.layers:
            u = layer.norm(x)
            if hasattr(layer.mixer, "moe"):
                moe = layer.mixer.moe
                _, _, _, idx = moe.route(
                    u._data.reshape(-1, u.shape[-1]),
                    moe.gate_weight.data()._data,
                    moe.router_bias.data()._data)
                load = jnp.bincount(idx.reshape(-1), length=moe.num_experts)
                out.append(float(load.max() / load.mean()))
            x = x + layer.mixer(u)
        return out

    balanced = builder.build(config, 7, 1024)["model"]
    monkeypatch.setattr(builder, "balance_routers", lambda net, tokens: None)
    plain = builder.build(config, 7, 1024)["model"]
    got, before = busiest(balanced), busiest(plain)
    assert len(got) == 5
    assert all(g < b for g, b in zip(got[1:], before[1:])), (got, before)
    assert max(got) < 2.0 < max(before), (got, before)
    bias = balanced.layers[1].mixer.moe.router_bias
    assert str(bias.data().dtype) == "float32" and bias.grad_req == "null"
    # it starts at zero: whatever it holds, the balancing put there
    assert float(jnp.abs(plain.layers[1].mixer.moe.router_bias.data()._data)
                 .max()) == 0.0
    assert float(jnp.abs(bias.data()._data).max()) > 0.0


def test_the_compared_forward_is_continuous_and_the_whole_one_is_not(
        harness, bench):
    """What the cell's `check` compares (the builder's `continuous_trunk`
    against the reference's `forward`) at the rehearsal size, bfloat16
    against float32: every token within 6 % and all together within 3 %
    (measured 1.4-1.5 % and 2.4-3.4 % for the worst token over three
    seeds: bfloat16 rounding through eleven layers at 128 wide). The
    whole model's features on the same tokens are 20-23 % apart with
    tokens over 100 %: flipped choices among the 4 of 64, which is why
    they are not what is compared."""
    from incubator_mxnet_tpu import nd
    _, _, config = harness.resolve(bench, CELL, rehearse=True)
    builder = harness.load_module("builders", config["builder"])
    reference = harness.load_module("reference", config["reference"])
    built = builder.build(config, 7, 256)
    tokens = np.random.default_rng(7).integers(
        0, config["vocab_size"], (1, 256)).astype("int32")
    params = builder.reference_params(built["model"])

    def per_token(got, want):
        got = got.asnumpy().astype("float32")
        want = np.asarray(want)
        return np.sqrt(((got - want) ** 2).mean(-1)) \
            / np.sqrt((want ** 2).mean())

    labels = np.zeros_like(tokens)
    want, _ = reference.forward(params, config, tokens, labels, 256)
    trunk = per_token(built["eval_net"](nd.array(tokens)), want)
    assert trunk.max() < 0.06 and np.sqrt((trunk ** 2).mean()) < 0.03
    whole = per_token(built["train_net"](nd.array(tokens)),
                      reference.features(params, config, tokens))
    assert whole.max() > 0.5 and np.sqrt((whole ** 2).mean()) > 0.1
    # the trunk shares the model's parameters: nothing of its own to train
    assert set(built["eval_net"].collect_params().keys()) \
        <= set(built["model"].collect_params().keys())
