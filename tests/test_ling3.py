"""Ling 3.0 on the CPU at small widths, seeded: multi-head latent attention
and the full-rank, bounded Kimi-Delta block against the float32 reference
the benchmark uses (perfbench/reference/ling-3.0-flash.py) in value and
gradient; the group-limited choice against a NumPy loop; the shares of
guide section 4 (the expert shares with the shared expert counted once)
against the uncut layer; the model's loss and every checked gradient; what
stays float32 under a bfloat16 cast; the normal path (TrainStep, every
layer recomputed) with its scopes and counters; and the pins that hold
Solar Open 2's and the accepted routers' traced programs to what they were
before `KimiDeltaAttention` and `MoELayer.route` grew their arguments.
"""
import hashlib
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, jit, models, nd, telemetry
from incubator_mxnet_tpu.ndarray import NDArray
from incubator_mxnet_tpu.parallel import MoELayer

PERFBENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench")


def _load(kind, name):
    if PERFBENCH not in sys.path:
        sys.path.insert(0, PERFBENCH)
    spec = importlib.util.spec_from_file_location(
        "ling3_test_" + kind, os.path.join(PERFBENCH, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = _load("reference", "ling-3.0-flash")
builder = _load("builders", "ling3_lm")

ROUTED, HELD, FIRST = 16, 4, 4
#: widths 24 / 16 stand in for 192 / 128
CFG = {"hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
       "num_kv_heads_for_linear_attn": 0, "short_conv_kernel_size": 4,
       "kv_lora_rank": 32, "qk_head_dim": 24, "qk_nope_head_dim": 16,
       "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 6e6,
       "intermediate_size": 96, "moe_intermediate_size": 24,
       "moe_shared_expert_intermediate_size": 24, "num_shared_experts": 1,
       "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2,
       "norm_topk_prob": True, "routed_scaling_factor": 2.5,
       "rms_norm_eps": 1e-6, "kda_lower_bound": -5, "kda_safe_gate": True,
       "no_kda_lora": True, "vocab_size": 128, "layer_pattern_run": "KKM",
       "first_k_dense_replace": 1, "first_held_expert": FIRST,
       "num_experts": HELD, "reduced_from": {"num_experts": ROUTED},
       "delta_rule_chunk": 16, "router_bias_rate": None,
       "init_head_scale": 4.0}
B, S = 2, 48


def build(cfg=CFG, dtype=None, seed=0, remat=False, held=(FIRST, HELD),
          bias_rate=None):
    mx.random.seed(seed)
    s = builder.shapes(cfg)
    net = models.Ling3Model(
        cfg["vocab_size"], s["units"], s["pattern"],
        delta=dict(num_heads=s["kda_heads"], head_dim=s["kda_dim"],
                   chunk=cfg["delta_rule_chunk"], rank="full",
                   decay=("bounded", -5.0), neg_eigval=False),
        latent=dict(num_heads=s["heads"], latent=s["latent"],
                    nope_dim=s["nope"], rope_dim=s["rope"],
                    v_dim=s["value"], rope_theta=cfg["rope_theta"]),
        moe=dict(num_experts=ROUTED, ffn_hidden=24, top_k=4,
                 shared_hidden=24, scale=2.5, held=held, bias_rate=bias_rate,
                 n_group=cfg["n_group"], topk_group=cfg["topk_group"]),
        dense_hidden=s["dense"], dense_layers=1, remat_layers=remat)
    net.initialize(mx.init.Xavier())
    head = net.lm_head.weight
    head.set_data(head.data() * 4.0)
    for name, p in net.collect_params().items():
        # gains and selection biases that are not all 1 or 0, so a
        # misplaced one shows
        if name.endswith(("gamma", "_gain")):
            p.set_data(p.data() * nd.random.uniform(0.5, 1.5, p.shape))
        if name.endswith("router_bias"):
            p.set_data(nd.random.uniform(-0.2, 0.2, p.shape))
    if dtype:
        net.cast(dtype)
    return net


def batch(seed=0, s=S):
    ids = onp.random.RandomState(seed).randint(
        0, CFG["vocab_size"], (B, s + 1)).astype("int32")
    return ids[:, :-1], ids[:, 1:]


def inputs(seed=3):
    return onp.random.default_rng(seed).standard_normal(
        (B, S, CFG["hidden_size"])).astype("float32")


def close(got, want, tol=1e-4):
    got, want = onp.asarray(got), onp.asarray(want)
    assert onp.abs(got - want).max() < tol * onp.abs(want).max()


# ----------------------------------------------------------------- blocks
@pytest.mark.parametrize("letter, layer", [("K", 1), ("M", 2)])
def test_a_mixer_is_the_references_in_value_and_gradient(letter, layer):
    """M against the per-head form with k built by concatenation and ONE
    rotary key for all heads; K with full-rank maps and the bounded decay
    against the recurrence a position at a time: outputs, and the gradient
    with respect to the input."""
    net = build()
    block = net.layers[layer].mixer
    p = builder.reference_params(net)["layers"][layer]
    ref = {"K": reference.kda, "M": reference.mla}[letter]
    x = inputs()
    with jax.default_matmul_precision("highest"):
        # (the reference compiled whole: op by op it is several times slower)
        close(block(nd.array(x)).asnumpy(),
              jax.jit(lambda x: ref(p, x, CFG))(jnp.asarray(x)))
        w = jnp.asarray(inputs(4))
        mine = jax.grad(lambda x: (block(NDArray(x))._data * w).sum())(
            jnp.asarray(x))
        want = jax.jit(jax.grad(lambda x: (ref(p, x, CFG) * w).sum()))(
            jnp.asarray(x))
    close(mine, want)


def test_the_rotary_key_is_one_for_all_heads_and_the_pairs_interleave():
    x = onp.random.default_rng(0).standard_normal((1, 3, 40, 8)) \
        .astype("float32")
    turned = onp.asarray(models.ling3.rope_interleaved(jnp.asarray(x), 1e4))
    angle = onp.arange(40)[:, None] * 1e4 ** (-onp.arange(0, 8, 2) / 8)
    want = onp.empty_like(x)
    want[..., 0::2] = x[..., 0::2] * onp.cos(angle) \
        - x[..., 1::2] * onp.sin(angle)
    want[..., 1::2] = x[..., 1::2] * onp.cos(angle) \
        + x[..., 0::2] * onp.sin(angle)
    onp.testing.assert_allclose(turned, want, rtol=1e-5, atol=1e-5)
    onp.testing.assert_allclose(
        turned, onp.asarray(reference.rotary(jnp.asarray(x), 1e4)),
        rtol=1e-6, atol=1e-6)
    # the block's keys: every head's last 8 channels are the same vector
    block = build().layers[2].mixer
    q = nd.array(inputs())
    down = block.kv_down(q)
    _, k, _ = block._heads(
        block.query(q)._data, block.kv_up(block.kv_norm(
            down[..., :32]))._data, down[..., 32:]._data,
        block.q_gain.data()._data, block.k_gain.data()._data)
    onp.testing.assert_array_equal(onp.asarray(k[:, 0, :, 16:]),
                                   onp.asarray(k[:, 3, :, 16:]))
    assert onp.abs(onp.asarray(k[:, 0, :, :16] - k[:, 3, :, :16])).max() > 0


def test_the_bounded_decay_stays_above_its_bound():
    """g = lower * sigmoid(.) in (lower, 0) however large the map's output;
    `decay="softplus"` passes it."""
    seen = {}
    real = models.solar_open2.gated_delta_rule_lanes

    def spy(q, k, v, g, beta, heads, chunk):
        seen["g"], seen["beta"] = g, beta
        return real(q, k, v, g, beta, heads, chunk)

    x = nd.array(20.0 * inputs())
    for decay, low in ((("bounded", -5.0), -5.0), ("softplus", -1e9)):
        mx.random.seed(0)
        block = models.KimiDeltaAttention(64, 4, 16, chunk=16, rank="full",
                                          decay=decay, neg_eigval=False)
        block.initialize(mx.init.Xavier())
        block.in_proj.weight.set_data(block.in_proj.weight.data() * 30.0)
        models.solar_open2.gated_delta_rule_lanes = spy
        try:
            block(x)
        finally:
            models.solar_open2.gated_delta_rule_lanes = real
        g = onp.asarray(seen["g"])
        assert g.max() <= 0 and g.min() >= low
        assert (g.min() < -5.0) == (decay == "softplus")
        assert 0 <= onp.asarray(seen["beta"]).min() \
            and onp.asarray(seen["beta"]).max() <= 1
    with pytest.raises(ValueError):
        models.KimiDeltaAttention(64, 4, 16, decay=("bounded", 5.0))


# ------------------------------------------------------ the group limit
def _choose_by_loop(c, n_group, topk_group, k):
    """The rule written out: a group's score is the sum of its two largest
    entries, the best groups are kept (ties to the lower index), then the k
    largest entries of the kept groups (ties to the lower index)."""
    out = []
    for row in c:
        groups = row.reshape(n_group, -1)
        score = onp.sort(groups, -1)[:, -2:].sum(-1)
        kept = onp.argsort(-score, kind="stable")[:topk_group]
        masked = onp.full(row.shape, -onp.inf, row.dtype)
        for g in kept:
            size = groups.shape[1]
            masked[g * size:(g + 1) * size] = groups[g]
        out.append(onp.argsort(-masked, kind="stable")[:k])
    return onp.array(out)


def test_the_group_limited_choice_is_the_loops():
    """Random scores; rows of ties (whole groups equal, equal entries
    inside a group); and a group whose bias hides it however high its
    scores."""
    rng = onp.random.default_rng(0)
    gates = rng.uniform(0.05, 0.95, (64, ROUTED)).astype("float32")
    gates[:8] = 0.5                              # every score equal
    gates[8:16, :8] = 0.75                       # two whole groups tie
    gates[16:24] = onp.round(gates[16:24], 1)    # many equal entries
    gates[24:] += onp.where(onp.arange(ROUTED) // 4 == 1, 0.9, 0.0)
    bias = onp.zeros(ROUTED, "float32")
    layer = MoELayer(ROUTED, 8, 8, top_k=4, router="sigmoid_bias",
                     n_group=4, topk_group=2)
    before = telemetry.REGISTRY.get("mxtpu_moe_group_limited_total").value()
    for hide in (False, True):
        if hide:
            bias[4:8] = -5.0                     # group 1 is never kept
        got = onp.asarray(layer.choose(jnp.asarray(gates), jnp.asarray(bias)))
        onp.testing.assert_array_equal(
            got, _choose_by_loop(gates + bias, 4, 2, 4))
        groups = got // 4
        assert all(len(set(row)) <= 2 for row in groups)
        if hide:
            assert not (groups == 1).any()
        else:
            assert (groups[24:] == 1).any()
        # the reference's `top_k` and masks choose the same experts
        cfg = dict(CFG, n_group=4, topk_group=2)
        kept = onp.asarray(reference.kept_groups(
            jnp.asarray(gates + bias), cfg))
        assert all(kept[t, e] for t, row in enumerate(got) for e in row)
    assert telemetry.REGISTRY.get(
        "mxtpu_moe_group_limited_total").value() == before + 2
    with pytest.raises(ValueError):
        MoELayer(ROUTED, 8, 8, top_k=4, router="softmax", n_group=4,
                 topk_group=2)
    with pytest.raises(ValueError):
        MoELayer(ROUTED, 8, 8, top_k=4, router="sigmoid_bias", n_group=4)


def test_expert_shares_add_up_to_the_uncut_layer():
    """The routed parts of the four shares `held=(4 j, 4)` of one layer's
    weights, the shared expert counted once, are the uncut layer of the
    uncut reference: the router as wide as ever, the choice under the
    group limit, the weights normalised over all four chosen."""
    uncut = build(held=(0, ROUTED))
    whole = uncut.layers[1].experts
    p = builder.reference_params(uncut)["layers"][1]
    x = inputs()
    with jax.default_matmul_precision("highest"):
        want = reference.experts(p, jnp.asarray(x), CFG, first=0)
        shared = whole.shared(nd.array(x)).asnumpy()
        total = shared.copy()
        for j in range(ROUTED // HELD):
            share = build(held=(HELD * j, HELD)).layers[1].experts
            rows = slice(HELD * j, HELD * (j + 1))
            for name in ("w1", "w2", "w3"):
                getattr(share.moe, name).set_data(nd.array(
                    onp.asarray(p[name])[rows]))
            for mine, theirs in ((share.moe.gate_weight, "router"),
                                 (share.moe.router_bias, "router_bias"),
                                 (share.shared.gate_up.weight,
                                  "shared_gate_up"),
                                 (share.shared.down.weight, "shared_down")):
                mine.set_data(nd.array(onp.asarray(p[theirs])))
            part = share(nd.array(x)).asnumpy()
            close(part, reference.experts(
                {**p, **{n: p[n][rows] for n in ("w1", "w2", "w3")}},
                jnp.asarray(x), CFG, first=HELD * j))
            total += part - shared
    close(total, want)
    close(whole(nd.array(x)).asnumpy(), want)


# ------------------------------------------------------------------ model
def test_parameter_count_and_what_stays_float32():
    net = build(dtype="bfloat16")
    params = net.collect_params()
    assert sum(int(onp.prod(p.shape)) for p in params.values()) \
        == builder.parameter_count(CFG)
    f32 = {n for n, p in params.items()
           if str(p.data().dtype) == "float32"}
    assert all(n.endswith(("A_log", "dt_bias", "norm_gamma", "q_gain",
                           "k_gain", "router_bias"))
               or "multiheadlatentattention0_rmsnorm0_gamma" in n
               for n in f32)
    assert len(f32) == 2 * 3 + 3 + 2      # K layers, M's gains, two biases
    block = net.layers[0].mixer
    assert not hasattr(block, "decay_up") and block.rank is None
    assert block.in_proj.weight.shape == (5 * 64 + 4, 64)


def test_the_model_is_the_references_in_loss_and_checked_gradients():
    net = build()
    tokens, labels = batch()
    params = builder.reference_params(net)
    with jax.default_matmul_precision("highest"):
        # (the reference compiled whole: op by op it is several times slower)
        close(net.features(nd.array(tokens)).asnumpy(), jax.jit(
            lambda p: reference.features(p, CFG, tokens))(params))
        compared, ref_loss = jax.jit(lambda p: reference.forward(
            p, CFG, tokens, labels, S))(params)
        close(builder.continuous_trunk(net)(nd.array(tokens)).asnumpy(),
              compared)
        # the trunk without the routed sum | the M mixer | the K mixer,
        # the two alone at a position's unit RMS
        units = CFG["hidden_size"]
        close(compared[..., :units], jax.jit(lambda p: reference.features(
            p, CFG, tokens, routed=False))(params))
        for part in (compared[..., units:2 * units],
                     compared[..., 2 * units:]):
            close(onp.sqrt(onp.mean(onp.square(part), -1)), onp.ones((B, S)))
        want = jax.jit(lambda p: reference.checked_grads(
            p, CFG, tokens, labels))(params)
        held = [p for _, p in sorted(net.collect_params().items())
                if p.grad_req != "null"]
        loss = models.ChunkedUntiedLMLoss(net)

        def total(datas):
            arrs = [p.data() for p in held]
            saved = [a._data for a in arrs]
            for a, d in zip(arrs, datas):
                a._data = d
            try:
                return loss(net.features(nd.array(tokens)),
                            nd.array(labels))._data.sum()
            finally:
                for a, was in zip(arrs, saved):
                    a._data = was

        value, grads = jax.value_and_grad(total)(
            [p.data()._data for p in held])
        mine = dict(zip([p.name for p in held], grads))
        close(value, ref_loss.sum())
    k, m, e = net.layers[1].mixer, net.layers[2].mixer, net.layers[2].experts
    inner = 64
    got = {"kda_A_log": mine[k.A_log.name], "kda_dt_bias": mine[k.dt_bias.name],
           "mla_q": mine[m.query.weight.name],
           "mla_kv_down": mine[m.kv_down.weight.name],
           "mla_kv_up": mine[m.kv_up.weight.name],
           "mla_kv_norm": mine[m.kv_norm.gamma.name],
           "mla_gate": mine[m.gate.weight.name],
           "moe_router": mine[e.moe.gate_weight.name],
           "moe_shared_gate_up": mine[e.shared.gate_up.weight.name],
           "moe_shared_down": mine[e.shared.down.weight.name]}
    got.update(zip(("kda_" + n for n in reference.KDA_ROWS), jnp.split(
        mine[k.in_proj.weight.name], [inner * i for i in range(1, 6)], 0)))
    got.update({"moe_%s_e%d" % (n, i): mine[getattr(e.moe, n).name][i]
                for n in ("w1", "w2", "w3") for i in range(HELD)})
    assert set(got) == set(want)
    assert onp.asarray(want["kda_beta"]).shape == (4, 64)
    for name in want:
        close(got[name], want[name]), name
        assert onp.abs(onp.asarray(want[name])).max() > 0, name


def test_one_train_step_keeps_the_scopes_and_counts_its_routes(monkeypatch):
    """The normal path (FeaturesView + ChunkedUntiedLMLoss through
    TrainStep, bfloat16 with float32 masters, every layer recomputed, the
    selection bias moved by the rule): a falling loss, the new scopes on
    forward and backward ops, and the counters of what was traced."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")

    def count(name, **labels):
        return telemetry.REGISTRY.get(name).value(**labels)

    before = (count("mxtpu_latent_attention_total", route="composite"),
              count("mxtpu_moe_group_limited_total"))
    net = build(dtype="bfloat16", remat=True, bias_rate=0.05)
    view = models.FeaturesView(net)
    trainer = gluon.Trainer(view.collect_params(), "adam",
                            {"learning_rate": 1e-3, "multi_precision": True})
    step = jit.TrainStep(view, models.ChunkedUntiedLMLoss(net), trainer)
    tokens, labels = batch(s=128)
    bias = net.layers[1].experts.moe.router_bias.data().asnumpy()
    losses = [float(step(nd.array(tokens), nd.array(labels)).asnumpy().mean())
              for _ in range(4)]
    assert losses[-1] < losses[0]
    assert onp.abs(net.layers[1].experts.moe.router_bias.data().asnumpy()
                   - bias).max() > 0
    # 128 positions of heads 24 wide are no shape of the kernels'
    assert count("mxtpu_latent_attention_total", route="composite") \
        > before[0]
    assert count("mxtpu_moe_group_limited_total") >= before[1] + 2
    (text,) = [t for model_id, t in jit.compiled_train_programs()
               if model_id == step._model_id]
    for scope in ("mla_down", "mla_up", "mla_rope", "mla_gate", "kda_decay",
                  "kda_gate_norm", "delta_rule", "router_groups"):
        paths = [l for l in text.splitlines() if "/" + scope + "/" in l]
        assert paths, scope
        if scope != "router_groups":            # the choice has no gradient
            assert any("transpose(" in l for l in paths), scope
    assert any("/router/router_groups/" in l for l in text.splitlines())
    for stem in ("kimideltaattention", "multiheadlatentattention",
                 "sharedexpertmoe", "swiglu"):
        assert stem in text


def test_the_routes_counted_at_build_are_the_train_steps(monkeypatch):
    """perfbench/builders/ling3_lm.py refuses by the counters it reads
    across `balance_routers` (each block's compiled forward on the step's
    own shapes): the labels counted there are the labels the train step's
    forward, recomputation and backward count, the route being a function
    of shape, type and platform alone."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    labels = [("mxtpu_delta_rule_total", {"path": p})
              for p in ("pallas", "xla")] \
        + [("mxtpu_latent_attention_total", {"route": r})
           for r in ("streamed", "composite")]

    def counts():
        return [telemetry.REGISTRY.get(name).value(**label)
                for name, label in labels]

    def taken(before):
        return [now > was for now, was in zip(counts(), before)]

    net = build(dtype="bfloat16", remat=True, bias_rate=0.05)
    tokens, targets = batch(s=128)
    before = counts()
    builder.balance_routers(net, tokens)
    at_build = taken(before)
    assert any(at_build[:2]) and any(at_build[2:])
    view = models.FeaturesView(net)
    trainer = gluon.Trainer(view.collect_params(), "adam",
                            {"learning_rate": 1e-3, "multi_precision": True})
    before = counts()
    jit.TrainStep(view, models.ChunkedUntiedLMLoss(net), trainer)(
        nd.array(tokens), nd.array(targets)).asnumpy()
    assert taken(before) == at_build


# ------------------------------------------------------------------- pins
#: sha256 (16 digits) of the lowered text of a tiny Solar Open 2 train step
#: and of str(jaxpr) of grad(MoELayer), taken on the tree of PR 47 before
#: `KimiDeltaAttention` grew `rank="full"` / `decay=` and `MoELayer`
#: `n_group=` / `topk_group=`: the defaults trace what they traced.
#: "solar" re-taken at PR 50 (495dfe07f6103be8 before): the step hands out
#: its three layers' rows an expert, ONE `stablehlo.concatenate` of three
#: (4,) int32 and one more result; every other line is the parent's but
#: for value numbers (tests/test_step_counters.py holds that).
#: "solar" RE-TAKEN at PR 51 (`d6c08dc62b8f5ead` before): `KimiDeltaAttention`
#: has ONE form, every per-head stage on (b, s, h d) (the K block's
#: reshapes to (b, s, h, d) were passes over the array on the chip, 40 ms
#: of the Ling cell's step), on this CPU step too, where the rule's XLA
#: form runs and the op splits the heads off itself. What moved in the
#: text, two K layers forward, recomputed and backward: the sums over a
#: head's channels are taken on the view (b, s / 8, h, 8, d) and broadcast
#: back (8 613 -> 8 729 lines: `reshape` 464 -> 520, `transpose` 181 ->
#: 229, `broadcast_in_dim` +8; s = 80 is whole tiles of 8, so no pad),
#: exp(A_log) is repeated to a channel each and the norm's gain tiled to
#: (h d) (`constant` +2, `reduce` +2); no other op's count moved. The values
#: are held by tests/test_solar_open2.py (outputs and gradients against
#: the block by heads as PR 50 had it, on both schedules).
PARENT = {"solar": "b70ccc94ca220f29", "sigmoid_bias": "cd7561396b0bef12",
          "softmax": "8556de4088ad355e"}


def _digest(text):
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def test_solar_open2s_train_step_is_the_program_it_was():
    mx.random.seed(0)
    net = models.SolarOpen2Model(
        128, 64, "GKK",
        delta=dict(num_heads=16, head_dim=16, chunk=16, shards=8),
        attention=dict(num_heads=2, num_kv_heads=1, head_dim=16,
                       attention="dense"),
        moe=dict(num_experts=16, ffn_hidden=24, top_k=4, shared_hidden=24,
                 held=(4, 4), bias_rate=0.05),
        remat_layers=True)
    net.initialize(mx.init.Xavier())
    net.cast("bfloat16")
    view = models.FeaturesView(net)
    trainer = gluon.Trainer(view.collect_params(), "adam",
                            {"learning_rate": 1e-3, "multi_precision": True})
    step = jit.TrainStep(view, models.ChunkedUntiedLMLoss(net), trainer)
    x = jax.ShapeDtypeStruct((2, 80), jnp.int32)
    assert _digest(step.lower(x, x).as_text()) == PARENT["solar"]


@pytest.mark.parametrize("router, kwargs", [
    ("sigmoid_bias", dict(held=(4, 4), bias_rate=0.05, scale=2.5)),
    ("softmax", {})])
def test_a_router_without_groups_is_the_program_it_was(router, kwargs):
    mx.random.seed(0)
    layer = MoELayer(16, 32, 24, top_k=4, activation="silu", gated=True,
                     router=router, **kwargs)
    layer.initialize(mx.init.Xavier())

    def loss(x, *ws):
        for p, w in zip(layer._weights(), ws):
            p._data = w
        y = layer(NDArray(x))
        y = y[0] if isinstance(y, tuple) else y
        return y._data.astype(jnp.float32).sum()

    ws = [w._data for w in layer._weights()]
    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1)))(
        jnp.zeros((40, 32), jnp.float32), *ws))
    assert _digest(text) == PARENT[router]
