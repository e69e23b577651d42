"""Solar Open 2 on the CPU at small widths, seeded: the model against the
float32 reference the benchmark uses
(perfbench/reference/solar-open2-250b.py) in value, loss and every checked
gradient (the gradients: tests/test_gradients_solar_open2.py, a file of its
own so that a second worker of the tier-1 run takes it); the shares of
guide section 4 (8 head shards of each mixer, 40 expert shares with the
shared expert counted once) against the uncut layer; what the reference
hands out to be compared; the K block (every per-head stage on (b, s, h d))
against the block by heads as PR 50 had it; the gate on the attention
heads; the names the streamed kernels trace from the `G` layer; and the
normal path (TrainStep, every layer recomputed) with its scopes.
"""
import importlib.util
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, jit, models, nd
from incubator_mxnet_tpu.ops import attention

PERFBENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench")


def _load(kind, name):
    if PERFBENCH not in sys.path:
        sys.path.insert(0, PERFBENCH)
    spec = importlib.util.spec_from_file_location(
        "solar_open2_test_" + kind, os.path.join(PERFBENCH, kind,
                                                 name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = _load("reference", "solar-open2-250b")
builder = _load("builders", "solar_open2_lm")

SHARDS, ROUTED, HELD = 8, 16, 4
CFG = {"hidden_size": 64, "head_dim": 16, "num_attention_heads": 16,
       "num_key_value_heads": 8,
       "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                              "num_heads": 16, "num_kv_heads": None},
       "moe_intermediate_size": 24, "n_shared_experts": 1,
       "num_experts_per_tok": 4, "norm_topk_prob": True,
       "routed_scaling_factor": 1, "rms_norm_eps": 1e-5,
       "kda_allow_neg_eigval": True, "vocab_size": 128,
       "layer_pattern_run": "GKK", "first_held_expert": 4,
       "mixer_shards": SHARDS, "n_routed_experts": HELD,
       "reduced_from": {"n_routed_experts": ROUTED}, "delta_rule_chunk": 16}
B, S = 2, 80        # five chunks of the delta rule


def build(cfg=CFG, dtype=None, seed=0, remat=False, shards=SHARDS,
          held=(CFG["first_held_expert"], HELD), routed=ROUTED,
          attention="dense", bias_rate=None):
    mx.random.seed(seed)
    lin = cfg["linear_attn_config"]
    net = models.SolarOpen2Model(
        cfg["vocab_size"], cfg["hidden_size"], cfg["layer_pattern_run"],
        delta=dict(num_heads=lin["num_heads"], head_dim=lin["head_dim"],
                   chunk=cfg["delta_rule_chunk"], shards=shards),
        attention=dict(num_heads=cfg["num_attention_heads"] // shards,
                       num_kv_heads=max(
                           1, cfg["num_key_value_heads"] // shards),
                       head_dim=cfg["head_dim"], attention=attention),
        moe=dict(num_experts=routed,
                 ffn_hidden=cfg["moe_intermediate_size"],
                 top_k=cfg["num_experts_per_tok"],
                 shared_hidden=cfg["moe_intermediate_size"], held=held,
                 bias_rate=bias_rate),
        remat_layers=remat)
    net.initialize(mx.init.Xavier())
    head = net.lm_head.weight
    head.set_data(head.data() * 4.0)      # logits that depend on the features
    for name, p in net.collect_params().items():
        # gains and selection biases that are not all 1 or 0, so a
        # misplaced one shows
        if name.endswith("gamma"):
            p.set_data(p.data() * nd.random.uniform(0.5, 1.5, p.shape))
        if name.endswith("router_bias"):
            p.set_data(nd.random.uniform(-0.2, 0.2, p.shape))
    if dtype:
        net.cast(dtype)
    return net


def batch(seed=0, cfg=CFG, s=S):
    ids = onp.random.RandomState(seed).randint(
        0, cfg["vocab_size"], (B, s + 1)).astype("int32")
    return ids[:, :-1], ids[:, 1:]


def rel_rms(got, want):
    got, want = (onp.asarray(x, onp.float32) for x in (got, want))
    return float(onp.sqrt(onp.mean((got - want) ** 2))
                 / onp.sqrt(onp.mean(want ** 2)))


def _set(pairs, values):
    for param, name in pairs:
        param.set_data(nd.array(onp.asarray(values[name])))


# ------------------------------------------------------------- the shares
def _kda_shard(whole, r, shards, cfg=CFG):
    """Rank r's rows of a whole mixer's parameters (reference layout): its
    heads' q, k, v, b rows and rank -> heads x d columns; the two
    units -> rank maps whole."""
    lin = cfg["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    inner, rank = heads * d, whole["decay_up"].shape[1]
    take = lambda start, size: onp.arange(                    # noqa: E731
        start + r * size // shards, start + (r + 1) * size // shards)
    mine = take(0, inner)
    conv = onp.concatenate([take(0, inner), take(inner, inner),
                            take(2 * inner, inner)])
    rows = onp.concatenate([conv, 3 * inner + onp.arange(2 * rank),
                            take(3 * inner + 2 * rank, heads)])
    return {"in_proj": whole["in_proj"][rows], "conv_w": whole["conv_w"][conv],
            "decay_up": whole["decay_up"][mine],
            "gate_up": whole["gate_up"][mine],
            "A_log": whole["A_log"][take(0, heads)],
            "dt_bias": whole["dt_bias"][mine],
            "gate_norm": whole["gate_norm"],
            "out_proj": whole["out_proj"][:, mine]}


def _kda_params(block):
    return ((block.in_proj.weight, "in_proj"), (block.conv_weight, "conv_w"),
            (block.decay_up, "decay_up"), (block.gate_up, "gate_up"),
            (block.A_log, "A_log"), (block.dt_bias, "dt_bias"),
            (block.norm_gamma, "gate_norm"),
            (block.out_proj.weight, "out_proj"))


def test_delta_rule_shards_add_up_to_the_whole_mixer():
    """Eight shards of two heads each (the system's blocks, each told it
    is one of eight) sum to the whole mixer's output as the reference
    computes it from the whole parameters: heads do not talk to each
    other, and the two units -> rank maps are every rank's. Float32; 2e-5
    rel-rms is summation order."""
    whole = builder.reference_params(build(shards=1))["layers"][1]
    u = jnp.asarray(onp.random.default_rng(0).standard_normal(
        (B, S, CFG["hidden_size"])), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = reference.kda(whole, u, CFG)
        total = 0
        for r in range(SHARDS):
            block = build(shards=SHARDS).layers[1].mixer
            assert (block.heads, block.inner) == (2, 32)
            shard = _kda_shard(whole, r, SHARDS)
            _set(_kda_params(block), shard)
            got = block(nd.array(onp.asarray(u)))._data
            # the system's shard is the reference's shard
            assert rel_rms(got, reference.kda(shard, u, CFG)) < 2e-5
            total = total + got
    assert rel_rms(total, want) < 2e-5


def test_attention_shards_add_up_to_the_whole_layer():
    """Gated grouped-query attention with 16 query heads on 8 key-value
    heads, cut into 8 shards of 2 on 1: the shards' out-projections sum
    to the whole layer's (the gate is a channel's, so it divides with the
    heads)."""
    whole = builder.reference_params(build(shards=1))["layers"][0]
    d = CFG["head_dim"]
    u = jnp.asarray(onp.random.default_rng(1).standard_normal(
        (B, S, CFG["hidden_size"])), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = reference.attention(whole, u, CFG)
        total = 0
        for r in range(SHARDS):
            block = build(shards=SHARDS).layers[0].mixer
            q = slice(r * 2 * d, (r + 1) * 2 * d)
            kv = slice(r * d, (r + 1) * d)
            shard = {"q": whole["q"][q], "k": whole["k"][kv],
                     "v": whole["v"][kv], "gate": whole["gate"][q],
                     "o": whole["o"][:, q]}
            _set(((block.query.weight, "q"), (block.key.weight, "k"),
                  (block.value.weight, "v"), (block.gate.weight, "gate"),
                  (block.proj.weight, "o")), shard)
            got = block(nd.array(onp.asarray(u)))._data
            assert rel_rms(got, reference.attention(shard, u, CFG)) < 2e-5
            total = total + got
    assert rel_rms(total, want) < 2e-5


def test_expert_shares_add_up_to_the_uncut_layer():
    """The guide's share test at the deployment's count: 40 chips hold 2
    of 80 experts each; every one computes the router and the shared
    expert alike. The routed parts all 40 give, with the shared expert
    counted once, add up to what the reference gives for the layer with
    all 80 experts; and each share is the reference's same share."""
    routed, held = 80, 2
    layer = build(held=None, routed=routed).layers[1]
    whole = builder.reference_params(
        build(held=None, routed=routed))["layers"][1]
    u_np = onp.random.default_rng(2).standard_normal(
        (B, S, CFG["hidden_size"])).astype("float32")
    u = jnp.asarray(u_np)

    def pairs(block):
        return ((block.moe.gate_weight, "router"),
                (block.moe.router_bias, "router_bias"),
                (block.moe.w1, "w1"), (block.moe.w2, "w2"),
                (block.moe.w3, "w3"),
                (block.shared.gate_up.weight, "shared_gate_up"),
                (block.shared.down.weight, "shared_down"))

    with jax.default_matmul_precision("highest"):
        want = reference.experts(whole, u, CFG, 0)
        shared = reference.experts(whole, u, CFG, routed=False)
        # the whole layer through the system's held=None path
        assert rel_rms(layer.experts(nd.array(u_np))._data, want) < 2e-5
        block = build(held=(0, held), routed=routed).layers[1].experts
        total = 0
        for first in range(0, routed, held):
            block.moe.held = (first, held)
            share = dict(whole, **{n: whole[n][first:first + held]
                                   for n in ("w1", "w2", "w3")})
            _set(pairs(block), share)
            got = block(nd.array(u_np))._data
            assert rel_rms(got, reference.experts(share, u, CFG, first)) \
                < 2e-5
            total = total + (got - shared)
    assert rel_rms(total + shared, want) < 2e-5


# ------------------------------------------------------------- the model
def test_pattern_string_builds_the_layers_it_names():
    net = build()
    kinds = {"K": models.KimiDeltaAttention,
             "G": models.GatedGroupedQueryAttention}
    assert [type(l.mixer) for l in net.layers] \
        == [kinds[c] for c in CFG["layer_pattern_run"]]
    assert all(isinstance(l.experts, models.SharedExpertMoE)
               for l in net.layers)
    moe = net.layers[1].experts.moe
    assert moe.w1.shape == moe.w3.shape == (HELD, 64, 24)
    assert moe.gate_weight.shape == (ROUTED, 64)
    with pytest.raises(ValueError):
        models.SolarOpen2Model(8, 8, "GMK", {}, {}, {})
    with pytest.raises(ValueError):
        models.KimiDeltaAttention(64, 6, 8, shards=4)   # half a head


def test_initialisation_and_what_stays_float32():
    mixer = build().layers[1].mixer
    a = onp.exp(mixer.A_log.data().asnumpy())
    assert ((a >= 1) & (a <= 16)).all()
    dt = onp.log1p(onp.exp(mixer.dt_bias.data().asnumpy()))   # softplus
    assert ((dt >= 1e-3 * 0.999) & (dt <= 0.1 * 1.001)).all()
    assert mixer.dt_bias.shape == (mixer.inner,)       # a decay a channel
    net = build(dtype="bfloat16")
    mixer = net.layers[1].mixer
    assert {str(p.data().dtype) for p in (mixer.A_log, mixer.dt_bias,
                                          mixer.norm_gamma)} == {"float32"}
    assert str(net.layers[1].experts.moe.router_bias.data().dtype) \
        == "float32"
    assert str(mixer.in_proj.weight.data().dtype) == "bfloat16"


def test_parameter_count_is_the_builders():
    have = sum(int(onp.prod(p.shape))
               for p in build().collect_params().values())
    assert have == builder.parameter_count(CFG)


def test_float32_model_matches_the_reference():
    """Forward and loss of the share (4 of 16 experts from the 4th on, one
    of eight mixer shards), float32 at "highest", the dense attention
    path. Both sides compute the same function in float32: 5e-5 rel-rms
    on the final norm's output allows three layers' summation order and no
    more; the router's choices are then identical."""
    net = build()
    tokens, labels = batch()
    with jax.default_matmul_precision("highest"):
        params = builder.reference_params(net)
        want_out = reference.features(params, CFG, tokens)
        _, want_loss = reference.forward(params, CFG, tokens, labels, S)
        got = net.features(nd.array(tokens))._data
        loss = models.ChunkedUntiedLMLoss(net)(
            nd.array(onp.asarray(got)), nd.array(labels)).asnumpy()
    assert rel_rms(got, want_out) < 5e-5
    onp.testing.assert_allclose(loss, want_loss, rtol=2e-5)


def test_bfloat16_trunk_stays_near_the_reference():
    """The cell's own comparison at the tiny size: the continuous trunk in
    bfloat16 weights and activations against the float32 reference of the
    same (rounded) weights."""
    net = build(dtype="bfloat16")
    tokens, labels = batch()
    want, _ = reference.forward(builder.reference_params(net), CFG, tokens,
                                labels, S)
    got = builder.continuous_trunk(net)(nd.array(tokens))._data
    assert rel_rms(got, want) < 0.05


def test_what_the_reference_hands_out_to_be_compared():
    """`forward`'s features are the trunk's with the routed sum left out —
    the same as the whole model's with every routed down-projection
    zeroed, and not the whole model's —, the last `tail` positions of
    them, and what the builder's `continuous_trunk` computes; its loss is
    the whole model's."""
    net = build()
    params = builder.reference_params(net)
    tokens, labels = batch()
    tail = 7
    out, loss = reference.forward(params, CFG, tokens, labels, tail)
    whole = reference.features(params, CFG, tokens)
    silenced = dict(params, layers=[dict(l, w2=jnp.zeros_like(l["w2"]))
                                    for l in params["layers"]])
    assert out.shape == (B, tail, CFG["hidden_size"])
    onp.testing.assert_allclose(
        out, reference.features(silenced, CFG, tokens)[:, -tail:],
        rtol=0, atol=1e-6)
    assert rel_rms(out, whole[:, -tail:]) > 0.02
    with jax.default_matmul_precision("highest"):
        trunk = builder.continuous_trunk(net)(nd.array(tokens))._data
        logp = jax.nn.log_softmax(whole @ params["head"].T, -1)
    assert rel_rms(trunk[:, -tail:], out) < 5e-5
    want = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0].mean(-1)
    onp.testing.assert_allclose(loss, want, rtol=2e-5)


@pytest.mark.parametrize("remat", [False, True])
def test_every_train_step_moves_every_routers_bias_by_the_rule(remat):
    """`moe["bias_rate"]`: the layers hand the moved bias out of their
    (recomputed) forward and the model books it as the step's auxiliary
    update: after a step every layer's bias is what the rule makes of that
    step's loads; the features, the loss and the first update are those of
    the model without the rule; outside training nothing moves."""
    rate = 0.05
    plain, ruled = (build(remat=remat, bias_rate=r) for r in (None, rate))
    tokens, labels = batch()
    before = [l.experts.moe.router_bias.data().asnumpy() for l in ruled.layers]
    onp.testing.assert_array_equal(
        ruled.features(nd.array(tokens)).asnumpy(),
        plain.features(nd.array(tokens)).asnumpy())
    for l, b in zip(ruled.layers, before):
        onp.testing.assert_array_equal(
            l.experts.moe.router_bias.data().asnumpy(), b)

    def loads():
        """Each layer's choices on `tokens` from the weights as they are."""
        out, x = [], ruled.tok_embed(nd.array(tokens))
        for l in ruled.layers:
            x = x + l.mixer(l.norm1(x))
            u = l.norm2(x)
            moe = l.experts.moe
            _, _, _, idx = moe.route(
                u._data.reshape(-1, u.shape[-1]), moe.gate_weight.data()._data,
                moe.router_bias.data()._data)
            out.append(onp.bincount(onp.asarray(idx).reshape(-1),
                                    minlength=ROUTED))
            x = x + l.experts(u)[0]
        return out

    want = [b + rate * onp.log((B * S * CFG["num_experts_per_tok"] / ROUTED)
                               / onp.maximum(load, 1))
            for b, load in zip(before, loads())]
    losses = []
    for net in (plain, ruled):
        view = models.FeaturesView(net)
        trainer = gluon.Trainer(view.collect_params(), "adam",
                                {"learning_rate": 1e-3})
        step = jit.TrainStep(view, models.ChunkedUntiedLMLoss(net), trainer)
        losses.append(step(nd.array(tokens), nd.array(labels)).asnumpy())
    onp.testing.assert_array_equal(*losses)
    for a, b in zip(plain.collect_params().values(),
                    ruled.collect_params().values()):
        if not a.name.endswith("router_bias"):
            onp.testing.assert_array_equal(a.data().asnumpy(),
                                           b.data().asnumpy())
    for l, w, b in zip(ruled.layers, want, before):
        got = l.experts.moe.router_bias.data().asnumpy()
        onp.testing.assert_allclose(got, w, rtol=1e-6, atol=1e-7)
        assert onp.abs(got - b).max() > 0
    for l, b in zip(plain.layers, before):
        onp.testing.assert_array_equal(
            l.experts.moe.router_bias.data().asnumpy(), b)


# ------------------------------------- the K block against the block by heads
def _mix_by_heads(block, proj, conv_w, a_log, dt_bias, gamma, decay_up=None,
                  gate_up=None):
    """`KimiDeltaAttention._mix` as PR 50 had it, the plain reference of
    the block: every per-head stage on (b, s, h, d), the sums
    over a head `jnp.sum(.., -1)`, the rule by its 4-D entry."""
    from incubator_mxnet_tpu.models.solar_open2 import _L2_EPS
    from incubator_mxnet_tpu.ops.delta_rule import gated_delta_rule
    b, s, _ = proj.shape
    inner, r, h, d = block.inner, block._map_in, block.heads, block.head_dim
    f32 = jnp.float32
    qkv, low_f, low_g, b_in = jnp.split(
        proj, [3 * inner, 3 * inner + r, 3 * inner + 2 * r], -1)
    padded = jnp.pad(qkv, [(0, 0), (block._k - 1, 0), (0, 0)])
    acc = sum(padded[:, j:j + s].astype(f32) * conv_w.astype(f32)[:, j]
              for j in range(block._k))
    q, k, v = (t.reshape(b, s, h, d)
               for t in jnp.split(jax.nn.silu(acc), 3, -1))

    def unit(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + _L2_EPS)

    pre = low_f.astype(f32) if decay_up is None else jnp.einsum(
        "bsr,cr->bsc", low_f, decay_up, preferred_element_type=f32)
    pre = (pre + dt_bias.astype(f32)).reshape(b, s, h, d)
    rate = jnp.exp(a_log.astype(f32))[:, None]
    g = -rate * jax.nn.softplus(pre) if block._lower is None \
        else block._lower * jax.nn.sigmoid(rate * pre)
    beta = block._beta_max * jax.nn.sigmoid(b_in.astype(f32))
    o = gated_delta_rule(unit(q) * d ** -0.5, unit(k), v.astype(qkv.dtype),
                         g, beta, block._chunk).astype(f32)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + block._eps) \
        * gamma.astype(f32)
    gate = jax.nn.sigmoid(low_g.astype(f32) if gate_up is None else jnp.einsum(
        "bsr,cr->bsc", low_g, gate_up, preferred_element_type=f32))
    return (o.reshape(b, s, inner) * gate).astype(proj.dtype)


KDA_FORMS = {"solar": {}, "ling": dict(rank="full", decay=("bounded", -5.0),
                                       neg_eigval=False)}


@pytest.mark.parametrize("kernels", [True, False], ids=["kernels", "xla"])
@pytest.mark.parametrize("heads", [4, 32])
@pytest.mark.parametrize("form", sorted(KDA_FORMS))
def test_the_k_block_in_lanes_is_the_block_by_heads(monkeypatch, form, heads,
                                                    kernels):
    """`KimiDeltaAttention._mix` in float32 against `_mix_by_heads`, output
    and every gradient (in_proj's output, taps, A_log, dt_bias, the norm's
    gain, the low-rank maps), batch 2 and 44 positions (no whole chunks of
    16, no whole tiles of 8): the block computes on (b, s, h d) and sums a
    head's channels on the tiles' view, whichever schedule the rule takes
    (the kernel pair interpreted, heads of 128; or the XLA form, for which
    the op splits the heads off)."""
    from incubator_mxnet_tpu.ops import delta_rule
    if kernels:
        monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    else:
        monkeypatch.delenv("MXTPU_FLASH_INTERPRET", raising=False)
    mx.random.seed(heads)
    block = models.KimiDeltaAttention(32, heads, 128, chunk=16,
                                      **KDA_FORMS[form])
    block.initialize(mx.init.Xavier())
    rng = onp.random.default_rng(heads)
    for p in (block.norm_gamma, block.A_log):   # not all ones, not all alike
        p.set_data(p.data() * nd.array(rng.uniform(0.5, 1.5, p.shape)))
    own = (block.conv_weight, block.A_log, block.dt_bias, block.norm_gamma) \
        + ((block.decay_up, block.gate_up) if block.rank else ())
    args = [jnp.asarray(rng.standard_normal(
        (B, 44, block.in_proj.weight.shape[0])), jnp.float32)] \
        + [p.data()._data for p in own]
    w = jnp.asarray(rng.standard_normal((B, 44, block.inner)), jnp.float32)
    every = tuple(range(len(args)))
    counted = delta_rule._CALLS.value(path="pallas" if kernels else "xla")
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(
            lambda *a: (block._mix(*a) * w).sum(), every))(*args)
        want = jax.jit(jax.value_and_grad(
            lambda *a: (_mix_by_heads(block, *a) * w).sum(), every))(*args)
        out = jax.jit(block._mix)(*args)
        assert out.shape == w.shape
        assert rel_rms(out, jax.jit(
            lambda *a: _mix_by_heads(block, *a))(*args)) < 1e-5
    assert delta_rule._CALLS.value(
        path="pallas" if kernels else "xla") == counted + 4
    for mine, theirs in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(want)):
        assert mine.shape == theirs.shape
        assert onp.abs(onp.asarray(theirs)).max() > 0
        assert rel_rms(mine, theirs) < 1e-5


def test_the_gate_is_on_the_heads_output_a_channel_at_a_time():
    """A gate weight of zero halves the heads' outputs (sigmoid(0)); a
    large positive row opens that channel and no other."""
    block = build(shards=1).layers[0].mixer
    plain = models.GroupedQueryAttention(
        CFG["hidden_size"], 16, 8, CFG["head_dim"], attention="dense")
    plain.initialize()
    for a, b in ((plain.query, block.query), (plain.key, block.key),
                 (plain.value, block.value), (plain.proj, block.proj)):
        a.weight.set_data(b.weight.data())
    x = nd.array(onp.random.default_rng(3).standard_normal(
        (B, S, CFG["hidden_size"])).astype("float32"))
    block.gate.weight.set_data(nd.zeros(block.gate.weight.shape))
    onp.testing.assert_allclose(block(x).asnumpy(), 0.5 * plain(x).asnumpy(),
                                rtol=1e-5, atol=1e-6)
    onp.testing.assert_allclose(
        block.heads_output(x).asnumpy(), plain.heads_output(x).asnumpy())


def test_the_g_layer_traces_the_kernels_the_nemotron_cell_does(monkeypatch):
    """`window`-free calls from `G`: the three streamed kernels under the
    names every causal cell traces (flash_fwd, flash_bwd_dkv,
    flash_bwd_dq), k and v repeated to the query heads, no window kernel."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    cfg = dict(CFG, head_dim=128)
    block = build(cfg, dtype="bfloat16", attention="flash").layers[0].mixer
    same = models.GroupedQueryAttention(CFG["hidden_size"], 2, 1, 128,
                                        attention="flash")
    same.initialize()
    same.cast("bfloat16")
    x = onp.random.default_rng(4).standard_normal(
        (1, 1024, CFG["hidden_size"])).astype("float32")

    def kernels(blk):
        def loss(xd):
            return blk(nd.NDArray(xd))._data.astype(jnp.float32).sum()
        return sorted(set(re.findall(r"name=(flash_\w+)", str(
            jax.make_jaxpr(jax.grad(loss))(jnp.asarray(x, jnp.bfloat16))))))

    assert attention.attention_route((1, 2, 1024, 128)) != "short"
    names = kernels(block)
    assert names == kernels(same)
    assert "flash_fwd" in names and len(names) >= 2
    assert not any("window" in n or "short" in n for n in names)


def test_one_train_step_lowers_once_and_keeps_the_scopes_under_recompute(
        monkeypatch):
    """The normal path (FeaturesView + ChunkedUntiedLMLoss through
    TrainStep, bfloat16 with float32 masters, the interpreted streamed
    kernels, every layer recomputed): one program, a falling loss, and the
    blocks' and the op's names on forward, recomputed and backward ops."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    cfg = dict(CFG, head_dim=128, hidden_size=128)
    net = build(cfg, dtype="bfloat16", remat=True, attention="flash")
    view = models.FeaturesView(net)
    trainer = gluon.Trainer(view.collect_params(), "adam",
                            {"learning_rate": 1e-3, "multi_precision": True})
    step = jit.TrainStep(view, models.ChunkedUntiedLMLoss(net), trainer)
    tokens, labels = batch(cfg=cfg, s=128)
    losses = [float(step(nd.array(tokens), nd.array(labels)).asnumpy().mean())
              for _ in range(4)]
    assert losses[-1] < losses[0]
    (text,) = [t for model_id, t in jit.compiled_train_programs()
               if model_id == step._model_id]
    for scope in ("delta_rule", "kda_conv", "kda_decay", "kda_gate_norm",
                  "gqa_gate", "moe_dispatch", "moe_combine", "ffn", "router"):
        paths = [l for l in text.splitlines() if "/" + scope + "/" in l]
        assert any("rematted_computation" in l or "/checkpoint/" in l
                   for l in paths), scope
        assert any("transpose(" in l for l in paths), scope
        assert any("transpose(" not in l for l in paths), scope
    for stem in ("kimideltaattention", "gatedgroupedqueryattention",
                 "sharedexpertmoe"):
        assert stem in text
