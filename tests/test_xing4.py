"""Xing 4.0 on the CPU at small widths, seeded: the hyper-connection against
the float32 reference the benchmark uses
(perfbench/reference/xing4.0-29b-a4b.py, a position at a time) in value and
gradient through the twenty Sinkhorn rounds; multi-head latent attention
with a low-rank query, no QK-norm, no gate, a YaRN table and the scale times
m^2 against the per-head form; the YaRN table itself; the shares of guide
section 4 (the expert shares with the shared expert counted once) against
the uncut layer; what stays float32 under a bfloat16 cast; and the pin that
holds Ling 3.0's traced program to what it was before
`MultiHeadLatentAttention` grew its arguments and `MixerStackLM` its two
hooks. The model's loss and checked gradients and the normal path through
TrainStep: tests/test_gradients_xing4.py (a file of its own, early in the
alphabet: under `--dist loadfile` the last file to end sets the run's time).
"""
import hashlib
import importlib.util
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, jit, models, nd, telemetry
from incubator_mxnet_tpu.models import ling3
from incubator_mxnet_tpu.ndarray import NDArray

PERFBENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench")


def _load(kind, name):
    if PERFBENCH not in sys.path:
        sys.path.insert(0, PERFBENCH)
    spec = importlib.util.spec_from_file_location(
        "xing4_test_" + kind, os.path.join(PERFBENCH, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = _load("reference", "xing4.0-29b-a4b")
builder = _load("builders", "xing4_lm")

ROUTED, HELD, FIRST = 32, 4, 8
YARN = {"type": "yarn", "factor": 64, "original_max_position_embeddings": 16,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
#: widths 24 / 16 stand in for 192 / 128
CFG = {"hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 24,
       "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
       "v_head_dim": 16, "rope_theta": 10000, "rope_scaling": YARN,
       "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
       "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
       "intermediate_size": 96, "moe_intermediate_size": 24,
       "n_shared_experts": 1, "num_experts_per_tok": 4, "n_group": 1,
       "topk_group": 1, "norm_topk_prob": True, "routed_scaling_factor": 2,
       "rms_norm_eps": 1e-6, "vocab_size": 128, "num_layers": 2,
       "first_k_dense_replace": 1, "first_held_expert": FIRST,
       "n_routed_experts": HELD, "reduced_from": {"n_routed_experts": ROUTED},
       "router_bias_rate": None, "init_head_scale": 4.0,
       "hc_init": {"weight_std_units": 1.0, "b_res_diagonal": 2.0,
                   "a": [0.7, 1.3, 1.0]}}
B, S = 2, 48
STREAM = CFG["hc_mult"] * CFG["hidden_size"]


def build(cfg=CFG, dtype=None, seed=0, remat=False, **over):
    cfg = dict(cfg, **over)
    mx.random.seed(seed)
    net = builder.make_model(cfg, remat=remat)
    net.initialize(mx.init.Xavier())
    builder.init_hyper_connections(net, cfg, seed)
    head = net.lm_head.weight
    head.set_data(head.data() * cfg["init_head_scale"])
    for name, p in net.collect_params().items():
        # gains, map biases and selection biases that are not all 1 or 0,
        # so a misplaced one shows
        if name.endswith("gamma"):
            p.set_data(p.data() * nd.random.uniform(0.5, 1.5, p.shape))
        if name.endswith("router_bias"):
            p.set_data(nd.random.uniform(-0.2, 0.2, p.shape))
        if "hyperconnection" in name and name.endswith("bias"):
            p.set_data(p.data() + nd.random.uniform(-0.5, 0.5, p.shape))
    if dtype:
        net.cast(dtype)
    return net


def batch(seed=0, s=S):
    ids = onp.random.RandomState(seed).randint(
        0, CFG["vocab_size"], (B, s + 1)).astype("int32")
    return ids[:, :-1], ids[:, 1:]


def inputs(seed=3, width=CFG["hidden_size"]):
    return onp.random.default_rng(seed).standard_normal(
        (B, S, width)).astype("float32")


def close(got, want, tol=1e-4):
    got, want = onp.asarray(got), onp.asarray(want)
    assert onp.abs(got - want).max() < tol * onp.abs(want).max()


def _hc_params(hc):
    return {"w": hc.weight.data()._data, "b": hc.bias.data()._data,
            "a": hc.scale.data()._data}


def _by_stream(x):
    """(B, S, n C) as the system holds it -> (B, S, n, C)."""
    return x.reshape(x.shape[:2] + (CFG["hc_mult"], -1))


# ------------------------------------------------------- hyper-connections
def test_a_hyper_connection_is_the_references_in_value_and_gradient():
    """One sublayer with F(u) = tanh(u W): X' and the gradients with respect
    to X, P, b and a, through the twenty rounds, against the reference's
    maps a position at a time."""
    hc = build().layers[1].hc_ffn
    x = jnp.asarray(inputs(width=STREAM))
    w = jnp.asarray(onp.random.default_rng(1).standard_normal(
        (CFG["hidden_size"],) * 2).astype("float32")) / 8.0
    weigh = jnp.asarray(inputs(4, STREAM))

    def mine(x, p):
        for param, value in zip((hc.weight, hc.bias, hc.scale),
                                (p["w"], p["b"], p["a"])):
            param.data()._data = value
        u, h_post, h_res = hc(NDArray(x))
        y = NDArray(jnp.tanh(u._data @ w))
        return (hc.write(NDArray(x), y, h_post, h_res)._data * weigh).sum()

    def theirs(x, p):
        out = reference.hyper_sublayer(p, _by_stream(x),
                                       lambda u: jnp.tanh(u @ w), CFG)
        return (out.reshape(x.shape) * weigh).sum()

    p = _hc_params(hc)
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(mine, (0, 1))(x, p)
        want = jax.jit(jax.value_and_grad(theirs, (0, 1)))(x, p)
        for param, value in zip((hc.weight, hc.bias, hc.scale),
                                (p["w"], p["b"], p["a"])):
            param.data()._data = value
    close(got[0], want[0])
    close(got[1][0], want[1][0])
    for name in "wba":
        close(got[1][1][name], want[1][1][name]), name
        assert onp.abs(onp.asarray(want[1][1][name])).max() > 0, name


def test_the_maps_are_a_positions_own_and_hres_is_doubly_stochastic():
    hc = build().layers[0].hc_mixer
    x = inputs(width=STREAM)
    params = [p.data()._data for p in (hc.weight, hc.bias, hc.scale)]
    h_pre, h_post, h_res = (onp.asarray(t) for t in hc.maps(
        jnp.asarray(x), *params))
    assert h_res.shape == (4, 4, B * S) and h_pre.shape == (4, B * S)
    assert (h_res > 0).all()
    onp.testing.assert_allclose(h_res.sum(0), 1.0, atol=1e-5)    # columns
    # the rows: twenty rounds leave them within 1e-4 of 1 at half the
    # positions and within 5e-3 at the worst conditioned ones
    rows = onp.abs(h_res.sum(1) - 1.0).max(0)
    assert onp.median(rows) < 1e-4 and rows.max() < 5e-3
    assert 0 < h_pre.min() and h_pre.max() < 1
    assert 0 < h_post.min() and h_post.max() < 2
    # the maps of different positions differ (the configuration's hc_init)
    assert h_res.std(-1).max() > 0.05
    # another position's stream changes nothing here
    other = x.copy()
    other[1, 7] += 1.0
    again = [onp.asarray(t) for t in hc.maps(jnp.asarray(other), *params)]
    changed = onp.zeros(B * S, bool)
    changed[S + 7] = True
    for was, now in zip((h_pre, h_post, h_res), again):
        assert (onp.abs(now - was).reshape(-1, B * S).max(0) > 0).tolist() \
            == changed.tolist()
    # one position against the reference's one-position function
    want = reference.hyper_maps(_hc_params(hc), jnp.asarray(
        x[1, 7].reshape(4, -1)), CFG)
    for got, ref in zip((h_pre, h_post, h_res), want):
        close(got[..., S + 7], ref)


def test_the_clamp_stands_before_the_exp():
    """A bias of 1000 on one entry of H~res: clipped to 30, the map is
    finite and that entry takes its row and column (to what twenty rounds
    reach); without the clamp exp overflows."""
    hc = build().layers[0].hc_mixer
    bias = hc.bias.data().asnumpy().copy()
    bias[8 + 1 * 4 + 2] = 1000.0                        # Hres[1, 2]
    x = jnp.asarray(inputs(width=STREAM))
    args = (hc.weight.data()._data, jnp.asarray(bias), hc.scale.data()._data)
    h_res = onp.asarray(hc.maps(x, *args)[2])
    assert onp.isfinite(h_res).all() and h_res[1, 2].min() > 0.9
    hc._clamp = (-1e9, 1e9)
    assert not onp.isfinite(onp.asarray(hc.maps(x, *args)[2])).all()


def test_the_streams_are_the_embedding_repeated_and_summed_at_the_end():
    net = build()
    e = nd.array(inputs())
    x = net.stream_in(e).asnumpy()
    assert x.shape == (B, S, STREAM)
    for i in range(4):
        onp.testing.assert_array_equal(_by_stream(x)[:, :, i], e.asnumpy())
    onp.testing.assert_allclose(net.stream_out(nd.array(x)).asnumpy(),
                                4 * e.asnumpy(), rtol=1e-6)


# ------------------------------------------------------------------ mixer
def test_the_yarn_table_is_the_published_keys():
    """rope_scaling of the source: low 10, high 23 of 32 pairs, m^2 2.0048;
    the first eleven frequencies untouched, the last nine divided by 64,
    a linear blend between."""
    assert ling3.yarn_correction_range(64, 10000.0, 4096, 32, 1) == (10, 23)
    m = ling3.yarn_mscale(64, 1)
    assert abs(m - 1.4159) < 1e-4 and abs(m * m - 2.0048) < 1e-3
    assert ling3.yarn_mscale(1.0) == 1.0
    table = onp.array(ling3.yarn_inv_freq(64, 10000.0, 64, 4096, 32, 1))
    plain = 10000.0 ** (-onp.arange(0, 64, 2) / 64)
    onp.testing.assert_allclose(table[:11], plain[:11], rtol=1e-12)
    onp.testing.assert_allclose(table[23:], plain[23:] / 64, rtol=1e-12)
    ramp = (onp.arange(11, 23) - 10) / 13
    onp.testing.assert_allclose(
        table[11:23], plain[11:23] * (1 - ramp) + plain[11:23] / 64 * ramp,
        rtol=1e-12)
    assert (onp.diff(table) < 0).all()
    # the reference's own table, from the same keys
    cfg = dict(CFG, qk_rope_head_dim=64, rope_scaling=dict(
        YARN, original_max_position_embeddings=4096))
    onp.testing.assert_allclose(onp.asarray(reference.inv_freq(cfg)), table,
                                rtol=1e-6)
    assert abs(reference.score_scale(dict(cfg, qk_nope_head_dim=128))
               - 2.0048 / math.sqrt(192)) < 1e-4
    # a table turns the pairs as theta's frequencies would
    x = jnp.asarray(onp.random.default_rng(0).standard_normal(
        (1, 2, 40, 8)).astype("float32"))
    onp.testing.assert_allclose(
        onp.asarray(ling3.rope_interleaved(
            x, None, tuple(1e4 ** (-onp.arange(0, 8, 2) / 8)))),
        onp.asarray(ling3.rope_interleaved(x, 1e4)), rtol=1e-5, atol=1e-5)


def test_the_latent_block_is_the_references_in_value_and_gradient():
    """A low-rank query with its norm, no QK-norm, no gate, YaRN and m^2,
    against the per-head form with k built by concatenation and ONE rotary
    key for all heads: outputs, and the gradient with respect to the
    input."""
    net = build()
    block = net.layers[1].mixer
    assert not hasattr(block, "gate") and not hasattr(block, "q_gain")
    p = builder.reference_params(net)["layers"][1]
    x = inputs()
    with jax.default_matmul_precision("highest"):
        close(block(nd.array(x)).asnumpy(),
              jax.jit(lambda x: reference.mla(p, x, CFG))(jnp.asarray(x)))
        w = jnp.asarray(inputs(4))
        mine = jax.grad(lambda x: (block(NDArray(x))._data * w).sum())(
            jnp.asarray(x))
        want = jax.jit(jax.grad(
            lambda x: (reference.mla(p, x, CFG) * w).sum()))(jnp.asarray(x))
    close(mine, want)
    # what the arguments change shows: plain rotary and the plain scale
    plain = dict(CFG, rope_scaling=dict(YARN, factor=1.000001))
    assert onp.abs(onp.asarray(reference.mla(p, jnp.asarray(x), plain))
                   - block(nd.array(x)).asnumpy()).max() > 1e-3
    with pytest.raises(ValueError):
        models.MultiHeadLatentAttention(64, 4, 32, 16, 8, 16,
                                        inv_freq=(1.0, 0.5))


# ------------------------------------------------------------------ shares
def test_expert_shares_add_up_to_the_uncut_layer():
    """The routed parts of the eight shares `held=(4 j, 4)` of one layer's
    weights, the shared expert counted once, are the uncut layer of the
    uncut reference: the router as wide as ever, the weights normalised
    over all four chosen."""
    whole_cfg = dict(first_held_expert=0, n_routed_experts=ROUTED)
    uncut = build(**whole_cfg)
    whole = uncut.layers[1].experts
    p = builder.reference_params(uncut)["layers"][1]
    x = inputs()
    with jax.default_matmul_precision("highest"):
        want = reference.experts(p, jnp.asarray(x), CFG, first=0)
        shared = whole.shared(nd.array(x)).asnumpy()
        total = shared.copy()
        for j in range(ROUTED // HELD):
            share = build(first_held_expert=HELD * j).layers[1].experts
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


# ------------------------------------------------------------------- model
def test_parameter_count_and_what_stays_float32():
    net = build(dtype="bfloat16")
    params = net.collect_params()
    assert sum(int(onp.prod(p.shape)) for p in params.values()) \
        == builder.parameter_count(CFG)
    f32 = {n for n, p in params.items() if str(p.data().dtype) == "float32"}
    assert all("hyperconnection" in n or n.endswith("router_bias")
               or ("multiheadlatentattention" in n and n.endswith("gamma"))
               for n in f32)
    # three hyper-connection parameters a sublayer, two latent gains a
    # layer, one selection bias
    assert len(f32) == 3 * 2 * 2 + 2 * 2 + 1
    block = net.layers[0].mixer
    assert block.query.weight.shape == (4 * 24, 24)
    assert block.q_down.weight.shape == (24, 64)


# --------------------------------------------------------------------- pin
#: sha256 (16 digits) of the lowered text of a tiny Ling 3.0 train step,
#: taken on the tree of PR 51 before `MultiHeadLatentAttention` grew
#: `q_latent=`, `qk_norm=`, `head_gate=`, `inv_freq=`, `scale=`,
#: `rope_interleaved` its table and `MixerStackLM` `stream_in` /
#: `stream_out`: the defaults trace what they traced.
PARENT_LING = "9893ed5ae1a6cd35"


def test_ling3s_train_step_is_the_program_it_was():
    mx.random.seed(0)
    net = models.Ling3Model(
        128, 64, "KM",
        delta=dict(num_heads=4, head_dim=16, chunk=16, rank="full",
                   decay=("bounded", -5.0), neg_eigval=False),
        latent=dict(num_heads=4, latent=32, nope_dim=16, rope_dim=8,
                    v_dim=16, rope_theta=6e6),
        moe=dict(num_experts=16, ffn_hidden=24, top_k=4, shared_hidden=24,
                 scale=2.5, held=(4, 4), bias_rate=0.05, n_group=4,
                 topk_group=2),
        dense_hidden=96, dense_layers=1, remat_layers=True)
    net.initialize(mx.init.Xavier())
    net.cast("bfloat16")
    view = models.FeaturesView(net)
    trainer = gluon.Trainer(view.collect_params(), "adam",
                            {"learning_rate": 1e-3, "multi_precision": True})
    step = jit.TrainStep(view, models.ChunkedUntiedLMLoss(net), trainer)
    x = jax.ShapeDtypeStruct((2, 80), jnp.int32)
    text = step.lower(x, x).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENT_LING
