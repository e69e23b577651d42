"""Keye-VL 2.0's decoder on the CPU at small widths, seeded: the model
against the float32 reference the benchmark uses
(perfbench/reference/keye-vl-2.0-30b-a3b.py) in value, in both loss terms
and in every checked gradient, on unequal position streams; which loss
moves which weights; the rotary embeddings alone; what the reference hands
out to be compared; and the normal path (TrainStep, every layer recomputed
but for what carries the op's names) with its scopes and its loss that is
the sum of the two terms.
"""
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, jit, models, nd
from incubator_mxnet_tpu.models import keye_vl2

PERFBENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench")


def _load(kind, name):
    if PERFBENCH not in sys.path:
        sys.path.insert(0, PERFBENCH)
    spec = importlib.util.spec_from_file_location(
        "keye_vl2_test_" + kind, os.path.join(PERFBENCH, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = _load("reference", "keye-vl-2.0-30b-a3b")
builder = _load("builders", "keye_vl2_lm")

ROUTED, HELD, FIRST = 8, 2, 4
CFG = {"hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
       "num_key_value_heads": 2, "moe_intermediate_size": 24,
       "num_experts_per_tok": 2, "norm_topk_prob": True,
       "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
       "rope_scaling": {"mrope_section": [2, 3, 3]},
       "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 3,
                     "indexer_num_kv_heads": 1, "topk": 24},
       "vocab_size": 128, "num_layers": 2, "num_experts": HELD,
       "first_held_expert": FIRST, "init_head_scale": 4.0,
       "reduced_from": {"num_experts": ROUTED}}
B, S = 2, 80          # 56 queries of each sequence drop keys


def build(cfg=CFG, dtype=None, seed=0, remat=False):
    s = builder.shapes(cfg)
    mx.random.seed(seed)
    net = models.KeyeVL2Model(
        cfg["vocab_size"], s["units"], s["layers"],
        attention=dict(
            num_heads=s["q_heads"], num_kv_heads=s["kv_heads"],
            head_dim=s["head_dim"], indexer_heads=s["index_heads"],
            indexer_dim=s["index_dim"], topk=s["topk"],
            rope_theta=cfg["rope_theta"],
            mrope_section=cfg["rope_scaling"]["mrope_section"]),
        moe=dict(num_experts=s["experts_routed"],
                 ffn_hidden=cfg["moe_intermediate_size"],
                 top_k=cfg["num_experts_per_tok"], norm_topk_prob=True,
                 held=(cfg["first_held_expert"], s["experts_held"])),
        epsilon=cfg["rms_norm_eps"], remat_layers=remat)
    net.initialize(mx.init.Xavier())
    head = net.lm_head.weight
    head.set_data(head.data() * cfg["init_head_scale"])
    for name, p in net.collect_params().items():
        # gains and shifts that are not all 1 or 0, so a misplaced one shows
        if name.endswith("gamma"):
            p.set_data(p.data() * nd.random.uniform(0.5, 1.5, p.shape))
        if name.endswith("beta"):
            p.set_data(nd.random.uniform(-0.2, 0.2, p.shape))
    if dtype:
        net.cast(dtype)
    return net


def batch(seed=0, cfg=CFG, s=S):
    ids = onp.random.RandomState(seed).randint(
        0, cfg["vocab_size"], (B, s + 1)).astype("int32")
    return ids[:, :-1], ids[:, 1:]


def streams(s=S):
    """Three position streams that differ, as an image inside a text gives:
    a time stream that stalls, a height and a width that wrap."""
    at = onp.arange(s)
    pos = onp.stack([at - onp.clip(at - 20, 0, 15), at // 3, (at * 5) % 17])
    return onp.broadcast_to(pos[:, None], (3, B, s)).astype("int32").copy()


def rel_rms(got, want):
    got, want = (onp.asarray(x, onp.float32) for x in (got, want))
    return float(onp.sqrt(onp.mean((got - want) ** 2))
                 / onp.sqrt(onp.mean(want ** 2)))


# ------------------------------------------------------------- the rotary
def test_mrope_turns_each_band_by_its_own_stream():
    x = jnp.asarray(onp.random.default_rng(0).standard_normal(
        (B, S, 3, 16)), jnp.float32)
    pos = jnp.asarray(streams())
    got = keye_vl2.mrope(x, pos, 1e4, (2, 3, 3))
    assert rel_rms(got, reference.mrope(x, pos, 1e4, (2, 3, 3))) < 1e-6
    # by hand: frequency i of 8 turns the pair (x[i], x[i + 8])
    inv = 1e4 ** (-onp.arange(8) / 8.0)
    stream = onp.array([0, 0, 1, 1, 1, 2, 2, 2])
    angle = onp.asarray(pos)[stream].transpose(1, 2, 0) * inv     # (B, S, 8)
    a, b = onp.asarray(x)[..., :8], onp.asarray(x)[..., 8:]
    cos, sin = onp.cos(angle)[:, :, None], onp.sin(angle)[:, :, None]
    want = onp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    assert rel_rms(got, want) < 1e-5
    # equal streams are plain rotary positions
    same = jnp.broadcast_to(pos[:1], pos.shape)
    plain = keye_vl2.mrope(x, same, 1e4, (8, 0, 0))
    assert rel_rms(keye_vl2.mrope(x, same, 1e4, (2, 3, 3)), plain) < 1e-6
    assert rel_rms(got, plain) > 0.1
    with pytest.raises(ValueError):
        keye_vl2.mrope(x, pos, 1e4, (2, 3, 4))


def test_the_indexers_rope_turns_the_first_half_by_the_first_stream():
    x = jnp.asarray(onp.random.default_rng(1).standard_normal(
        (B, S, 3, 8)), jnp.float32)
    pos = jnp.asarray(streams())
    got = keye_vl2.rope_first_half(x, pos[0], 1e4)
    assert rel_rms(got, reference.rope_indexer(x, pos, 1e4)) < 1e-6
    onp.testing.assert_array_equal(got[..., 4:], x[..., 4:])
    assert rel_rms(got[..., :4], x[..., :4]) > 0.1
    # a rotation: norms stay
    onp.testing.assert_allclose(jnp.linalg.norm(got, axis=-1),
                                jnp.linalg.norm(x, axis=-1), rtol=1e-5)


# ------------------------------------------------------------- the model
def test_the_blocks_are_the_ones_the_configuration_names():
    net = build()
    attn, moe = net.layers[0].attn, net.layers[0].moe
    assert isinstance(attn, models.SparseGroupedQueryAttention)
    assert attn.query.weight.shape == (64, 64)
    assert attn.key.weight.shape == attn.value.weight.shape == (32, 64)
    assert attn.q_norm.gamma.shape == attn.k_norm.gamma.shape == (16,)
    assert attn.index_q.weight.shape == (24, 64)
    assert attn.index_k.weight.shape == (8, 64)
    assert attn.index_w.weight.shape == (3, 64)
    assert attn.index_k_norm.gamma.shape == (8,)
    assert moe.gate_weight.shape == (ROUTED, 64)
    assert moe.w1.shape == moe.w3.shape == (HELD, 64, 24)
    assert moe.held == (FIRST, HELD)
    have = sum(int(onp.prod(p.shape)) for p in net.collect_params().values())
    assert have == builder.parameter_count(CFG)


@pytest.mark.parametrize("positions", ["text", "unequal"])
def test_float32_model_matches_the_reference(positions):
    """Features, the indexer's KL and the loss that is their sum, float32
    at "highest", on text positions (None: all three streams 0 .. S - 1)
    and on three unequal streams. Both sides compute the same function:
    5e-5 rel-rms allows two layers' summation order, and the chosen keys
    and experts are then the same."""
    net = build()
    tokens, labels = batch()
    pos = None if positions == "text" else streams()
    with jax.default_matmul_precision("highest"):
        params = builder.reference_params(net)
        want, want_li = reference.features(
            params, CFG, tokens, None if pos is None else jnp.asarray(pos))
        lm, li = reference.loss_terms(
            params, CFG, tokens, labels,
            None if pos is None else jnp.asarray(pos))
        got, got_li = net.features(
            nd.array(tokens), None if pos is None else nd.array(pos))
        loss = models.ChunkedUntiedLMLoss(net)(
            (got, got_li), nd.array(labels)).asnumpy()
    assert rel_rms(got._data, want) < 5e-5
    onp.testing.assert_allclose(got_li.asnumpy(), want_li, rtol=2e-5)
    onp.testing.assert_allclose(want_li, li, rtol=1e-6)
    onp.testing.assert_allclose(loss, lm + li, rtol=2e-5)
    assert (onp.asarray(li) > 0.01).all()
    if pos is not None:
        text, _ = net.features(nd.array(tokens))
        assert rel_rms(text._data, want) > 0.01


def test_what_the_reference_hands_out_to_be_compared():
    """`forward`'s features are the trunk's with the experts left out — the
    whole model's with every down-projection zeroed, and not the whole
    model's —, the last `tail` positions of them, and what the builder's
    `continuous_trunk` computes; its loss is the whole model's two terms."""
    net = build()
    params = builder.reference_params(net)
    tokens, labels = batch()
    tail = 7
    with jax.default_matmul_precision("highest"):
        out, loss = reference.forward(params, CFG, tokens, labels, tail)
        whole, _ = reference.features(params, CFG, tokens)
        silenced = dict(params, layers=[
            dict(l, w2=jnp.zeros_like(l["w2"])) for l in params["layers"]])
        quiet, _ = reference.features(silenced, CFG, tokens)
        trunk = builder.continuous_trunk(net)(nd.array(tokens))._data
        lm, li = reference.loss_terms(params, CFG, tokens, labels)
    assert out.shape == (B, tail, CFG["hidden_size"])
    onp.testing.assert_allclose(out, quiet[:, -tail:], rtol=0, atol=1e-6)
    assert rel_rms(out, whole[:, -tail:]) > 0.02
    assert rel_rms(trunk[:, -tail:], out) < 5e-5
    onp.testing.assert_allclose(loss, lm + li, rtol=1e-6)


def test_bfloat16_trunk_stays_near_the_reference():
    """The cell's own comparison at the tiny size: the continuous trunk in
    bfloat16 weights and activations against the float32 reference of the
    same (rounded) weights. (A key that flips at the 24th place carries a
    24th of a row's weight here, a 2048th in the cell: 6.6 % at this size.)"""
    net = build(dtype="bfloat16")
    tokens, labels = batch()
    want, _ = reference.forward(builder.reference_params(net), CFG, tokens,
                                labels, S)
    got = builder.continuous_trunk(net)(nd.array(tokens))._data
    assert rel_rms(got, want) < 0.1


def _grads(net, tokens, labels, term, positions=None):
    """{parameter name: gradient} of the sum over the batch of `term`
    ("lm", "li" or "both") of the system's own loss path."""
    params = [p for _, p in sorted(net.collect_params().items())
              if p.grad_req != "null"]
    loss_fn = models.ChunkedUntiedLMLoss(net)
    pos = None if positions is None else nd.array(positions)

    def fn(datas):
        arrs = [p.data() for p in params]
        saved = [a._data for a in arrs]
        for a, d in zip(arrs, datas):
            a._data = d
        try:
            feats, li = net.features(nd.array(tokens), pos)
            out = {"lm": lambda: loss_fn(feats, nd.array(labels)),
                   "li": lambda: li,
                   "both": lambda: loss_fn((feats, li), nd.array(labels))
                   }[term]()
        finally:
            for a, s in zip(arrs, saved):
                a._data = s
        return out._data.sum()

    grads = jax.grad(fn)([p.data()._data for p in params])
    return dict(zip([p.name for p in params], grads))


def _by_reference_name(net, grads):
    """The last layer's gradients under the reference's names."""
    last = net.layers[-1]
    a, e = last.attn, last.moe
    mine = {"iq": a.index_q.weight, "ik": a.index_k.weight,
            "iw": a.index_w.weight, "q": a.query.weight, "k": a.key.weight,
            "v": a.value.weight, "o": a.proj.weight, "router": e.gate_weight}
    out = {n: grads[p.name] for n, p in mine.items()}
    out.update({"moe_%s_e%d" % (n, i): grads[p.name][i]
                for n, p in (("w1", e.w1), ("w2", e.w2), ("w3", e.w3))
                for i in range(HELD)})
    return out


@pytest.mark.parametrize("remat", [False, True],
                         ids=["stored", "recomputed"])
def test_gradients_match_the_reference(remat):
    """Every checked parameter's gradient (the last layer's three indexer
    maps, q, k, v, o, the router, each held expert's three matrices)
    against the reference's, float32 at "highest", with and without
    per-layer recomputation: 1e-4 of each gradient's largest entry."""
    net = build(remat=remat)
    tokens, labels = batch()
    with jax.default_matmul_precision("highest"):
        want = reference.checked_grads(builder.reference_params(net), CFG,
                                       jnp.asarray(tokens),
                                       jnp.asarray(labels))
        mine = _by_reference_name(net, _grads(net, tokens, labels, "both"))
    assert set(want) == set(mine)
    for name in want:
        w, g = onp.asarray(want[name]), onp.asarray(mine[name])
        assert onp.abs(w).max() > 0, name
        assert onp.abs(g - w).max() < 1e-4 * onp.abs(w).max(), name


def test_each_loss_moves_its_own_weights_only():
    """LI has a gradient into the indexer's three maps and its LayerNorm
    and into NOTHING else (its input and its target are stopped); the LM
    loss has a gradient into everything else and none into the indexer
    (the choice is not differentiable)."""
    net = build()
    tokens, labels = batch()
    pos = streams()
    by_li = _grads(net, tokens, labels, "li", pos)
    by_lm = _grads(net, tokens, labels, "lm", pos)
    indexer = set()
    for layer in net.layers:
        a = layer.attn
        indexer |= {a.index_q.weight.name, a.index_k.weight.name,
                    a.index_w.weight.name, a.index_k_norm.gamma.name,
                    a.index_k_norm.beta.name}
    assert len(indexer) == 5 * CFG["num_layers"]
    for name in by_li:
        li, lm = (float(jnp.abs(g[name]).max()) for g in (by_li, by_lm))
        if name in indexer:
            assert li > 0 and lm == 0, name
        else:
            assert li == 0 and lm > 0, name


def test_recomputed_layers_give_the_same_step():
    stored, again = build(), build(remat=True)
    tokens, labels = batch()
    a = _grads(stored, tokens, labels, "both")
    b = _grads(again, tokens, labels, "both")
    for (_, x), (_, y) in zip(sorted(a.items()), sorted(b.items())):
        onp.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-7)


def test_one_train_step_lowers_once_and_keeps_the_scopes_under_recompute(
        monkeypatch):
    """The normal path (FeaturesView + ChunkedUntiedLMLoss through
    TrainStep, bfloat16 with float32 masters, the kernels interpreted,
    every layer recomputed): one program; its first loss is the reference's
    LM + LI; thirty steps on fresh batches stay finite (a recomputed layer
    that rounds the indexer's operands differently leaves query 0 without a
    key: the op's names keep the bits) and fall; the block's and the op's
    names are on forward and backward ops, and the selection is made once."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    net = build(dtype="bfloat16", remat=True)
    view = models.FeaturesView(net)
    trainer = gluon.Trainer(view.collect_params(), "adam",
                            {"learning_rate": 1e-3, "multi_precision": True})
    step = jit.TrainStep(view, models.ChunkedUntiedLMLoss(net), trainer)
    tokens, labels = batch(s=128)
    lm, li = reference.loss_terms(builder.reference_params(net), CFG, tokens,
                                  labels)
    losses = [step(nd.array(tokens), nd.array(labels)).asnumpy()]
    onp.testing.assert_allclose(losses[0], lm + li, rtol=0.02)
    assert (onp.asarray(li) > 0.05).all()      # the sum is not the LM loss
    for seed in range(1, 30):
        t, y = batch(seed, s=128)
        losses.append(step(nd.array(t), nd.array(y)).asnumpy())
    assert onp.isfinite(onp.asarray(losses)).all()
    assert onp.mean(losses[-5:]) < onp.mean(losses[:5])
    (text,) = [t for model_id, t in jit.compiled_train_programs()
               if model_id == step._model_id]
    assert "sparsegroupedqueryattention" in text
    for scope in ("indexer", "topk_select", "sparse_attention", "rope",
                  "moe_dispatch", "router"):
        paths = [l for l in text.splitlines() if "/" + scope + "/" in l]
        assert any("transpose(" in l for l in paths) \
            or scope == "topk_select", scope
        assert any("transpose(" not in l for l in paths), scope
    # the thresholds are found in the forward pass alone
    select = [l for l in text.splitlines()
              if "/topk_select/" in l and "shift-left" in l]
    assert select and not any("rematted_computation" in l for l in select)


def test_recompute_keeps_what_a_policy_names():
    """`gluon.utils.recompute(block, *args, policy=)`: the values are the
    block's; a gradient through it keeps, besides the block's inputs, the
    results the policy names, and nothing of the block without one."""
    from incubator_mxnet_tpu.gluon import utils as gutils
    from incubator_mxnet_tpu.ops import sparse_attention as op
    layer = build().layers[0]
    x = nd.array(onp.random.default_rng(5).standard_normal(
        (B, S, CFG["hidden_size"])).astype("float32"))
    pos = nd.array(streams())
    kept = jax.checkpoint_policies.save_only_these_names(
        op.TOPK_NAME, op.ATTENDED_NAME)
    for got, want in zip(gutils.recompute(layer, x, pos, policy=kept),
                         layer(x, pos)):
        onp.testing.assert_allclose(got.asnumpy(), want.asnumpy(),
                                    rtol=1e-6, atol=1e-6)

    def selections(policy):
        def loss(xd):
            y, li = gutils.recompute(layer, nd.NDArray(xd), pos,
                                     policy=policy)
            return y._data.sum() + li._data.sum()
        return str(jax.make_jaxpr(jax.grad(loss))(x._data)).count(
            "shift_left")

    assert selections(kept) == 1 and selections(None) == 2
