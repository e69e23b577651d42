"""models/olmoe.py against its float32 reference (tests/olmoe_reference.py)
on seeded weights at a small size, and its new blocks each against a
hand-written case. The tolerances are stated where they are used."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon, jit, models, nd
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.models import olmoe
from incubator_mxnet_tpu.parallel.moe import MoELayer

import olmoe_reference as reference

#: the tiny preset: heads of 128 so that, interpreted, the streamed Pallas
#: kernels run (as in the cell's rehearsal); keys as the source names them
CFG = {"hidden_size": 256, "intermediate_size": 64, "num_layers": 2,
       "num_attention_heads": 2, "num_experts": 8, "num_experts_per_tok": 2,
       "norm_topk_prob": False, "rope_theta": 10000, "rms_norm_eps": 1e-5,
       "vocab_size": 512, "max_position_embeddings": 256}
B, S = 2, 128


@pytest.fixture(autouse=True)
def _interpreted_kernels(monkeypatch):
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")


def build(cfg=CFG, dtype=None, seed=0):
    mx.random.seed(seed)
    net = models.OLMoEModel(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        ffn_hidden=cfg["intermediate_size"], num_layers=cfg["num_layers"],
        num_heads=cfg["num_attention_heads"], num_experts=cfg["num_experts"],
        top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        max_length=cfg["max_position_embeddings"])
    net.initialize(mx.init.Xavier())
    head = net.lm_head.weight
    head.set_data(head.data() * 4.0)      # logits that depend on the features
    # norms whose gains are not all 1, so a misplaced gain shows
    for name, p in net.collect_params().items():
        if name.endswith("gamma"):
            p.set_data(p.data() * nd.random.uniform(0.5, 1.5, p.shape))
    if dtype:
        net.cast(dtype)
    return net


def reference_params(net):
    def w(p):
        return p.data()._data
    return {
        "tok_embed": w(net.tok_embed.weight),
        "layers": [{
            "n1": w(l.ln1.gamma), "n2": w(l.ln2.gamma),
            "q": w(l.attn.query.weight), "k": w(l.attn.key.weight),
            "v": w(l.attn.value.weight), "o": w(l.attn.proj.weight),
            "q_norm": w(l.attn.q_norm.gamma),
            "k_norm": w(l.attn.k_norm.gamma),
            "router": w(l.moe.gate_weight), "gate": w(l.moe.w1),
            "up": w(l.moe.w3), "down": w(l.moe.w2)} for l in net.layers],
        "norm_f": w(net.norm_f.gamma), "head": w(net.lm_head.weight)}


def batch(seed=0, cfg=CFG):
    ids = onp.random.RandomState(seed).randint(
        0, cfg["vocab_size"], (B, S + 1)).astype("int32")
    return ids[:, :-1], ids[:, 1:]


def rel_rms(got, want):
    got, want = (onp.asarray(x, onp.float32) for x in (got, want))
    return float(onp.sqrt(onp.mean((got - want) ** 2))
                 / onp.sqrt(onp.mean(want ** 2)))


def system_routing(net, tokens):
    """The last layer's (T, k) expert choices as the system makes them:
    captured where MoELayer.route returns them."""
    seen = []
    layer = net.layers[len(net.layers) - 1].moe
    route = layer.route
    layer.route = lambda *a: seen.append(route(*a)) or seen[-1]
    try:
        net.features(nd.array(tokens))
    finally:
        del layer.route
    return onp.asarray(seen[-1][3])


# ------------------------------------------------------------- the blocks
def test_rmsnorm_is_x_over_root_mean_square_times_gain():
    norm = nn.RMSNorm(in_channels=4, epsilon=1e-5)
    norm.initialize()
    norm.gamma.set_data(nd.array([1.0, 2.0, 0.5, -1.0]))
    x = onp.array([[3.0, 4.0, 0.0, 0.0], [1.0, 1.0, 1.0, 1.0]], "float32")
    got = norm(nd.array(x)).asnumpy()
    # rows: mean square 25/4 -> rms 2.5; mean square 1 -> rms 1
    want = onp.array([[3 / 2.5, 2 * 4 / 2.5, 0.0, 0.0],
                      [1.0, 2.0, 0.5, -1.0]], "float32")
    onp.testing.assert_allclose(got, want, rtol=1e-5)
    # no mean is subtracted: a constant row keeps its sign and size
    assert got[1, 0] == pytest.approx(1.0, rel=1e-5)
    # the statistics are float32 on a bfloat16 input, which comes back
    norm.cast("bfloat16")
    big = nd.array(onp.full((1, 4), 300.0, "float32")).astype("bfloat16")
    out = norm(big)
    assert "bfloat16" in str(out.dtype)
    onp.testing.assert_allclose(out.asnumpy().astype("float32"),
                                [[1.0, 2.0, 0.5, -1.0]], rtol=1e-2)
    # deferred shape: in_channels unknown until the first input
    late = nn.RMSNorm()
    late.initialize()
    assert late(nd.ones((2, 6))).shape == (2, 6)
    assert late.gamma.shape == (6,)


def test_rope_rotates_pairs_half_a_head_apart():
    # D = 4, theta = 10000: pair (x0, x2) turns by pos * 1, pair (x1, x3)
    # by pos * 10000^(-1/2) = pos / 100
    x = onp.zeros((1, 1, 3, 4), "float32")
    x[0, 0, :, 0] = 1.0                 # (1, 0) in the first pair
    x[0, 0, :, 1] = 2.0                 # (2, 0) in the second
    got = onp.asarray(olmoe.rope(jnp.asarray(x), 10000.0))[0, 0]
    for pos in range(3):
        want = [onp.cos(pos), 2 * onp.cos(pos / 100.0),
                onp.sin(pos), 2 * onp.sin(pos / 100.0)]
        onp.testing.assert_allclose(got[pos], want, rtol=1e-6, atol=1e-7)
    # position 0 is untouched, lengths are kept, and a score depends on
    # the distance between the two positions alone
    rng = onp.random.RandomState(0)
    q, k = (jnp.asarray(rng.randn(1, 1, 16, 8), jnp.float32)
            for _ in range(2))
    same_q = jnp.broadcast_to(q[:, :, :1], q.shape)
    same_k = jnp.broadcast_to(k[:, :, :1], k.shape)
    rq, rk = olmoe.rope(same_q), olmoe.rope(same_k)
    onp.testing.assert_allclose(rq[0, 0, 0], q[0, 0, 0], rtol=1e-6)
    onp.testing.assert_allclose(jnp.linalg.norm(rq, axis=-1),
                                jnp.linalg.norm(same_q, axis=-1), rtol=1e-5)
    scores = onp.asarray(jnp.einsum("bhqd,bhkd->bhqk", rq, rk))[0, 0]
    onp.testing.assert_allclose(scores[5, 2], scores[9, 6], rtol=1e-4)
    onp.testing.assert_allclose(scores[5, 2], scores[15, 12], rtol=1e-4)
    assert abs(scores[5, 2] - scores[5, 3]) > 1e-3
    # and the reference's rotate-half is the same rotation
    onp.testing.assert_allclose(olmoe.rope(q), reference._rope(q, 10000.0),
                                rtol=1e-5, atol=1e-6)


def test_qk_norm_spans_all_heads_before_the_split():
    units, heads = 8, 2
    attn = models.RotaryMultiHeadAttention(units, heads, attention="dense")
    attn.initialize()
    eye = nd.array(onp.eye(units, dtype="float32"))
    for lyr in (attn.query, attn.key, attn.value):
        lyr.weight.set_data(eye)
    assert attn.query.bias is None and attn.proj.bias is None
    # one position (RoPE leaves position 0 alone): head 0 holds all the
    # size, head 1 almost none
    x = onp.array([[[6.0, 0, 0, 0, 0, 0, 0, 8.0e-3]]], "float32")
    q, k, v = (t.asnumpy() for t in attn.project(nd.array(x)))
    assert q.shape == (1, heads, 1, units // heads)
    rms_all = onp.sqrt((x ** 2).mean() + 1e-5)
    onp.testing.assert_allclose(q[0, 0, 0], x[0, 0, :4] / rms_all, rtol=1e-5)
    onp.testing.assert_allclose(q[0, 1, 0], x[0, 0, 4:] / rms_all, rtol=1e-5)
    # a per-head norm would have blown head 1 up to unit size
    assert onp.abs(q[0, 1, 0]).max() < 0.01
    onp.testing.assert_allclose(k, q, rtol=1e-6)
    onp.testing.assert_allclose(v[0, :, 0].ravel(), x.ravel(), rtol=1e-6)


# ------------------------------------------- the model against the reference
def test_float32_model_matches_the_reference_with_identical_routing():
    net = build()
    tokens, labels = batch()
    feats = net.features(nd.array(tokens)).asnumpy()
    want, want_loss = reference.forward(reference_params(net), CFG, tokens,
                                        labels, S)
    # float32 against float32 at "highest": what is left is summation
    # order (the interpreted kernels' online softmax, the grouped matmul)
    assert rel_rms(feats, want) < 1e-5
    logits = net(nd.array(tokens)).asnumpy()
    want_logits = onp.asarray(want) @ onp.asarray(
        net.lm_head.weight.data().asnumpy()).T
    onp.testing.assert_allclose(logits, want_logits, rtol=1e-4, atol=2e-5)
    loss = models.ChunkedUntiedLMLoss(net)(
        nd.array(feats), nd.array(labels)).asnumpy()
    onp.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    # every (token, slot) choice is the reference's
    chosen = system_routing(net, tokens)
    assert onp.array_equal(chosen, onp.asarray(
        reference.routing(reference_params(net), CFG, tokens)))


def test_bfloat16_model_stays_near_the_reference():
    """bfloat16 weights and activations against the float32 reference run
    on the same (bfloat16-rounded) weights. A bfloat16 rounding is 2^-9
    relative; through two layers of matmuls whose sums are float32 the
    features of a token routed as the reference routes it land within
    about 1 % rel-rms (measured here: 1.2 %; limit 2 %). Top-k is
    discontinuous: where two experts' float32 probabilities are closer than
    the rounding of the router's input, the second choice flips (here 2.5 %
    of the choices, limit 8 %), and at this preset (2 of 8 experts, weights
    not renormalised) a flipped token is wrong by a quarter of its size,
    which is no error of precision. So the tolerance is on the tokens whose
    routing agrees, and the share that does not is bounded beside it."""
    net = build(dtype="bfloat16")
    tokens, labels = batch()
    feats = net.features(nd.array(tokens)).asnumpy().astype("float32")
    params = reference_params(net)
    want, want_loss = reference.forward(params, CFG, tokens, labels, S)
    agrees = system_routing(net, tokens) \
        == onp.asarray(reference.routing(params, CFG, tokens))
    assert agrees.mean() > 0.92, agrees.mean()
    same = agrees.all(-1)
    width = CFG["hidden_size"]
    err = rel_rms(feats.reshape(-1, width)[same],
                  onp.asarray(want).reshape(-1, width)[same])
    assert err < 0.02, err
    loss = models.ChunkedUntiedLMLoss(net)(
        net.features(nd.array(tokens)), nd.array(labels)).asnumpy()
    onp.testing.assert_allclose(loss.astype("float32"), want_loss, rtol=5e-3)


def _perturbed_features(net, tokens, route):
    layer_cls_route = MoELayer.route
    MoELayer.route = route
    try:
        return net.features(nd.array(tokens)).asnumpy()
    finally:
        MoELayer.route = layer_cls_route


def test_the_float32_tolerance_tells_a_bfloat16_router_and_a_dropped_token():
    net = build()
    tokens, labels = batch()
    want, _ = reference.forward(reference_params(net), CFG, tokens, labels, S)
    exact = MoELayer.route

    def bf16_softmax(self, t, gw):
        logits = jnp.einsum("td,ed->te", t, gw).astype(jnp.bfloat16)
        gates = jax.nn.softmax(logits, -1)
        top_vals, top_idx = jax.lax.top_k(gates, self.top_k)
        return logits, gates, top_vals.astype(jnp.float32), top_idx

    def drop_one(self, t, gw):
        logits, gates, top_vals, top_idx = exact(self, t, gw)
        return logits, gates, top_vals.at[7, 1].set(0.0), top_idx

    for route in (bf16_softmax, drop_one):
        err = rel_rms(_perturbed_features(net, tokens, route), want)
        assert err > 1e-4, (route.__name__, err)     # the limit is 1e-5


def test_gradients_of_router_expert_and_wq_match_the_reference():
    net = build()
    tokens, labels = batch()
    loss_fn = models.ChunkedUntiedLMLoss(net)
    with autograd.record():
        loss = loss_fn(net.features(nd.array(tokens)), nd.array(labels))
    loss.backward()
    params = reference_params(net)

    def total(p):
        return reference.forward(p, CFG, tokens, labels, 1)[1].sum()

    want = jax.grad(total)(reference._f32(params))
    last, first = net.layers[1], net.layers[0]
    pairs = {
        "router": (last.moe.gate_weight, want["layers"][1]["router"]),
        "gate of every expert": (last.moe.w1, want["layers"][1]["gate"]),
        "down of every expert": (first.moe.w2, want["layers"][0]["down"]),
        "Wq": (first.attn.query.weight, want["layers"][0]["q"]),
        "q_norm": (first.attn.q_norm.gamma, want["layers"][0]["q_norm"]),
    }
    for name, (param, g) in pairs.items():
        # float32 both sides; summation order only
        assert rel_rms(param.grad().asnumpy(), g) < 2e-4, name
    expert = onp.asarray(want["layers"][1]["gate"])[3]
    assert onp.abs(expert).max() > 0          # one expert, really trained


def test_one_train_step_lowers_once_and_lowers_the_loss():
    net = build(dtype="bfloat16")
    view = models.FeaturesView(net)
    trainer = gluon.Trainer(view.collect_params(), "adam",
                            {"learning_rate": 1e-3, "multi_precision": True})
    step = jit.TrainStep(view, models.ChunkedUntiedLMLoss(net), trainer)
    tokens, labels = (nd.array(x) for x in batch())
    lowerings = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, _s, **_k: lowerings.append(name)
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration"
        else None)
    first = float(step(tokens, labels).asnumpy().mean())
    after_first = len(lowerings)
    assert after_first >= 1
    losses = [float(step(tokens, labels).asnumpy().mean()) for _ in range(3)]
    assert len(lowerings) == after_first, "a later step lowered a program"
    assert losses[-1] < first - 0.5, (first, losses)
    # stacked 3-D parameters update like any other: masters are float32
    assert net.layers[0].moe.w1.data().dtype == onp.dtype("bfloat16") \
        or "bfloat16" in str(net.layers[0].moe.w1.data().dtype)


def test_positions_past_the_declared_context_are_refused():
    net = build()
    with pytest.raises(ValueError):
        net.features(nd.array(onp.zeros((1, 257), "int32")))
