"""Chunked LM cross-entropy (ops/lm_ce.py + models.ChunkedLMLoss) — the
vocab-softmax HBM lever from docs/PERF_BERT.md: parity with the dense
logits+softmax path, gradient flow into the tied embedding, and the
structural guarantee that the full (T, V) logits never materialize."""
import math

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd, gluon, jit, models
from incubator_mxnet_tpu.ops.lm_ce import chunked_lm_cross_entropy


def _largest_intermediate(jaxpr):
    """Elements of the largest value any equation of a jaxpr produces,
    nested jaxprs (scan and checkpoint bodies) included."""
    best = 0
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            best = max(best, math.prod(shape) if shape else 0)
        for sub in eqn.params.values():
            if hasattr(sub, "jaxpr"):
                best = max(best, _largest_intermediate(sub.jaxpr))
    return best


def test_chunked_ce_matches_dense():
    rng = onp.random.RandomState(0)
    T, U, V = 64, 16, 40
    h = jnp.asarray(rng.randn(T, U).astype("float32"))
    w = jnp.asarray(rng.randn(V, U).astype("float32") * 0.2)
    y = jnp.asarray(rng.randint(0, V, T).astype("int32"))

    def dense(h, w, y):
        logits = (h @ w.T).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        lab = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return lse - lab

    for chunk in (16, 64, 7):  # 7: non-dividing -> pads 64 -> 70
        got = chunked_lm_cross_entropy(h, w, y, chunk=chunk)
        onp.testing.assert_allclose(onp.asarray(got),
                                    onp.asarray(dense(h, w, y)),
                                    rtol=1e-5, atol=1e-6)
    # gradients match too (autodiff through lax.map)
    g1 = jax.grad(lambda h, w: chunked_lm_cross_entropy(h, w, y, 16).sum(),
                  argnums=(0, 1))(h, w)
    g2 = jax.grad(lambda h, w: dense(h, w, y).sum(), argnums=(0, 1))(h, w)
    for a, b in zip(g1, g2):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=1e-4, atol=1e-5)


def test_chunked_ce_never_materializes_full_logits():
    """Structural guarantee on the TRAINING path: no intermediate of total
    size >= T*V exists in the jaxpr of grad(loss) — this is what catches
    the grad-of-map residual stacking ((n, chunk, V) == full logits) that
    a forward-only, exact-shape check would miss."""
    T, U, V, chunk = 256, 8, 64, 32
    h = jnp.zeros((T, U))
    w = jnp.zeros((V, U))
    y = jnp.zeros((T,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        jax.grad(lambda h, w: chunked_lm_cross_entropy(h, w, y, chunk)
                 .sum(), argnums=(0, 1)))(h, w)
    assert _largest_intermediate(jaxpr.jaxpr) < T * V


def test_chunked_ce_non_dividing_stays_chunked():
    """T % chunk != 0 must NOT silently fall back to one full-T chunk
    (r4: the stream pads to the chunk multiple; pad losses discarded)."""
    T, U, V = 96, 8, 32
    rng = onp.random.RandomState(3)
    h = jnp.asarray(rng.randn(T, U).astype("float32"))
    w = jnp.asarray(rng.randn(V, U).astype("float32") * 0.2)
    y = jnp.asarray(rng.randint(0, V, T).astype("int32"))
    # chunk=40, T=96 -> padded to 120, 3 chunks of 40 (never dense)
    jaxpr = jax.make_jaxpr(
        lambda h, w: chunked_lm_cross_entropy(h, w, y, 40).sum())(h, w)
    assert _largest_intermediate(jaxpr.jaxpr) < T * V  # never dense
    # and the values still match the dense computation
    dense_logits = h @ w.T
    lse = jax.nn.logsumexp(dense_logits, axis=-1)
    lab = jnp.take_along_axis(dense_logits, y[:, None], axis=-1)[:, 0]
    onp.testing.assert_allclose(
        onp.asarray(chunked_lm_cross_entropy(h, w, y, 40)),
        onp.asarray(lse - lab), rtol=1e-5, atol=1e-6)


def test_gpt_chunked_loss_trains_and_ties_embedding():
    """FeaturesView(gpt) + ChunkedLMLoss == dense GPT forward + softmax CE:
    same per-token losses, and training through the fused TrainStep moves
    the TIED embedding (grads flow through weight.data())."""
    mx.random.seed(0)
    V, U, S, B = 64, 16, 32, 2
    gpt = models.GPTModel(vocab_size=V, units=U, num_layers=1, num_heads=2,
                          max_length=S, attention="dense")
    gpt.initialize(mx.init.Xavier())
    tokens = nd.array(onp.random.RandomState(1).randint(0, V, (B, S))
                      .astype("int32"))

    dense_logits = gpt(tokens)
    dense_loss = gluon.loss.SoftmaxCrossEntropyLoss()(dense_logits, tokens)
    loss_fn = models.ChunkedLMLoss(gpt, chunk=16)
    chunked = loss_fn(gpt.features(tokens), tokens)
    onp.testing.assert_allclose(chunked.asnumpy(),
                                dense_loss.asnumpy(), rtol=1e-4, atol=1e-5)

    view = models.FeaturesView(gpt)
    before = gpt.tok_embed.weight.data().asnumpy().copy()
    tr = gluon.Trainer(view.collect_params(), "sgd", {"learning_rate": 0.5})
    step = jit.TrainStep(view, loss_fn, tr)
    l0 = float(step(tokens, tokens).mean().asnumpy())
    l1 = float(step(tokens, tokens).mean().asnumpy())
    assert l1 < l0
    after = gpt.tok_embed.weight.data().asnumpy()
    assert onp.abs(after - before).max() > 1e-5  # tied head got gradients


def test_auto_chunk_routing():
    """chunk=None: dense (one chunk) below the 128 MiB logits threshold.
    Parity asserted across genuinely DIFFERENT lowerings (auto-dense vs
    explicit small chunks, even and odd T)."""
    from incubator_mxnet_tpu.ops import lm_ce
    U = 8
    for T in (256, 251):             # odd/prime T takes the padding path
        h = jnp.asarray(onp.random.RandomState(0).randn(T, U), jnp.float32)
        w = jnp.asarray(onp.random.RandomState(1).randn(64, U), jnp.float32)
        y = jnp.asarray(onp.random.RandomState(2).randint(0, 64, T))
        auto = lm_ce.chunked_lm_cross_entropy(h, w, y)      # tiny: dense
        small = lm_ce.chunked_lm_cross_entropy(h, w, y, chunk=64)
        onp.testing.assert_allclose(onp.asarray(auto), onp.asarray(small),
                                    rtol=1e-4, atol=1e-5)


def _scans(jaxpr):
    """Every scan equation in a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in eqn.params.values():
            if hasattr(sub, "jaxpr"):
                yield from _scans(sub.jaxpr)


def _abstract(T, V, U):
    return (jax.ShapeDtypeStruct((T, U), jnp.bfloat16),
            jax.ShapeDtypeStruct((V, U), jnp.bfloat16),
            jax.ShapeDtypeStruct((T,), jnp.int32))


# (T, V, U) of heads users train: the GPT cells, OLMoE, BERT-large's MLM
# head at 16 x 512, a 32k x 32k pre-training batch, Llama-3's and Gemma's
# vocabularies, and a token count nothing divides
@pytest.mark.parametrize("T,V,U", [
    (16384, 50257, 2048), (16384, 50304, 2048), (8192, 30522, 1024),
    (32768, 32768, 1024), (8192, 128256, 4096), (4096, 262144, 2048),
    (16385, 50257, 2048), (8193, 30522, 1024), (4096, 524288, 1024)])
def test_auto_rows_over_the_ridge_and_under_the_ceiling(T, V, U):
    """The picker reads the shape alone: whole MXU passes, never under
    twice the v5e's ridge, a chunk's float32 logits under the ceiling
    wherever the floor leaves room, and next to no padded rows. The op
    traced at that shape (nothing runs) loops over exactly those rows."""
    from incubator_mxnet_tpu.ops import lm_ce
    assert T * V * 4 > lm_ce._DENSE_BYTES
    rows = lm_ce._auto_rows(T, V)
    assert rows % 256 == 0 and rows >= 512
    if 512 * V * 4 <= lm_ce._CEILING_BYTES:
        assert rows * V * 4 <= lm_ce._CEILING_BYTES
    else:
        assert rows == 512              # V > 256 k: the floor wins
    trips = -(-T // rows)
    assert trips * rows - T <= T // 32
    if T % 512 == 0:
        assert T % rows == 0            # no padded row where T allows
    jaxpr = jax.make_jaxpr(lm_ce.chunked_lm_cross_entropy)(
        *_abstract(T, V, U))
    scan, = _scans(jaxpr.jaxpr)
    assert scan.params["length"] == trips
    assert scan.params["jaxpr"].jaxpr.invars[-2].aval.shape == (rows, U)


@pytest.mark.parametrize("T,V", [(512, 50257), (2048, 16384), (1024, 32768)])
def test_auto_route_is_dense_under_128_mib(T, V):
    from incubator_mxnet_tpu.ops import lm_ce
    assert T * V * 4 <= lm_ce._DENSE_BYTES
    jaxpr = jax.make_jaxpr(lm_ce.chunked_lm_cross_entropy)(
        *_abstract(T, V, 64))
    assert not list(_scans(jaxpr.jaxpr))


def test_auto_routed_grad_loops_over_chunks_and_never_holds_full_logits():
    """grad of the op at the GPT cells' (T, V), traced on abstract inputs
    (nothing runs): the backward is one scan of T / rows trips, and no
    intermediate anywhere has T x V elements
    (test_chunked_ce_never_materializes_full_logits at the auto size)."""
    from incubator_mxnet_tpu.ops import lm_ce
    T, V, U = 16384, 50257, 64
    rows = lm_ce._auto_rows(T, V)
    h, w, y = _abstract(T, V, U)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda h, w, y: lm_ce.chunked_lm_cross_entropy(h, w, y)
        .astype(jnp.float32).sum(), argnums=(0, 1)))(h, w, y)
    lengths = [s.params["length"] for s in _scans(jaxpr.jaxpr)]
    assert lengths and set(lengths) == {T // rows}
    assert _largest_intermediate(jaxpr.jaxpr) < T * V


def test_route_counter_counts_traced_calls():
    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.ops import lm_ce
    before = {r: lm_ce._ROUTES.value(route=r) for r in ("dense", "chunked")}
    f = jax.jit(lm_ce.chunked_lm_cross_entropy, static_argnums=3)
    args = (jnp.zeros((64, 8)), jnp.zeros((16, 8)),
            jnp.zeros((64,), jnp.int32))
    for _ in range(3):                       # traced once, run three times
        f(*args, None)
    f(*args, 16)
    jax.make_jaxpr(lm_ce.chunked_lm_cross_entropy)(
        *_abstract(16384, 50257, 64))        # auto-routed over the threshold
    after = {r: lm_ce._ROUTES.value(route=r) for r in before}
    assert {r: after[r] - before[r] for r in before} == {
        "dense": 1, "chunked": 2}
    assert 'mxtpu_lm_ce_route_total{route="chunked"}' \
        in telemetry.REGISTRY.export_text()


def test_odd_token_count_keeps_chunk_size():
    """T=8193 at chunk 256 must PAD (33 map iterations), not collapse to
    the largest divisor 3 (2731 iterations) — the auto-default regression
    the r4 review caught."""
    from incubator_mxnet_tpu.ops.lm_ce import chunked_lm_cross_entropy
    U, V, T = 8, 16, 8193
    h = jnp.asarray(onp.random.RandomState(3).randn(T, U), jnp.float32)
    w = jnp.asarray(onp.random.RandomState(4).randn(V, U), jnp.float32)
    y = jnp.asarray(onp.random.RandomState(5).randint(0, V, T))
    jaxpr = jax.make_jaxpr(
        lambda *a: chunked_lm_cross_entropy(*a, chunk=256))(h, w, y)
    # the map's scan length rides the jaxpr as the leading dim of its
    # carried inputs: ceil(8193/256) = 33, not 2731
    text = str(jaxpr)
    assert "2731" not in text
    got = chunked_lm_cross_entropy(h, w, y, chunk=256)
    ref = chunked_lm_cross_entropy(h, w, y, chunk=T)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(ref),
                                rtol=1e-4, atol=1e-5)


def test_chunked_ce_backward_memory_bound():
    """The committed memory claim, CI-checkable: XLA's compiled temp
    buffer for grad(chunked CE) must undercut grad(dense CE) by at least
    half the (T, V) fp32 logits block (the backward stays chunked — the
    jax.checkpoint in ops/lm_ce.py is what keeps residuals per-chunk)."""
    T, U, V = 4096, 64, 8192        # dense logits fp32 = 128 MB
    from incubator_mxnet_tpu.ops.lm_ce import chunked_lm_cross_entropy
    h = jnp.zeros((T, U), jnp.bfloat16)
    w = jnp.zeros((V, U), jnp.bfloat16)
    y = jnp.zeros((T,), jnp.int32)

    def dense(h, w, y):
        logits = (h @ w.T).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        lab = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - lab)

    def chunked(h, w, y):
        return jnp.sum(chunked_lm_cross_entropy(h, w, y, chunk=512))

    g_dense = jax.jit(jax.grad(dense, argnums=(0, 1)))
    g_chunk = jax.jit(jax.grad(chunked, argnums=(0, 1)))
    mem_d = g_dense.lower(h, w, y).compile().memory_analysis() \
        .temp_size_in_bytes
    mem_c = g_chunk.lower(h, w, y).compile().memory_analysis() \
        .temp_size_in_bytes
    logits_bytes = T * V * 4
    assert mem_d - mem_c > logits_bytes // 2, (mem_d, mem_c, logits_bytes)


def test_bert_chunked_mlm_loss_matches_dense_and_trains():
    """r4: ChunkedMLMLoss (untied, BIASED decoder head) == dense BERT
    forward + softmax CE; training through TrainStep moves the loss and
    the decoder params get gradients (bias rides the chunked path)."""
    mx.random.seed(0)
    V, U, S, B = 64, 16, 32, 2
    bert = models.BERTModel(vocab_size=V, units=U, hidden_size=2 * U,
                            num_layers=1, num_heads=2, max_length=S,
                            dropout=0.0, attention="dense")
    bert.initialize(mx.init.Xavier())
    tokens = nd.array(onp.random.RandomState(1).randint(0, V, (B, S))
                      .astype("int32"))
    dense = gluon.loss.SoftmaxCrossEntropyLoss()(bert(tokens), tokens)
    chunked = models.ChunkedMLMLoss(bert, chunk=16)(
        bert.features(tokens), tokens)
    onp.testing.assert_allclose(chunked.asnumpy(), dense.asnumpy(),
                                rtol=1e-4, atol=1e-5)
    view = models.FeaturesView(bert)
    before = bert.mlm_decoder.bias.data().asnumpy().copy()
    tr = gluon.Trainer(view.collect_params(), "sgd",
                       {"learning_rate": 0.5})
    step = jit.TrainStep(view, models.ChunkedMLMLoss(bert), tr)
    l0 = float(step(tokens, tokens).mean().asnumpy())
    l1 = float(step(tokens, tokens).mean().asnumpy())
    assert l1 < l0
    after = bert.mlm_decoder.bias.data().asnumpy()
    assert onp.abs(after - before).max() > 1e-6  # bias got gradients


@pytest.mark.parametrize("head", ["tied", "untied"])
def test_a_models_own_loss_is_added_to_the_heads(head):
    """A trunk that hands out (hidden, its layers' own loss a sample (B,)):
    the head's loss is the cross-entropy of the hidden states plus that
    term, sample by sample; through TrainStep the term's gradient reaches
    the weights it depends on and the reported loss is the sum."""
    mx.random.seed(0)
    V, U, S, B = 64, 16, 32, 2
    gpt = models.GPTModel(vocab_size=V, units=U, num_layers=1, num_heads=2,
                          max_length=S, attention="dense")
    gpt.initialize(mx.init.Xavier())
    tokens = nd.array(onp.random.RandomState(1).randint(0, V, (B, S))
                      .astype("int32"))
    if head == "tied":
        loss_fn = models.ChunkedLMLoss(gpt, chunk=16)
    else:
        gpt.lm_head = gluon.nn.Dense(V, flatten=False, in_units=U,
                                     use_bias=False)
        gpt.lm_head.initialize(mx.init.Xavier())
        loss_fn = models.ChunkedUntiedLMLoss(gpt, chunk=16)
    hidden = gpt.features(tokens)
    plain = loss_fn(hidden, tokens).asnumpy()
    own = nd.array(onp.array([0.25, 1.5], "float32"))
    onp.testing.assert_allclose(loss_fn((hidden, own), tokens).asnumpy(),
                                plain + own.asnumpy(), rtol=1e-6)

    class WithOwnLoss(gluon.HybridBlock):
        """hidden, and the mean square of the hidden states a sample"""
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.model = gpt

        def forward(self, ids):
            h = gpt.features(ids)
            return h, (h * h).mean(axis=(1, 2))

    view = WithOwnLoss()
    h = gpt.features(tokens)
    want = plain + (h * h).mean(axis=(1, 2)).asnumpy()
    tr = gluon.Trainer(view.collect_params(), "sgd", {"learning_rate": 0.1})
    step = jit.TrainStep(view, loss_fn, tr)
    onp.testing.assert_allclose(step(tokens, tokens).asnumpy(), want,
                                rtol=1e-5)
