"""EvaByte (PR 45): EVA attention against the plain reference's one masked
softmax (values and the gradients of q, k, v, phi, mu; float32 and
bfloat16), the partition that adds up to full causal attention, the
eight-head loss against eight plain cross-entropies, the model against the
reference, the residual stream's type and the MLP's row blocks. W 32, c 4,
seeded."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, models, nd, telemetry
from incubator_mxnet_tpu.gluon import utils as gutils
from incubator_mxnet_tpu.models import evabyte
from incubator_mxnet_tpu.ops import eva_attention as eva
from incubator_mxnet_tpu.ops.lm_ce import multibyte_cross_entropy

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
W, C = 32, 4
B, H, D = 2, 3, 16


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "perfbench_reference_evabyte",
        os.path.join(ROOT, "perfbench", "reference", "evabyte.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def operands(seq_len, dtype=jnp.float32, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed + seq_len), 6)
    q, k, v = (jax.random.normal(keys[i], (B, H, seq_len, D)).astype(dtype)
               for i in range(3))
    phi = jax.random.normal(keys[3], (H, D))
    mu = 0.3 * jax.random.normal(keys[4], (H, D))
    ct = jax.random.normal(keys[5], (B, H, seq_len, D))
    return (q, k, v, phi, mu), ct


def both(reference, args, ct, window=W, chunk=C):
    """-> ((o, grads) of the op, (o, grads) of the reference), the
    reference on float32 copies of the same values."""
    def run(fn, args):
        o, back = jax.vjp(lambda *a: fn(*a, window, chunk), *args)
        return o, back(ct.astype(o.dtype))

    with jax.default_matmul_precision("highest"):
        return run(eva.eva_attention, args), run(
            reference.eva_attention,
            tuple(a.astype(jnp.float32) for a in args))


def largest(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b)))


@pytest.mark.parametrize("windows", [1, 2, 5])
def test_the_op_is_the_references_one_softmax_in_float32(reference, windows):
    args, ct = operands(windows * W)
    (o, grads), (o_ref, grads_ref) = both(reference, args, ct)
    assert o.dtype == jnp.float32 and largest(o, o_ref) < 1e-4
    for name, g, g_ref in zip("q k v phi mu".split(), grads, grads_ref):
        assert largest(g, g_ref) < 1e-4 * max(1.0, float(
            jnp.max(jnp.abs(g_ref)))), name
    if windows == 1:
        # no summary: the two learned vectors get nothing
        assert not np.any(grads[3]) and not np.any(grads[4])
    else:
        assert np.any(grads[3]) and np.any(grads[4])


@pytest.mark.parametrize("windows", [1, 2, 5])
def test_the_op_in_bfloat16_is_a_rounding_from_the_reference(reference,
                                                              windows):
    args, ct = operands(windows * W, jnp.bfloat16)
    (o, grads), (o_ref, grads_ref) = both(reference, args, ct)
    assert o.dtype == jnp.bfloat16
    assert grads[3].dtype == grads[4].dtype == jnp.float32

    def rel(a, b):
        return float(jnp.sqrt(jnp.mean((a.astype(jnp.float32) - b) ** 2))
                     / jnp.sqrt(jnp.mean(b ** 2)))

    assert rel(o, o_ref) < 0.01
    for name, g, g_ref in zip("q k v phi mu".split(), grads, grads_ref):
        if np.any(g_ref):
            assert rel(g, g_ref) < 0.03, name


def causal_attention(q, k, v):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    seen = jnp.arange(q.shape[2])[:, None] >= jnp.arange(k.shape[2])[None]
    return jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v)


def test_the_partition_adds_up_to_full_causal_attention():
    """At chunk size 1 and mu = 0 a chunk's summary is its key and value:
    exact set and summaries together are every key u <= t, each once, and
    the op is full causal attention at S = 5 W. At S = W it is that for any
    chunk size (and any phi, mu)."""
    (q, k, v, phi, mu), _ = operands(5 * W)
    with jax.default_matmul_precision("highest"):
        want = causal_attention(q, k, v)
        got = eva.eva_attention(q, k, v, phi, jnp.zeros_like(mu), W, 1)
        assert largest(got, want) < 1e-5
        # the offset is part of the mathematics: with mu it is not
        assert largest(eva.eva_attention(q, k, v, phi, mu, W, 1), want) \
            > 1e-3
        # a chunk of 4 pools: no longer every key
        assert largest(eva.eva_attention(q, k, v, phi, jnp.zeros_like(mu),
                                         W, C), want) > 1e-3
        (q, k, v, phi, mu), _ = operands(W)
        for chunk in (1, C, 8):
            assert largest(eva.eva_attention(q, k, v, phi, mu, W, chunk),
                           causal_attention(q, k, v)) < 1e-5


def test_what_a_query_sees_is_counted_and_other_shapes_are_refused():
    assert eva.seen_pairs(16384, 2048, 16) == (16785408, 7340032)
    assert eva.seen_pairs(5 * W, W, C) == (5 * W * (W + 1) // 2,
                                           W * (W // C) * 10)
    assert eva.seen_pairs(20, W, C) == (210, 0)
    (q, k, v, phi, mu), _ = operands(5 * W)
    calls = telemetry.REGISTRY.get("mxtpu_eva_attention_total")
    before = calls.value(local="dense", remote="strips")
    eva.eva_attention(q, k, v, phi, mu, W, C)
    assert calls.value(local="dense", remote="strips") == before + 1
    pairs = telemetry.REGISTRY.get("mxtpu_eva_pairs")
    assert (pairs.value(kind="local"), pairs.value(kind="remote")) \
        == eva.seen_pairs(5 * W, W, C)
    for seq_len, window, chunk in ((W + C, W, C), (2 * W, W, 5),
                                   (W + 2, W, C)):
        with pytest.raises(ValueError, match="aligned and whole"):
            eva.eva_attention(q[:, :, :seq_len], k[:, :, :seq_len],
                              v[:, :, :seq_len], phi, mu, window, chunk)


# ------------------------------------------------------------- the heads
HEADS, VOCAB, UNITS = 8, 10, 24


def plain_heads_loss(hidden, head_w, labels):
    """Eight cross-entropies, a loop a head: head i of position t against
    labels[t + i], t < S - i."""
    s = hidden.shape[1]
    total, count = 0.0, 0
    for i in range(HEADS):
        z = hidden[:, :s - i] @ head_w[VOCAB * i:VOCAB * (i + 1)].T
        logp = jax.nn.log_softmax(z.astype(jnp.float32), -1)
        total = total - jnp.take_along_axis(
            logp, labels[:, i:, None], -1)[..., 0].sum(-1)
        count += s - i
    return total / count


@pytest.mark.parametrize("rows", [None, 16])
def test_the_eight_heads_loss_is_eight_plain_cross_entropies(rows):
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    s = 64
    hidden = jax.random.normal(keys[0], (B, s, UNITS))
    head_w = jax.random.normal(keys[1], (HEADS * VOCAB, UNITS)) * 0.3
    labels = jax.random.randint(keys[2], (B, s), 0, VOCAB)

    def ours(hidden, head_w):
        per, count = multibyte_cross_entropy(hidden, head_w, labels, HEADS,
                                             rows)
        assert count == HEADS * s - 28
        return per.sum(-1) / count

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(ours(hidden, head_w),
                                   plain_heads_loss(hidden, head_w, labels),
                                   rtol=1e-5)
        got = jax.grad(lambda h, w: ours(h, w).sum(), (0, 1))(hidden, head_w)
        want = jax.grad(lambda h, w: plain_heads_loss(h, w, labels).sum(),
                        (0, 1))(hidden, head_w)
        for g, g_want in zip(got, want):
            np.testing.assert_allclose(g, g_want, atol=1e-5)
        # the last i positions of head i are without loss: the last
        # position feeds head 0 alone
        per, _ = multibyte_cross_entropy(hidden, head_w, labels, HEADS, rows)
        z = hidden[:, -1] @ head_w[:VOCAB].T
        np.testing.assert_allclose(
            per[:, -1], -jnp.take_along_axis(
                jax.nn.log_softmax(z, -1), labels[:, -1:], -1)[:, 0],
            rtol=1e-5)
        # every head's rows are in the loss
        assert np.all(np.any(np.asarray(got[1]) != 0, -1))


# -------------------------------------------------------------- the model
CONFIG = {"hidden_size": 64, "intermediate_size": 96,
          "num_attention_heads": 4, "num_layers": 2, "window_size": W,
          "chunk_size": C, "vocab_size": 20, "num_pred_heads": 8,
          "rms_norm_eps": 1e-5, "rope_theta": 100000}


#: heads of 128 in windows of 128: the exact part on the streamed kernels
KERNEL_CONFIG = dict(CONFIG, hidden_size=256, num_attention_heads=2,
                     window_size=128)


def tiny_model(seed=0, remat=False, config=CONFIG):
    mx.random.seed(seed)
    net = models.EvaByteModel(
        config["vocab_size"], config["hidden_size"],
        config["intermediate_size"], config["num_layers"],
        attention=dict(num_heads=config["num_attention_heads"],
                       window=config["window_size"], chunk=C,
                       rope_theta=1e5),
        remat_layers=remat)
    net.initialize(mx.init.Xavier())
    for layer in net.layers:
        for p in (layer.attn.phi, layer.attn.mu, layer.norm1.gamma):
            p.set_data(nd.random.normal(0, 0.5, p.shape))
    return net


def reference_params(net):
    spec = importlib.util.spec_from_file_location(
        "perfbench_builders_evabyte_lm",
        os.path.join(ROOT, "perfbench", "builders", "evabyte_lm.py"))
    builder = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(builder)
    return builder.reference_params(net), builder


def batch(seq_len, seed=1):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, CONFIG["vocab_size"], (B, seq_len + 1))
    return ids[:, :-1].astype("int32"), ids[:, 1:].astype("int32")


@pytest.mark.parametrize("remat,config", [
    (False, CONFIG), (True, CONFIG), (True, KERNEL_CONFIG)],
    ids=["kept", "recomputed", "recomputed_kernels"])
def test_the_model_is_the_reference(monkeypatch, reference, remat, config):
    """The last case: the exact windows on the streamed kernels
    (interpreted), every layer recomputed. This model's layers keep
    NOTHING of their forward (`recompute` gets no policy): at the cell's
    size the 0.6 GB that the windows' o and lse would take are not there,
    XLA recomputes other things to fit and the step gains nothing
    (PERF.md section 6, PR 46)."""
    if config is KERNEL_CONFIG:
        monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    window = config["window_size"]
    calls = eva._CALLS.value(local="streamed", remote="strips")
    policies = [gutils._RECOMPUTES.value(policy=p) for p in ("none", "given")]
    net = tiny_model(remat=remat, config=config)
    tokens, labels = batch(3 * window)
    params, _ = reference_params(net)
    loss_fn = models.MultiByteLoss(net)
    with jax.default_matmul_precision("highest"):
        tail, ref_loss = reference.forward(params, config, tokens, labels, 16)
        with autograd.record():
            feats = net.features(nd.array(tokens))
            loss = loss_fn(feats, nd.array(labels))
        loss.backward()
        np.testing.assert_allclose(feats.asnumpy()[:, -16:], tail, atol=2e-5)
        np.testing.assert_allclose(loss.asnumpy(), ref_loss, rtol=1e-5)
        z = net(nd.array(tokens))
        assert z.shape == (B, 3 * window, 8, config["vocab_size"])
        assert z.dtype == np.float32
        np.testing.assert_allclose(
            z.asnumpy(), reference.logits(params, config, tokens), atol=2e-5)
        grads = reference.checked_grads(params, config, tokens, labels)
    last = net.layers[-1]
    got = {"phi": last.attn.phi, "mu": last.attn.mu,
           "q": last.attn.query.weight, "k": last.attn.key.weight,
           "v": last.attn.value.weight, "o": last.attn.proj.weight,
           "gate": last.mlp.gate.weight, "up": last.mlp.up.weight,
           "down": last.mlp.down.weight}
    assert set(grads) == set(got) | {"head_pred0", "head_pred7"}
    for name, param in got.items():
        np.testing.assert_allclose(param.grad().asnumpy(), grads[name],
                                   atol=2e-5, err_msg=name)
    head = net.lm_head.weight.grad().asnumpy()
    np.testing.assert_allclose(head[:20], grads["head_pred0"], atol=2e-5)
    np.testing.assert_allclose(head[-20:], grads["head_pred7"], atol=2e-5)
    assert np.any(grads["head_pred7"]) and np.any(grads["phi"])
    assert (eva._CALLS.value(local="streamed", remote="strips") > calls) \
        == (config is KERNEL_CONFIG)
    # (the eager tape and the second forward each trace the stack once)
    none, given = (gutils._RECOMPUTES.value(policy=p) - was
                   for p, was in zip(("none", "given"), policies))
    assert (none > 0, given) == (remat, 0)


def test_the_residual_stream_stays_float32_under_a_bfloat16_cast():
    net = tiny_model()
    net.cast("bfloat16")
    tokens, _ = batch(2 * W)
    def dt(x):
        return jnp.dtype(x.dtype)

    bf16, f32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)
    assert dt(net.tok_embed.weight) == dt(net.layers[0].mlp.up.weight) \
        == dt(net.layers[0].norm1.gamma) == bf16
    assert dt(net.layers[0].attn.phi) == dt(net.layers[0].attn.mu) == f32
    x = net.stream(nd.array(tokens))
    assert dt(x) == f32
    layer = net.layers[0]
    assert dt(layer.norm1(x)) == bf16                   # a block's own type
    assert dt(layer.attn(layer.norm1(x))) == bf16
    assert dt(layer(x)) == f32
    assert dt(net.features(nd.array(tokens))) == bf16
    assert dt(net(nd.array(tokens))) == f32             # fp32_logits
    # every add of a (B, S, U) tensor on the way is the stream's, in float32
    jaxpr = str(jax.make_jaxpr(
        lambda t: net.stream(nd.NDArray(t))._data)(jnp.asarray(tokens)))
    stream = "[%d,%d,%d]" % (B, 2 * W, CONFIG["hidden_size"])
    adds = [line.split("=")[0] for line in jaxpr.splitlines()
            if " add " in line and stream in line.split("=")[0]]
    assert len(adds) >= 2 * CONFIG["num_layers"]
    assert all("f32" + stream in out for out in adds)


def test_the_mlp_in_row_blocks_is_the_whole_mlp_to_the_bit():
    """The row blocks change no arithmetic: the blocked MLP's output and
    input gradient are, bit for bit, the whole MLP's applied to each block
    of 16 rows (a row's result depends on its own row alone). Against the
    whole MLP over all 128 rows at once the last bits differ on the CPU,
    whose matmul picks its blocking by the row count: held to 1e-5."""
    def mlp(rows):
        mx.random.seed(0)
        block = evabyte.RowBlockedSwiGLU(CONFIG["hidden_size"],
                                         CONFIG["intermediate_size"], rows)
        block.initialize(mx.init.Xavier())
        return block

    whole, blocked = mlp(0), mlp(16)
    x = np.random.default_rng(3).standard_normal(
        (B, 2 * W, CONFIG["hidden_size"])).astype("float32")

    def run(mlp, x):
        weights = (mlp.gate.weight, mlp.up.weight, mlp.down.weight)
        for p in weights:
            p.zero_grad()
        m = nd.array(x)
        m.attach_grad()
        with autograd.record():
            out = mlp(m)
        out.backward()
        return out.asnumpy(), m.grad.asnumpy(), [
            p.grad().asnumpy().copy() for p in weights]

    out, dm, dw = run(blocked, x)
    rows = x.reshape(-1, 16, x.shape[-1])
    parts = [run(whole, r) for r in rows]
    assert np.array_equal(out.reshape(rows.shape[0], 16, -1),
                          np.stack([p[0] for p in parts]))
    assert np.array_equal(dm.reshape(rows.shape), np.stack([p[1]
                                                            for p in parts]))
    out_w, dm_w, dw_w = run(whole, x)
    np.testing.assert_allclose(out, out_w, rtol=0, atol=1e-5)
    np.testing.assert_allclose(dm, dm_w, rtol=0, atol=1e-5)
    for a, b in zip(dw, dw_w):                          # a sum of 8 blocks
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    # 128 tokens in blocks of 16: eight blocks traced, each recomputed
    jaxpr = str(jax.make_jaxpr(lambda t: blocked(
        nd.NDArray(t))._data)(jnp.asarray(x)))
    assert jaxpr.count("prevent_cse=True") == 8


def test_parameter_count_is_the_issues_and_the_models():
    net = tiny_model()
    _, builder = reference_params(net)
    held = sum(int(np.prod(p.shape))
               for p in net.collect_params().values())
    assert held == builder.parameter_count(CONFIG)
    published = dict(CONFIG, hidden_size=4096, intermediate_size=11008,
                     num_attention_heads=32, num_layers=4, vocab_size=320)
    assert builder.parameter_count(published) == 821366784
    assert net.layers[0].attn.phi.shape == net.layers[0].attn.mu.shape \
        == (4, 16)
