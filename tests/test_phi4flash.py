"""Phi-4-mini-flash (SambaY) on the CPU at small widths, seeded: the model
against the float32 reference the benchmark uses
(perfbench/reference/phi-4-mini-flash-reasoning.py) in value, loss and
every gradient; the selective scan against the recurrence a position at a
time; the window kernels (interpreted) against the dense masked softmax;
what the cross-decoder reads; that `window=None` is the kernel it was; and
differential attention's two wide-value calls a layer against the four
calls of one width it made before.
"""
import collections
import hashlib
import importlib.util
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, jit, models, nd, telemetry
from incubator_mxnet_tpu.gluon import utils as gutils
from incubator_mxnet_tpu.models import phi4flash
from incubator_mxnet_tpu.ops import attention
from incubator_mxnet_tpu.ops import selective_scan as scan_mod
from incubator_mxnet_tpu.ops.selective_scan import selective_scan

PERFBENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench")


def _load(kind, name):
    if PERFBENCH not in sys.path:
        sys.path.insert(0, PERFBENCH)
    spec = importlib.util.spec_from_file_location(
        "phi4flash_test_" + kind, os.path.join(PERFBENCH, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = _load("reference", "phi-4-mini-flash-reasoning")
builder = _load("builders", "phi4flash_lm")

CFG = {"hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 8,
       "num_key_value_heads": 4, "sliding_window": 24, "vocab_size": 128,
       "layer_norm_eps": 1e-5, "mamba_expand": 2, "mamba_d_state": 16,
       "mamba_d_conv": 4, "init_tok_embed_scale": 2.0,
       "layer_pattern_run": "MSMFGCGC"}
B, S = 2, 160       # three chunks of the scan, the last one padded


def build(cfg=CFG, dtype="float32", seed=0, remat=False):
    mx.random.seed(seed)
    s = builder.shapes(cfg)
    net = models.Phi4FlashModel(
        cfg["vocab_size"], s["units"], s["hidden"], s["pattern"],
        mamba=dict(inner=s["inner"], state=s["state"], conv_kernel=s["conv"]),
        attention=dict(num_heads=s["heads"], num_kv_heads=s["kv_heads"],
                       head_dim=s["head_dim"]),
        window=s["window"], epsilon=cfg["layer_norm_eps"],
        remat_layers=remat)
    net.initialize(mx.init.Xavier())
    for p in net.collect_params().values():
        if p.name.endswith(("bias", "beta", "gamma")) \
                and "dt_bias" not in p.name:
            # the zero and one initialisations hide a misplaced bias or gain
            p.set_data(p.data() + nd.random.normal(0, 0.1, p.shape))
    net.cast(dtype)
    return net


def batch(seed=0, cfg=CFG, s=S):
    rng = onp.random.default_rng(seed)
    ids = rng.integers(0, cfg["vocab_size"], (B, s + 1)).astype("int32")
    return ids[:, :-1], ids[:, 1:]


def rel_rms(got, want):
    got, want = (onp.asarray(x, "float32") for x in (got, want))
    return float(onp.sqrt(onp.mean((got - want) ** 2))
                 / onp.sqrt(onp.mean(want ** 2)))


# ------------------------------------------------------------ the scan
_SCAN_ARGS = ["x", "dt", "dt_w", "dt_b", "A", "B", "C", "D"]


def _scan_inputs(seed, b, s, c, n, r=6):
    """x, the step sizes' low-rank input and projection, A, B, C, D. Decays
    from nearly 0 (dt A = -30) to nearly 1 (dt A = -1e-5)."""
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = jax.random.normal(k[0], (b, s, c))
    low = jax.random.normal(k[1], (b, s, r))
    w = 1.2 * jax.random.normal(k[2], (c, r))
    bias = jax.random.normal(k[3], (c,)) - 3
    a = -jnp.exp(jax.random.uniform(k[4], (c, n), minval=-7.0, maxval=3.0))
    return (x, low, w, bias, a, jax.random.normal(k[5], (b, s, n)),
            jax.random.normal(k[6], (b, s, n)), jax.random.normal(k[7], (c,)))


def _step_sizes(low, w, bias):
    return jax.nn.softplus(jnp.einsum("bsr,cr->bsc", low, w) + bias)


def _sequential(x, low, w, bias, a, bm, cm, d):
    """The reference's recurrence a position at a time, fed the step sizes
    formed outside it."""
    return reference._scan(x, _step_sizes(low, w, bias), a, bm, cm) + d * x


def _chunked(x, low, w, bias, a, bm, cm, d, chunk=16):
    return selective_scan(x, low, a, bm, cm, d, (w, bias), chunk)


@pytest.mark.parametrize("s,chunk", [(64, 16), (100, 16), (100, 64),
                                     (48, 64), (96, 96)])
def test_selective_scan_is_the_sequential_recurrence(s, chunk):
    """float32 against the recurrence a position at a time, chunks that do
    and do not divide S, decays near 0 and near 1: 1e-4 of the largest
    output (the limit chip_smoke.py --phases hybrid holds the chip to; the
    sums' order is all that differs, and reads 1e-6). Through the one
    entry the model uses: the step sizes formed inside the channel block,
    the pad decayed by the bias."""
    args = _scan_inputs(1, 2, s, 40, 16)
    decay = jnp.exp(_step_sizes(*args[1:4])[..., None] * args[4])
    assert decay.min() < 1e-6 and decay.max() > 0.9999
    want = _sequential(*args)
    got = _chunked(*args, chunk)
    assert got.dtype == args[0].dtype
    assert jnp.abs(got - want).max() < 1e-4 * jnp.abs(want).max()


def test_the_default_chunk_is_the_modules_constant():
    args = _scan_inputs(1, 1, 3 * scan_mod._CHUNK + 5, 24, 16)
    x, low, w, bias, a, bm, cm, d = args
    onp.testing.assert_array_equal(
        selective_scan(x, low, a, bm, cm, d, (w, bias)),
        _chunked(*args, scan_mod._CHUNK))


def test_a_bfloat16_state_fails_the_float32_tolerance(monkeypatch):
    """What the 1e-4 is for: with the states and sums in bfloat16 the same
    comparison reads a hundred times the limit."""
    args = _scan_inputs(1, 2, 64, 40, 16)
    want = _sequential(*args)
    monkeypatch.setattr(scan_mod, "_F32", jnp.bfloat16)
    got = _chunked(*args).astype(jnp.float32)
    assert jnp.abs(got - want).max() > 1e-3 * jnp.abs(want).max()


@pytest.mark.parametrize("arg", range(8), ids=_SCAN_ARGS)
def test_selective_scan_gradients_are_the_sequential_ones(arg):
    """Every input's, the step sizes' projection and bias among them, at a
    length the chunks do not divide."""
    args = _scan_inputs(2, 1, 72, 24, 16)
    want = jax.grad(lambda *a: jnp.sum(_sequential(*a) ** 2), arg)(*args)
    got = jax.grad(lambda *a: jnp.sum(_chunked(*a) ** 2), arg)(*args)
    assert jnp.abs(got - want).max() < 1e-4 * jnp.abs(want).max()


def test_the_scan_runs_in_channel_blocks_and_counts_its_traces(monkeypatch):
    """More channels than one block: the blocks are independent, so the
    result is the unblocked one; one increment a traced call."""
    args = _scan_inputs(3, 1, 32, 64, 16)
    whole = _chunked(*args)
    monkeypatch.setattr(scan_mod, "_CHANNEL_BLOCK", 16)
    before = scan_mod._SCANS.value(path="chunked_xla")
    # (the projection's sum runs block by block too: the last bits move)
    onp.testing.assert_allclose(_chunked(*args), whole,
                                rtol=1e-5, atol=1e-5)
    assert scan_mod._SCANS.value(path="chunked_xla") == before + 1
    assert 'mxtpu_selective_scan_total{path="chunked_xla"}' \
        in telemetry.REGISTRY.export_text()


# ----------------------------------------------------- the window kernels
def _dense_window(q, k, v, window):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / onp.sqrt(q.shape[-1])
    d = jnp.arange(q.shape[2])[:, None] - jnp.arange(q.shape[2])[None, :]
    seen = d >= 0 if window is None else (d >= 0) & (d < window)
    return jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v)


@pytest.mark.parametrize("window,block", [
    (64, 128), (128, 128), (200, 128), (100, 256), (1000, 128), (1, 128)],
    ids=["under_a_block", "a_block", "over_a_block", "wide_blocks",
         "over_S", "diagonal"])
def test_window_kernels_are_the_dense_masked_softmax(monkeypatch, window,
                                                     block):
    """Interpreted, float32, forward and all three gradients, at windows
    under, at and over a block and over the whole sequence."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    shape = (1, 2, 512, 128)
    q, k, v, do = (jax.random.normal(key, shape) for key in
                   jax.random.split(jax.random.PRNGKey(window), 4))
    assert attention.attention_route(shape, block_q=block, block_k=block,
                                     window=window) == "streamed"

    def system(q, k, v):
        return attention.flash_attention(q, k, v, True, None, block, block,
                                         window)

    want = _dense_window(q, k, v, window)
    assert jnp.abs(system(q, k, v) - want).max() < 1e-5
    got = jax.grad(lambda *a: (system(*a) * do).sum(), (0, 1, 2))(q, k, v)
    ref = jax.grad(lambda *a: (_dense_window(*a, window) * do).sum(),
                   (0, 1, 2))(q, k, v)
    for g, r in zip(got, ref):
        assert jnp.abs(g - r).max() < 1e-5 * (jnp.abs(r).max() + 1)


def test_window_kernels_have_names_of_their_own_and_a_band_for_a_grid(
        monkeypatch):
    """A capture tells them from the causal kernels of the same program;
    the grid's last axis is the band (2 kv-blocks of 512 a q-block at
    window 512), not all 4 of them; the gauge says what was visited."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    x = jax.ShapeDtypeStruct((1, 2, 2048, 64), jnp.bfloat16)

    def loss(q, k, v):
        return attention.flash_attention(q, k, v, True, window=512) \
            .astype(jnp.float32).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(x, x, x))
    assert "flash_window_fwd" in text and "flash_window_bwd" in text
    assert "flash_fwd" not in text and "flash_bwd_dkvq" not in text
    assert "grid=(2, 4, 2)" in text.replace("\n", "")
    gauge = attention._LIVE_PAIRS
    assert (gauge.value(kind="window"), gauge.value(kind="causal")) == (7, 10)


def test_a_window_goes_to_no_new_family(monkeypatch):
    """The short family knows the diagonal only: a windowed shape it would
    have taken goes to the composite, which masks the band; a window
    without `causal` is refused."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    shape = (2, 2, 256, 64)
    assert attention.attention_route(shape) == "short"
    assert attention.attention_route(shape, window=32) == "composite"
    q, k, v = (jax.random.normal(key, shape) for key in
               jax.random.split(jax.random.PRNGKey(0), 3))
    got = attention.flash_attention(q, k, v, True, window=32)
    assert jnp.abs(got - _dense_window(q, k, v, 32)).max() < 1e-5
    g = jax.grad(lambda q: attention.flash_attention(
        q, k, v, True, window=32).sum())(q)
    r = jax.grad(lambda q: _dense_window(q, k, v, 32).sum())(q)
    assert jnp.abs(g - r).max() < 1e-5
    with pytest.raises(ValueError):
        attention.flash_attention(q, k, v, False, window=32)


#: sha256 of str(jaxpr) of grad(flash_attention) with no window, interpreted
#: on the CPU. Up to PR 46 the text of commit 9e90788, before the window
#: came ("e82e6e70d2660122", "8fdc20b2caf01631"); PR 47 rewrote the forward
#: kernel's body (two bodies, no guards, sub-tiles), so the pin is that
#: PR's text: the backward's call and everything around both are unchanged.
PARENT_JAXPR = {((1, 2, 2048, 128), True): "8abb5f8c04fb697e",
                ((2, 2, 2048, 64), False): "57c094ccbcadbeef"}


@pytest.mark.parametrize("shape,causal", list(PARENT_JAXPR),
                         ids=["causal", "dense"])
def test_window_none_is_the_kernel_it_was(monkeypatch, shape, causal):
    """The traced calls (grid, specs, names, the kernels' bodies) of
    flash_attention with the argument absent, with `window=None`, and as
    pinned above are one text; the lowered text with and without the
    argument is equal too, and the calls are flash_fwd / flash_bwd_dkvq."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    def absent(q, k, v):
        return attention.flash_attention(q, k, v, causal) \
            .astype(jnp.float32).sum()

    def none(q, k, v):
        return attention.flash_attention(q, k, v, causal, window=None) \
            .astype(jnp.float32).sum()

    def traced():
        return [str(jax.make_jaxpr(jax.grad(f, (0, 1, 2)))(x, x, x))
                for f in (absent, none)]

    texts = traced()
    assert texts[0] == texts[1]
    # since PR 46 the forward rule names o and lse for checkpoint policies:
    # two equations that lower to nothing; without them, the parent's text
    named = " = name[name=%s]" % attention.ATTENDED_NAME
    assert texts[0].count(named) == 2
    with monkeypatch.context() as patch:
        patch.setattr(attention, "checkpoint_name", lambda x, name: x)
        plain = traced()[0]
    assert len(plain.splitlines()) == len(texts[0].splitlines()) - 2
    assert hashlib.sha256(plain.encode()).hexdigest()[:16] \
        == PARENT_JAXPR[shape, causal]
    assert "name=flash_fwd" in texts[0] and "name=flash_bwd_dkvq" in texts[0]
    assert "flash_window" not in texts[0]
    lowered = [jax.jit(jax.grad(f, (0, 1, 2))).lower(x, x, x).as_text()
               for f in (absent, none)]
    assert lowered[0].replace("none", "absent") \
        == lowered[1].replace("none", "absent")


# ------------------------------------------- differential attention's calls
def _diff_block(heads, kv_heads, window=None):
    return phi4flash.DifferentialAttention(
        64, heads, kv_heads, 64, depth=1, window=window)


def _diff_inputs(block, s, dtype, key=None):
    """q (1, S, H d), k, v (1, S, Hkv d), the four l vectors, the gain: as
    arrays from ``key``, else as shapes."""
    d, h, hkv = block._d, block._h, block._hkv
    shapes = [((1, s, h * d), dtype), ((1, s, hkv * d), dtype),
              ((1, s, hkv * d), dtype), ((4, d), jnp.float32),
              ((2 * d,), jnp.float32)]
    if key is None:
        return [jax.ShapeDtypeStruct(*x) for x in shapes]
    keys = jax.random.split(key, len(shapes))
    return [(0.3 * jax.random.normal(k, shape)).astype(t)
            for k, (shape, t) in zip(keys, shapes)]


def _four_calls(block, q, k, v, lambdas, gamma):
    """Differential attention as it was called up to PR 36: a_1 v_1,
    a_1 v_2, a_2 v_1, a_2 v_2, four attentions of one shape, concatenated
    in pairs."""
    b, s, _ = q.shape
    d, h, hkv = block._d, block._h, block._hkv

    def halves(t, n):
        t = t.reshape(b, s, n // 2, 2, d).transpose(3, 0, 2, 1, 4)
        return [jnp.repeat(x, h // hkv, 1) if n != h else x for x in t]

    def attend(q, k, v):
        return attention.flash_attention(
            q, k, v, True, window=block._window).astype(jnp.float32)

    (q1, q2), (k1, k2), (v1, v2) = halves(q, h), halves(k, hkv), \
        halves(v, hkv)
    lam = jnp.exp(jnp.sum(lambdas[0] * lambdas[1])) \
        - jnp.exp(jnp.sum(lambdas[2] * lambdas[3])) + block._l_init
    o = jnp.concatenate([attend(q1, k1, v1), attend(q1, k1, v2)], -1) \
        - lam * jnp.concatenate([attend(q2, k2, v1), attend(q2, k2, v2)], -1)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + block._eps) \
        * gamma * (1.0 - block._l_init)
    return o.transpose(0, 2, 1, 3).reshape(b, s, h * d).astype(q.dtype)


@pytest.mark.parametrize("s,window,forward,backward,wide", [
    (2048, None, ("flash_fwd", 2), ("flash_bwd_dkvq", 2), 2),
    (2048, 512, ("flash_window_fwd", 2), ("flash_window_bwd", 2), 2),
    # (the short family's calls are jitted: the jaxpr names a call by its
    # function and holds the kernel's text once)
    (512, None, ("_short_call", 4), ("_short_bwd_call", 4), 0)],
    ids=["full_causal", "window", "short"])
def test_differential_attention_is_two_streamed_calls_a_layer(
        monkeypatch, s, window, forward, backward, wide):
    """Read from the jaxpr of the layer's gradient at 16 query heads on 8
    key-value heads of 64, calls of (1, 8, S, 64 | 128): TWO forward and
    two backward kernels, each map once against [v_1; v_2]; where the
    equal-width shape belongs to the short family (one width in its lane
    layout) the four calls there were; the counter reads the wide calls."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    block = _diff_block(16, 8, window)
    assert attention.attention_route((1, 8, s, 64), window=window) \
        == ("streamed" if s == 2048 else "short")
    before = attention._WIDE_VALUES.value(route="streamed")

    def loss(*args):
        return block._attend(*args).astype(jnp.float32).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2, 3, 4)))(
        *_diff_inputs(block, s, jnp.bfloat16)))
    named = collections.Counter(re.findall(r"name=(\w+)", text))
    assert {k: named[k] for k in (
        "flash_fwd", "flash_bwd_dkvq", "flash_window_fwd", "flash_window_bwd",
        "_short_call", "_short_bwd_call") if named[k]} \
        == dict([forward, backward])
    assert attention._WIDE_VALUES.value(route="streamed") - before == wide
    if wide:
        assert 'mxtpu_attention_wide_value_total{route="streamed"}' \
            in telemetry.REGISTRY.export_text()


@pytest.mark.parametrize("s,heads,kv_heads,window,interpret", [
    (192, 8, 4, None, False), (192, 8, 8, 24, False),
    (2048, 4, 2, None, True), (2048, 4, 2, 512, True),
    (512, 4, 4, None, True)],
    ids=["composite", "composite_window", "streamed", "streamed_window",
         "short"])
def test_differential_attention_is_the_four_call_composition(
        monkeypatch, s, heads, kv_heads, window, interpret):
    """Output and every gradient (q, k, v, the four l vectors, the gain),
    float32, against the four calls written above: on the composite, on the
    interpreted streamed kernels (key-value pairs repeated to the query
    pairs) and where the block itself still makes the four short calls."""
    if interpret:
        monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    block = _diff_block(heads, kv_heads, window)
    args = _diff_inputs(block, s, jnp.float32, jax.random.PRNGKey(s))
    cot = jax.random.normal(jax.random.PRNGKey(1), args[0].shape)
    got = block._attend(*args)
    want = _four_calls(block, *args)
    assert got.shape == want.shape and jnp.abs(got - want).max() < 1e-5
    every = tuple(range(5))
    grads = jax.grad(lambda *a: (block._attend(*a) * cot).sum(), every)(*args)
    refs = jax.grad(lambda *a: (_four_calls(block, *a) * cot).sum(),
                    every)(*args)
    for g, r in zip(grads, refs):
        assert jnp.abs(r).max() > 0
        assert jnp.abs(g - r).max() < 1e-5 * (jnp.abs(r).max() + 1)


# ------------------------------------------------------------ the model
def test_the_pattern_rule_gives_the_published_order():
    assert phi4flash.sambay_pattern(32, 2) \
        == "MS" * 8 + "M" + "F" + "GC" * 7
    for bad in ("", "MSX", "GC", "MGFC", "FGC", "MFFC"):
        with pytest.raises(ValueError):
            models.Phi4FlashModel(
                16, 16, 16, bad, mamba=dict(inner=32),
                attention=dict(num_heads=2, num_kv_heads=2, head_dim=8),
                window=4)


def test_float32_model_matches_the_reference():
    """Features, logits' loss: float32 at "highest" on both sides, two
    algorithms for the scan and for the attentions."""
    net = build()
    tokens, labels = batch()
    with jax.default_matmul_precision("highest"):
        want, want_loss = reference.forward(
            builder.reference_params(net), CFG, jnp.asarray(tokens),
            jnp.asarray(labels), S)
        got = net.features(nd.array(tokens)).asnumpy()
        loss = models.ChunkedLMLoss(net)(
            net.features(nd.array(tokens)), nd.array(labels)).asnumpy()
    assert onp.abs(got - onp.asarray(want)).max() \
        < 1e-4 * onp.abs(want).max()
    onp.testing.assert_allclose(loss, want_loss, rtol=1e-5)


def test_bfloat16_model_stays_near_the_reference():
    net = build(dtype="bfloat16")
    tokens, labels = batch()
    want, _ = reference.forward(builder.reference_params(net), CFG,
                                jnp.asarray(tokens), jnp.asarray(labels), S)
    got = net.features(nd.array(tokens)).asnumpy()
    assert rel_rms(got, want) < 0.03


def _grads(net, tokens, labels):
    """The gradient of the summed loss with respect to every parameter, in
    the reference's tree: the parameters' arrays are swapped for tracers,
    then for their gradients while the builder gathers the tree."""
    params = [p for p in net.collect_params().values()
              if p.grad_req != "null"]
    loss_fn = models.ChunkedLMLoss(net)
    arrs = [p.data() for p in params]

    def swapped(datas, then):
        saved = [a._data for a in arrs]
        for a, d in zip(arrs, datas):
            a._data = d
        try:
            return then()
        finally:
            for a, s in zip(arrs, saved):
                a._data = s

    def fn(datas):
        return swapped(datas, lambda: loss_fn(
            net.features(nd.array(tokens)), nd.array(labels))._data.sum())

    grads = jax.grad(fn)([a._data for a in arrs])
    return swapped(grads, lambda: builder.reference_params(net))


# 16 x 64 = 1024 channels: the narrowest Mamba the scan's kernels take (4
# states: the interpreted kernels' unrolled bodies are what the case costs)
KERNEL_CFG = dict(CFG, mamba_expand=16, mamba_d_state=4)


# heads of 128 at 128 positions: the streamed attention kernels (causal in
# F and C, under the window in S), a value 256 wide on keys of 128
ATTENTION_KERNEL_CFG = dict(CFG, hidden_size=256, num_attention_heads=2,
                            num_key_value_heads=2, sliding_window=64,
                            layer_pattern_run="MSMFGC")


@pytest.mark.parametrize("remat,cfg,path,s", [
    (False, CFG, "chunked_xla", S), (True, CFG, "chunked_xla", S),
    (True, KERNEL_CFG, "pallas", S),
    (True, ATTENTION_KERNEL_CFG, "chunked_xla", 128)],
    ids=["stored", "recomputed", "recomputed_scan_kernels",
         "recomputed_attention_kernels"])
def test_every_gradient_matches_the_reference(monkeypatch, remat, cfg, path,
                                              s):
    """EVERY parameter's gradient against autodiff of the reference,
    float32 at "highest", with and without per-layer recomputation (the
    tuples `gluon.utils.recompute` carries): 2e-4 of each gradient's
    largest entry. Two G and two C behind the F: F's projection holds dK
    and dV summed over itself and both readers, the memory's Mamba the sum
    over both gates. The third case: the scans as their kernel pair
    (interpreted; three chunks of 64, the last one padded), forward,
    recomputed with the chunks' start states kept, and backward: the
    recomputation keeps what the kernels wrote (`phi4flash._KEPT`) and
    runs none of them again. The last case, the same for the streamed
    attention kernels (interpreted): o and lse of the first forward beside
    q, k, v made again."""
    kernels = path == "pallas" or cfg is ATTENTION_KERNEL_CFG
    if kernels:
        monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    scans = scan_mod._SCANS.value(path=path)
    streamed = attention._ROUTES.value(route="streamed")
    given = gutils._RECOMPUTES.value(policy="given")
    net = build(cfg, remat=remat)
    tokens, labels = batch(cfg=cfg, s=s)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda p: reference.forward(
            p, cfg, jnp.asarray(tokens), jnp.asarray(labels), 1)[1].sum())(
                reference._f32(builder.reference_params(net)))
        got = _grads(net, tokens, labels)
    assert scan_mod._SCANS.value(path=path) > scans
    assert (attention._ROUTES.value(route="streamed") > streamed) \
        == (cfg is ATTENTION_KERNEL_CFG)
    assert gutils._RECOMPUTES.value(policy="given") - given \
        == (len(cfg["layer_pattern_run"]) if remat else 0)
    flat_w, tree_w = jax.tree_util.tree_flatten_with_path(want)
    flat_g, tree_g = jax.tree_util.tree_flatten_with_path(got)
    assert tree_w == tree_g \
        and len(flat_w) > (70 if cfg is ATTENTION_KERNEL_CFG else 90)
    for (path, w), (_, g) in zip(flat_w, flat_g):
        name = jax.tree_util.keystr(path)
        assert onp.abs(w).max() > 0, name
        assert onp.abs(g - w).max() < 2e-4 * onp.abs(w).max(), name


def _drop(layer_params, *names):
    return {k: (jnp.zeros_like(v) if k in names else v)
            for k, v in layer_params.items()}


def test_dk_dv_are_the_sum_over_the_readers():
    """The gradient that reaches F's key and value rows is what F itself
    sends plus what EACH cross-attention layer sends: with one reader's
    out-projection zeroed it loses that reader's part, and the parts add up
    (the loss's gradient is taken at the same point every time: the
    readers feed the stream only through their out-projections... so the
    comparison is of one linear map's three terms, at a fixed cotangent)."""
    net = build()
    u = CFG["hidden_size"]
    x = nd.array(onp.random.default_rng(7).standard_normal(
        (B, S, u)).astype("float32"))
    full, c1, c2 = (net.layers[i].mixer for i in (3, 5, 7))

    def reach(readers):
        """d sum(outputs of `readers`) / d (k, v) of F."""
        def fn(k, v):
            total = 0.0
            for r in readers:
                total = total + r(x, nd.NDArray(k), nd.NDArray(v)) \
                    ._data.sum()
            return total
        _, k, v = full(x)
        return jax.grad(fn, (0, 1))(k._data, v._data)

    both, one, other = reach([c1, c2]), reach([c1]), reach([c2])
    for b_, o1, o2 in zip(both, one, other):
        assert jnp.abs(o1).max() > 0 and jnp.abs(o2).max() > 0
        assert jnp.abs(b_ - (o1 + o2)).max() < 1e-5 * jnp.abs(b_).max()


def test_g_reads_the_memory_of_the_last_m_and_no_other():
    """The features change when the LAST Mamba before F hands on another
    memory, and do not see what an earlier Mamba's scan put out: the first
    M's `hand_on` is off and its mixer returns one array."""
    net = build()
    tokens, _ = batch()
    first, last = net.layers[0].mixer, net.layers[2].mixer
    assert not first._hand_on and last._hand_on
    u = nd.array(onp.random.default_rng(3).standard_normal(
        (B, S, CFG["hidden_size"])).astype("float32"))
    assert not isinstance(first(u), tuple)
    out, memory = last(u)
    assert memory.shape == (B, S, 2 * CFG["hidden_size"])
    # the gate's input is exactly that memory: zero it and G adds nothing
    gmu = net.layers[4].mixer
    assert onp.abs(gmu(u, memory).asnumpy()).max() > 0
    assert onp.abs(gmu(u, memory * 0).asnumpy()).max() == 0
    # and the model hands G the last M's: a changed D (the skip is part of
    # y, the memory) of the FIRST M moves the memory only through the
    # stream, of the LAST M directly
    base = net.features(nd.array(tokens)).asnumpy()
    seen = []
    features = phi4flash.Phi4FlashModel.features

    def spy(self, h, m):
        seen.append(m)
        return orig(self, h, m)

    orig = phi4flash.GatedMemoryUnit.forward
    phi4flash.GatedMemoryUnit.forward = spy
    try:
        features(net, nd.array(tokens))
    finally:
        phi4flash.GatedMemoryUnit.forward = orig
    assert len(seen) == 2 and seen[0] is seen[1]
    x = net.tok_embed(nd.array(tokens))
    for layer in list(net.layers)[:2]:
        x = layer(x)
    _, want = net.layers[2](x)
    onp.testing.assert_allclose(seen[0].asnumpy(), want.asnumpy(),
                                rtol=1e-5, atol=1e-6)
    assert base.shape == (B, S, CFG["hidden_size"])


def test_recompute_carries_a_tuple_and_one_array_as_before():
    net = build()
    x = nd.array(onp.random.default_rng(5).standard_normal(
        (B, S, CFG["hidden_size"])).astype("float32"))
    one = gutils.recompute(net.layers[1], x)
    assert isinstance(one, nd.NDArray)
    onp.testing.assert_array_equal(one.asnumpy(), net.layers[1](x).asnumpy())
    three = gutils.recompute(net.layers[3], x)
    assert isinstance(three, tuple) and len(three) == 3
    for got, want in zip(three, net.layers[3](x)):
        onp.testing.assert_array_equal(got.asnumpy(), want.asnumpy())


@pytest.mark.parametrize("mamba,path", [
    ({}, "chunked_xla"),
    ({"mamba_expand": 4, "mamba_d_state": 4}, "pallas")],
    ids=["scan_in_xla_ops", "scan_kernels"])
def test_one_train_step_lowers_once_and_keeps_the_scopes_under_recompute(
        monkeypatch, mamba, path):
    """The normal path (FeaturesView + ChunkedLMLoss over the tied
    embedding through TrainStep, bfloat16 with float32 masters, the
    interpreted streamed kernels at heads of 128, every layer recomputed):
    one program, a falling loss, the new scopes on forward, recomputed and
    backward ops, and both kinds of kernel in the one program. At 1024
    channels the scans are their kernel pair, under the same scope."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    scans = scan_mod._SCANS.value(path=path)
    cfg = dict(CFG, hidden_size=256, num_attention_heads=2,
               num_key_value_heads=2, sliding_window=64,
               layer_pattern_run="MSMFGC", **mamba)
    net = build(cfg, dtype="bfloat16", remat=True)
    view = models.FeaturesView(net)
    trainer = gluon.Trainer(view.collect_params(), "adam",
                            {"learning_rate": 1e-3, "multi_precision": True})
    step = jit.TrainStep(view, models.ChunkedLMLoss(net), trainer)
    tokens, labels = batch(cfg=cfg, s=128)
    losses = [float(step(nd.array(tokens), nd.array(labels)).asnumpy().mean())
              for _ in range(4)]
    assert losses[-1] < losses[0]
    (text,) = [t for model_id, t in jit.compiled_train_programs()
               if model_id == step._model_id]
    for scope in ("selective_scan", "mamba_conv", "mamba_gate",
                  "diff_attention", "gmu", "cross_attention", "ffn"):
        paths = [l for l in text.splitlines() if "/" + scope + "/" in l]
        # (the scan as its kernel pair is ALL that is under its scope, and
        # a recomputed layer keeps what the forward kernel wrote: nothing
        # of the scope is computed again)
        assert any("rematted_computation" in l for l in paths) \
            == ((scope, path) != ("selective_scan", "pallas")), scope
        assert any("transpose(" in l for l in paths), scope
        assert any("transpose(" not in l for l in paths), scope
    assert attention._WINDOWS.value(route="streamed") >= 1
    assert scan_mod._SCANS.value(path=path) == scans + 2    # two M layers
