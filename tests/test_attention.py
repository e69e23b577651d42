"""Flash-attention Pallas kernels, run in interpret mode on CPU.

The same kernels run compiled on TPU (verified on-chip); interpret mode
exercises the kernel bodies, BlockSpecs, and the custom-VJP plumbing in CI.
Ref parity target: the XLA composite (ops/attention.py _blocked_reference)
for the streamed family, a float32 jax.numpy reference for the short one.
"""
import math
import types

import numpy as onp
import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")


def _rand_qkv(B=1, H=2, S=256, D=128, seed=0):
    rng = onp.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(B, H, S, D).astype("float32"))
                 for _ in range(3))


def test_auto_block_rejects_odd_lengths():
    from incubator_mxnet_tpu.ops import attention as A
    assert not A.flash_attention_legal((1, 2, 200, 128))  # no block divides
    assert not A.flash_attention_supported((1, 2, 200, 128))
    out = A.flash_attention(*_rand_qkv(S=200))  # falls back, no crash
    assert out.shape == (1, 2, 200, 128)


@pytest.mark.parametrize("D", [64, 128])
def test_flash_head_dims(D):
    # standard head dims ride kernels too: D=128 the streamed family,
    # D=64 (BERT's) at this short S the short one
    from incubator_mxnet_tpu.ops import attention as A
    q, k, v = _rand_qkv(D=D)
    assert A.flash_attention_legal(q.shape)
    assert A.attention_route(q.shape) == {64: "short", 128: "streamed"}[D]
    out = A.flash_attention(q, k, v, True)
    ref = A._blocked_reference(q, k, v, True, 1.0 / onp.sqrt(D))
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-4
    g = jax.grad(lambda a, b, c: jnp.sum(A.flash_attention(a, b, c, True)),
                 (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda a, b, c: jnp.sum(
        A._blocked_reference(a, b, c, True, 1.0 / onp.sqrt(D))),
        (0, 1, 2))(q, k, v)
    for x, y in zip(g, gr):
        assert float(jnp.max(jnp.abs(x - y)) / jnp.max(jnp.abs(y))) < 1e-3


def _case(shape=(1, 2, 256, 128), d_v=None, dtype="float32", causal=True,
          window=None, scale=None, blocks=(None, None)):
    return types.SimpleNamespace(**locals())


# What the streamed forward must carry: the tile's two bodies (interior:
# never masked; diagonal: one select), no guard of an empty row where none
# can be empty, and the guarded body under a window.
_FORWARD = {
    "dense": _case(causal=False),
    "causal": _case(),
    # a diagonal tile holds rows that see none of its keys, both ways
    "causal-256x128": _case(shape=(1, 2, 512, 128), blocks=(256, 128)),
    "causal-128x256": _case(shape=(1, 2, 512, 128), blocks=(128, 256)),
    "dense-128x256": _case(shape=(1, 2, 512, 128), causal=False,
                           blocks=(128, 256)),
    # blocks of 1024 in two sub-tiles of 512 keys, one interior pair and
    # two diagonal; heads of 64 against a value 128 wide (the SambaY F, C)
    "subtiles-64|128": _case(shape=(1, 1, 2048, 64), d_v=128),
    "bf16": _case(dtype="bfloat16"),
    "scale-0.3": _case(scale=0.3),                       # Ulysses's own
    # rows 448.. of q-block 1 see no key of their first live tile (keys
    # 128..255): the window body's guards carry them at zero
    "window-empty-first-tile": _case(shape=(1, 2, 512, 128), window=64,
                                     blocks=(256, 128)),
}


def _case_qkv(c, seed=0):
    rng = onp.random.RandomState(seed)
    wide = c.shape[:3] + (c.d_v or c.shape[3],)
    return tuple(jnp.asarray(rng.randn(*s).astype("float32")).astype(c.dtype)
                 for s in (c.shape, c.shape, wide))


@pytest.mark.parametrize("case", list(_FORWARD))
def test_flash_forward_matches_composite(case):
    from incubator_mxnet_tpu.ops import attention as A
    c = _FORWARD[case]
    q, k, v = _case_qkv(c)
    assert A.attention_route(q.shape, k.shape, v.shape, *c.blocks,
                             c.window) == "streamed"
    out = A.flash_attention(q, k, v, c.causal, c.scale, *c.blocks, c.window)
    assert out.shape == v.shape and out.dtype == v.dtype
    ref = A._blocked_reference(
        *(x.astype(jnp.float32) for x in (q, k, v)), c.causal,
        c.scale or 1.0 / onp.sqrt(q.shape[-1]), c.window)
    assert bool(jnp.isfinite(out).all())
    # bfloat16: the output's own rounding, 2^-9 of values up to ~4
    tol = 2e-4 if c.dtype == "float32" else 2e-2
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref))) < tol


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block", [None, 128])  # 128 forces a multi-block
def test_flash_backward_matches_composite(causal, block):               # grid
    from incubator_mxnet_tpu.ops import attention as A
    q, k, v = _rand_qkv()
    scale = 1.0 / onp.sqrt(q.shape[-1])

    def loss_flash(q, k, v):
        return jnp.sum(jnp.sin(A.flash_attention(q, k, v, causal, None,
                                                 block, block)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(A._blocked_reference(q, k, v, causal, scale)))

    gf = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        rel = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        assert rel < 1e-3


def test_flash_backward_never_materializes_scores():
    """The backward jaxpr must contain no (S, S)-shaped intermediate."""
    from incubator_mxnet_tpu.ops import attention as A
    q, k, v = _rand_qkv(S=256)
    S = q.shape[2]

    def loss(q, k, v):
        return jnp.sum(A.flash_attention(q, k, v, True))

    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, v)
    for eqn in jaxpr.jaxpr.eqns:
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            assert not (len(shape) >= 2 and shape[-1] == S and shape[-2] == S), \
                f"(S,S) intermediate found: {eqn.primitive} -> {shape}"


@pytest.mark.parametrize("case", [
    "dense", "causal", "causal-256x128", "causal-128x256", "dense-128x256",
    "scale-0.3"])
def test_flash_lse_saved_from_forward(case):
    """The log-sum-exp the forward saves for its backward is the one
    `flash_attention_lse` returns, and `_dense_with_lse`'s to 1e-5 (the
    running sum is kept a lane, the scale multiplies float32 scores)."""
    from incubator_mxnet_tpu.ops import attention as A
    c = _FORWARD[case]
    q, k, v = _case_qkv(c, seed=1)
    B, H, S, D = q.shape
    scale = c.scale or 1.0 / onp.sqrt(D)
    blocks = tuple(b or 128 for b in c.blocks)
    out, res = A._fa_fwd(q, k, v, c.causal, c.scale, *blocks)
    lse = res[4]
    assert lse is not None and lse.shape == (B * H, 1, S)
    out2, lse2 = A.flash_attention_lse(q, k, v, c.causal, scale, *blocks)
    onp.testing.assert_array_equal(out, out2)
    onp.testing.assert_array_equal(lse.reshape(B, H, S), lse2)
    want_out, want_lse = A._dense_with_lse(q, k, v, c.causal, scale)
    assert float(jnp.max(jnp.abs(lse2 - want_lse))) < 1e-5
    assert float(jnp.max(jnp.abs(out2 - want_out))) < 2e-4


@pytest.mark.parametrize("shape,causal,interior,diagonal", [
    ((1, 16, 16384, 128), True, 120, 16),      # cerebras-gpt-1.3b.train-s16k
    ((1, 16, 16384, 128), False, 256, 0),      # dense: never masked
    ((256, 1, 2048, 128), True, 1, 2),         # EvaByte's windows of 2048
])
def test_live_pair_gauge_says_which_body_a_pair_takes(shape, causal,
                                                      interior, diagonal):
    """mxtpu_attention_live_block_pairs{kind="interior"|"diagonal"}, set
    when a call is traced: how often the unmasked body engages."""
    from incubator_mxnet_tpu.ops import attention as A
    spec = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    jax.eval_shape(lambda q, k, v: A._fa_call(
        q, k, v, causal, 1.0 / math.sqrt(shape[-1]), 1024, 1024),
        spec, spec, spec)
    assert A._LIVE_PAIRS.value(kind="interior") == interior
    assert A._LIVE_PAIRS.value(kind="diagonal") == diagonal


@pytest.mark.parametrize("causal", [False, True])
def test_flash_streamed_kv_long_chain(causal):
    """r3: K/V are streamed on a grid axis (whole-S staging would cap S at
    VMEM). 8 sequential k-blocks per q-block exercises the scratch carry
    (m/l/acc) across grid steps + the causal dead-block index clamping."""
    from incubator_mxnet_tpu.ops import attention as A
    q, k, v = _rand_qkv(B=1, H=1, S=1024, D=128)  # D=128: streamed by shape
    assert A.attention_route(q.shape, block_q=128, block_k=128) == "streamed"
    scale = 1.0 / onp.sqrt(q.shape[-1])

    def loss_flash(q, k, v):
        return jnp.sum(jnp.sin(A.flash_attention(q, k, v, causal, None,
                                                 128, 128)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(A._blocked_reference(q, k, v, causal, scale)))

    out = A.flash_attention(q, k, v, causal, None, 128, 128)
    ref = A._blocked_reference(q, k, v, causal, scale)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-4
    gf = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        rel = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        assert rel < 1e-3


@pytest.mark.parametrize("D", [8, 64])
def test_streamed_kernels_take_narrow_heads(D):
    """D rides each BlockSpec as the full last dim, so the streamed kernels
    run any D % 8 == 0. The router sends them narrow heads only from
    S = 2048 (next test); here they are called directly, as the short
    tests call theirs, on a 2 x 2 grid of blocks."""
    from incubator_mxnet_tpu.ops import attention as A
    q, k, v = _rand_qkv(B=1, H=2, S=256, D=D)
    w = _rand_qkv(B=1, H=2, S=256, D=D, seed=1)[0]
    scale = 1.0 / math.sqrt(D)
    out, lse = A._fa_call(q, k, v, True, scale, 128, 128)
    grads = A._fa_bwd_call(q, k, v, out, lse, w, True, scale, 128, 128)
    ref, vjp = jax.vjp(lambda q, k, v: _f32_reference(q, k, v, True),
                       q, k, v)
    for got, want in zip((out,) + tuple(grads), (ref,) + tuple(vjp(w))):
        assert float(jnp.abs(got - want).max() / jnp.abs(want).max()) < 1e-5


def test_narrow_heads_at_2048_ride_the_streamed_kernels():
    """D = 64 from S = 2048 up is a streamed shape on the chip and here."""
    from incubator_mxnet_tpu.ops import attention as A
    q, k, v = _rand_qkv(B=1, H=1, S=2048, D=64)
    assert A.attention_route(q.shape) == "streamed"
    assert A.flash_attention_supported(q.shape)
    loss = lambda attn: lambda q, k, v: jnp.sum(jnp.sin(attn(q, k, v)))  # noqa: E731
    text = str(jax.make_jaxpr(jax.grad(loss(
        lambda q, k, v: A.flash_attention(q, k, v, True)), (0, 1, 2)))(q, k, v))
    assert text.count("pallas_call") == 2 and "flash_short" not in text
    out = A.flash_attention(q, k, v, True)
    ref = _f32_reference(q, k, v, True)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-4


def test_flash_cross_attention_shape_guard():
    """Sq != Sk (cross-attention) must take the composite path, not feed
    the self-attention-shaped kernels garbage."""
    from incubator_mxnet_tpu.ops import attention as A
    rng = onp.random.RandomState(4)
    q = jnp.asarray(rng.randn(1, 2, 256, 128).astype("float32") * 0.3)
    k = jnp.asarray(rng.randn(1, 2, 384, 128).astype("float32") * 0.3)
    v = jnp.asarray(rng.randn(1, 2, 384, 128).astype("float32") * 0.3)
    out = A.flash_attention(q, k, v, False)
    ref = A._blocked_reference(q, k, v, False, 1.0 / onp.sqrt(128))
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-4
    o2, lse = A.attention_with_lse(q, k, v)
    assert o2.shape == (1, 2, 256, 128) and lse.shape == (1, 2, 256)
    assert float(jnp.max(jnp.abs(o2 - ref))) < 2e-4
    # grads flow through the fallback too
    g = jax.grad(lambda q, k, v: jnp.sum(
        A.flash_attention(q, k, v, False) ** 2), (0, 1, 2))(q, k, v)
    assert all(bool(jnp.isfinite(x).all()) for x in g)


# ------------------------------------------------------------ short family
def _f32_reference(q, k, v, causal, with_lse=False):
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") \
        / math.sqrt(q.shape[-1])
    if causal:
        n = q.shape[2]
        s = jnp.where(jnp.arange(n)[:, None] >= jnp.arange(n)[None, :],
                      s, -jnp.inf)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v,
                     precision="highest")
    return (out, jax.scipy.special.logsumexp(s, axis=-1)) if with_lse \
        else out


def _short_against_reference(S, causal, dtype, spoil=lambda *g: g):
    """Worst error of out, dq, dk, dv from the short kernels (called
    directly: what the router sends them is another test's business),
    as a share of the reference's largest value. ``spoil`` stands in for a
    faulty backward kernel."""
    from incubator_mxnet_tpu.ops import attention as A
    rng = onp.random.RandomState(S)
    # (B, H) = (2, 4): two lane blocks of a head pair, one grid step each
    q, k, v, w = (jnp.asarray(rng.randn(2, 4, S, 64), jnp.float32)
                  .astype(dtype) for _ in range(4))
    scale = 1.0 / math.sqrt(64)
    out, lse = A._short_call(q, k, v, causal, scale, True)
    grads = spoil(*A._short_bwd_call(q, k, v, lse, w, causal, scale, True))
    assert out.dtype == dtype and all(g.dtype == dtype for g in grads)
    ref_out, vjp = jax.vjp(lambda q, k, v: _f32_reference(q, k, v, causal),
                           q, k, v)
    ref_grads = vjp(w.astype(jnp.float32))
    return max(float(jnp.abs(got.astype(jnp.float32) - want).max()
                     / jnp.abs(want).max())
               for got, want in zip((out,) + tuple(grads),
                                    (ref_out,) + tuple(ref_grads)))


# one rounding of a bf16 operand or output is 2^-9 of its value; float32
# operands leave only the order of the sums
_SHORT_LIMIT = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("S", [128, 256, 512])
def test_short_kernels_match_float32_reference(S, causal, dtype):
    assert _short_against_reference(S, causal, dtype) < _SHORT_LIMIT[dtype]


@pytest.mark.parametrize("fault", ["negated dQ", "zeroed dK", "swapped dV"])
def test_short_reference_check_catches_a_faulty_backward(fault):
    spoil = {"negated dQ": lambda dq, dk, dv: (-dq, dk, dv),
             "zeroed dK": lambda dq, dk, dv: (dq, jnp.zeros_like(dk), dv),
             "swapped dV": lambda dq, dk, dv: (dq, dk, dv[:, ::-1])}[fault]
    assert _short_against_reference(256, False, jnp.bfloat16, spoil) \
        > 10 * _SHORT_LIMIT[jnp.bfloat16]


def test_short_path_honours_a_callers_scale():
    """Ulysses passes its own scale through flash_attention."""
    from incubator_mxnet_tpu.ops import attention as A
    q, k, v = _rand_qkv(S=256, D=64)
    assert A.attention_route(q.shape) == "short"
    f = lambda attn: jax.value_and_grad(  # noqa: E731
        lambda q, k, v: jnp.sum(jnp.sin(attn(q, k, v))), (0, 1, 2))(q, k, v)
    (got, g), (want, gr) = (
        f(lambda q, k, v: A.flash_attention(q, k, v, True, 0.3)),
        f(lambda q, k, v: A._blocked_reference(q, k, v, True, 0.3)))
    assert abs(float(got - want)) < 1e-3 * abs(float(want))
    for a, b in zip(g, gr):
        assert float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))) < 1e-3


_BERT_CELL, _GPT_CELL = (16, 16, 512, 64), (1, 16, 16384, 128)


@pytest.fixture()
def as_on_a_tpu(monkeypatch):
    """No device, no interpreter: the platform test answered 'tpu'."""
    monkeypatch.delenv("MXTPU_FLASH_INTERPRET")
    monkeypatch.setattr(
        jax, "devices", lambda *a: [types.SimpleNamespace(platform="tpu")])


@pytest.mark.parametrize("q_shape,k_shape,route", [
    (_BERT_CELL, None, "short"),               # bert-large.train-s512
    (_GPT_CELL, None, "streamed"),             # cerebras-gpt-1.3b.train-s16k
    ((1, 2, 200, 128), None, "composite"),     # no block divides S
    ((2, 16, 4096, 64), None, "streamed"),     # narrow but long
    ((2, 4, 512, 128), None, "streamed"),      # wide heads stay streamed
    ((8, 16, 768, 64), None, "short"),         # the longest short tile
    ((8, 16, 1024, 64), None, "composite"),    # narrow, past it, under 2048
    ((32, 16, 128, 64), None, "composite"),    # narrow, too short to win
    ((16, 8, 512, 96), None, "composite"),     # heads do not tile 128 lanes
    ((16, 1, 512, 64), None, "composite"),     # one head: half a lane block
    ((8, 8, 512, 32), None, "short"),          # four heads a lane block
    ((8, 16, 512, 16), None, "composite"),     # eight: never timed
    ((4, 16, 200, 64), None, "composite"),     # narrow, no whole lane tiles
    ((1, 2, 256, 64), (1, 2, 384, 64), "composite"),    # cross-attention
    ((1, 2, 256, 128), (1, 2, 384, 128), "composite"),
], ids=str)
def test_route_is_a_function_of_the_shape(q_shape, k_shape, route,
                                          as_on_a_tpu, monkeypatch):
    from incubator_mxnet_tpu.ops import attention as A

    def check():
        assert A.attention_route(q_shape, k_shape, k_shape) == route
        if k_shape is None:     # what ring and Ulysses ask, of their q alone
            assert A.flash_attention_supported(q_shape) == (
                route == "streamed")
    check()
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")         # same table
    check()


def test_kernels_route_nothing_off_the_tpu(monkeypatch):
    from incubator_mxnet_tpu.ops import attention as A
    assert A.attention_route(_BERT_CELL) == "short"           # interpreted
    assert A.flash_attention_supported((1, 2, 128, 128))       # likewise
    assert not A.flash_attention_supported((1, 2, 128, 8))     # as on a chip
    monkeypatch.delenv("MXTPU_FLASH_INTERPRET")                # this CPU host
    assert A.attention_route(_BERT_CELL) == "composite"
    assert A.attention_route(_GPT_CELL) == "composite"
    assert not A.flash_attention_supported(_GPT_CELL)


def test_supported_still_means_the_streamed_kernels_take_it(as_on_a_tpu):
    """What ring and Ulysses ask before flash_attention_lse, which has only
    the streamed kernels: never a short-family shape on the chip."""
    from incubator_mxnet_tpu.ops import attention as A
    assert not A.flash_attention_supported(_BERT_CELL)
    assert not A.flash_attention_supported((8, 16, 1024, 64))
    assert A.flash_attention_supported(_GPT_CELL)
    assert A.flash_attention_supported((2, 16, 4096, 64))
    assert not A.flash_attention_supported((1, 2, 200, 128))


def test_route_counter_counts_traced_calls():
    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.ops import attention as A
    before = {r: A._ROUTES.value(route=r)
              for r in ("short", "streamed", "composite")}
    f = jax.jit(lambda q, k, v: A.flash_attention(q, k, v))
    for _ in range(3):                       # traced once, run three times
        f(*_rand_qkv(S=256, D=64))
    A.flash_attention(*_rand_qkv(S=256, D=128))
    A.flash_attention(*_rand_qkv(S=200, D=64))
    after = {r: A._ROUTES.value(route=r) for r in before}
    assert {r: after[r] - before[r] for r in before} == {
        "short": 1, "streamed": 1, "composite": 1}
    assert 'mxtpu_attention_route_total{route="short"}' \
        in telemetry.REGISTRY.export_text()


# ------------------------------------------- the one-visit streamed backward
def _streamed_against_reference(shape, causal, dtype, block_q, block_k,
                                g_lse=False, seed=0):
    """Worst error of dq, dk, dv from the streamed kernels (called as the
    custom VJPs call them) against the float32 composite's, as a share of
    the reference's largest value. With ``g_lse`` the loss also reads the
    LSE, as ring attention's combine does (flash_attention_lse)."""
    from incubator_mxnet_tpu.ops import attention as A
    rng = onp.random.RandomState(seed)
    q, k, v, w = (jnp.asarray(rng.randn(*shape), jnp.float32).astype(dtype)
                  for _ in range(4))
    u = jnp.asarray(rng.randn(*shape[:3]), jnp.float32)
    scale = 1.0 / math.sqrt(shape[-1])     # _f32_reference's

    def loss(attn_lse):
        def f(q, k, v):
            out, lse = attn_lse(q, k, v)
            total = jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32))
            return total + jnp.sum(lse * u) if g_lse else total
        return f

    def kernels(q, k, v):
        if g_lse:
            return A.flash_attention_lse(q, k, v, causal, scale, block_q,
                                         block_k)
        return A.flash_attention(q, k, v, causal, scale, block_q,
                                 block_k), None

    def reference(q, k, v):
        return _f32_reference(q, k, v, causal, with_lse=True)

    got = jax.grad(loss(kernels), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(reference), (0, 1, 2))(q, k, v)
    assert all(g.dtype == dtype and g.shape == shape for g in got)
    return max(float(jnp.abs(g.astype(jnp.float32) - r.astype(jnp.float32))
                     .max() / jnp.abs(r).max()) for g, r in zip(got, want))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("causal", [False, True])
def test_one_visit_backward_matches_float32_composite(causal, dtype):
    """A 4 x 4 grid of 128-blocks at S = 512: dK/dV accumulate over the
    inner q steps and dQ into the head's resident slab, from one P and
    one dS a pair."""
    assert _streamed_against_reference((1, 2, 512, 128), causal, dtype,
                                       128, 128) < _SHORT_LIMIT[dtype]


@pytest.mark.parametrize("causal", [False, True])
def test_one_visit_backward_rounds_nothing_on_its_way_to_the_mxu(causal):
    """bf16 in and out, float32 between: q, P and dS meet the matmuls as
    float32 values, as in the pair this kernel replaced. Against the
    float32 composite's gradients rounded to bf16 (rel-rms, interpreted):
    0.07-0.14 % as shipped, 0.25-0.28 % with P and dS rounded to bf16."""
    from incubator_mxnet_tpu.ops import attention as A
    rng = onp.random.RandomState(3)
    q, k, v, w = (jnp.asarray(rng.randn(1, 2, 512, 128), jnp.float32)
                  .astype(jnp.bfloat16) for _ in range(4))
    scale = 1.0 / math.sqrt(128)
    out, lse = A._fa_call(q, k, v, causal, scale, 128, 128)
    got = A._fa_bwd_call(q, k, v, out, lse, w, causal, scale, 128, 128)
    want = jax.vjp(lambda q, k, v: _f32_reference(q, k, v, causal),
                   q, k, v)[1](w.astype(jnp.float32))
    for g, r in zip(got, want):
        g, r = g.astype(jnp.float32), r.astype(jnp.float32)
        assert float(jnp.sqrt(jnp.mean((g - r) ** 2) / jnp.mean(r ** 2))) \
            < 2e-3


@pytest.mark.parametrize("block_q,block_k", [(256, 128), (128, 256)])
@pytest.mark.parametrize("causal", [False, True])
def test_one_visit_backward_with_unequal_blocks(causal, block_q, block_k):
    assert _streamed_against_reference((1, 2, 512, 128), causal,
                                       jnp.float32, block_q, block_k) < 1e-5


@pytest.mark.parametrize("causal", [False, True])
def test_one_visit_backward_takes_the_lse_cotangent(causal):
    """flash_attention_lse: the LSE's cotangent folds into delta outside
    the kernel, as it did for the pair."""
    assert _streamed_against_reference((1, 2, 512, 128), causal, jnp.float32,
                                       128, 128, g_lse=True) < 1e-5


def test_one_visit_backward_on_narrow_heads_at_2048():
    """D = 64 at S = 2048 through the router, default 1024-blocks: the
    slab's last dim is the full D, as every block's is."""
    assert _streamed_against_reference((1, 1, 2048, 64), True, jnp.float32,
                                       None, None) < 1e-5


@pytest.mark.parametrize("S,segments,label", [
    (512, 1, "flash_bwd_dkvq"),              # the slab just fits: one call
    (640, 5, "flash_bwd_dkvq_segmented"),    # 5 blocks: no even split but 5
    (768, 2, "flash_bwd_dkvq_segmented"),    # the first length with two
    (1024, 2, "flash_bwd_dkvq_segmented"),
    (1536, 3, "flash_bwd_dkvq_segmented"),
])
@pytest.mark.parametrize("causal", [False, True])
def test_backward_segments_follow_the_slab_budget(S, segments, label, causal,
                                                  monkeypatch):
    """The shape rule at its boundary, made small: with room for the slab
    of 512 rows (both buffers), S = 512 is one call and longer sequences
    one call a q-segment, the dK/dV partials summed outside; the counter
    says which a traced backward took."""
    from incubator_mxnet_tpu.ops import attention as A
    monkeypatch.setattr(A, "_DQ_SLAB_BYTES", 2 * 512 * 128 * 4)
    assert A._dq_segments(S, 128, 128) == segments
    labels = ("flash_bwd_dkvq", "flash_bwd_dkvq_segmented")
    before = {k: A._BACKWARDS.value(kernel=k) for k in labels}
    assert _streamed_against_reference((1, 1, S, 128), causal, jnp.float32,
                                       128, 128, seed=S) < 1e-5
    after = {k: A._BACKWARDS.value(kernel=k) for k in labels}
    assert {k: after[k] - before[k] for k in labels} == {
        k: int(k == label) for k in labels}


@pytest.mark.parametrize("shape,segments", [
    ((1, 16, 16384, 128), 1),        # cerebras-gpt-1.3b.train-s16k
    ((8, 16, 2048, 128), 1),         # cerebras-gpt-1.3b.train-s2k
    ((4, 16, 4096, 128), 1),         # olmoe-1b-7b.train-s4k
    ((1, 16, 65536, 128), 1),        # the longest single slab at D = 128
    ((1, 16, 2048, 64), 1),          # narrow heads: 64 lanes pad to 128
    ((1, 4, 131072, 128), 2),        # 64 MiB a buffer: two segments
    ((1, 4, 131072, 256), 4),
], ids=str)
def test_streamed_backward_is_one_mosaic_call_a_segment(shape, segments,
                                                        monkeypatch):
    """Lowered for 'tpu' from this CPU host through jax.grad of the public
    entry point: the forward kernel and ONE backward kernel a q-segment
    (the pair this replaced was two), one segment at every cell's shape."""
    from incubator_mxnet_tpu.ops import attention as A
    monkeypatch.delenv("MXTPU_FLASH_INTERPRET")
    monkeypatch.setattr(A, "_kernels_run_here", lambda: True)
    assert A._dq_segments(shape[2], shape[3], A._auto_block(shape[2])) \
        == segments

    def loss(q, k, v):
        return A.flash_attention(q, k, v, True).astype(jnp.float32).sum()

    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    text = jax.jit(jax.grad(loss, (0, 1, 2))).trace(x, x, x).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count('kernel_name = "flash_fwd"') == 1
    assert text.count('kernel_name = "flash_bwd_dkvq"') == segments
    assert text.count("tpu_custom_call") == 1 + segments


def test_streamed_backward_does_five_matmuls_a_pair():
    """P and dS are computed once: S, dP, dV, dK, dQ."""
    from incubator_mxnet_tpu.ops import attention as A
    q, k, v = _rand_qkv(S=256)
    out, lse = A._fa_call(q, k, v, True, 0.1, 128, 128)
    text = str(jax.make_jaxpr(lambda *a: A._fa_bwd_call(
        *a, True, 0.1, 128, 128))(q, k, v, out, lse, out))
    assert text.count("pallas_call") == 1
    assert text.count("dot_general") == 5


def test_backward_counter_counts_a_streamed_backward_once_a_trace():
    """The other routes' backwards follow mxtpu_attention_route_total and
    are not counted twice."""
    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.ops import attention as A
    before = A._BACKWARDS.value(kernel="flash_bwd_dkvq")
    g = jax.jit(jax.grad(lambda q, k, v: jnp.sum(A.flash_attention(q, k, v))))
    for _ in range(3):                       # traced once, run three times
        g(*_rand_qkv(S=256, D=128))
    g(*_rand_qkv(S=256, D=64))               # short
    g(*_rand_qkv(S=200, D=64))               # composite
    assert A._BACKWARDS.value(kernel="flash_bwd_dkvq") == before + 1
    labels = {ln.split('"')[1]
              for ln in telemetry.REGISTRY.export_text().splitlines()
              if ln.startswith("mxtpu_attention_backward_total{")}
    assert "flash_bwd_dkvq" in labels
    assert labels <= {"flash_bwd_dkvq", "flash_bwd_dkvq_segmented"}


# --------------------------------- a value width of its own (streamed family)
#: (q / k shape, D_v): narrow heads ride the streamed kernels from S = 2048
#: on, so the 64 | 128 of a differential pair is met there
_WIDE = {"64|128": ((1, 1, 2048, 64), 128), "128|256": ((1, 2, 512, 128), 256)}
_MODES = {"dense": (False, None), "causal": (True, None),
          "window": (True, 200)}


def _wide_qkv(shape, d_v, seed):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    wide = shape[:3] + (d_v,)
    return tuple(jax.random.normal(key, s) for key, s in
                 zip(keys, (shape, shape, wide, wide)))


def _dense_masked(q, k, v, causal, window):
    """The dense masked softmax of tests/test_phi4flash.py's window tests,
    at any value width."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
    d = jnp.arange(q.shape[2])[:, None] - jnp.arange(q.shape[2])[None, :]
    if causal:
        seen = d >= 0 if window is None else (d >= 0) & (d < window)
        s = jnp.where(seen, s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("mode", list(_MODES))
@pytest.mark.parametrize("widths", list(_WIDE))
def test_wide_value_kernels_are_the_dense_masked_softmax(widths, mode):
    """Interpreted, float32, blocks of 128: forward and all three gradients
    of ONE call whose v, dO, O and dV are D_v wide while q, k, dQ, dK stay
    D, to the limits the window kernels are held to; the scale is q's."""
    from incubator_mxnet_tpu.ops import attention as A
    (shape, d_v), (causal, window) = _WIDE[widths], _MODES[mode]
    q, k, v, do = _wide_qkv(shape, d_v, seed=d_v + (window or 0))
    assert A.attention_route(shape, shape, v.shape, 128, 128,
                             window) == "streamed"

    def system(q, k, v):
        return A.flash_attention(q, k, v, causal, None, 128, 128, window)

    want = _dense_masked(q, k, v, causal, window)
    got = system(q, k, v)
    assert got.shape == v.shape and got.dtype == v.dtype
    assert jnp.abs(got - want).max() < 1e-5
    grads = jax.grad(lambda *a: (system(*a) * do).sum(), (0, 1, 2))(q, k, v)
    refs = jax.grad(lambda *a: (_dense_masked(*a, causal, window) * do).sum(),
                    (0, 1, 2))(q, k, v)
    for g, r, x in zip(grads, refs, (q, k, v)):
        assert g.shape == x.shape and g.dtype == x.dtype
        assert jnp.abs(g - r).max() < 1e-5 * (jnp.abs(r).max() + 1)


@pytest.mark.parametrize("widths,mode", [
    ("128|256", "dense"), ("128|256", "causal"), ("128|256", "window"),
    ("64|128", "causal")])
def test_a_wide_call_is_the_two_narrow_calls_it_replaces(widths, mode):
    """`P [v_1 v_2]` is `[P v_1, P v_2]` column for column: the output
    columns to the bit; dV's columns likewise are each half's own; dQ and
    dK are one dS from all D_v columns where the narrow calls' two are
    added outside, so they agree inside the kernels' limit."""
    from incubator_mxnet_tpu.ops import attention as A
    (shape, d_v), (causal, window) = _WIDE[widths], _MODES[mode]
    q, k, v, do = _wide_qkv(shape, d_v, seed=3)
    half = d_v // 2

    def wide(q, k, v):
        return A.flash_attention(q, k, v, causal, None, 128, 128, window)

    def narrow(q, k, v):
        # the scale is 1 / sqrt(D of q) on both sides
        return jnp.concatenate(
            [A.flash_attention(q, k, v[..., :half], causal, None, 128, 128,
                               window),
             A.flash_attention(q, k, v[..., half:], causal, None, 128, 128,
                               window)], -1)

    before = A._WIDE_VALUES.value(route="streamed")
    onp.testing.assert_array_equal(wide(q, k, v), narrow(q, k, v))
    assert A._WIDE_VALUES.value(route="streamed") == before + (
        1 if half == shape[-1] else 3)     # 128 | 256: the halves are wide
    got = jax.grad(lambda *a: (wide(*a) * do).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (narrow(*a) * do).sum(), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert jnp.abs(g - w).max() < 1e-5 * (jnp.abs(w).max() + 1)


@pytest.mark.parametrize("q_shape,d_v,window,route", [
    (_GPT_CELL, 256, None, "streamed"),             # where the equal shape is
    ((1, 20, 16384, 64), 128, None, "streamed"),    # the SambaY cell's F, C
    ((1, 20, 16384, 64), 128, 512, "streamed"),     # and its S
    ((2, 4, 512, 128), 64, None, "streamed"),       # narrower than its keys
    (_BERT_CELL, 128, None, "composite"),           # short: one width only
    ((8, 8, 512, 32), 64, None, "composite"),
    ((8, 16, 1024, 64), 128, None, "composite"),    # narrow, under 2048
    ((1, 2, 200, 128), 256, None, "composite"),     # no block divides S
    ((2, 4, 512, 128), 132, None, "composite"),     # sublanes not packed
], ids=str)
def test_route_of_a_value_with_a_width_of_its_own(q_shape, d_v, window,
                                                  route, as_on_a_tpu,
                                                  monkeypatch):
    """The streamed family takes D_v != D wherever it takes the equal
    shape; the short family and its lane layout know one width; nothing
    but the last dimension of v may differ; off the TPU the composite."""
    from incubator_mxnet_tpu.ops import attention as A
    v_shape = q_shape[:3] + (d_v,)

    def check():
        assert A.attention_route(q_shape, q_shape, v_shape,
                                 window=window) == route
        longer = (q_shape[0], q_shape[1], 2 * q_shape[2], d_v)
        assert A.attention_route(q_shape, q_shape, longer,
                                 window=window) == "composite"
    check()
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")         # same table
    check()
    monkeypatch.delenv("MXTPU_FLASH_INTERPRET")
    monkeypatch.setattr(
        jax, "devices", lambda *a: [types.SimpleNamespace(platform="cpu")])
    assert A.attention_route(q_shape, q_shape, v_shape,
                             window=window) == "composite"


@pytest.mark.parametrize("mode", list(_MODES))
def test_composite_carries_a_value_of_any_width(mode, monkeypatch):
    """Off the TPU (no interpreter) a wide call is the XLA composite:
    forward and its hand-written backward at D 64 | D_v 96."""
    from incubator_mxnet_tpu.ops import attention as A
    monkeypatch.delenv("MXTPU_FLASH_INTERPRET")
    causal, window = _MODES[mode]
    window = window and 40
    q, k, v, do = _wide_qkv((2, 2, 160, 64), 96, seed=5)
    before = A._WIDE_VALUES.value(route="composite")

    def system(q, k, v):
        return A.flash_attention(q, k, v, causal, window=window)

    want = _dense_masked(q, k, v, causal, window)
    assert jnp.abs(system(q, k, v) - want).max() < 1e-5
    assert A._WIDE_VALUES.value(route="composite") == before + 1
    grads = jax.grad(lambda *a: (system(*a) * do).sum(), (0, 1, 2))(q, k, v)
    refs = jax.grad(lambda *a: (_dense_masked(*a, causal, window) * do).sum(),
                    (0, 1, 2))(q, k, v)
    for g, r, x in zip(grads, refs, (q, k, v)):
        assert g.shape == x.shape
        assert jnp.abs(g - r).max() < 1e-5 * (jnp.abs(r).max() + 1)
