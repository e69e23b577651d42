"""The streamed attention kernels at a q.k width that is 1.5 lane tiles
(D = 192, D_v = 128: multi-head latent attention's non-rotary 128 beside
the rotary 64), interpreted on the CPU: forward, log-sum-exp and the five
gradients (dQ, dK, dV of the output; dQ, dK of the log-sum-exp) against the
dense masked softmax, causal, at blocks of 128 to 1024. What Mosaic makes
of the shape is tests/perfbench/test_ling3_compile_tpu.py's.
"""
import math

import jax
import jax.numpy as jnp
import pytest

from incubator_mxnet_tpu.ops import attention as A

D, DV, S = 192, 128, 2048


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")


def operands(seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return tuple(jax.random.normal(key, (1, 1, S, w))
                 for key, w in zip(keys, (D, D, DV, DV)))


def dense(q, k, v):
    """-> (causal softmax(q k^T / sqrt(192)) v, its log-sum-exp)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(D)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    return (jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v),
            jax.nn.logsumexp(s, -1))


def test_the_shape_is_the_streamed_kernels():
    shape = (1, 32, 8192, D)
    assert A.attention_route(shape, shape, shape[:3] + (DV,)) == "streamed"
    assert A._dq_segments(8192, D, 1024) == 1
    # the dQ slab's 192 columns lie in 256 lanes
    assert A._slab_bytes(8192, D) == A._slab_bytes(8192, 256) \
        == 2 * A._slab_bytes(8192, 128)


@pytest.mark.parametrize("block", [128, 256, 512, 1024])
def test_forward_lse_and_five_gradients_are_the_dense_softmaxs(block):
    q, k, v, do = operands(block)
    want_o, want_lse = dense(q, k, v)
    out, lse = A.flash_attention_lse(q, k, v, True, None, block, block)
    assert out.shape == v.shape and lse.shape == (1, 1, S)
    assert jnp.abs(out - want_o).max() < 1e-5
    assert jnp.abs(lse - want_lse).max() < 1e-5
    assert jnp.abs(A.flash_attention(q, k, v, True, None, block, block)
                   - want_o).max() < 1e-5
    weight = jnp.linspace(-1.0, 1.0, S)

    def of(fn):
        def total(q, k, v):
            o, l = fn(q, k, v)
            return (o * do).sum() + (l * weight).sum()
        return jax.grad(total, (0, 1, 2))(q, k, v)

    got = of(lambda q, k, v: A.flash_attention_lse(q, k, v, True, None,
                                                   block, block))
    for g, r, x in zip(got, of(dense), (q, k, v)):
        assert g.shape == x.shape
        assert jnp.abs(g - r).max() < 1e-5 * (jnp.abs(r).max() + 1)
    # the log-sum-exp's own two, apart from the output's three
    lse_only = jax.grad(lambda q, k: (A.flash_attention_lse(
        q, k, v, True, None, block, block)[1] * weight).sum(), (0, 1))(q, k)
    want = jax.grad(lambda q, k: (dense(q, k, v)[1] * weight).sum(),
                    (0, 1))(q, k)
    for g, r in zip(lse_only, want):
        assert jnp.abs(g - r).max() < 1e-5 * (jnp.abs(r).max() + 1)
