"""Ring attention — sequence/context parallelism over the ICI ring.

NEW capability (SURVEY §5: absent in the reference; required for long-context
parity with modern workloads). The sequence axis is sharded over the ``sp``
mesh axis; each device holds a Q block and streams K/V blocks around the ring
with ``ppermute`` while maintaining an online-softmax (flash-style) running
max/denominator in fp32. Compute and ICI transfer overlap because XLA
schedules the collective-permute asynchronously with the local matmuls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ring_attention", "local_attention"]


def local_attention(q, k, v, scale=None, causal=False, q_offset=0, kv_offset=0):
    """Plain blockwise attention on local shards (fp32 softmax accumulators).

    q: (B, H, Sq, D), k/v: (B, H, Sk, D).
    Returns (out, row_max, row_sumexp) for online-softmax combination.
    """
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        qi = jnp.arange(q.shape[2])[:, None] + q_offset
        ki = jnp.arange(k.shape[2])[None, :] + kv_offset
        s = jnp.where(qi >= ki, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)                       # (B,H,Sq,1)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe)
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)                       # (B,H,Sq,1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v).astype(jnp.float32)
    return o, m, l


def _combine(o1, m1, l1, o2, m2, l2):
    """Merge two online-softmax partials."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(jnp.where(jnp.isfinite(m1), m1 - m, -jnp.inf))
    a2 = jnp.exp(jnp.where(jnp.isfinite(m2), m2 - m, -jnp.inf))
    a1 = jnp.where(jnp.isnan(a1), 0.0, a1)
    a2 = jnp.where(jnp.isnan(a2), 0.0, a2)
    o = o1 * a1 + o2 * a2
    l = l1 * a1 + l2 * a2
    return o, m, l


def _local_partials(q, k, v, scale, causal):
    """One local attention step as an online-softmax partial triple
    (o, m, l). Rides the Pallas flash kernel when the local shard shape
    supports it — (out, lse) from the kernel is the equivalent partial
    (out, lse, 1): out*1*e^lse == numerator, 1*e^lse == denominator —
    so per-shard memory is O(block^2), not O((S/n)^2). Dense fallback
    otherwise (small shards / non-TPU)."""
    from ..ops.attention import attention_with_lse, flash_attention_supported
    if flash_attention_supported(q.shape):
        out, lse = attention_with_lse(q, k, v, causal=causal, scale=scale)
        return (out.astype(jnp.float32), lse[..., None],
                jnp.ones(lse.shape + (1,), jnp.float32))
    return local_attention(q, k, v, scale=scale, causal=causal)


def _ring_attention_sharded(q, k, v, axis_name, causal, scale):
    """Runs inside shard_map: local blocks + ring exchange of K/V.

    Causal masking is decomposed at BLOCK granularity (no in-kernel offset
    support needed): the shard's own K/V block uses the plain causal mask,
    earlier shards (src < idx) are fully visible (dense step), later shards
    contribute nothing (skipped partial) — the standard ring-attention
    causal decomposition."""
    n = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)

    o0, m0, l0 = _local_partials(q, k, v, scale, causal)

    def body(i, carry):
        o, m, l, kk, vv = carry
        # pass K/V to the next device on the ring (ICI neighbour)
        perm = [(j, (j + 1) % n) for j in range(n)]
        kk = lax.ppermute(kk, axis_name, perm)
        vv = lax.ppermute(vv, axis_name, perm)
        src = (idx - i - 1) % n  # which shard we now hold
        if causal:
            oi, mi, li = lax.cond(
                src < idx,
                lambda kk, vv: _local_partials(q, kk, vv, scale, False),
                lambda kk, vv: (jnp.zeros_like(o),
                                jnp.full_like(m, -jnp.inf),
                                jnp.zeros_like(l)),
                kk, vv)
        else:
            oi, mi, li = _local_partials(q, kk, vv, scale, False)
        o, m, l = _combine(o, m, l, oi, mi, li)
        return o, m, l, kk, vv

    o, m, l, _, _ = lax.fori_loop(0, n - 1, body, (o0, m0, l0, k, v))
    return (o / jnp.maximum(l, 1e-37)).astype(q.dtype)


def ring_attention(q, k, v, mesh=None, axis="sp", causal=False, scale=None):
    """Sequence-parallel attention: q/k/v sharded on the sequence dim (axis 2)
    over mesh axis ``axis``. Shapes (B, H, S, D) global.

    Use inside a jit under the mesh; arrives/leaves with seq-sharded layout.
    """
    from .mesh import current_mesh
    mesh = mesh or current_mesh()
    fn = functools.partial(_ring_attention_sharded, axis_name=axis,
                           causal=causal, scale=scale)
    spec = P(None, None, axis, None)
    # check_vma=False: pallas_call out_shapes carry no vma annotation, and
    # the local flash kernel runs inside this shard_map
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)
