"""Ulysses-style sequence parallelism — all-to-all head scatter.

NEW capability (SURVEY §5: the reference has no sequence parallelism; this
is the all-to-all alternative to ring attention, after DeepSpeed-Ulysses).

Where ring attention keeps the sequence sharded and streams K/V around the
ICI ring, Ulysses re-shards with two all-to-alls: tokens arrive sharded on
the sequence axis, an all-to-all converts to HEAD-sharded (each device
holds ALL tokens for H/n heads), attention runs fully local (any kernel —
here the dense/flash local path), and a second all-to-all restores
sequence sharding. Cost: 2 all-to-alls of activation size per layer vs the
ring's (n-1) K/V hops; Ulysses wins when heads >> devices and the
per-device sequence is long.

Requires num_heads % axis_size == 0 and S % axis_size == 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.lax import axis_size
from jax.sharding import PartitionSpec as P
from .ring_attention import local_attention

__all__ = ["ulysses_attention"]


def _ulysses_sharded(q, k, v, axis_name, causal, scale):
    """Inside shard_map: q/k/v local shapes (B, H, S/n, D)."""
    n = axis_size(axis_name)

    def seq_to_heads(x):
        # (B, H, s, D) -> (B, H/n, S, D): split heads across devices,
        # gather the full sequence. all_to_all splits axis 1, concats axis 2.
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    # full-sequence local attention on H/n heads: the Pallas flash kernel
    # when the (B, H/n, S, D) shape supports it — O(block^2) VMEM instead
    # of the dense path's O(S^2) HBM score block
    from ..ops.attention import flash_attention, flash_attention_supported
    if flash_attention_supported(qh.shape):
        out = flash_attention(qh, kh, vh, causal, scale)
    else:
        o, m, l = local_attention(qh, kh, vh, scale=scale, causal=causal)
        out = (o / jnp.maximum(l, 1e-37)).astype(q.dtype)
    return heads_to_seq(out)


def ulysses_attention(q, k, v, mesh=None, axis="sp", causal=False, scale=None):
    """Sequence-parallel attention via head-scatter all-to-all.

    q/k/v global shapes (B, H, S, D), sequence-sharded on mesh axis
    ``axis``; returns the same layout. H and S must divide the axis size.
    """
    from .mesh import current_mesh
    mesh = mesh or current_mesh()
    n = mesh.shape[axis]
    if q.shape[1] % n:
        raise ValueError("num_heads %d not divisible by %s=%d"
                         % (q.shape[1], axis, n))
    fn = functools.partial(_ulysses_sharded, axis_name=axis, causal=causal,
                           scale=scale)
    spec = P(None, None, axis, None)
    # check_vma=False: the local flash pallas_call carries no vma annotation
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)
