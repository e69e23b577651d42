"""Fused (flash) attention Pallas kernels for TPU — forward AND backward.

The hot exception to "let XLA fuse" (SURVEY §7 table): attention's softmax
forces an HBM round-trip of the (S, S) score matrix under plain XLA. The
forward kernel tiles Q against K/V blocks in VMEM with an online-softmax
accumulator and saves the per-row log-sum-exp (LSE); the backward kernels
recompute probabilities blockwise from the LSE (FlashAttention-2
formulation) and accumulate dQ/dK/dV across sequential grid steps, so the
(S, S) score matrix NEVER materializes in HBM in either direction and VMEM
use is O(block^2 + block*D) — long sequences fit.

Used by models.bert MultiHeadAttention (attention='flash'). The kernels run
on a TPU for every shape flash_attention_supported accepts; a Mosaic
refusal of such a shape surfaces as the compile error it is — nothing
catches it to degrade. Off-TPU (the CPU test backend) and for shapes no
block divides, the XLA composite runs instead. MXTPU_FLASH_INTERPRET=1 runs
the kernels in Pallas interpret mode (CPU tests only; chip_smoke.py and
bench.py refuse to start with it set).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

__all__ = ["flash_attention", "flash_attention_supported",
           "flash_attention_legal", "flash_attention_lse",
           "attention_with_lse", "flash_attention_on_mesh"]


def _interpret():
    from ..config import get_env
    return get_env("MXTPU_FLASH_INTERPRET")


def _auto_block(S):
    """Largest MXU-friendly block dividing S — measured on v5e (r3 sweep,
    fwd+bwd causal, D=128): 1024 beats 512 by ~1.3x at S=8k..32k (13.0 vs
    20.1 ms at 8k; 77 vs 97 ms at 32k), and 512 beats 128 by 1.3-3.5x
    (fewer grid steps, better VMEM reuse). None when no candidate divides
    S — such shapes are NOT kernel-legal and take the XLA composite
    fallback."""
    for b in (1024, 512, 256, 128):
        if S % b == 0:
            return b
    return None


def _resolve_blocks(S, block_q, block_k):
    from ..config import get_env
    block_q = block_q or get_env("MXTPU_FLASH_BLOCK_Q") or None
    block_k = block_k or get_env("MXTPU_FLASH_BLOCK_K") or None
    return (block_q or _auto_block(S)), (block_k or _auto_block(S))


def _blocked_reference(q, k, v, causal, scale):
    """XLA fallback with fp32 softmax (numerics match the kernel)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        qi = jnp.arange(q.shape[2])[:, None]
        ki = jnp.arange(k.shape[2])[None, :]
        s = jnp.where(qi >= ki, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def flash_attention_legal(q_shape, block_q=None, block_k=None):
    """Capability: the kernels can run this shape. D rides each BlockSpec as
    the FULL last dim (legal for any size when equal to the array dim);
    8-alignment keeps sublanes packed."""
    B, H, S, D = q_shape
    block_q, block_k = _resolve_blocks(S, block_q, block_k)
    if block_q is None or block_k is None:
        return False
    if not _interpret() and jax.devices()[0].platform != "tpu":
        return False
    return S % block_q == 0 and S % block_k == 0 and D % 8 == 0


def flash_attention_supported(q_shape, block_q=None, block_k=None):
    """Legality AND profitability: D=64-style narrow heads leave MXU lanes
    half-empty, so the kernel only engages once S is long enough that the
    composite's (S,S) materialization hits HBM pressure (v5e, H=16, 512
    blocks: parity at ~2k, 2x at 4k, >6x at 8k — and the composite's score
    memory scales with B*H*S^2, so real batches hit the cliff earlier).
    Set MXTPU_FLASH_FORCE=1 to override the heuristic (e.g. large B*H at
    moderate S nearing OOM); interpret mode ignores it so CI exercises
    every legal shape."""
    if not flash_attention_legal(q_shape, block_q, block_k):
        return False
    B, H, S, D = q_shape
    if D % 128 != 0 and S < 2048 and not _interpret():
        from ..config import get_env
        return get_env("MXTPU_FLASH_FORCE")
    return True


# --------------------------------------------------------------- forward
def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s, *,
               block_k, causal, scale):
    """One (batch*head, q-block, k-block) program: K/V are STREAMED by the
    grid — VMEM holds only (block_q + 2*block_k) x D tiles plus the online
    softmax carry (m/l/acc scratch, persisted across the sequential k-block
    steps), so sequence length is bounded by HBM, not VMEM (S=32k+ on one
    chip).  Writes the per-row LSE (m + log l) the backward kernels consume.
    """
    from jax.experimental import pallas as pl

    qb, kb = pl.program_id(1), pl.program_id(2)
    block_q = q_ref.shape[1]

    @pl.when(kb == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, -jnp.inf)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # K/V blocks fully above the diagonal contribute nothing in causal mode
    live = ((qb + 1) * block_q - 1 >= kb * block_k) if causal else (kb >= 0)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale        # (block_q, D)
        k_blk = k_ref[0].astype(jnp.float32)            # (block_k, D)
        v_blk = v_ref[0].astype(jnp.float32)
        s = q @ k_blk.T                                  # (block_q, block_k)
        if causal:
            qi = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0)
            ki = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            s = jnp.where(qi >= ki, s, -jnp.inf)
        m, l, acc = m_s[...], l_s[...], acc_s[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe)
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
        alpha = jnp.where(jnp.isnan(alpha), 0.0, alpha)
        m_s[...] = m_new
        l_s[...] = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_s[...] = acc * alpha + p @ v_blk

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finish():
        l = jnp.maximum(l_s[...], 1e-37)
        o_ref[0, :, :] = (acc_s[...] / l).astype(o_ref.dtype)
        # rows with l=0 cannot occur (causal keeps the diagonal; dense
        # keeps all)
        lse_ref[0, 0, :] = (m_s[...] + jnp.log(l))[:, 0]


def _fa_call(q, k, v, causal, scale, block_q, block_k):
    """Returns (out (B,H,S,D), lse (B*H,S) fp32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, S, D = q.shape
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * H, S, D)
    vf = v.reshape(B * H, S, D)
    grid = (B * H, S // block_q, S // block_k)
    kernel = functools.partial(_fa_kernel, block_k=block_k, causal=causal,
                               scale=scale)
    if causal:
        # dead blocks above the diagonal: clamp the index map so the grid
        # step re-uses the resident block instead of DMA-ing one it will
        # never read (compute is skipped by pl.when in the kernel)
        def kv_idx(b, i, j):
            return (b, jnp.minimum(j, ((i + 1) * block_q - 1) // block_k), 0)
    else:
        def kv_idx(b, i, j):
            return (b, j, 0)
    out, lse = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
                   jax.ShapeDtypeStruct((B * H, 1, S), jnp.float32)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), kv_idx),
            pl.BlockSpec((1, block_k, D), kv_idx),
        ],
        out_specs=(pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
                   pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i))),
        scratch_shapes=[pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=_interpret(),
        name="flash_fwd",
    )(qf, kf, vf)
    return out.reshape(B, H, S, D), lse


# --------------------------------------------------------------- backward
def _recompute_p_ds(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, qb, kb,
                    causal, scale, block_q, block_k):
    """Shared FA2 recompute: returns (q, do, k_blk, p, ds) for one block pair."""
    q = q_ref[0].astype(jnp.float32)                     # (block_q, D)
    do = do_ref[0].astype(jnp.float32)                   # (block_q, D)
    lse = lse_ref[0, 0][:, None]                         # (block_q, 1)
    delta = delta_ref[0, 0][:, None]                     # (block_q, 1)
    k_blk = k_ref[0].astype(jnp.float32)                 # (block_k, D)
    v_blk = v_ref[0].astype(jnp.float32)

    s = (q @ k_blk.T) * scale                            # (block_q, block_k)
    if causal:
        qi = qb * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)
        ki = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        s = jnp.where(qi >= ki, s, -jnp.inf)
    p = jnp.exp(s - lse)                                 # (block_q, block_k)
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    dp = do @ v_blk.T                                    # (block_q, block_k)
    ds = p * (dp - delta) * scale
    return q, do, k_blk, p, ds


def _fa_bwd_dkv_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                       dk_ref, dv_ref, *, causal, scale, block_q, block_k):
    """Grid (bh, kv-block, q-block): accumulate dK/dV over sequential q steps."""
    from jax.experimental import pallas as pl

    qb = pl.program_id(2)

    @pl.when(qb == 0)
    def _init():
        dk_ref[0, :, :] = jnp.zeros_like(dk_ref[0])
        dv_ref[0, :, :] = jnp.zeros_like(dv_ref[0])

    kb = pl.program_id(1)
    # q-blocks fully above the diagonal contribute nothing in causal mode
    live = (qb + 1) * block_q - 1 >= kb * block_k if causal else qb >= 0

    @pl.when(live)
    def _compute():
        q, do, _k, p, ds = _recompute_p_ds(
            q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, qb, kb,
            causal, scale, block_q, block_k)
        dv_ref[0, :, :] += (p.T @ do).astype(dv_ref.dtype)
        dk_ref[0, :, :] += (ds.T @ q).astype(dk_ref.dtype)


def _fa_bwd_dq_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, *, causal, scale, block_q, block_k):
    """Grid (bh, q-block, kv-block): accumulate dQ over sequential kv steps."""
    from jax.experimental import pallas as pl

    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_ref[0, :, :] = jnp.zeros_like(dq_ref[0])

    qb = pl.program_id(1)
    # K/V blocks fully above the diagonal contribute nothing in causal mode
    live = (qb + 1) * block_q - 1 >= kb * block_k if causal else kb >= 0

    @pl.when(live)
    def _compute():
        _q, _do, k_blk, _p, ds = _recompute_p_ds(
            q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, qb, kb,
            causal, scale, block_q, block_k)
        dq_ref[0, :, :] += (ds @ k_blk).astype(dq_ref.dtype)


def _fa_bwd_call(q, k, v, o, lse, do, causal, scale, block_q, block_k,
                 g_lse=None):
    from jax.experimental import pallas as pl

    B, H, S, D = q.shape
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * H, S, D)
    vf = v.reshape(B * H, S, D)
    dof = do.reshape(B * H, S, D)
    # delta_i = sum_d dO_i * O_i — O(S*D), computed by XLA
    delta = jnp.sum(dof.astype(jnp.float32) *
                    o.reshape(B * H, S, D).astype(jnp.float32),
                    axis=-1)[:, None, :]                 # (B*H, 1, S)
    if g_lse is not None:
        # When LSE is a second primal output (flash_attention_lse), its
        # cotangent enters ds exactly as -delta does: d lse_i/d s_ij = p_ij,
        # so ds_ij = p_ij*(dp_ij - delta_i + g_lse_i)*scale — fold it in.
        delta = delta - g_lse.astype(jnp.float32)

    if causal:
        # dkv grid streams q-blocks (j) per kv-block (i): q-blocks strictly
        # above the diagonal are dead — clamp to the first live one so no
        # DMA is issued for blocks pl.when will skip
        def q_idx(b, i, j):
            return (b, jnp.maximum(j, (i * block_k) // block_q), 0)

        def row_idx(b, i, j):
            return (b, 0, jnp.maximum(j, (i * block_k) // block_q))
    else:
        def q_idx(b, i, j):
            return (b, j, 0)

        def row_idx(b, i, j):
            return (b, 0, j)
    qspec = pl.BlockSpec((1, block_q, D), q_idx)
    rowspec = pl.BlockSpec((1, 1, block_q), row_idx)
    kvspec = pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, i, 0))
    dkv_kernel = functools.partial(_fa_bwd_dkv_kernel, causal=causal,
                                   scale=scale, block_q=block_q,
                                   block_k=block_k)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        out_shape=(jax.ShapeDtypeStruct((B * H, S, D), jnp.float32),
                   jax.ShapeDtypeStruct((B * H, S, D), jnp.float32)),
        grid=(B * H, S // block_k, S // block_q),
        in_specs=[qspec, qspec, rowspec, rowspec, kvspec, kvspec],
        out_specs=(kvspec, kvspec),
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(qf, dof, lse, delta, kf, vf)

    if causal:
        # dq grid streams kv-blocks (j) per q-block (i): kv-blocks above
        # the diagonal are dead — clamp to the last live one
        def kv_idx2(b, i, j):
            return (b, jnp.minimum(j, ((i + 1) * block_q - 1) // block_k), 0)
    else:
        def kv_idx2(b, i, j):
            return (b, j, 0)
    qspec2 = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0))
    rowspec2 = pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i))
    kvspec2 = pl.BlockSpec((1, block_k, D), kv_idx2)
    dq_kernel = functools.partial(_fa_bwd_dq_kernel, causal=causal,
                                  scale=scale, block_q=block_q,
                                  block_k=block_k)
    dq = pl.pallas_call(
        dq_kernel,
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), jnp.float32),
        grid=(B * H, S // block_q, S // block_k),
        in_specs=[kvspec2, kvspec2, qspec2, qspec2, rowspec2, rowspec2],
        out_specs=qspec2,
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(kf, vf, qf, dof, lse, delta)

    shape = (B, H, S, D)
    return (dq.reshape(shape).astype(q.dtype),
            dk.reshape(shape).astype(k.dtype),
            dv.reshape(shape).astype(v.dtype))


# --------------------------------------------------------------- custom VJP
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None):
    """q,k,v: (B, H, S, D) → (B, H, S, D). Blocks default to the measured
    optimum (largest of 512/256/128 dividing S)."""
    return _fa_fwd(q, k, v, causal, scale, block_q, block_k)[0]


def _fa_fwd(q, k, v, causal, scale, block_q, block_k):
    block_q, block_k = _resolve_blocks(q.shape[2], block_q, block_k)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    # the kernels assume self-attention shapes (Sq == Sk); cross-attention
    # with mismatched lengths takes the composite (which handles it)
    if k.shape == q.shape and v.shape == q.shape \
            and flash_attention_supported(q.shape, block_q, block_k):
        out, lse = _fa_call(q, k, v, causal, scale, block_q, block_k)
    else:
        out, lse = _blocked_reference(q, k, v, causal, scale), None
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, scale, block_q, block_k, res, do):
    q, k, v, o, lse = res
    block_q, block_k = _resolve_blocks(q.shape[2], block_q, block_k)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if lse is not None:
        return _fa_bwd_call(q, k, v, o, lse, do, causal, scale, block_q,
                            block_k)
    # XLA composite fallback (materializes (S,S); only off-TPU small shapes)
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
    if causal:
        qi = jnp.arange(q.shape[2])[:, None]
        ki = jnp.arange(k.shape[2])[None, :]
        s = jnp.where(qi >= ki, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    dof = do.astype(jnp.float32)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
    dp = jnp.einsum("bhqd,bhkd->bhqk", dof, vf)
    delta = jnp.sum(dof * o.astype(jnp.float32), axis=-1, keepdims=True)
    ds = p * (dp - delta) * scale
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, kf)
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


def flash_attention_on_mesh(q, k, v, mesh, batch_axis=None, head_axis=None,
                            causal=False):
    """flash_attention inside a program partitioned over ``mesh``.

    GSPMD cannot partition a Mosaic kernel: lowering a pallas_call under a
    multi-device jit raises "Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map" (first seen on four
    real chips, PR 21; the interpreter the CPU tests use lowers to plain
    ops and never met it). So the call is wrapped in a shard_map with every
    mesh axis manual: the batch dim split over ``batch_axis``, heads over
    ``head_axis`` (either may be None), replicated over the remaining
    axes; each device runs the kernel on its own (B/dp, H/tp, S, D)."""
    from jax.sharding import PartitionSpec as P
    spec = P(batch_axis, head_axis, None, None)
    # check_vma=False: pallas_call out_shapes carry no vma annotation
    return jax.shard_map(
        lambda q, k, v: flash_attention(q, k, v, causal), mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)(q, k, v)


# ------------------------------------------------- out + LSE (for SP paths)
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_lse(q, k, v, causal=False, scale=None, block_q=None,
                        block_k=None):
    """Like flash_attention but ALSO returns the per-row log-sum-exp
    (B, H, S) fp32 — the sufficient statistic ring attention's online
    combine needs. Both outputs are differentiable: the LSE cotangent
    folds into the existing backward kernels as a delta shift (see
    _fa_bwd_call). Requires flash_attention_supported(q.shape)."""
    return _fa_lse_fwd(q, k, v, causal, scale, block_q, block_k)[0]


def _fa_lse_fwd(q, k, v, causal, scale, block_q, block_k):
    B, H, S, D = q.shape
    block_q, block_k = _resolve_blocks(S, block_q, block_k)
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    out, lse = _fa_call(q, k, v, causal, scale, block_q, block_k)
    return (out, lse.reshape(B, H, S)), (q, k, v, out, lse)


def _fa_lse_bwd(causal, scale, block_q, block_k, res, cts):
    do, dlse = cts
    q, k, v, o, lse = res
    B, H, S, D = q.shape
    block_q, block_k = _resolve_blocks(S, block_q, block_k)
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    return _fa_bwd_call(q, k, v, o, lse, do, causal, scale, block_q, block_k,
                        g_lse=dlse.reshape(B * H, 1, S))


flash_attention_lse.defvjp(_fa_lse_fwd, _fa_lse_bwd)


def _dense_with_lse(q, k, v, causal, scale):
    """Differentiable XLA fallback returning (out, lse) — same contract as
    flash_attention_lse for shapes/platforms the kernels can't take."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        qi = jnp.arange(q.shape[2])[:, None]
        ki = jnp.arange(k.shape[2])[None, :]
        s = jnp.where(qi >= ki, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe)
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-37)
    out = jnp.einsum("bhqk,bhkd->bhqd", (p / l).astype(v.dtype), v)
    lse = (m_safe + jnp.log(l))[..., 0]
    return out.astype(q.dtype), lse


def attention_with_lse(q, k, v, causal=False, scale=None):
    """(out, lse) via the Pallas kernels when supported, dense otherwise.
    The local step of ring/Ulysses sequence parallelism — per-shard memory
    is O(block^2), not O((S/n)^2), when the kernel engages."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if k.shape == q.shape and v.shape == q.shape \
            and flash_attention_supported(q.shape):
        return flash_attention_lse(q, k, v, causal, scale)
    return _dense_with_lse(q, k, v, causal, scale)
