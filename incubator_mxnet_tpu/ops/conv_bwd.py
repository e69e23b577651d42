"""Fused 3x3/stride-1 conv BACKWARD Pallas kernel (dgrad + wgrad in one pass).

The pilot kernel behind docs/PERF_RESNET.md's central claim: XLA's
conv-backward codegen emits ~2.7x the fused-ideal HBM traffic (42.2 GB of
the ResNet-50 step's 76.4 GB), because dgrad and wgrad are separate ops —
each re-reads dy, dgrad materializes a padded/dilated grad, and wgrad runs
fp32 accumulation sweeps.  This kernel computes BOTH gradients in a single
grid pass that reads x once, reads dy once, and writes dx once:

    bytes = |x| + |dy| + |dx| + |dw|        (the fused ideal)

Formulation (NHWC, HWIO, stride 1, SAME padding, correlation semantics —
matches ``lax.conv_general_dilated``; ref src/operator/nn/convolution-inl.h
backward, re-derived for the MXU instead of im2col+GEMM):

    y[n,p,q,k]  = sum_{r,s,c} x[n, p+r-1, q+s-1, c] * w[r,s,c,k]
    dx[n,a,b,c] = sum_{r,s}   dy[n, a+1-r, b+1-s, :] @ w[r,s].T   (9 taps)
    dw[r,s,c,k] = sum_{n,p,q} x[n, p+r-1, q+s-1, c] * dy[n,p,q,k]

Each tap is a dense [M, K] x [K, C] (dgrad) or [M, C].T x [M, K] (wgrad)
matmul over the valid spatial overlap — 18 MXU matmuls per grid step, all
operands resident in VMEM.  The grid walks batch chunks sequentially; dw
accumulates in an fp32 VMEM scratch across steps (the flash-attention carry
idiom) and is written on the last step.  fp32 accumulation for BOTH outputs
(dx is cast to the activation dtype only on the final store), matching
XLA's conv-backward numerics.

Used by ``conv3x3_s1`` (custom_vjp) — forward stays XLA's conv (already at
the bandwidth roofline); backward takes this kernel when the shape is legal.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["conv3x3_bwd", "conv3x3_bwd_legal", "conv3x3_s1", "conv3x3_bytes"]


def _interpret():
    from ..config import get_env
    return get_env("MXTPU_FLASH_INTERPRET")


def _on_tpu():
    return jax.devices()[0].platform == "tpu"


# VMEM budget for one grid step's resident blocks (x, dy bf16 in; dx out;
# padded scratch; fp32 dx accumulator). The compiler double-buffers the
# in/out blocks on top of this (~1.5x observed), so 6 MB keeps the total
# under the 16 MB scoped-vmem limit.
_VMEM_BUDGET = 6 * 1024 * 1024


def _per_img_bytes(H, W, C, K, itemsize):
    """Resident VMEM bytes per image: x/dx blocks (C lanes), dy block
    (K lanes), the padded copies, the im2col patch buffer (9*max(C,K)
    lanes — the big one), and the fp32 dx matmul result on the stack.

    Shared between the block chooser and the legality gate so the two
    can never disagree about what fits."""
    pad = (H + 2) * (W + 2)
    return (H * W * (2 * itemsize * C + itemsize * K + 4 * C)
            + pad * itemsize * (C + K)
            + H * W * 9 * max(C, K) * itemsize)


def _auto_block_n(N, H, W, C, K, itemsize):
    """Largest batch-chunk dividing N whose resident blocks fit the budget."""
    per_img = _per_img_bytes(H, W, C, K, itemsize)
    bn = max(1, _VMEM_BUDGET // max(per_img, 1))
    while bn > 1 and N % bn:
        bn -= 1
    return min(bn, N)


def conv3x3_bwd_legal(x_shape, w_shape, stride=(1, 1), padding=(1, 1),
                      dilation=(1, 1), groups=1, itemsize=4):
    """Capability: 3x3, stride 1, SAME (pad 1), dense, NHWC/HWIO, C and K
    lane-packable (mult of 8); TPU or interpret mode."""
    if len(x_shape) != 4 or len(w_shape) != 4:
        return False
    KH, KW, C, K = w_shape
    if (KH, KW) != (3, 3) or x_shape[3] != C:
        return False
    if tuple(stride) != (1, 1) or tuple(padding) != (1, 1):
        return False
    if tuple(dilation) != (1, 1) or groups != 1:
        return False
    if C % 8 or K % 8:
        return False
    # the (9C, K) fp32 dw accumulator must fit VMEM alongside the patch
    # buffer — C=K=512 (conv5-class) exceeds it in this single-pass design
    if 9 * C * K * 4 > _VMEM_BUDGET:
        return False
    # even at block_n=1 the per-image resident footprint (dominated by the
    # H*W*9*max(C,K) patch buffer) must fit, or the kernel fails scoped-VMEM
    # allocation at compile time instead of falling back to XLA
    _, H, W, _ = x_shape
    if _per_img_bytes(H, W, C, K, itemsize) > _VMEM_BUDGET:
        return False
    from ..config import get_env
    if not get_env("MXTPU_CONV_BWD_PALLAS"):
        return False
    try:
        import jax.experimental.pallas  # noqa: F401
    except ImportError:
        return False
    return _on_tpu() or _interpret()


def _conv_bwd_kernel(x_ref, dy_ref, wd_ref, dx_ref, dw_ref, xp, dyp, pb, dwa,
                     *, H, W):
    """One batch-chunk step, im2col-in-VMEM form: ONE MXU matmul per
    gradient direction instead of 9 small taps each.

    x and dy are copied into zero-padded VMEM scratch (halo 1); the 9
    shifted views are laid side-by-side in a patch buffer ``pb``
    (im2col, entirely in VMEM — HBM traffic stays at the fused ideal):

      dgrad:  pb[m, t*K:(t+1)*K] = dyp shifted by tap t
              dx = pb @ wd                 (M x 9K) @ (9K x C)
      wgrad:  pb[m, t*C:(t+1)*C] = xp shifted by tap t   (buffer REUSED)
              dw = pb^T @ dy               (9C x M) @ (M x K)

    ``wd`` is the pre-rotated weight (flip + transpose to (9K, C)),
    prepared by XLA outside the kernel.  Large contraction dims (9K, M)
    keep the MXU busy; fp32 accumulation via preferred_element_type; dw
    accumulates across the sequential batch-chunk grid in fp32 scratch.
    """
    from jax.experimental import pallas as pl

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dwa[...] = jnp.zeros_like(dwa)

    xp[...] = jnp.zeros_like(xp)
    dyp[...] = jnp.zeros_like(dyp)
    xp[:, 1:H + 1, 1:W + 1, :] = x_ref[...]
    dyp[:, 1:H + 1, 1:W + 1, :] = dy_ref[...]

    dyv = dy_ref[...]
    BN = dyv.shape[0]
    K = dyv.shape[3]
    C = x_ref.shape[3]
    m = BN * H * W

    # ---- dgrad: im2col dy (tap t=(tr,ts) reads dyp[a+tr, b+ts], which is
    # dy[a+1-r, b+1-s] for r=2-tr, s=2-ts — wd's rows are ordered to match)
    for tr in range(3):
        for ts in range(3):
            t = tr * 3 + ts
            pb[:, :, :, t * K:(t + 1) * K] = dyp[:, tr:tr + H, ts:ts + W, :]
    dx = lax.dot_general(
        pb[...].reshape(m, pb.shape[3])[:, :9 * K], wd_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (M, C)
    dx_ref[...] = dx.reshape(BN, H, W, C).astype(dx_ref.dtype)

    # ---- wgrad: im2col x into the SAME buffer (lanes sized max(9C, 9K))
    for tr in range(3):
        for ts in range(3):
            t = tr * 3 + ts
            pb[:, :, :, t * C:(t + 1) * C] = xp[:, tr:tr + H, ts:ts + W, :]
    dwa[...] += lax.dot_general(
        pb[...].reshape(m, pb.shape[3])[:, :9 * C], dyv.reshape(m, K),
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (9C, K)

    @pl.when(i == pl.num_programs(0) - 1)
    def _flush():
        dw_ref[...] = dwa[...].reshape(3, 3, C, K).astype(dw_ref.dtype)


def conv3x3_bwd(x, dy, w, *, block_n=None, interpret=None):
    """Fused backward of ``y = conv3x3_s1_same(x, w)`` (NHWC / HWIO).

    Returns ``(dx, dw)``; reads x and dy from HBM exactly once each.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, H, W, C = x.shape
    K = w.shape[3]
    assert w.shape == (3, 3, C, K), w.shape
    assert dy.shape == (N, H, W, K), dy.shape
    if interpret is None:
        interpret = _interpret()
    bn = block_n or _auto_block_n(N, H, W, C, K, x.dtype.itemsize)
    assert N % bn == 0, "block_n=%d must divide N=%d" % (bn, N)
    grid = (N // bn,)
    # pre-rotate the weight for the single dgrad matmul: wd[(tr*3+ts)*K+k,
    # c] = w[2-tr, 2-ts, c, k] (XLA does this once; it is 9*C*K elements)
    wd = jnp.flip(w, (0, 1)).transpose(0, 1, 3, 2).reshape(9 * K, C)
    kernel = functools.partial(_conv_bwd_kernel, H=H, W=W)
    dx, dw = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((N, H, W, C), x.dtype),
                   jax.ShapeDtypeStruct((3, 3, C, K), w.dtype)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, H, W, C), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((bn, H, W, K), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((9 * K, C), lambda i: (0, 0)),
        ],
        out_specs=(pl.BlockSpec((bn, H, W, C), lambda i: (i, 0, 0, 0)),
                   pl.BlockSpec((3, 3, C, K), lambda i: (0, 0, 0, 0))),
        scratch_shapes=[pltpu.VMEM((bn, H + 2, W + 2, C), x.dtype),
                        pltpu.VMEM((bn, H + 2, W + 2, K), dy.dtype),
                        pltpu.VMEM((bn, H, W, 9 * max(C, K)), x.dtype),
                        pltpu.VMEM((9 * C, K), jnp.float32)],
        interpret=interpret,
    )(x, dy, wd)
    return dx, dw


def conv3x3_bytes(x_shape, k):
    """Fused-ideal HBM bytes for the backward: |x| + |dy| + |dx| + |dw|."""
    n, h, w, c = x_shape
    act = n * h * w
    return 2 * (act * c + act * k + act * c) + 2 * 9 * c * k


# ------------------------------------------------------------ custom_vjp
@functools.partial(jax.custom_vjp, nondiff_argnums=())
def conv3x3_s1(x, w):
    """3x3/s1/SAME NHWC conv whose BACKWARD is the fused Pallas kernel.

    Forward is XLA's conv (already bandwidth-optimal); backward replaces
    XLA's dgrad+wgrad pair (the 2.7x byte inflation) with ``conv3x3_bwd``.
    """
    return _conv_fwd_ref(x, w)


def _conv_fwd_ref(x, w):
    dn = lax.conv_dimension_numbers(x.shape, w.shape, ("NHWC", "HWIO", "NHWC"))
    return lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding=[(1, 1), (1, 1)],
        dimension_numbers=dn)


def _conv_fwd(x, w):
    return _conv_fwd_ref(x, w), (x, w)


def _conv_bwd_rule(res, dy):
    x, w = res
    if conv3x3_bwd_legal(x.shape, w.shape, itemsize=x.dtype.itemsize):
        return conv3x3_bwd(x, dy, w)
    # XLA fallback for off-TPU / odd shapes
    _, vjp = jax.vjp(_conv_fwd_ref, x, w)
    return vjp(dy)


conv3x3_s1.defvjp(_conv_fwd, _conv_bwd_rule)
