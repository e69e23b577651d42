"""The Mamba-2 selective state-space recurrence in its chunked
("state-space dual") form (Dao & Gu 2024, arXiv:2405.21060, section 6),
differentiable, in XLA ops. NEW capability: no recurrent-state layer in the
reference framework.

The function, a head at a time (h a (P, N) state, A a negative scalar):

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T        y_t = h_t C_t + D x_t

with B_t, C_t (N,) shared by the heads of a group. Run a step at a time it
is S dependent steps of rank-one updates; here the sequence is cut into
chunks of Q positions and everything becomes matmuls:

  inside a chunk   y_i += sum_{j<=i} exp(a_i - a_j) (C_i . B_j) dt_j x_j
                   (a = cumsum(dt A) inside the chunk): one (Q, Q) matrix a
                   head, masked BEFORE the exp so no upper entry overflows
  chunk states     s_c = sum_j exp(a_last - a_j) dt_j x_j B_j^T
  between chunks   h_c = exp(a_last,c) h_{c-1} + s_c, written as one small
                   matmul over the (chunks, chunks) matrix of decays
  the carried part y_i += exp(a_i) C_i h_{c-1}

`dt A`, the cumulative sums, every exp and the states are float32 whatever
x's type; the two large products take their operands in x's type and
accumulate in float32, the two over the states run at full float32
precision (they are 0.2 % of the scan's operations). The gradient is
autodiff's of exactly this form. Every op is under the scope `ssd_scan`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import telemetry

__all__ = ["ssd_chunked"]

_SCANS = telemetry.counter(
    "mxtpu_ssd_scan_total",
    "State-space scans traced, by path (one is there: the chunked dual "
    "form in XLA ops).", ("path",))

_F32 = jnp.float32
_EXACT = jax.lax.Precision.HIGHEST


def _masked_exp_diff(a, b, keep):
    """exp(a - b) where `keep`, 0 elsewhere; the mask goes in before the
    exp, so a dropped entry's exponent (positive, unbounded) is never
    evaluated, forward or backward."""
    return jnp.exp(jnp.where(keep, a - b, -jnp.inf))


def ssd_chunked(x, dt, A, B, C, D, chunk):
    """x (b, s, h, p); dt (b, s, h) the step sizes, already positive
    (softplus applied); A (h,) negative; B, C (b, s, g, n) with h a
    multiple of g; D (h,) the skip; -> y (b, s, h, p) in x's type.

    s is padded on the right to a multiple of `chunk` with dt = 0 and
    x = 0 (a step that neither decays nor adds) and the pad cut off."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    r = h // g
    if h != g * r:
        raise ValueError("%d heads are not a multiple of %d groups" % (h, g))
    _SCANS.inc(path="chunked")
    with jax.named_scope("ssd_scan"):
        pad = -s % chunk
        if pad:
            x, dt, B, C = (jnp.pad(t, [(0, 0), (0, pad)]
                                   + [(0, 0)] * (t.ndim - 2))
                           for t in (x, dt, B, C))
        c, q = (s + pad) // chunk, chunk
        xg = x.reshape(b, c, q, g, r, p)
        dt = dt.astype(_F32).reshape(b, c, q, g, r)
        Bc, Cc = B.reshape(b, c, q, g, n), C.reshape(b, c, q, g, n)
        acs = jnp.cumsum(dt * A.astype(_F32).reshape(g, r), axis=2)
        xdt = (xg.astype(_F32) * dt[..., None]).astype(x.dtype)

        # inside a chunk: (L o C B^T) X
        cb = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc,
                        preferred_element_type=_F32)
        at = acs.transpose(0, 1, 3, 4, 2)                   # (b, c, g, r, q)
        causal = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
        decay = _masked_exp_diff(at[..., :, None], at[..., None, :], causal)
        mix = (decay * cb[:, :, :, None]).astype(x.dtype)   # (b,c,g,r,q,q)
        y = jnp.einsum("bcgrij,bcjgrp->bcigrp", mix, xdt,
                       preferred_element_type=_F32)

        # each chunk's own state, as if it started from zero
        to_end = jnp.exp(acs[:, :, -1:] - acs)               # (b,c,q,g,r)
        weighted = (xdt.astype(_F32) * to_end[..., None]).astype(x.dtype)
        states = jnp.einsum("bcjgn,bcjgrp->bcgrpn", Bc, weighted,
                            preferred_element_type=_F32)

        # the recurrence between chunks: h_c = sum_{z<=c} (decay z..c) s_z,
        # and chunk c starts from h_{c-1}
        total = jnp.cumsum(acs[:, :, -1], axis=1)            # (b, c, g, r)
        tt = total.transpose(0, 2, 3, 1)                     # (b, g, r, c)
        before = jnp.arange(c)[:, None] > jnp.arange(c)[None, :]
        shifted = jnp.concatenate(
            [jnp.zeros_like(tt[..., :1]), tt[..., :-1]], -1)
        carry = _masked_exp_diff(shifted[..., :, None], tt[..., None, :],
                                 before)                     # (b,g,r,c,z)
        start = jnp.einsum("bgrcz,bzgrpn->bcgrpn", carry, states,
                           precision=_EXACT)
        y_carried = jnp.einsum("bcign,bcgrpn->bcigrp", Cc.astype(_F32),
                               start, precision=_EXACT)
        y = y + y_carried * jnp.exp(acs)[..., None]
        y = y + xg.astype(_F32) * D.astype(_F32).reshape(g, r)[..., None]
        return y.reshape(b, s + pad, h, p)[:, :s].astype(x.dtype)
