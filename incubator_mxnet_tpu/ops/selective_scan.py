"""The Mamba-1 selective state-space recurrence (Gu & Dao 2023,
arXiv:2312.00752, section 3), differentiable, in XLA ops. NEW capability:
ops/ssd.py is Mamba-2's dual form, whose decay is one scalar a head, so a
chunk becomes matmuls; here the decay is per (channel, state) and there is
no matmul form: it is vector-unit and bandwidth work.

The function, a channel c at a time (h an (N,) state, A[c] negative):

    h_t = exp(dt_t[c] A[c]) h_{t-1} + dt_t[c] x_t[c] B_t
    y_t[c] = h_t . C_t + D[c] x_t[c]

with B_t, C_t (N,) shared by all channels. Run a step at a time over the
whole sequence it is S dependent steps on a (C, N) state: S small ops. Here
the sequence is cut into G = S / Q chunks and the Q positions of EVERY
chunk are stepped together, so there are Q dependent steps on a
(G, N, channels) state each:

  inside the chunks  Q steps of the recurrence from a zero state, all
                     chunks at once (`lax.scan`); y_local, and each chunk's
                     end state
  between chunks     H_g = exp(A sum_chunk dt) H_{g-1} + end_g, a
                     first-order recurrence over G entries, run as an
                     associative scan (log2 G levels on (G, N, channels))
  the carried part   y_t += sum_n C_t[n] exp(A[n] cumsum_t dt) H_{g-1}[n]:
                     one reduction, no (S, C, N) tensor kept

**What is held.** The states of every position, (S, C, N) float32, are 5.4
GB at S = 16 384, C = 5120, N = 16 and never exist. Channels are
independent, so the scan runs a block of `_CHANNEL_BLOCK` channels at a
time (`lax.map`), each block under `jax.checkpoint`: a gradient keeps the
inputs and, while ONE block's backward runs, that block's state at every
position (S x block x N float32: 0.54 GB at 512 channels; autodiff of the
stepped scan needs h_{t-1} beside the adjoint of h_t at every t). The
step's own temporaries (the decays) are recomputed, not kept. The skip,
the cast to x's type and the step sizes (softplus of their low-rank
projection) are formed inside the block, so no (S, C) float32 tensor
crosses the loop: the (S, C) float32 step sizes, 0.34 GB at 16k x 5120, and
their gradient never exist whole. That is the one entry: what the tests
and chip_smoke.py hold to the recurrence is what the model runs.

Measured alone on a v5e at (1, 16384, 5120) x 16 states, bfloat16 x,
chunks of 64 (PERF.md section 6, PR 34): forward 36-38 ms, forward +
backward 77-80 ms, whatever the block (256 to 5120 channels: forward 35-40
ms), the chunk (32 to 256) or the loop's unrolling (1 to 8): 2.8 x the 13
ms it takes to read and write the (G, N, C) state once a step (two
readings with the loop unrolled 4 x stood apart, 21-25 ms forward, and
were not followed up: PERF.md section 7). A Pallas
kernel that keeps a block's state in VMEM across a chunk's positions is
the lever (ROADMAP 2a).

`dt A`, every exp, the states and all sums are float32 whatever x's type
(the cell's check cannot see the state's type: tests/test_phi4flash.py and
chip_smoke.py --phases hybrid hold it to 1e-4 against the recurrence a
position at a time). Every op is under the scope `selective_scan`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import telemetry

__all__ = ["selective_scan"]

_SCANS = telemetry.counter(
    "mxtpu_selective_scan_total",
    "Selective (Mamba-1) scans traced, by path (one is there: chunks "
    "stepped together in XLA ops).", ("path",))

_F32 = jnp.float32
#: channels scanned at a time: what one block's backward keeps is
#: S x block x N float32 (0.54 GB at 16k positions and 16 states)
_CHANNEL_BLOCK = 512
#: positions a chunk: the Q positions of all S / Q chunks are stepped
#: together. Alone on a v5e 32 to 256 read within 2 ms of each other
_CHUNK = 64


def _combine(left, right):
    """Two steps of h <- a h + b as one: (a, b) then (a', b')."""
    (a1, b1), (a2, b2) = left, right
    return a1 * a2, a2 * b1 + b2


def _scan_block(x, low, w, bias, a, d, bm, cm):
    """One block of channels. x (b, g, q, c) in its own type; low (b, g, q,
    r) the input of the step sizes' projection, w (c, r) and bias (c,) its
    weights; a (n, c); d (c,) the skip; bm, cm (b, g, q, n) float32 ->
    y (b, g, q, c) in x's type."""
    b, g, q, c = x.shape
    n = a.shape[0]
    out = x.dtype
    x = x.astype(_F32)
    dt = jax.nn.softplus(jnp.einsum(
        "bgqr,cr->bgqc", low, w, preferred_element_type=_F32) + bias)

    @jax.checkpoint      # a step keeps its incoming state, not its decays
    def step(h, at):
        x_t, dt_t, b_t, c_t = at                       # (b, g, c) / (b, g, n)
        h = jnp.exp(dt_t[..., None, :] * a) * h \
            + (dt_t * x_t)[..., None, :] * b_t[..., None]
        return h, (h * c_t[..., None]).sum(-2)

    by_step = tuple(jnp.moveaxis(t, 2, 0) for t in (x, dt, bm, cm))
    end, y = jax.lax.scan(step, jnp.zeros((b, g, n, c), _F32), by_step)
    y = jnp.moveaxis(y, 0, 2) + x * d                  # (b, g, q, c)
    if g == 1:
        return y.astype(out)
    # the state each chunk starts from: H_g = decay_g H_{g-1} + end_g
    cum = jnp.cumsum(dt, axis=2)                       # (b, g, q, c)
    decay = jnp.exp(cum[:, :, -1, None, :] * a)        # (b, g, n, c)
    _, after = jax.lax.associative_scan(_combine, (decay, end), axis=1)
    start = jnp.concatenate(
        [jnp.zeros_like(after[:, :1]), after[:, :-1]], 1)
    carried = (cm[..., None] * jnp.exp(cum[..., None, :] * a)
               * start[:, :, None]).sum(-2)            # (b, g, q, c)
    return (y + carried).astype(out)


def selective_scan(x, dt, A, B, C, D, dt_proj, chunk=_CHUNK):
    """x (b, s, c); dt (b, s, r) the low-rank INPUT of the step sizes'
    projection and ``dt_proj`` = (w (c, r), bias (c,)) its weights: the
    step sizes are softplus(dt w^T + bias), formed in float32 a channel
    block at a time inside the block's checkpoint; A (c, n) negative;
    B, C (b, s, n); D (c,) the skip; -> y (b, s, c) in x's type.

    s is padded on the right to a multiple of `chunk` with zeros and the
    pad cut off: the pad's step sizes are what the bias gives, which decays
    a state nothing reads."""
    b, s, c = x.shape
    n = A.shape[1]
    w, bias = dt_proj
    _SCANS.inc(path="chunked_xla")
    with jax.named_scope("selective_scan"):
        pad = -s % chunk
        xp, low, bm, cm = x, dt, B.astype(_F32), C.astype(_F32)
        if pad:
            xp, low, bm, cm = (jnp.pad(t, [(0, 0), (0, pad), (0, 0)])
                               for t in (xp, low, bm, cm))
        g = (s + pad) // chunk
        block = _CHANNEL_BLOCK if c % _CHANNEL_BLOCK == 0 else c
        nb = c // block
        xp = jnp.moveaxis(xp.reshape(b, g, chunk, nb, block), 3, 0)
        low, bm, cm = (t.reshape(b, g, chunk, -1) for t in (low, bm, cm))
        a = A.astype(_F32).T.reshape(n, nb, block).swapaxes(0, 1)
        one = jax.checkpoint(_scan_block)
        y = jax.lax.map(
            lambda at: one(at[0], low, at[1], at[2], at[3], at[4], bm, cm),
            (xp, w.reshape(nb, block, -1),
             bias.astype(_F32).reshape(nb, block), a,
             D.astype(_F32).reshape(nb, block)))
        return jnp.moveaxis(y, 0, 3).reshape(b, s + pad, c)[:, :s]
