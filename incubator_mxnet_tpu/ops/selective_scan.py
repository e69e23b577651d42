"""The Mamba-1 selective state-space recurrence (Gu & Dao 2023,
arXiv:2312.00752, section 3), differentiable. ops/ssd.py is Mamba-2's dual
form, whose decay is one scalar a head, so a chunk becomes matmuls; here
the decay is per (channel, state) and there is no matmul form: it is
vector-unit work.

The function, a channel c at a time (h an (N,) state, A[c] negative):

    h_t = exp(dt_t[c] A[c]) h_{t-1} + dt_t[c] x_t[c] B_t
    y_t[c] = h_t . C_t + D[c] x_t[c]

with B_t, C_t (N,) shared by all channels, and the step sizes dt =
softplus(low W^T + bias) formed from their low-rank input where they are
used: no (S, C) float32 tensor crosses the op's edge.

**One recurrence, two schedules**, chosen by what a call can see, the
platform and its shape (`_kernels_run_here()`, the rule of ops/attention.py:
a TPU, or MXTPU_FLASH_INTERPRET=1 for the CPU tests; `_kernel_takes`:
channels a multiple of 1024, at most 16 states, a chunk of whole bfloat16
tiles). No argument chooses, and a traced call holds one of them, never
both; `mxtpu_selective_scan_total{path}` says which.

  pallas       A `jax.custom_vjp` over two Mosaic kernels that step the
               recurrence a position at a time with the state in VMEM.
               Grid (batch, channel block of 1024, chunk), the chunks
               sequential. A channel block is ONE (8, 128) vreg, its state
               N of them (float32 scratch across the chunks, zeroed at
               chunk 0): a position costs N exps and about 7 N vector
               operations and no cross-lane work; B_t[n], C_t[n] are SMEM
               scalars; the step sizes come from the MXU a chunk at a time.
               The forward that is differentiated also writes the state
               each chunk STARTS from ((S / Q) x N x C float32: 84 MB at 16k
               x 5120 x 16, Q = 64) and nothing a position. The backward
               takes the chunks in reverse: it steps a chunk's states again
               from its start into VMEM (Q x N x 4 kB a block), then runs
               the adjoint state back over the positions; dB and dC, sums
               over all channels, are vreg sums over the lane tiles with
               one cross-lane sum for 8 positions, a channel block's part
               each, added outside; dA, dD and the projection's gradients
               accumulate across the chunks in their output blocks; the
               projection's three matmuls run on the MXU. So a recomputed
               layer runs forward kernel, forward kernel, backward kernel,
               and the (S, block, N) states autodiff keeps exist nowhere.
  chunked_xla  Off the TPU, and for shapes the layout does not take: XLA
               ops. The sequence is cut into G = S / Q chunks and the Q
               positions of EVERY chunk are stepped together (`lax.scan`,
               Q dependent steps on a (G, N, channels) state), the states
               between chunks by an associative scan, the carried part one
               reduction; `_CHANNEL_BLOCK` channels at a time (`lax.map`),
               each block under `jax.checkpoint` (its backward keeps S x
               block x N float32, 0.54 GB at 16k x 512 x 16; never
               (S, C, N)). The tests' second witness.

Measured alone on a v5e at (1, 16384, 5120) x 16 states, bfloat16 x
(PERF.md section 6; my chip runs, PR 35): the kernels 3.9 ms forward and
25.1 ms forward + backward at chunks of 64 (3.7 / 24.3 at 128, 3.7 / 23.9
at 256); on the device the forward kernel 3.08 ms and the backward 20.13
(its states again 4.9, dB and dC 3.3, the adjoint loop and the
projection's matmuls 10.2, staging and the pipeline 1.8). The XLA form
29-38 and 76-80 (PR 34, PR 35), flat in block, chunk and unrolling: its
state makes a trip through HBM at each of the Q steps. What bounds the
kernels is the vector unit's issue slots (2.2 M element-wise operations a
token a layer: 35 cycles a position a block forward, 230 backward), not
bandwidth: the required bytes are 1 ms a layer.

`dt A`, every exp, the states, the adjoint states and all sums are float32
whatever x's type, in both schedules (the cell's check cannot see the
state's type: tests/test_phi4flash.py, tests/test_selective_scan_kernels.py
and chip_smoke.py --phases hybrid hold both to 1e-4 against the recurrence
a position at a time, outputs and every gradient). Every op and both
kernels are under the scope `selective_scan`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .. import telemetry
from . import kernel_trace
from .attention import _interpret, _kernels_run_here

__all__ = ["selective_scan", "SCANNED_NAME"]

#: what the forward kernel writes, for a `jax.checkpoint` policy: y and the
#: chunks' starting states. A recomputed layer that saves the name runs the
#: forward kernel once a step.
SCANNED_NAME = "selective_scanned"

_SCANS = telemetry.counter(
    "mxtpu_selective_scan_total",
    "Selective (Mamba-1) scans traced, by path: pallas (the kernel pair, "
    "the state in VMEM: a TPU and a shape its layout takes) or chunked_xla "
    "(chunks stepped together in XLA ops: everything else).", ("path",))

_F32 = jnp.float32
#: channels scanned at a time: what one block's backward keeps is
#: S x block x N float32 (0.54 GB at 16k positions and 16 states)
_CHANNEL_BLOCK = 512
#: positions a chunk, of both schedules (the kernels keep a state a chunk
#: and hold a chunk's states in VMEM). Alone on a v5e the XLA form read
#: within 2 ms from 32 to 256; the kernels' backward 1.2 ms less at 256
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


# ------------------------------------------------------------ the kernels
# A block of `_KERNEL_CHANNELS` channels is ONE (8, 128) float32 vreg (channel
# 128 k + l of the block at sublane k, lane l), the state its N such vregs,
# and position t of a chunk a leading index: the recurrence needs no
# cross-lane work. A chunk's x, step sizes, dy and the stepped loops' results
# lie in VMEM "stepped": (q / 8, 64, 128) with position 8 i + j of lane tile
# k at [i, 8 k + j], so rows [i, 8 k : 8 k + 8] are the natural tile (8
# positions x 128 channels, what the matmuls and the HBM blocks hold) and
# rows [i, j :: 8] are position 8 i + j's vreg (a strided load or store).
#
# The stepped loops take 8 positions a trip: for each state n its vreg is
# carried through the 8 positions. The loop over n (and the loops over the 8
# lane tiles) are `_unrolled`: traced ONCE, the body emitted N times with a
# constant index when Mosaic lowers it. Unrolled in Python instead, a
# backward kernel is 5 000 traced operations and a train step's six scan
# kernels cost every process 40 s of tracing before its cached executable
# loads (measured on the chip's host, PR 35: `setup_s` 61 -> 99 s). What is
# left, 0.2 s a forward and 1.0 s a backward kernel there, is paid once a
# program and not once a layer (`kernel_trace.traced_once`).
_SUB, _LANE = 8, 128
_KERNEL_CHANNELS = _SUB * _LANE
#: states the layout is laid out for (a state is a vreg carried through 8
#: positions beside 16 to 32 vregs of their operands and sums)
_KERNEL_STATES = 16
#: the backward holds a chunk's states, (q + 8) x N x 4 kB (4.5 MiB at 64 x
#: 16), beside seven stepped buffers and the blocks' double buffers
_VMEM_LIMIT = 64 << 20


def _kernel_takes(c, n, chunk):
    """The shapes the kernels' layout takes (s is padded to the chunk)."""
    return c % _KERNEL_CHANNELS == 0 and n <= _KERNEL_STATES \
        and chunk % 16 == 0


def _unrolled(trips, body, carry):
    """``body(index, carry)`` `trips` times: one traced body, emitted with a
    constant index a trip where it is lowered."""
    return jax.lax.fori_loop(0, trips, body, carry, unroll=trips)


def _tile(k):
    """(rows of lane tile k in a stepped buffer, its lanes in a block)."""
    from jax.experimental import pallas as pl
    return pl.ds(pl.multiple_of(k * _SUB, _SUB), _SUB), \
        pl.ds(pl.multiple_of(k * _LANE, _LANE), _LANE)


def _position(j):
    """Position 8 i + j's rows in group i of a stepped buffer."""
    from jax.experimental import pallas as pl
    return pl.ds(j, _SUB, stride=_SUB)


def _stage(ref, to_s):
    """A (q, 1024) block into a stepped float32 buffer."""
    q = ref.shape[0]

    def tile(k, _):
        rows, lanes = _tile(k)
        to_s[:, rows, :] = ref[:, lanes].astype(_F32).reshape(
            q // _SUB, _SUB, _LANE)
        return 0

    _unrolled(_SUB, tile, 0)


def _unstage(from_s, ref):
    q = ref.shape[0]

    def tile(k, _):
        rows, lanes = _tile(k)
        ref[:, lanes] = from_s[:, rows, :].reshape(q, _LANE).astype(
            ref.dtype)
        return 0

    _unrolled(_SUB, tile, 0)


def _pre_activation(low_ref, w_ref, bias_ref, k):
    """(q, 128) float32: lane tile k's step sizes before the softplus."""
    from jax.experimental import pallas as pl
    return jax.lax.dot_general(
        low_ref[...], w_ref[_tile(k)[1], :], (((1,), (1,)), ((), ())),
        preferred_element_type=_F32) + bias_ref[pl.ds(k, 1), :]


def _stage_step_sizes(low_ref, w_ref, bias_ref, dt_s):
    q = low_ref.shape[0]

    def tile(k, _):
        dt_s[:, _tile(k)[0], :] = jax.nn.softplus(_pre_activation(
            low_ref, w_ref, bias_ref, k)).reshape(q // _SUB, _SUB, _LANE)
        return 0

    _unrolled(_SUB, tile, 0)


def _eight(i, *stepped):
    """Group i's 8 positions of each stepped buffer, a vreg a position."""
    return tuple([s[i, _position(j), :] for j in range(_SUB)]
                 for s in stepped)


def _fwd_kernel(x_ref, low_ref, w_ref, bias_ref, a_ref, d_ref, b_ref, c_ref,
                *rest, keep):
    """One (batch, channel block, chunk) program, the chunks in order and
    sequential: the state is `h_s`, zeroed at chunk 0. ``keep``: also write
    the state the chunk STARTS from (all the backward needs beside the
    inputs)."""
    from jax.experimental import pallas as pl
    y_ref, *rest = rest
    if keep:
        start_ref, *rest = rest
    h_s, x_s, dt_s, y_s = rest
    q, states = x_ref.shape[0], a_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        h_s[...] = jnp.zeros_like(h_s)

    if keep:
        start_ref[...] = h_s[...]
    _stage(x_ref, x_s)
    _stage_step_sizes(low_ref, w_ref, bias_ref, dt_s)
    skip = d_ref[...]

    def eight(i, _):
        xs, dts = _eight(i, x_s, dt_s)
        dtx = [dt_t * x_t for dt_t, x_t in zip(dts, xs)]

        def state(n, ys):
            h, a_n, ys = h_s[n], a_ref[n], list(ys)
            for j in range(_SUB):
                t = i * _SUB + j
                h = jnp.exp(dts[j] * a_n) * h + dtx[j] * b_ref[t, n]
                ys[j] = ys[j] + h * c_ref[t, n]
            h_s[n] = h
            return tuple(ys)

        ys = _unrolled(states, state, tuple(skip * x_t for x_t in xs))
        for j in range(_SUB):
            y_s[i, _position(j), :] = ys[j]
        return 0

    jax.lax.fori_loop(0, q // _SUB, eight, 0)
    _unstage(y_s, y_ref)


def _over_channels(h_s, v_s, out_ref):
    """out[t, n] = sum over the block's channels of h_s[n][slot t + 8]
    v_s[t]: the lane tiles added as vregs, ONE cross-lane sum for 8
    positions."""
    from jax.experimental import pallas as pl
    states, groups = h_s.shape[0], v_s.shape[0]
    column = jax.lax.broadcasted_iota(jnp.int32, (_SUB, states), 1)

    def eight(i, _):
        def state(n, out):
            def tile(k, part):
                rows = _tile(k)[0]
                return part + h_s[n, i + 1, rows, :] * v_s[i, rows, :]

            part = _unrolled(_SUB, tile, jnp.zeros((_SUB, _LANE), _F32))
            return jnp.where(column == n, part.sum(-1, keepdims=True), out)

        out_ref[pl.ds(pl.multiple_of(i * _SUB, _SUB), _SUB), :] = \
            _unrolled(states, state, jnp.zeros((_SUB, states), _F32))
        return 0

    jax.lax.fori_loop(0, groups, eight, 0)


def _bwd_kernel(x_ref, low_ref, w_ref, bias_ref, a_ref, d_ref, b_ref, c_ref,
                dy_ref, start_ref,
                dx_ref, dlow_ref, db_ref, dc_ref, da_ref, dw_ref, dbias_ref,
                dd_ref, g_s, h_s, x_s, dt_s, dy_s, ddt_s, dx_s):
    """One (batch, channel block, chunk) program, the chunks in REVERSE.
    The chunk's states are stepped again from its start into `h_s` (slot
    t + 8 holds h_t, slot 7 the start: h_{t-1} is slot t + 7 for every t),
    then the adjoint state `g_s` (carried from the chunk behind) runs back
    over the positions; each state's slot takes its adjoint once read, so
    dB is summed as dC is. dA, the projection's gradients and dD accumulate
    in their output blocks across the chunks; dlow, dB, dC are this channel
    block's part of sums over all channels."""
    from jax.experimental import pallas as pl
    q, states = x_ref.shape[0], a_ref.shape[0]
    groups = q // _SUB

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        for ref in (g_s, da_ref, dw_ref, dbias_ref, dd_ref):
            ref[...] = jnp.zeros_like(ref)

    _stage(x_ref, x_s)
    _stage(dy_ref, dy_s)
    _stage_step_sizes(low_ref, w_ref, bias_ref, dt_s)

    def start(n, _):
        h_s[n, 0, _position(_SUB - 1), :] = start_ref[n]
        return 0

    _unrolled(states, start, 0)

    def again(i, _):
        xs, dts = _eight(i, x_s, dt_s)
        dtx = [dt_t * x_t for dt_t, x_t in zip(dts, xs)]

        def state(n, _):
            h, a_n = h_s[n, i, _position(_SUB - 1), :], a_ref[n]
            for j in range(_SUB):
                h = jnp.exp(dts[j] * a_n) * h \
                    + dtx[j] * b_ref[i * _SUB + j, n]
                h_s[n, i + 1, _position(j), :] = h
            return 0

        return _unrolled(states, state, 0)

    jax.lax.fori_loop(0, groups, again, 0)
    _over_channels(h_s, dy_s, dc_ref)
    skip = d_ref[...]

    def eight(back, _):
        i = groups - 1 - back
        dts, dys = _eight(i, dt_s, dy_s)

        def state(n, sums):
            ddtx, ddt = (list(s) for s in sums)
            g, a_n = g_s[n], a_ref[n]
            da = jnp.zeros_like(g)
            for j in reversed(range(_SUB)):
                t = i * _SUB + j
                before = h_s[n, i + int(j > 0), _position((j - 1) % _SUB), :]
                g = g + dys[j] * c_ref[t, n]       # the adjoint of h_t
                h_s[n, i + 1, _position(j), :] = g
                ddtx[j] = ddtx[j] + g * b_ref[t, n]
                g = g * jnp.exp(dts[j] * a_n)         # ... of h_{t-1}
                bent = g * before                     # d decay x decay
                ddt[j] = ddt[j] + bent * a_n
                da = da + bent * dts[j]
            g_s[n] = g
            da_ref[n] += da
            return tuple(ddtx), tuple(ddt)

        zeros = (jnp.zeros((_SUB, _LANE), _F32),) * _SUB
        ddtx, ddt = _unrolled(states, state, (zeros, zeros))
        for j in range(_SUB):
            at = _position(j)
            ddt_s[i, at, :] = ddt[j] + ddtx[j] * x_s[i, at, :]
            dx_s[i, at, :] = ddtx[j] * dts[j] + dys[j] * skip
        return 0

    jax.lax.fori_loop(0, groups, eight, 0)
    _unstage(dx_s, dx_ref)
    low = low_ref[...]

    def tile(k, dlow):
        rows, lanes = _tile(k)
        dd_ref[k] += (dy_s[:, rows, :] * x_s[:, rows, :]).sum(0)
        # x_s becomes dt x: what dB's sum multiplies the adjoints by
        x_s[:, rows, :] = x_s[:, rows, :] * dt_s[:, rows, :]
        dpre = ddt_s[:, rows, :].reshape(q, _LANE) * jax.nn.sigmoid(
            _pre_activation(low_ref, w_ref, bias_ref, k))
        dbias_ref[k] += dpre.reshape(groups, _SUB, _LANE).sum(0)
        dpre = dpre.astype(low.dtype)
        dw_ref[lanes, :] += jax.lax.dot_general(
            dpre, low, (((0,), (0,)), ((), ())), preferred_element_type=_F32)
        return dlow + jnp.dot(dpre, w_ref[lanes, :],
                              preferred_element_type=_F32)

    dlow_ref[...] = _unrolled(_SUB, tile, jnp.zeros(low.shape, _F32))
    _over_channels(h_s, x_s, db_ref)


def _specs(n, r, q, chunks, reverse):
    """The eight inputs' blocks on the grid (batch, channel block, chunk);
    ``reverse``: grid step g is chunk `chunks - 1 - g`. -> (specs, chunk)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def chunk(g):
        return chunks - 1 - g if reverse else g

    def scalars():
        return pl.BlockSpec((None, q, n), lambda b, j, g: (b, chunk(g), 0),
                            memory_space=pltpu.SMEM)

    def a_vreg():
        return pl.BlockSpec((None, _SUB, _LANE), lambda b, j, g: (j, 0, 0))

    return [
        pl.BlockSpec((None, q, _KERNEL_CHANNELS),
                     lambda b, j, g: (b, chunk(g), j)),             # x
        pl.BlockSpec((None, q, r),
                     lambda b, j, g: (b, chunk(g), 0)),             # low
        pl.BlockSpec((_KERNEL_CHANNELS, r), lambda b, j, g: (j, 0)),  # w
        a_vreg(),                                                   # bias
        pl.BlockSpec((None, n, _SUB, _LANE),
                     lambda b, j, g: (j, 0, 0, 0)),                 # A
        a_vreg(),                                                   # D
        scalars(), scalars(),                                       # B, C
    ], chunk


def _operands(x, low, A, B, C, D, w, bias):
    """The kernels' operands: per-channel vectors a vreg a channel block,
    B and C float32 (SMEM scalars)."""
    c, n = A.shape
    nb = c // _KERNEL_CHANNELS
    return (x, low, w, bias.astype(_F32).reshape(nb, _SUB, _LANE),
            A.astype(_F32).T.reshape(n, nb, _SUB, _LANE).swapaxes(0, 1),
            D.astype(_F32).reshape(nb, _SUB, _LANE),
            B.astype(_F32), C.astype(_F32))


def _stepped(q):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM((q // _SUB, _SUB * _SUB, _LANE), _F32)


def _params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


@kernel_trace.traced_once("q", "keep", "interpret")
def _fwd_call(args, q, keep, interpret):
    """-> (y,) or (y, the state each chunk starts from (b, nb, chunks, n,
    8, 128) float32). s a multiple of q."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    x, low = args[:2]
    (b, s, c), r, n = x.shape, low.shape[-1], args[2].shape[1]
    nb, chunks = c // _KERNEL_CHANNELS, s // q
    in_specs, _ = _specs(n, r, q, chunks, False)
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    out_specs = [in_specs[0]]
    if keep:
        out_shape.append(jax.ShapeDtypeStruct(
            (b, nb, chunks, n, _SUB, _LANE), _F32))
        out_specs.append(pl.BlockSpec(
            (None, None, None, n, _SUB, _LANE),
            lambda b, j, g: (b, j, g, 0, 0, 0)))
    return kernel_trace.pallas_call(
        functools.partial(_fwd_kernel, keep=keep), _operands(*args),
        out_shape=out_shape, grid=(b, nb, chunks), in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((n, _SUB, _LANE), _F32)]
        + [_stepped(q)] * 3,
        compiler_params=_params(), interpret=interpret,
        name="selective_scan_fwd")


@kernel_trace.traced_once("q", "interpret")
def _bwd_call(args, starts, dy, q, interpret):
    """-> the eight gradients, in the inputs' shapes and types."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    x, low, A, B, C, D, w, bias = args
    (b, s, c), r, n = x.shape, low.shape[-1], A.shape[1]
    nb, chunks = c // _KERNEL_CHANNELS, s // q
    in_specs, chunk = _specs(n, r, q, chunks, True)

    def part(width):     # a channel block's part of a sum over channels
        return (jax.ShapeDtypeStruct((b, nb, s, width), _F32), pl.BlockSpec(
            (None, None, q, width), lambda b, j, g: (b, j, chunk(g), 0)))

    def kept(*shape):    # accumulated across the chunks of (b, j)
        return (jax.ShapeDtypeStruct((b, nb) + shape, _F32), pl.BlockSpec(
            (None, None) + shape,
            lambda b, j, g: (b, j) + (0,) * len(shape)))

    outs = [(jax.ShapeDtypeStruct(x.shape, x.dtype), in_specs[0]),
            part(r), part(n), part(n), kept(n, _SUB, _LANE),
            kept(_KERNEL_CHANNELS, r), kept(_SUB, _SUB, _LANE),
            kept(_SUB, _SUB, _LANE)]
    dx, dlow, db, dc, da, dw, dbias, dd = kernel_trace.pallas_call(
        _bwd_kernel, (*_operands(*args), dy, starts),
        out_shape=[o[0] for o in outs], grid=(b, nb, chunks),
        in_specs=in_specs + [in_specs[0], pl.BlockSpec(
            (None, None, None, n, _SUB, _LANE),
            lambda b, j, g: (b, j, chunk(g), 0, 0, 0))],
        out_specs=[o[1] for o in outs],
        scratch_shapes=[pltpu.VMEM((n, _SUB, _LANE), _F32),
                        pltpu.VMEM((n, q // _SUB + 1, _SUB * _SUB, _LANE),
                                   _F32)]
        + [_stepped(q)] * 5,
        compiler_params=_params(), interpret=interpret,
        name="selective_scan_bwd")
    return (dx, dlow.sum(1).astype(low.dtype),
            da.sum(0).swapaxes(0, 1).reshape(n, c).T.astype(A.dtype),
            db.sum(1).astype(B.dtype), dc.sum(1).astype(C.dtype),
            dd.sum((0, 3)).reshape(c).astype(D.dtype),
            dw.sum(0).reshape(c, r).astype(w.dtype),
            dbias.sum((0, 3)).reshape(c).astype(bias.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _scan_kernels(x, low, A, B, C, D, w, bias, q):
    return _fwd_call((x, low, A, B, C, D, w, bias), q, False,
                     _interpret())[0]


def _scan_kernels_fwd(x, low, A, B, C, D, w, bias, q):
    args = (x, low, A, B, C, D, w, bias)
    y, starts = (checkpoint_name(t, SCANNED_NAME)
                 for t in _fwd_call(args, q, True, _interpret()))
    return y, (args, starts)


def _scan_kernels_bwd(q, kept, dy):
    # (the caller's scope is on the forward's ops; the backward names its own)
    with jax.named_scope("selective_scan"):
        return _bwd_call(*kept, dy, q, _interpret())


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd)


def selective_scan(x, dt, A, B, C, D, dt_proj, chunk=_CHUNK):
    """x (b, s, c); dt (b, s, r) the low-rank INPUT of the step sizes'
    projection and ``dt_proj`` = (w (c, r), bias (c,)) its weights: the
    step sizes are softplus(dt w^T + bias), formed in float32 a channel
    block at a time where they are used; A (c, n) negative;
    B, C (b, s, n); D (c,) the skip; -> y (b, s, c) in x's type.

    s is padded on the right to a multiple of `chunk` with zeros and the
    pad cut off: the pad's step sizes are what the bias gives, which decays
    a state nothing reads."""
    b, s, c = x.shape
    n = A.shape[1]
    w, bias = dt_proj
    pad = -s % chunk
    kernels = _kernels_run_here() and _kernel_takes(c, n, chunk)
    _SCANS.inc(path="pallas" if kernels else "chunked_xla")
    with jax.named_scope("selective_scan"):
        xp, low, bm, cm = (x, dt, B, C) if kernels \
            else (x, dt, B.astype(_F32), C.astype(_F32))
        if pad:
            xp, low, bm, cm = (jnp.pad(t, [(0, 0), (0, pad), (0, 0)])
                               for t in (xp, low, bm, cm))
        if kernels:
            return _scan_kernels(xp, low, A, bm, cm, D, w, bias,
                                 chunk)[:, :s]
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
