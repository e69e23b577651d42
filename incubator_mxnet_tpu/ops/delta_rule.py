"""The gated delta rule with a decay per channel (Kimi Delta Attention,
arXiv:2510.26692, on the delta rule of arXiv:2406.06484 / 2412.06464) in
its chunked WY form, differentiable. NEW capability: no linear-attention
mixer in the reference framework.

The function, a head at a time (S a (d_k, d_v) float32 state, S_0 = 0):

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t                 a_t = exp(g_t) in (0, 1]^{d_k}, b_t scalar

Run a position at a time it is T dependent rank-one updates of a matrix.
With the pseudo-value u_t = b_t (v_t - (Diag(a_t) S_{t-1})^T k_t) the
update is S_t = Diag(a_t) S_{t-1} + k_t u_t^T, and inside a chunk of C
positions that starts from S, with G_i = sum_{j<=i} g_j (C x d_k),

    A_ij = b_i sum_c k_ic k_jc exp(G_ic - G_jc)   i > j, else 0
    (I + A) [W  U] = [b K * exp(G)   b V]         unit lower-triangular
    U' = U - W S                                  (its rows are the u_t)
    P_ij = sum_c q_ic k_jc exp(G_ic - G_jc)       i >= j, else 0
    O = (Q * exp(G)) S + P U'
    S <- Diag(exp(G_C)) S + (K * exp(G_C - G))^T U'

A, P, W and U do not read S; what reads S is a recurrence over the chunks.

**exp(G_i - G_j) is never split into exp(G_i) exp(-G_j).** Its exponent is
<= 0 wherever it is used, but -G_j alone passes 88 once a channel's
cumulated decay does (A = 16 and a softplus near 4: two positions), and
float32 ends there. A and P are therefore computed in sub-blocks of
SUB = 16 rows: a block of rows against columns before it takes both sides
relative to G at a row r between them: exp(G_i - G_r) and exp(G_r - G_j)
are both <= 1 for j < r <= i, and where either is flushed to zero so is
the product; a diagonal sub-block has no such row between i and j and is
computed element by element, products of exp(G_i - G_j) masked BEFORE the
exp (the published kernels do the same). Nothing is clamped: a decay that
strong simply forgets.

Everything inside is float32 and every matmul runs at full float32
precision, whatever the inputs' type: the products are a hundredth of a
hybrid model's operations, and the forward substitution amplifies what
they round. What the gradient keeps of the states is the one at each
chunk's start, (T / C, d_k, d_v) a head, never one a position. Every op
and both kernels are under the scope `delta_rule`.

**Two entries, one form each.** `gated_delta_rule` takes (b, t, h, d)
operands, `gated_delta_rule_lanes` (b, t, h d) with h stated: the form the
kernels read, a head's channels a block of whole 128-lane tiles of the last
dimension. XLA tiles (b, t, h, d) by (h, d) and (b, t, h d) by (t, h d), so
a reshape between the two is a pass over the array, not a view; a caller
that makes its operands in lanes (`models.KimiDeltaAttention`) moves
nothing but beta where the kernels run. Either entry serves either schedule
(the heads merged or split off for the other form) and counts the call once.

**One recurrence, two schedules**, chosen by what a call can see, the
platform and its shape (`_kernels_run_here()`, the rule of ops/attention.py
and ops/selective_scan.py: a TPU, or MXTPU_FLASH_INTERPRET=1 for the CPU
tests; `_kernel_takes`: d_k and d_v whole lane tiles of 128, a chunk of
16, 32, 64, 128 or 256 rows: whole 16-row sub-blocks that pair up). No argument
chooses, and a traced call holds one of them, never both;
`mxtpu_delta_rule_total{path}` says which.

  pallas  A `jax.custom_vjp` over two Mosaic kernels, `delta_rule_fwd` and
          `delta_rule_bwd`, grid (batch, head group, chunk) with the chunks
          sequential: the (128, 128) state of a head is a float32 VMEM
          scratch that lives across the chunks (zeroed at chunk 0), and a
          chunk's G, A, P, (I + A)^-1, W, U, u never leave VMEM. A head's
          chunk is a chain of small DEPENDENT matmuls, each waiting on the
          one before it: a group's heads (8 at a chunk of 64) are a leading
          batch axis of every value, so a link of the chain is that many
          independent matmuls. A, P: the
          diagonal sub-blocks a diagonal (i - j = 1 .. 15) of all of them
          at a time (a sublane roll, one exp, two lane sums); below them
          the pairs that first meet in blocks of 16, 32, .. rows, one
          matmul a block size, relative to G at the first row between.
          (I + A)^-1 by substitution, no power of A: the 16-row diagonal
          blocks a column at a time on the vector unit, then blocks of 32,
          64 rows by two matmuls a size (the off-diagonal block of a
          doubled block is -X_22 A_21 X_11). The forward that is
          differentiated also writes the state each chunk STARTS from
          (67 MB a layer at 8192 x 8 x 128 x 128, C = 64) and nothing a
          position; the layer's first pass under `gluon.utils.recompute`
          writes o only. The backward takes the
          chunks in reverse with the adjoint state in VMEM: a chunk forms
          G .. u again from its inputs and its start state, then every
          transpose above, the maps' among them (dq, dk, dv, dg as a
          reverse cumulative sum, db). Every dot says precision=HIGHEST:
          Mosaic's default rounds float32 operands to bfloat16.
          More than one head group has run on the chip (PR 48: 32 heads
          are 4 groups of 8 on the grid's second axis, 160 head-layers
          a step of the Ling cell): a group of 8 heads at 8192 positions
          costs 1.37 ms forward and 3.07 ms backward in the step's
          capture, what Solar's one group costs, so the time is linear in
          the groups; the chunk-start states are 268 MB a layer there.
  xla     Off the TPU, and for shapes the kernels do not take: A, P, W, U
          for all chunks at once (batched matmuls and one batched forward
          substitution, `lax.linalg.triangular_solve`), one `lax.scan` over
          the chunks that carries S; the gradient is autodiff's. The
          program PR 38 traced, text for text; the tests' second witness.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .. import telemetry
from . import kernel_trace
from .attention import _interpret, _kernels_run_here

__all__ = ["gated_delta_rule", "gated_delta_rule_lanes", "RULED_NAME"]

#: what the forward kernel writes, for a `jax.checkpoint` policy: o and the
#: chunks' starting states. A recomputed layer that saves the name runs the
#: forward kernel once a step.
RULED_NAME = "delta_ruled"

_CALLS = telemetry.counter(
    "mxtpu_delta_rule_total",
    "Gated delta rules traced, by path: pallas (the kernel pair, the state "
    "in VMEM: a TPU and a shape the kernels take) or xla (the chunked WY "
    "form in XLA ops: everything else).", ("path",))

_F32 = jnp.float32
#: rows of a sub-block of A and P (the module's docstring)
SUB = 16


@jax.checkpoint
def _decayed_products(x, k, G):
    """M_ij = sum_c x_ic k_jc exp(G_ic - G_jc) for i >= j, else 0:
    x, k, G (..., C, d) -> (..., C, C). No exponent that is evaluated is
    positive. (checkpoint: the gradient keeps x, k and G, not the diagonal
    sub-blocks' (16, 16, d) products, 16 d a position. A call a map: two
    maps from one call would share the exps, and an exp with two readers
    is not fused into either sum but written out, 16 d floats a position
    a head.)"""
    C, d = x.shape[-2:]
    sub = SUB if C % SUB == 0 else C

    def blocks(t):
        return t.reshape(t.shape[:-2] + (C // sub, sub, d))

    xb, kb, Gb = blocks(x), blocks(k), blocks(G)
    keep = jnp.arange(sub)[:, None] >= jnp.arange(sub)[None, :]
    diff = Gb[..., :, None, :] - Gb[..., None, :, :]
    decay = jnp.exp(jnp.where(keep[..., None], diff, -jnp.inf))
    diag = jnp.sum(xb[..., :, None, :] * (kb[..., None, :, :] * decay), -1)
    out = []
    for i in range(C // sub):
        lo, hi = i * sub, (i + 1) * sub
        row = [diag[..., i, :, :]]
        if i:
            ref = G[..., lo:lo + 1, :]
            row.insert(0, jnp.einsum(
                "...id,...jd->...ij",
                x[..., lo:hi, :] * jnp.exp(G[..., lo:hi, :] - ref),
                k[..., :lo, :] * jnp.exp(ref - G[..., :lo, :])))
        if hi < C:
            row.append(jnp.zeros(x.shape[:-2] + (sub, C - hi), _F32))
        out.append(jnp.concatenate(row, -1))
    return jnp.concatenate(out, -2)


def _rule_xla(q, k, v, g, beta, chunk):
    """The schedule in XLA ops: the maps and the solve batched over all
    chunks, a `lax.scan` over the chunks that carries the state; the
    gradient is autodiff's."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    with jax.named_scope("delta_rule"), \
            jax.default_matmul_precision("highest"):
        pad = -t % chunk
        n = (t + pad) // chunk

        def chunks(x):
            """(b, t, h, ...) -> float32 (b, h, n, chunk, ...)."""
            x = x.astype(_F32)
            if pad:
                x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
            x = x.reshape((b, n, chunk) + x.shape[2:])
            return jnp.moveaxis(x, 3, 1)

        qc, kc, vc, gc = (chunks(x) for x in (q, k, v, g))
        bc = chunks(beta)[..., None]                     # (b, h, n, C, 1)
        G = jnp.cumsum(gc, axis=-2)
        into = jnp.exp(G)                # from the chunk's start to i
        kb = kc * bc
        # A's diagonal is there and never read: the solve takes ones for
        # it, and nothing above it
        A = _decayed_products(kb, kc, G)
        P = _decayed_products(qc, kc, G)
        WU = jax.lax.linalg.triangular_solve(
            A, jnp.concatenate([kb * into, vc * bc], -1), left_side=True,
            lower=True, unit_diagonal=True)
        W, U = WU[..., :dk], WU[..., dk:]
        q_in = qc * into
        k_out = kc * jnp.exp(G[..., -1:, :] - G)   # from j to the chunk's end
        through = into[..., -1, :]                       # (b, h, n, d_k)

        def one(S, chunk_of):
            W, U, q_in, P, k_out, through = chunk_of
            u = U - W @ S
            o = q_in @ S + P @ u
            S = through[..., None] * S + jnp.swapaxes(k_out, -1, -2) @ u
            return S, o

        by_chunk = tuple(jnp.moveaxis(x, 2, 0)
                         for x in (W, U, q_in, P, k_out, through))
        _, o = jax.lax.scan(one, jnp.zeros((b, h, dk, dv), _F32), by_chunk)
        o = jnp.moveaxis(o, 0, 2)                        # (b, h, n, C, d_v)
        o = jnp.moveaxis(o, 1, 3).reshape(b, t + pad, h, dv)
        return o[:, :t].astype(v.dtype)


# ------------------------------------------------------------ the kernels
# One (batch, head group, chunk) grid step a chunk, the chunks sequential.
# A head's chunk is a CHAIN of small dependent matmuls (the substitution,
# W U, what reads the state), and a dependent float32 matmul waits ~0.25 us
# for the one before it whatever its size (measured, PERF.md section 6
# PR 40): the group's heads are therefore a leading batch axis of every
# value, (heads, C, .), so that each step of the chain is `heads`
# independent matmuls side by side. The state is kept TRANSPOSED, (d_v,
# d_k), so that a channel's decay scales a lane and every product with it
# is a matmul against rows (`_NT`). All of a chunk's intermediates are
# values of the step: Mosaic keeps them in vregs and VMEM, nothing but the
# inputs, o and (kept) the start state crosses the kernel's edge.
_NN = (((2,), (1,)), ((0,), (0,)))       # (h, m, k) x (h, k, n)
_NT = (((2,), (2,)), ((0,), (0,)))       # (h, m, k) x (h, n, k)
_TN = (((1,), (1,)), ((0,), (0,)))       # (h, k, m) x (h, k, n)
_VMEM_LIMIT = 64 << 20
#: rows a grid step (heads x chunk), at most: a step's values grow with
#: heads x chunk^2, and at 8 x 128 the backward's do not fit a v5e's VMEM
#: (compiled for a described v5e: 8 x 64, 4 x 128 and 2 x 256 do)
_ROWS = 512
#: the longest chunk compiled so
_MAX_CHUNK = 256


def _kernel_takes(dk, dv, chunk):
    """The shapes the kernels take (t is padded to the chunk): heads whose
    keys and values are whole 128-lane tiles (a head is a lane block of the
    (b, t, h d) inputs), a chunk of whole 16-row sub-blocks that pair up
    into it: 16, 32, 64, 128 or 256 rows."""
    subs = chunk // SUB
    return dk % 128 == 0 and dv % 128 == 0 and chunk % SUB == 0 \
        and subs & (subs - 1) == 0 and chunk <= _MAX_CHUNK


def _dot(a, b, dims=_NN):
    """A matmul a head, float32 operands at full precision: Mosaic's
    default rounds them to bfloat16 (PERF.md section 6, PR 30)."""
    return jax.lax.dot_general(a, b, dims, precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=_F32)


def _unrolled(lo, hi, body, carry):
    """``body(index, carry)`` for lo <= index < hi: one traced body, emitted
    with a constant index a trip where it is lowered."""
    return jax.lax.fori_loop(lo, hi, body, carry, unroll=max(hi - lo, 1))


def _doublings(start, n):
    """How many times `start` doubles before it reaches n."""
    return max(0, -(-n // start) - 1).bit_length()


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _grid(C):
    """(row, col) of a (1, C, C) map, every head's alike."""
    return _iota((1, C, C), 1), _iota((1, C, C), 2)


def _pairs(C, shift):
    """With blocks of 2^shift rows, where in (1, C, C): (row in an odd
    block) and (col in the even block just before it): the pairs (i, j),
    i > j, that meet first at this block size."""
    row, col = _grid(C)
    br, bc = row >> shift, col >> shift
    return ((br & 1) == 1) & (bc == br - 1)


def _roll(x, shift):
    """Rows down by `shift` (row i takes row i - shift, the top wraps)."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.roll(x, shift, 1)


def _blocks(x, rows):
    """(h, C, w) -> (h, C / rows, rows, w): blocks of whole sublane tiles."""
    h, C, w = x.shape
    return x.reshape(h, C // rows, rows, w)


def _over_blocks(x, rows):
    """(h, C / rows, 1, w), a row a block -> (h, C, w), the row on all of
    its block's rows."""
    h, n, _, w = x.shape
    return jnp.broadcast_to(x, (h, n, rows, w)).reshape(h, n * rows, w)


def _diagonal(k, G, delta):
    """The diagonal i - j = delta of every 16-row sub-block at once ->
    (exp(G_i - G_j) (h, C, d), masked BEFORE the exp where j = i - delta
    lies in another sub-block; k_j times it; where (i, j) lies in (1, C,
    C))."""
    C = k.shape[1]
    inside = _iota((1, C, 1), 1) & (SUB - 1)       # the row in its sub-block
    decay = jnp.exp(jnp.where(inside >= delta, G - _roll(G, delta),
                              -jnp.inf))
    row, col = _grid(C)
    return decay, _roll(k, delta) * decay, col == row - delta


def _below(G, level):
    """The pairs that first meet in blocks of s = 16 << level rows -> (exp(
    G_i - G_r) for i in an odd block, exp(G_r - G_j) for j in the even
    block before it, r the odd block's first row, both zero elsewhere and
    masked before the exp; where those pairs lie in (1, C, C))."""
    C = G.shape[1]
    s, shift = SUB << level, level + SUB.bit_length() - 1
    first = _blocks(G, s)[:, :, :1, :]                       # a row a block
    after = jnp.concatenate([first[:, 1:], jnp.zeros_like(first[:, :1])], 1)
    block = _iota((1, C, 1), 1) >> shift
    odd = (block & 1) == 1
    even = (~odd) & ((block + 1) * s < C)
    return (jnp.exp(jnp.where(odd, G - _over_blocks(first, s), -jnp.inf)),
            jnp.exp(jnp.where(even, _over_blocks(after, s) - G, -jnp.inf)),
            _pairs(C, shift))


def _maps(q, kb, k, G):
    """-> (A strictly lower, P lower) (h, C, C): sum_c x_ic k_jc exp(G_ic -
    G_jc) for x = kb, q. The module's rule: the diagonal 16-row sub-blocks
    element by element, a diagonal (i - j = delta) of all of them at a time,
    the mask before the exp; below them the pairs that first meet in blocks
    of 16, 32, .. rows (two sizes at a chunk of 64: a Python loop), a
    matmul a size, both sides relative to G at the first row between them.
    No exponent that is evaluated is positive."""
    h, C, _ = k.shape
    row, col = _grid(C)

    def diagonal(delta, maps):
        A, P = maps
        _, kd, on = _diagonal(k, G, delta)
        return (A + jnp.where(on, jnp.sum(kb * kd, -1, keepdims=True), 0.0),
                P + jnp.where(on, jnp.sum(q * kd, -1, keepdims=True), 0.0))

    A, P = _unrolled(1, min(SUB, C), diagonal, (
        jnp.zeros((h, C, C), _F32),
        jnp.where(row == col, jnp.sum(q * k, -1, keepdims=True), 0.0)))
    for level in range(_doublings(SUB, C)):
        into, out, live = _below(G, level)
        m = _dot(jnp.concatenate([kb * into, q * into], 1), k * out, _NT)
        A, P = A + jnp.where(live, m[:, :C], 0.0), \
            P + jnp.where(live, m[:, C:], 0.0)
    return A, P


def _maps_bwd(q, kb, k, G, dA, dP):
    """`_maps`' transpose -> (dq, dkb, dk, dG), each (h, C, d). dG is x dx
    - k dk summed over both maps: d/dG_i of a term is the term, d/dG_j its
    negative, and the reference rows cancel."""
    C = k.shape[1]
    row, col = _grid(C)

    def diagonal(delta, grads):
        dq, dkb, dk = grads
        decay, kd, on = _diagonal(k, G, delta)
        da = jnp.sum(jnp.where(on, dA, 0.0), -1, keepdims=True)
        dp = jnp.sum(jnp.where(on, dP, 0.0), -1, keepdims=True)
        # (rows that wrapped have a zero decay: nothing rolls back over)
        return (dq + dp * kd, dkb + da * kd,
                dk + _roll((da * kb + dp * q) * decay, C - delta))

    dp = jnp.sum(jnp.where(row == col, dP, 0.0), -1, keepdims=True)
    dq, dkb, dk = _unrolled(1, min(SUB, C), diagonal,
                            (dp * k, jnp.zeros_like(k), dp * q))
    for level in range(_doublings(SUB, C)):
        into, out, live = _below(G, level)
        dm = jnp.concatenate([jnp.where(live, dA, 0.0),
                              jnp.where(live, dP, 0.0)], 1)
        dx = _dot(dm, k * out)
        dks = _dot(dm, jnp.concatenate([kb * into, q * into], 1), _TN)
        dq, dkb, dk = dq + dx[:, C:] * into, dkb + dx[:, :C] * into, \
            dk + dks * out
    return dq, dkb, dk, q * dq + kb * dkb - k * dk


def _inverse(A):
    """(I + A)^-1 for A strictly lower (h, C, C), by substitution: no power
    of A is formed (b reaches 2 and A's entries with it: a product form
    (I - A)(I + A^2).. cancels entries of A^8 against each other). The
    diagonal 16-row sub-blocks a column at a time on the vector unit (row
    m of a block's inverse is final once the columns before m are swept:
    15 steps of a masked lane sum and a product, in Python: the step is a
    static row of the blocks); then blocks of 32, 64, .. rows: with X the
    inverse of the diagonal blocks of s rows, the blocks of 2 s take
    -X_22 A_21 X_11 below their diagonal, all of them in two matmuls."""
    h, C, _ = A.shape
    row, col = _grid(C)
    first = row & -SUB                  # the first row of the row's block
    X = jnp.broadcast_to((row == col).astype(_F32), (h, C, C))
    for m in range(SUB - 1):
        column = jnp.sum(jnp.where(col == first + m, A, 0.0), -1,
                         keepdims=True)
        X = X - column * _over_blocks(_blocks(X, SUB)[:, :, m:m + 1, :], SUB)
    shift = SUB.bit_length() - 1

    def doubled(shift, X):
        return X - _dot(_dot(X, jnp.where(_pairs(C, shift), A, 0.0)), X)

    return _unrolled(shift, shift + _doublings(SUB, C), doubled, X)


def _chunk(q, k, v, g, beta):
    """What a chunk computes without the state, forward and backward
    alike; q, k, g (h, C, d_k), v (h, C, d_v), beta (h, C, 1), float32."""
    h, C, dk = q.shape
    row, col = _grid(C)
    lower = jnp.broadcast_to((row >= col).astype(_F32), (h, C, C))
    G = _dot(lower, g)                       # cumulative sums down the chunk
    into = jnp.exp(G)                        # from the chunk's start to i
    out = jnp.exp(G[:, C - 1:] - G)          # from j to the chunk's end
    kb = k * beta
    A, P = _maps(q, kb, k, G)
    X = _inverse(A)
    rhs = jnp.concatenate([kb * into, v * beta], -1)
    WU = _dot(X, rhs)
    return dict(G=G, into=into, out=out, kb=kb, P=P, X=X, rhs=rhs, WU=WU,
                W=WU[..., :dk], U=WU[..., dk:], lower=lower, q_in=q * into,
                k_out=k * out, through=into[:, C - 1:])


def _heads(ref, heads):
    """A (C, heads x d) block -> (heads, C, d) float32: a head's lanes are
    whole tiles."""
    d = ref.shape[1] // heads
    return jnp.stack([ref[:, j * d:(j + 1) * d].astype(_F32)
                      for j in range(heads)])


def _lanes(ref, x):
    """(heads, C, d) into a (C, heads x d) block, in its type."""
    d = x.shape[2]
    for j in range(x.shape[0]):
        ref[:, j * d:(j + 1) * d] = x[j].astype(ref.dtype)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest, keep):
    """``keep``: also write the state each chunk STARTS from (all the
    backward needs beside the inputs)."""
    from jax.experimental import pallas as pl
    if keep:
        start_ref, *rest = rest
    state, = rest                                  # (heads, d_v, d_k)
    heads = state.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        state[...] = jnp.zeros_like(state)

    S = state[...]
    if keep:
        start_ref[...] = S
    q, k, v, g = (_heads(r, heads) for r in (q_ref, k_ref, v_ref, g_ref))
    c = _chunk(q, k, v, g, b_ref[...].astype(_F32))
    C = q.shape[1]
    ws = _dot(jnp.concatenate([c["W"], c["q_in"]], 1), S, _NT)
    u = c["U"] - ws[:, :C]
    _lanes(o_ref, ws[:, C:] + _dot(c["P"], u))
    state[...] = S * c["through"] + _dot(u, c["k_out"], _TN)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, do_ref, start_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, adjoint):
    """The chunks in REVERSE; `adjoint` is dS^T (heads, d_v, d_k) of the
    state the chunk hands on, zero behind the last chunk."""
    from jax.experimental import pallas as pl
    heads = adjoint.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        adjoint[...] = jnp.zeros_like(adjoint)

    q, k, v, g, do = (_heads(r, heads)
                      for r in (q_ref, k_ref, v_ref, g_ref, do_ref))
    beta = b_ref[...].astype(_F32)
    c = _chunk(q, k, v, g, beta)
    S, dS, W, U = start_ref[...], adjoint[...], c["W"], c["U"]
    C, dk = q.shape[1:]
    u = U - _dot(W, S, _NT)
    du = _dot(c["P"], do, _TN) + _dot(c["k_out"], dS, _NT)
    row, col = _grid(C)
    dP = jnp.where(row >= col, _dot(do, u, _NT), 0.0)
    dq_in = _dot(do, S)
    dk_out = _dot(u, dS)
    dthrough = jnp.sum(S * dS, 1, keepdims=True)                 # (h, 1, d_k)
    adjoint[...] = _dot(do, c["q_in"], _TN) + dS * c["through"] \
        - _dot(du, W, _TN)
    # (I + A) [W U] = rhs: d rhs = X^T [dW dU], dA = -d rhs [W U]^T below
    drhs = _dot(c["X"], jnp.concatenate([-_dot(du, S), du], -1), _TN)
    dA = jnp.where(row > col, -_dot(drhs, c["WU"], _NT), 0.0)
    dq, dkb, dk_, dG = _maps_bwd(q, c["kb"], k, c["G"], dA, dP)
    dkb = dkb + drhs[..., :dk] * c["into"]
    decayed = dk_out * c["k_out"]
    dG = dG + dq_in * c["q_in"] + drhs[..., :dk] * c["rhs"][..., :dk] \
        - decayed
    last = jnp.sum(decayed, 1, keepdims=True) + dthrough * c["through"]
    dG = dG + jnp.where(_iota((1, C, 1), 1) == C - 1, last, 0.0)
    _lanes(dq_ref, dq + dq_in * c["into"])
    _lanes(dk_ref, dk_ + dk_out * c["out"] + dkb * beta)
    _lanes(dv_ref, drhs[..., dk:] * beta)
    _lanes(dg_ref, _dot(c["lower"], dG, _TN))
    db_ref[...] = (jnp.sum(dkb * k, -1, keepdims=True) + jnp.sum(
        drhs[..., dk:] * v, -1, keepdims=True)).astype(db_ref.dtype)


def _heads_a_step(h, chunk):
    """The largest group of heads that divides h and keeps a step within
    `_ROWS` rows."""
    return max(n for n in range(1, max(_ROWS // chunk, 1) + 1) if h % n == 0)


def _specs(dk, dv, q, heads, chunks, reverse):
    """The five inputs' blocks on the grid (batch, head group, chunk): q,
    k, g (b, t, h d_k) and v (b, t, h d_v) the group's lanes, beta (b, h, t,
    1) its columns. ``reverse``: grid step n is chunk `chunks - 1 - n`.
    -> (specs, the state's spec of (b, h, chunks, d_v, d_k))."""
    from jax.experimental import pallas as pl

    def chunk(n):
        return chunks - 1 - n if reverse else n

    def lanes(d):
        return pl.BlockSpec((None, q, heads * d),
                            lambda b, h, n: (b, chunk(n), h))

    return [lanes(dk), lanes(dk), lanes(dv), lanes(dk), pl.BlockSpec(
        (None, heads, q, 1), lambda b, h, n: (b, h, chunk(n), 0))], \
        pl.BlockSpec((None, heads, None, dv, dk),
                     lambda b, h, n: (b, h, chunk(n), 0, 0))


def _params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


@kernel_trace.traced_once("h", "q", "keep", "interpret")
def _fwd_call(args, h, q, keep, interpret):
    """args: q, k, g (b, t, h d_k), v (b, t, h d_v), beta (b, h, t, 1), t a
    multiple of q -> (o (b, t, h d_v) in v's type,) or (o, the state each
    chunk starts from, transposed: (b, h, chunks, d_v, d_k) float32)."""
    from jax.experimental.pallas import tpu as pltpu
    v = args[2]
    (b, t, _), dk, dv = v.shape, args[0].shape[-1] // h, v.shape[-1] // h
    chunks, heads = t // q, _heads_a_step(h, q)
    in_specs, start_spec = _specs(dk, dv, q, heads, chunks, False)
    out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype)]
    out_specs = [in_specs[2]]
    if keep:
        out_shape.append(jax.ShapeDtypeStruct((b, h, chunks, dv, dk), _F32))
        out_specs.append(start_spec)
    return kernel_trace.pallas_call(
        functools.partial(_fwd_kernel, keep=keep), args,
        out_shape=out_shape, grid=(b, h // heads, chunks), in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), _F32)],
        compiler_params=_params(), interpret=interpret,
        name="delta_rule_fwd")


@kernel_trace.traced_once("h", "q", "interpret")
def _bwd_call(args, starts, do, h, q, interpret):
    """-> the five gradients, in the operands' shapes and types."""
    from jax.experimental.pallas import tpu as pltpu
    v = args[2]
    t, dk, dv = v.shape[1], args[0].shape[-1] // h, v.shape[-1] // h
    chunks, heads = t // q, _heads_a_step(h, q)
    in_specs, start_spec = _specs(dk, dv, q, heads, chunks, True)
    return kernel_trace.pallas_call(
        _bwd_kernel, (*args, do, starts),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in args],
        grid=(v.shape[0], h // heads, chunks),
        in_specs=in_specs + [in_specs[2], start_spec],
        out_specs=in_specs,
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), _F32)],
        compiler_params=_params(), interpret=interpret,
        name="delta_rule_bwd")


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule_kernels(q, k, v, g, beta, h, chunk):
    return _fwd_call((q, k, v, g, beta), h, chunk, False, _interpret())[0]


def _rule_kernels_fwd(q, k, v, g, beta, h, chunk):
    args = (q, k, v, g, beta)
    o, starts = (checkpoint_name(t, RULED_NAME)
                 for t in _fwd_call(args, h, chunk, True, _interpret()))
    return o, (args, starts)


def _rule_kernels_bwd(h, chunk, kept, do):
    # (the caller's scope is on the forward's ops; the backward names its own)
    with jax.named_scope("delta_rule"):
        return tuple(_bwd_call(*kept, do, h, chunk, _interpret()))


_rule_kernels.defvjp(_rule_kernels_fwd, _rule_kernels_bwd)


def _rule_pallas(q, k, v, g, beta, h, chunk):
    """The lanes form as the kernels take it: t padded to whole chunks,
    beta (b, t, h) a column a head."""
    t = q.shape[1]
    pad = -t % chunk
    with jax.named_scope("delta_rule"):
        def whole(x):
            return jnp.pad(x, [(0, 0), (0, pad), (0, 0)]) if pad else x

        o = _rule_kernels(whole(q), whole(k), whole(v), whole(g),
                          jnp.swapaxes(whole(beta), 1, 2)[..., None], h, chunk)
        return o[:, :t]


def _on_kernels(dk, dv, chunk):
    """Whether a call of these head widths and this chunk runs the kernel
    pair here; counts the call under the schedule it takes."""
    kernels = bool(_kernels_run_here()) and _kernel_takes(dk, dv, chunk)
    _CALLS.inc(path="pallas" if kernels else "xla")
    return kernels


def gated_delta_rule_lanes(q, k, v, g, beta, heads, chunk=64):
    """`gated_delta_rule` on the kernels' own form: q, k, g (b, t, h d_k)
    and v (b, t, h d_v), a head's channels a block of the last dimension;
    beta (b, t, h) -> o (b, t, h d_v) in v's type. Where the kernels run
    nothing is moved but beta; elsewhere the heads are split off for the
    XLA form."""
    b, t, _ = q.shape
    if _on_kernels(q.shape[-1] // heads, v.shape[-1] // heads, chunk):
        return _rule_pallas(q, k, v, g, beta, heads, chunk)
    q, k, v, g = (x.reshape(b, t, heads, -1) for x in (q, k, v, g))
    return _rule_xla(q, k, v, g, beta, chunk).reshape(b, t, -1)


def gated_delta_rule(q, k, v, g, beta, chunk=64):
    """q, k (b, t, h, d_k); v (b, t, h, d_v); g (b, t, h, d_k) the log of
    the decay, <= 0; beta (b, t, h) -> o (b, t, h, d_v) in v's type.

    t is padded on the right to a multiple of ``chunk`` with positions
    that neither decay nor write (g = 0, beta = 0) and the pad cut off.
    Where the kernels run the heads are merged into the lanes form for
    them and split off again; a caller that can make its operands in that
    form calls `gated_delta_rule_lanes` and moves nothing."""
    b, t, h, _ = q.shape
    if not _on_kernels(q.shape[-1], v.shape[-1], chunk):
        return _rule_xla(q, k, v, g, beta, chunk)
    q, k, v, g = (x.reshape(b, t, -1) for x in (q, k, v, g))
    return _rule_pallas(q, k, v, g, beta, h, chunk).reshape(b, t, h, -1)
