"""The gated delta rule with a decay per channel (Kimi Delta Attention,
arXiv:2510.26692, on the delta rule of arXiv:2406.06484 / 2412.06464) in
its chunked WY form, differentiable, in XLA ops. NEW capability: no
linear-attention mixer in the reference framework.

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

A, P, W and U do not read S: they are computed for all chunks at once
(batched matmuls and one batched forward substitution,
`lax.linalg.triangular_solve`, which is backward stable where a product
form of (I + A)^-1 is not: b reaches 2, so A's entries reach 2). What
reads S runs as one `lax.scan` over the chunks that carries it.

**exp(G_i - G_j) is never split into exp(G_i) exp(-G_j).** Its exponent is
<= 0 wherever it is used, but -G_j alone passes 88 once a channel's
cumulated decay does (A = 16 and a softplus near 4: two positions), and
float32 ends there. A and P are therefore computed in sub-blocks of
SUB = 16 rows: a block of rows I against the columns before it takes both
sides relative to G at I's first row, r: exp(G_i - G_r) and exp(G_r - G_j)
are both <= 1 for j < r <= i, and where either is flushed to zero so is
the product; a diagonal sub-block has no such row between i and j and is
computed element by element, (16, 16, d_k) products of exp(G_i - G_j)
masked BEFORE the exp (the published kernels do the same). Nothing is
clamped: a decay that strong simply forgets.

Everything inside is float32 and every matmul runs at full float32
precision, whatever the inputs' type: the products are a hundredth of a
hybrid model's operations, and the forward substitution amplifies what
they round. The gradient is autodiff's of exactly this form; what it keeps
of the states is the one at each chunk's start, (T / C, d_k, d_v) a head,
never one a position. Every op is under the scope `delta_rule`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import telemetry

__all__ = ["gated_delta_rule"]

_CALLS = telemetry.counter(
    "mxtpu_delta_rule_total",
    "Gated delta rules traced, by path (one is there: the chunked WY form "
    "in XLA ops).", ("path",))

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


def gated_delta_rule(q, k, v, g, beta, chunk=64):
    """q, k (b, t, h, d_k); v (b, t, h, d_v); g (b, t, h, d_k) the log of
    the decay, <= 0; beta (b, t, h) -> o (b, t, h, d_v) in v's type.

    t is padded on the right to a multiple of ``chunk`` with positions
    that neither decay nor write (g = 0, beta = 0) and the pad cut off."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    _CALLS.inc(path="xla")
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
