"""Learned sparse attention: a lightning indexer picks the keys a query
attends to (DeepSeek-V3.2-Exp's sparse attention, arXiv:2512.02556, here on
grouped-query heads), and softmax attention runs over the picked keys only.

    I[t, s] = sum_j w[t, j] relu(qI_j[t] . kI[s])          s <= t, float32
    S_t     = the min(topk, t + 1) keys s <= t of largest I[t, s]; ties to
              the lower s
    a_h[t, s] = softmax over s in S_t of q_h[t] . k_g[s] * scale
    o_h[t]  = sum over S_t of a_h[t, s] v_g[s]             g = h // (H / G)
    KL_t    = KL( stop_gradient(mean_h a_h[t, .]) || softmax over S_t of
              I[t, .] )                            the indexer's own loss

`sparse_attention` returns (o, mean_t KL_t). The choice S_t is not
differentiable: o has gradients into q, k, v alone (at the chosen keys) and
the KL into qI, kI, w alone (its target is stopped), as the source trains
its indexer.

**How it is computed.** The selection is EXACT: a row's k-th largest score
is found by bisection on the float32 bits (32 counts a row, no sort, no
`approx_max_k`), ties at that value by the lowest positions. Everything runs
in STRIPS of `block_q` queries (`lax.scan`): the strip's index scores
(block_q, S) are the largest thing that exists (33.5 MB at 512 x 16 384),
never (S, S). A first scan finds every row's threshold (`topk_select`); a
second computes the strip's scores again (the same kernel on the same
operands: the same bits), and attends over `I >= threshold` with the mask a
strip wide; the backward makes the mask a third time. **The thresholds mean
the same set only against the same bits**: the indexer's three operands and
the thresholds carry the name `sparse_topk` for `jax.checkpoint` policies,
and a recomputed layer saves them (35 MB a layer at 16k), because a layer
run again by XLA may round qI, kI or w differently (a convert pair dropped
in one fusion and kept in another), a key at the threshold then falls out,
and query 0, which has one key, is left with none: NaN. The output and the
softmax statistics carry `sparse_attended`: a layer that saves them too
(136 MB at 16k) runs no attention forward again. On a TPU (or
interpreted, `MXTPU_FLASH_INTERPRET`) the strip's work is five Pallas
kernels — `sparse_index_fwd` / `sparse_index_bwd` (the scores and their
transposes, 16 heads a (block_q, block_k) tile in VMEM),
`sparse_flash_fwd` / `sparse_flash_bwd` (streamed softmax attention under
the mask, a key-value head's tile read once for its query heads; the
backward also hands out the head-averaged probabilities it has passed
through), `sparse_head_mean` (the same average for the forward's KL, once
the softmax statistics are known) — and elsewhere the
same strips in plain `jax.numpy`. Both are masked-dense: every causal
(query, key) pair is scored and the unchosen are masked, so the work is that
of causal attention, not of `topk` keys a query; a kernel that gathers the
chosen rows would do a quarter of it at 16k (ROADMAP 2a).

**The forward kernel's online softmax (PR 49; `_flash_fwd_kernel` has the
argument in full).** What it leans on: a row is never empty over its live
key blocks (S_t holds min(topk, t + 1) >= 1 keys, all at or left of the
diagonal), though it may be empty tile after tile before its first chosen
key. So nothing guards an empty row: `_FLOOR`, the lowest finite float32,
stands for `-inf` in the masked scores and in the running max's start, the
first chosen key's alpha = exp(_FLOOR - real) = 0 wipes what an empty row
gathered, and `o` and `lse` are the sums over the chosen keys alone. The
running max and sum live in every lane of (R, block_q, 128) float32 scratch
(the sum as lane partials, ONE cross-lane reduction a strip call), as
`ops/attention.py`'s `_fa_kernel` keeps them.
Measured on a v5e at the Keye cell's shape (a layer's 32 strip calls
alone; PERF.md section 6, PR 49; the table in docs/PERF_NOTES.md): the
statistics' layout is what paid, 30.8 -> 14.4 ms (in the step's capture
29.8 -> 13.4); the guards, on columns or on lanes, 0.0-0.1 ms; an additive
bias for the select -0.1 ms (not taken: exact only while |score| < 1e22);
a select on P instead of on the scores +0.8 ms and a max over unchosen
keys (not taken); a key block of 1024 in two sub-tiles for this kernel
alone 0.0 ms, whole +1.0, sub-tiles of 256 +0.4 (not taken).

Scopes: `indexer` (index scores, the head mean, the KL), `topk_select`,
`sparse_attention`. Counters: `mxtpu_sparse_attention_total{path}`,
`mxtpu_topk_select_total{path}`.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .. import telemetry
from . import kernel_trace
from .attention import (_interpret, _kernels_run_here, _lanes, _nt, _tn,
                        _mm)

__all__ = ["sparse_attention", "index_scores", "select_thresholds",
           "chosen_strip", "TOPK_NAME", "ATTENDED_NAME"]

#: the names the selection (its operands and its thresholds) and the
#: attention's output and statistics carry (`jax.checkpoint` policies)
TOPK_NAME = "sparse_topk"
ATTENDED_NAME = "sparse_attended"

_ATTENTIONS = telemetry.counter(
    "mxtpu_sparse_attention_total",
    "Sparse attention calls traced, by path (pallas_masked_strips: the "
    "Pallas kernels over a strip's mask; xla_masked_strips: the same strips "
    "in plain jax.numpy).", ("path",))
_SELECTS = telemetry.counter(
    "mxtpu_topk_select_total",
    "Exact top-k selections traced, by path (bisect_bits: a k-th-value "
    "threshold a row by bisection on the float32 bits, ties by position).",
    ("path",))

_INT_MIN = -2 ** 31
_VMEM_LIMIT = 100 << 20


def _block(n, want):
    """The largest of want, want / 2, .. that divides n (n itself below)."""
    while want > 8 and n % want:
        want //= 2
    return want if n % want == 0 else n


def _last_block(strip, block_q, block_k):
    """The last key block a query of strip ``strip`` sees."""
    return ((strip + 1) * block_q - 1) // block_k


# ------------------------------------------------------------ index scores
def _index_kernel(b_ref, qi_ref, ki_ref, w_ref, out_ref, *, block_k):
    from jax.experimental import pallas as pl
    kb = pl.program_id(0)
    block_q = qi_ref.shape[1]

    @pl.when(kb <= _last_block(b_ref[0], block_q, block_k))
    def _():
        ki = ki_ref[...]
        w = w_ref[...]
        acc = jnp.zeros(out_ref.shape, jnp.float32)
        for j in range(qi_ref.shape[0]):
            acc = acc + w[:, j:j + 1] * jnp.maximum(_nt(qi_ref[j], ki), 0.0)
        out_ref[...] = acc


def _index_strip_pallas(strip, qi, ki, w, block_k):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    heads, block_q, di = qi.shape
    seq = ki.shape[0]

    def key_block(kb, b):
        return (jnp.minimum(kb, _last_block(b[0], block_q, block_k)), 0)

    return kernel_trace.pallas_call(
        functools.partial(_index_kernel, block_k=block_k),
        (strip.reshape(1), qi, ki, w),
        out_shape=jax.ShapeDtypeStruct((block_q, seq), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(seq // block_k,),
            in_specs=[
                pl.BlockSpec((heads, block_q, di), lambda kb, b: (0, 0, 0)),
                pl.BlockSpec((block_k, di), key_block),
                pl.BlockSpec((block_q, heads), lambda kb, b: (0, 0))],
            out_specs=pl.BlockSpec((block_q, block_k),
                                   lambda kb, b: (0, kb))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(), name="sparse_index_fwd")


def _index_strip_xla(strip, qi, ki, w, block_k):
    def one(acc, at):
        qi_j, w_j = at
        return acc + w_j[:, None] * jnp.maximum(_nt(qi_j, ki), 0.0), None

    return jax.lax.scan(
        one, jnp.zeros((qi.shape[1], ki.shape[0]), jnp.float32),
        (qi, w.T))[0]


def _index_bwd_kernel(b_ref, di_ref, qi_ref, ki_ref, w_ref, dqi_ref, dw_ref,
                      dki_ref, *, block_k):
    from jax.experimental import pallas as pl
    kb = pl.program_id(0)
    block_q = qi_ref.shape[1]

    @pl.when(kb == 0)
    def _():
        dqi_ref[...] = jnp.zeros_like(dqi_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    live = kb <= _last_block(b_ref[0], block_q, block_k)

    @pl.when(live)
    def _():
        ki = ki_ref[...]
        w = w_ref[...]
        d_i = di_ref[...]
        dki = jnp.zeros(dki_ref.shape, jnp.float32)
        for j in range(qi_ref.shape[0]):
            qi_j = qi_ref[j]
            r = _nt(qi_j, ki)
            dw_ref[:, j:j + 1] += jnp.sum(d_i * jnp.maximum(r, 0.0), -1,
                                          keepdims=True)
            t = jnp.where(r > 0, d_i * w[:, j:j + 1], 0.0).astype(ki.dtype)
            dqi_ref[j] += _mm(t, ki)
            dki = dki + _tn(t, qi_j)
        dki_ref[...] = dki

    @pl.when(jnp.logical_not(live))
    def _():
        dki_ref[...] = jnp.zeros_like(dki_ref)


def _index_bwd_strip_pallas(strip, d_i, qi, ki, w, block_k):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    heads, block_q, di = qi.shape
    seq = ki.shape[0]
    last = functools.partial(_last_block, block_q=block_q, block_k=block_k)
    return kernel_trace.pallas_call(
        functools.partial(_index_bwd_kernel, block_k=block_k),
        (strip.reshape(1), d_i, qi, ki, w),
        out_shape=(jax.ShapeDtypeStruct(qi.shape, jnp.float32),
                   jax.ShapeDtypeStruct(w.shape, jnp.float32),
                   jax.ShapeDtypeStruct(ki.shape, jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(seq // block_k,),
            in_specs=[
                pl.BlockSpec((block_q, block_k), lambda kb, b: (
                    0, jnp.minimum(kb, last(b[0])))),
                pl.BlockSpec((heads, block_q, di), lambda kb, b: (0, 0, 0)),
                pl.BlockSpec((block_k, di), lambda kb, b: (
                    jnp.minimum(kb, last(b[0])), 0)),
                pl.BlockSpec((block_q, heads), lambda kb, b: (0, 0))],
            out_specs=(
                pl.BlockSpec((heads, block_q, di), lambda kb, b: (0, 0, 0)),
                pl.BlockSpec((block_q, heads), lambda kb, b: (0, 0)),
                pl.BlockSpec((block_k, di), lambda kb, b: (kb, 0)))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(), name="sparse_index_bwd")


def _index_bwd_strip_xla(strip, d_i, qi, ki, w, block_k):
    def one(dki, at):
        qi_j, w_j = at
        r = _nt(qi_j, ki)
        t = jnp.where(r > 0, d_i * w_j[:, None], 0.0)
        return dki + _tn(t, qi_j.astype(jnp.float32)), (
            _mm(t, ki.astype(jnp.float32)),
            jnp.sum(d_i * jnp.maximum(r, 0.0), -1))

    dki, (dqi, dw) = jax.lax.scan(
        one, jnp.zeros(ki.shape, jnp.float32), (qi, w.T))
    return dqi, dw.T, dki


# --------------------------------------------------------------- selection
def _sortable(x):
    """float32 -> int32 whose signed order is the floats' (-0.0 as +0.0)."""
    bits = jax.lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.int32)
    return jnp.where(bits < 0, bits ^ 0x7fffffff, bits)


def _unsortable(key):
    return jax.lax.bitcast_convert_type(
        jnp.where(key < 0, key ^ 0x7fffffff, key), jnp.float32)


def _select_strip(strip, scores, topk):
    """scores (block_q, S) float32, whatever right of the diagonal ->
    (tau (block_q,) float32, cut (block_q,) int32): row t attends to s <= t
    with I > tau, or I == tau and s <= cut."""
    block_q, seq = scores.shape
    t = strip * block_q + jnp.arange(block_q, dtype=jnp.int32)
    s = jnp.arange(seq, dtype=jnp.int32)
    seen = s[None, :] <= t[:, None]
    key = jnp.where(seen, _sortable(scores), _INT_MIN)
    want = jnp.minimum(topk, t + 1)

    def count(hit):
        return jnp.sum(hit, -1, dtype=jnp.int32)

    def bit(i, tau):
        # in unsigned order: the largest value at least `want` keys reach
        cand = tau | jnp.left_shift(jnp.int32(1), 31 - i)
        enough = count(key >= (cand ^ _INT_MIN)[:, None]) >= want
        return jnp.where(enough, cand, tau)

    tau = jax.lax.fori_loop(0, 32, bit, jnp.zeros((block_q,), jnp.int32)) \
        ^ _INT_MIN
    at_tau = seen & (key == tau[:, None])
    # of the keys AT the threshold the first `need` positions
    need = want - count(key > tau[:, None])

    def by_position(_):
        rank = jnp.cumsum(at_tau, -1, dtype=jnp.int32)
        return jnp.max(jnp.where(at_tau & (rank <= need[:, None]),
                                 s[None, :], -1), -1)

    cut = jax.lax.cond(jnp.any(count(at_tau) > need), by_position,
                       lambda _: jnp.full((block_q,), seq, jnp.int32), None)
    return _unsortable(tau), cut


def _mask_strip(strip, scores, tau, cut):
    block_q, seq = scores.shape
    t = strip * block_q + jnp.arange(block_q, dtype=jnp.int32)
    s = jnp.arange(seq, dtype=jnp.int32)
    return (s[None, :] <= t[:, None]) & (
        (scores > tau[:, None])
        | ((scores == tau[:, None]) & (s[None, :] <= cut[:, None])))


# ------------------------------------------------------ attention, a strip
#: what a masked score reads and what a row's running max starts from: the
#: lowest FINITE float32. No real score is below it, so a maximum taken over
#: masked and chosen scores is the chosen ones' as soon as there is one, and
#: `exp(x - max)` never sees `-inf - -inf`.
_FLOOR = float(jnp.finfo(jnp.float32).min)


def _flash_fwd_kernel(b_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                      m_s, l_s, acc_s, *, block_k, scale):
    """One (key-value head, key block) program of a strip: the online
    softmax of the head's R query heads over the tile's CHOSEN keys, the
    key and value tile read once for all R.

    **A tile does its own work and no more** (PR 49; `ops/attention.py`
    `_fa_kernel` is the model, PR 47). The running max and sum live in every
    lane of (R, block_q, lanes) float32 scratch, not in (block_q, 1)
    columns: the max is the row's in every lane, the sum a lane's PARTIAL
    sum (adds of whole vregs rescaled by the row's alpha); the one
    cross-lane sum a strip call is the last key block's. That is what paid:
    29.8 -> 13.4 ms a layer in the Keye step on a v5e, 86 % of the MXU's
    floor over the scored pairs (PERF.md section 6, PR 49).

    **No guard of an empty row.** A row CAN be empty in its first tiles (a
    query past `topk` need not have chosen a key of block 0), but never
    over all its live key blocks: `select_thresholds` gives every row
    min(topk, t + 1) >= 1 keys at or left of its diagonal. So a masked
    score reads `_FLOOR`, finite, and the max starts there. While a row has
    met no chosen key its max stays `_FLOOR`, every p is exp(0) = 1 and the
    row gathers finite sums of no meaning; its first chosen key makes the
    max real and alpha = exp(_FLOOR - real) = 0 EXACTLY, which wipes them;
    from then on a masked score is exp(_FLOOR - real) = 0 without a second
    select. What a row ends with is the sum over its chosen keys alone, and
    l >= 1 (the row's largest chosen score reads exp(0)). The mask's
    compare is made once a tile for the R heads; each head pays one select.

    The R heads are R independent bodies a grid step (Mosaic puts one
    head's Q K^T under the last one's softmax on its own), traced once and
    unrolled where the kernel is lowered. Scores, statistics and the
    accumulator are float32; P goes to the MXU in v's type, as the
    backward's does; `lse` = max + log(sum), which `sparse_head_mean` and
    `sparse_flash_bwd` read.
    """
    from jax.experimental import pallas as pl
    kb = pl.program_id(1)
    heads, block_q = q_ref.shape[1], q_ref.shape[2]
    lanes = m_s.shape[-1]

    @pl.when(kb == 0)
    def _():
        m_s[...] = jnp.full_like(m_s, _FLOOR)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(kb <= _last_block(b_ref[0], block_q, block_k))
    def _():
        k, v = k_ref[0], v_ref[0]
        chosen = mask_ref[...].astype(jnp.float32) > 0

        def head(r, carry):
            s = jnp.where(chosen, _nt(q_ref[0, r], k) * scale, _FLOOR)
            m = m_s[r]
            m_new = jnp.maximum(m, jnp.max(s, -1, keepdims=True))
            p = jnp.exp(s - _lanes(m_new, block_k))
            alpha = jnp.exp(m - m_new)
            m_s[r] = m_new
            l_s[r] = l_s[r] * alpha + functools.reduce(
                jnp.add, [p[:, i:i + lanes] for i in range(0, block_k, lanes)])
            acc_s[r] = acc_s[r] * _lanes(alpha, acc_s.shape[-1]) \
                + _mm(p.astype(v.dtype), v)
            return carry

        jax.lax.fori_loop(0, heads, head, 0, unroll=True)

    @pl.when(kb == pl.num_programs(1) - 1)
    def _():
        l = jnp.sum(l_s[...], -1, keepdims=True)
        o_ref[0] = (acc_s[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_s[..., :1] + jnp.log(l)


def _flash_fwd_strip_pallas(strip, q, k, v, mask, block_k, scale):
    """q (G, R, block_q, D), k, v (G, S, D), mask (block_q, S) int8 ->
    o like q, lse (G, R, block_q) float32."""
    return _flash_fwd_call(strip, q, k, v, mask, block_k, scale,
                           _interpret())


@kernel_trace.traced_once("block_k", "scale", "interpret")
def _flash_fwd_call(strip, q, k, v, mask, block_k, scale, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    groups, heads, block_q, d = q.shape
    seq = k.shape[1]
    last = functools.partial(_last_block, block_q=block_q, block_k=block_k)
    # the statistics' lanes: a vreg's 128 wherever the key block is whole
    # vregs (the tests' small blocks: the block itself)
    lanes = math.gcd(block_k, 128)

    def kv_block(g, kb, b):
        return (g, jnp.minimum(kb, last(b[0])), 0)

    def own(g, kb, b):
        return (g, 0, 0, 0)

    o, lse = kernel_trace.pallas_call(
        functools.partial(_flash_fwd_kernel, block_k=block_k, scale=scale),
        (strip.reshape(1), q, k, v, mask),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((groups, heads, block_q, 1),
                                        jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(groups, seq // block_k),
            in_specs=[
                pl.BlockSpec((1, heads, block_q, d), own),
                pl.BlockSpec((1, block_k, d), kv_block),
                pl.BlockSpec((1, block_k, d), kv_block),
                pl.BlockSpec((block_q, block_k), lambda g, kb, b: (
                    0, jnp.minimum(kb, last(b[0]))))],
            out_specs=(pl.BlockSpec((1, heads, block_q, d), own),
                       pl.BlockSpec((1, heads, block_q, 1), own)),
            scratch_shapes=[pltpu.VMEM((heads, block_q, lanes), jnp.float32),
                            pltpu.VMEM((heads, block_q, lanes), jnp.float32),
                            pltpu.VMEM((heads, block_q, d), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name="sparse_flash_fwd")
    return o, lse[..., 0]


def _scores_xla(q, k, mask, scale):
    s = jnp.einsum("grtd,gsd->grts", q, k,
                   preferred_element_type=jnp.float32) * scale
    return jnp.where(mask > 0, s, -jnp.inf)


def _flash_fwd_strip_xla(strip, q, k, v, mask, block_k, scale):
    s = _scores_xla(q, k, mask, scale)
    lse = jax.nn.logsumexp(s, -1)
    p = jnp.exp(s - lse[..., None])
    o = jnp.einsum("grts,gsd->grtd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype), lse


def _flash_bwd_kernel(b_ref, q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                      mask_ref, dq_ref, dk_ref, dv_ref, p_ref, *, block_k,
                      scale, n_heads):
    from jax.experimental import pallas as pl
    kb, g = pl.program_id(0), pl.program_id(1)
    heads, block_q = q_ref.shape[1], q_ref.shape[2]

    @pl.when((kb == 0) & (g == 0))
    def _():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    @pl.when(g == 0)
    def _():
        p_ref[...] = jnp.zeros_like(p_ref)

    live = kb <= _last_block(b_ref[0], block_q, block_k)

    @pl.when(live)
    def _():
        k, v = k_ref[0], v_ref[0]
        chosen = mask_ref[...].astype(jnp.float32) > 0
        dk = jnp.zeros(dk_ref.shape[1:], jnp.float32)
        dv = jnp.zeros(dv_ref.shape[1:], jnp.float32)
        total = jnp.zeros(p_ref.shape, jnp.float32)
        for r in range(heads):
            q, do = q_ref[g, r], do_ref[g, r]
            s = _nt(q, k) * scale
            p = jnp.where(chosen, jnp.exp(s - lse_ref[g, r]), 0.0)
            total = total + p
            ds = (p * (_nt(do, v) - delta_ref[g, r]) * scale).astype(k.dtype)
            dq_ref[g, r] += _mm(ds, k)
            dk = dk + _tn(ds, q)
            dv = dv + _tn(p.astype(do.dtype), do)
        dk_ref[0] = dk
        dv_ref[0] = dv
        p_ref[...] += total * (1.0 / n_heads)

    @pl.when(jnp.logical_not(live))
    def _():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)


def _flash_bwd_strip_pallas(strip, q, k, v, mask, lse, delta, do, block_k,
                            scale):
    """-> dq like q, dk, dv like k, all float32: this strip's part; and the
    head-averaged probabilities (block_q, S) float32, which the backward of
    the KL needs and this kernel has computed a head at a time anyway. Key
    blocks outermost and the key-value heads inside, so that a tile of the
    mean sums its heads in place; the queries' side (q, dO, the statistics,
    dq) stays in VMEM for the whole call."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    groups, heads, block_q, d = q.shape
    seq = k.shape[1]
    last = functools.partial(_last_block, block_q=block_q, block_k=block_k)

    def kv_block(kb, g, b):
        return (g, jnp.minimum(kb, last(b[0])), 0)

    def whole(kb, g, b):
        return (0, 0, 0, 0)

    rows = pl.BlockSpec((groups, heads, block_q, d), whole)
    column = pl.BlockSpec((groups, heads, block_q, 1), whole)
    return kernel_trace.pallas_call(
        functools.partial(_flash_bwd_kernel, block_k=block_k, scale=scale,
                          n_heads=groups * heads),
        (strip.reshape(1), q, do, lse[..., None], delta[..., None], k, v,
         mask),
        out_shape=(jax.ShapeDtypeStruct(q.shape, jnp.float32),
                   jax.ShapeDtypeStruct(k.shape, jnp.float32),
                   jax.ShapeDtypeStruct(v.shape, jnp.float32),
                   jax.ShapeDtypeStruct((block_q, seq), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(seq // block_k, groups),
            in_specs=[
                rows, rows, column, column,
                pl.BlockSpec((1, block_k, d), kv_block),
                pl.BlockSpec((1, block_k, d), kv_block),
                pl.BlockSpec((block_q, block_k), lambda kb, g, b: (
                    0, jnp.minimum(kb, last(b[0]))))],
            out_specs=(rows,
                       pl.BlockSpec((1, block_k, d),
                                    lambda kb, g, b: (g, kb, 0)),
                       pl.BlockSpec((1, block_k, d),
                                    lambda kb, g, b: (g, kb, 0)),
                       pl.BlockSpec((block_q, block_k),
                                    lambda kb, g, b: (0, kb)))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(), name="sparse_flash_bwd")


def _flash_bwd_strip_xla(strip, q, k, v, mask, lse, delta, do, block_k,
                         scale):
    p = jnp.exp(_scores_xla(q, k, mask, scale) - lse[..., None])
    dp = jnp.einsum("grtd,gsd->grts", do, v,
                    preferred_element_type=jnp.float32)
    ds = p * (dp - delta[..., None]) * scale
    f32 = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)
    return (f32("grts,gsd->grtd", ds, k.astype(jnp.float32)),
            f32("grts,grtd->gsd", ds, q.astype(jnp.float32)),
            f32("grts,grtd->gsd", p, do.astype(jnp.float32)),
            p.mean((0, 1)))


def _head_mean_kernel(b_ref, q_ref, k_ref, lse_ref, mask_ref, p_ref, *,
                      block_k, scale, n_heads):
    from jax.experimental import pallas as pl
    kb, g = pl.program_id(0), pl.program_id(1)
    heads, block_q = q_ref.shape[1], q_ref.shape[2]

    @pl.when(g == 0)
    def _():
        p_ref[...] = jnp.zeros_like(p_ref)

    @pl.when(kb <= _last_block(b_ref[0], block_q, block_k))
    def _():
        k = k_ref[0]
        chosen = mask_ref[...].astype(jnp.float32) > 0
        total = jnp.zeros(p_ref.shape, jnp.float32)
        for r in range(heads):
            s = _nt(q_ref[0, r], k) * scale
            total = total + jnp.where(chosen, jnp.exp(s - lse_ref[0, r]),
                                      0.0)
        p_ref[...] += total * (1.0 / n_heads)


def _head_mean_strip_pallas(strip, q, k, mask, lse, block_k, scale):
    """-> (block_q, S) float32: the mean over all heads of a_h[t, s]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    groups, heads, block_q, d = q.shape
    seq = k.shape[1]
    last = functools.partial(_last_block, block_q=block_q, block_k=block_k)

    def own(kb, g, b):
        return (g, 0, 0, 0)

    def tile(kb, g, b):
        return (0, jnp.minimum(kb, last(b[0])))

    return kernel_trace.pallas_call(
        functools.partial(_head_mean_kernel, block_k=block_k, scale=scale,
                          n_heads=groups * heads),
        (strip.reshape(1), q, k, lse[..., None], mask),
        out_shape=jax.ShapeDtypeStruct((block_q, seq), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(seq // block_k, groups),
            in_specs=[
                pl.BlockSpec((1, heads, block_q, d), own),
                pl.BlockSpec((1, block_k, d), lambda kb, g, b: (
                    g, jnp.minimum(kb, last(b[0])), 0)),
                pl.BlockSpec((1, heads, block_q, 1), own),
                pl.BlockSpec((block_q, block_k), tile)],
            out_specs=pl.BlockSpec((block_q, block_k),
                                   lambda kb, g, b: (0, kb))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(), name="sparse_head_mean")


def _head_mean_strip_xla(strip, q, k, mask, lse, block_k, scale):
    p = jnp.exp(_scores_xla(q, k, mask, scale) - lse[..., None])
    return p.mean((0, 1))


_PALLAS = {"index": _index_strip_pallas, "index_bwd": _index_bwd_strip_pallas,
           "fwd": _flash_fwd_strip_pallas, "bwd": _flash_bwd_strip_pallas,
           "head_mean": _head_mean_strip_pallas}
_XLA = {"index": _index_strip_xla, "index_bwd": _index_bwd_strip_xla,
        "fwd": _flash_fwd_strip_xla, "bwd": _flash_bwd_strip_xla,
        "head_mean": _head_mean_strip_xla}


def _strips_of():
    return ("pallas_masked_strips", _PALLAS) if _kernels_run_here() \
        else ("xla_masked_strips", _XLA)


# --------------------------------------------------------- one sequence
def _by_strip(x, block_q, axis=0):
    """(.., S, ..) -> (S / block_q, .., block_q, ..): strips leading."""
    n = x.shape[axis] // block_q
    x = x.reshape(x.shape[:axis] + (n, block_q) + x.shape[axis + 1:])
    return jnp.moveaxis(x, axis, 0)


def _from_strips(x, axis=0):
    """The inverse of `_by_strip` (axis: where S goes)."""
    x = jnp.moveaxis(x, 0, axis)
    return x.reshape(x.shape[:axis] + (-1,) + x.shape[axis + 2:])


def _strip_ids(n):
    return jnp.arange(n, dtype=jnp.int32)


def _select_seq(qi, ki, w, topk, block_q, block_k):
    """qi (J, S, Di), ki (S, Di), w (S, J) -> tau (S,), cut (S,)."""
    _, strips = _strips_of()

    def one(_, at):
        strip, qi_s, w_s = at
        with jax.named_scope("indexer"):
            scores = strips["index"](strip, qi_s, ki, w_s, block_k)
        with jax.named_scope("topk_select"):
            return None, _select_strip(strip, scores, topk)

    n = ki.shape[0] // block_q
    tau, cut = jax.lax.scan(one, None, (
        _strip_ids(n), _by_strip(qi, block_q, 1), _by_strip(w, block_q)))[1]
    return tau.reshape(-1), cut.reshape(-1)


def _kl_strip(p, scores, mask):
    """-> (the strip's sum of KL_t, pi (block_q, S)): p the target, pi the
    indexer's softmax over the chosen keys."""
    logits = jnp.where(mask, scores, -jnp.inf)
    log_pi = logits - jax.nn.logsumexp(logits, -1, keepdims=True)
    kl = jnp.where(p > 0, p * (jnp.log(jnp.where(p > 0, p, 1.0))
                               - jnp.where(mask, log_pi, 0.0)), 0.0)
    return kl.sum(), jnp.exp(log_pi)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _attend_seq(block_q, block_k, scale, q, k, v, qi, ki, w, tau, cut):
    """q (G, R, S, D), k, v (G, S, D), qi (J, S, Di), ki (S, Di), w (S, J),
    tau, cut (S,) -> o like q, kl () float32: the mean over t of KL_t."""
    return _attend_fwd(block_q, block_k, scale, q, k, v, qi, ki, w, tau,
                       cut)[0]


def _strip_mask(strips, strip, qi_s, ki, w_s, tau_s, cut_s, block_k):
    with jax.named_scope("indexer"):
        scores = strips["index"](strip, qi_s, ki, w_s, block_k)
    with jax.named_scope("topk_select"):
        mask = _mask_strip(strip, scores, tau_s, cut_s)
    return scores, mask


def _attend_fwd(block_q, block_k, scale, q, k, v, qi, ki, w, tau, cut):
    _, strips = _strips_of()

    def one(_, at):
        strip, q_s, qi_s, w_s, tau_s, cut_s = at
        scores, mask = _strip_mask(strips, strip, qi_s, ki, w_s, tau_s,
                                   cut_s, block_k)
        small = mask.astype(jnp.int8)
        with jax.named_scope("sparse_attention"):
            o_s, lse_s = strips["fwd"](strip, q_s, k, v, small, block_k,
                                       scale)
        with jax.named_scope("indexer"):
            p = strips["head_mean"](strip, q_s, k, small, lse_s, block_k,
                                    scale)
            kl = _kl_strip(p, scores, mask)[0]
        return None, (o_s, lse_s, kl)

    n = ki.shape[0] // block_q
    o, lse, kl = jax.lax.scan(one, None, (
        _strip_ids(n), _by_strip(q, block_q, 2), _by_strip(qi, block_q, 1),
        _by_strip(w, block_q), tau.reshape(n, -1), cut.reshape(n, -1)))[1]
    o = checkpoint_name(_from_strips(o, 2), ATTENDED_NAME)
    lse = checkpoint_name(_from_strips(lse, 2), ATTENDED_NAME)
    return (o, kl.sum() / ki.shape[0]), (q, k, v, qi, ki, w, tau, cut, o,
                                         lse)


def _attend_bwd(block_q, block_k, scale, res, cts):
    q, k, v, qi, ki, w, tau, cut, o, lse = res
    do, dkl = cts
    _, strips = _strips_of()
    seq = ki.shape[0]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)

    def one(carry, at):
        dk, dv, dki = carry
        strip, q_s, qi_s, w_s, tau_s, cut_s, lse_s, delta_s, do_s = at
        scores, mask = _strip_mask(strips, strip, qi_s, ki, w_s, tau_s,
                                   cut_s, block_k)
        small = mask.astype(jnp.int8)
        with jax.named_scope("sparse_attention"):
            dq_s, dk_s, dv_s, p = strips["bwd"](
                strip, q_s, k, v, small, lse_s, delta_s, do_s, block_k, scale)
        with jax.named_scope("indexer"):
            # d KL_t / d I[t, s] = pi - p on the chosen keys
            d_i = jnp.where(mask, _kl_strip(p, scores, mask)[1] - p,
                            0.0) * (dkl / seq)
            dqi_s, dw_s, dki_s = strips["index_bwd"](
                strip, d_i, qi_s, ki, w_s, block_k)
        return (dk + dk_s, dv + dv_s, dki + dki_s), (dq_s, dqi_s, dw_s)

    n = seq // block_q
    zeros = functools.partial(jnp.zeros, dtype=jnp.float32)
    (dk, dv, dki), (dq, dqi, dw) = jax.lax.scan(
        one, (zeros(k.shape), zeros(v.shape), zeros(ki.shape)),
        (_strip_ids(n), _by_strip(q, block_q, 2), _by_strip(qi, block_q, 1),
         _by_strip(w, block_q), tau.reshape(n, -1), cut.reshape(n, -1),
         _by_strip(lse, block_q, 2), _by_strip(delta, block_q, 2),
         _by_strip(do, block_q, 2)))
    return (_from_strips(dq, 2).astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype), _from_strips(dqi, 1).astype(qi.dtype),
            dki.astype(ki.dtype), _from_strips(dw).astype(w.dtype),
            jnp.zeros_like(tau), None)


_attend_seq.defvjp(_attend_fwd, _attend_bwd)


# ------------------------------------------------------------------ public
def _blocks(seq, block_q, block_k):
    return _block(seq, block_q or 512), _block(seq, block_k or 512)


def index_scores(qi, ki, w):
    """The index scores of ONE sequence, whole: qi (S, J, Di), ki (S, Di),
    w (S, J) -> (S, S) float32, -inf right of the diagonal. For tests and
    probes at small S; the op never holds this."""
    seq = ki.shape[0]
    scores = _index_strip_xla(jnp.int32(0), jnp.moveaxis(qi, 1, 0), ki,
                              w.astype(jnp.float32), seq)
    return jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), scores, -jnp.inf)


def select_thresholds(qi, ki, w, topk, block_q=None, block_k=None):
    """qi (B, S, J, Di), ki (B, S, Di), w (B, S, J) -> (tau (B, S) float32,
    cut (B, S) int32): query t attends to the keys s <= t with
    I[t, s] > tau[t], or I[t, s] == tau[t] and s <= cut[t]; exactly
    min(topk, t + 1) of them. No gradient."""
    block_q, block_k = _blocks(ki.shape[1], block_q, block_k)
    _SELECTS.inc(path="bisect_bits")
    qi, ki, w = (jax.lax.stop_gradient(x) for x in (qi, ki, w))
    tau, cut = jax.lax.map(
        lambda a: _select_seq(jnp.moveaxis(a[0], 1, 0), a[1],
                              a[2].astype(jnp.float32), topk, block_q,
                              block_k), (qi, ki, w))
    return checkpoint_name(tau, TOPK_NAME), checkpoint_name(cut, TOPK_NAME)


def chosen_strip(qi, ki, w, tau, cut, strip, block_q=None, block_k=None):
    """The mask of S_t for the queries of strip ``strip`` of ONE sequence,
    as the op makes it: qi (S, J, Di), ki (S, Di), w (S, J), tau, cut (S,)
    from `select_thresholds` -> (block_q, S) bool. For tests and probes."""
    block_q, block_k = _blocks(ki.shape[0], block_q, block_k)
    strip = jnp.asarray(strip, jnp.int32)

    def rows(x):
        return jax.lax.dynamic_slice_in_dim(x, strip * block_q, block_q, 0)

    return _strip_mask(_strips_of()[1], strip,
                       jnp.moveaxis(rows(qi), 1, 0), ki,
                       rows(w).astype(jnp.float32), rows(tau), rows(cut),
                       block_k)[1]


def sparse_attention(q, k, v, qi, ki, w, topk, scale=None, block_q=None,
                     block_k=None):
    """q (B, S, H, D), k, v (B, S, G, D) with G dividing H (query head h
    reads key-value head h // (H / G)), the indexer's qi (B, S, J, Di),
    ki (B, S, Di) and w (B, S, J) -> (o (B, S, H, D) in q's type,
    kl (B,) float32: the mean over a sequence's queries of the indexer's
    KL). See the module's docstring."""
    b, seq, h, d = q.shape
    g = k.shape[2]
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    block_q, block_k = _blocks(seq, block_q, block_k)
    # selected on, masked by and differentiated at the same bits
    qi, ki, w = (checkpoint_name(x, TOPK_NAME)
                 for x in (qi, ki, w.astype(jnp.float32)))
    tau, cut = select_thresholds(qi, ki, w, topk, block_q, block_k)
    _ATTENTIONS.inc(path=_strips_of()[0])

    def one(a):
        q, k, v, qi, ki, w, tau, cut = a
        o, kl = _attend_seq(
            block_q, block_k, scale,
            jnp.moveaxis(q, 0, 1).reshape(g, h // g, seq, d),
            jnp.moveaxis(k, 0, 1), jnp.moveaxis(v, 0, 1),
            jnp.moveaxis(qi, 1, 0), ki, w, tau, cut)
        return jnp.moveaxis(o.reshape(h, seq, d), 0, 1), kl

    return jax.lax.map(one, (q, k, v, qi, ki, w, tau, cut))
