"""Fused (flash) attention Pallas kernels for TPU — forward AND backward.

The hot exception to "let XLA fuse" (SURVEY §7 table): attention's softmax
forces an HBM round-trip of the (S, S) score matrix under plain XLA. Two
kernel families keep it in VMEM, chosen by attention_route() from the
shape alone:

* **streamed** (flash_fwd, flash_bwd_dkvq): Q blocks against K/V blocks
  streamed by the grid, with an online-softmax carry in the forward. ONE
  backward kernel visits each live block pair once: it recomputes the
  probabilities from the saved log-sum-exp (FlashAttention-2) and
  accumulates dK/dV across the inner grid steps and dQ into a float32
  (S, D) slab of the (batch*head) that stays in VMEM until the head
  changes: five matmuls a pair. The forward's VMEM use is O(block^2 +
  block*D); the backward's adds the slab, 2 * S * roundup(D, 128) * 4
  bytes with its second buffer, and while that is within _DQ_SLAB_BYTES
  (every S <= 65 536 at D <= 128) the backward is one call. Past it the
  same kernel is called once a q-segment whose slab fits and XLA sums the
  dK/dV partials, so the length stays bounded by HBM, not VMEM. The
  budgets assume a v5e-class VMEM (_V5E_VMEM_BYTES, 128 MiB a core: the
  backward asks for a scoped limit of the slab + 24 MiB, the old pair
  needed only the default); a chip with less refuses at compile time.
  Takes D % 128 == 0, or S >= 2048. The kernels know dense, causal and,
  with ``window=``, a causal sliding window (key j seen from query i iff
  0 <= i - j < window): the same two bodies under the names
  flash_window_fwd / flash_window_bwd, so a capture tells them from the
  causal calls of the same program. **The value has a width of its own:**
  v, O, dO and dV are D_v = v.shape[-1] wide (their blocks, the forward's
  accumulator, delta = sum dO O), q, k, dQ, dK and the dQ slab stay D, the
  scale stays 1 / sqrt(D) of q; at D_v = D the traced calls are the ones
  they always were, text for text. A differential pair's two maps each
  meet [v_1; v_2] (D 64, D_v 128) in ONE call this way, not two. The
  tiles' VMEM was sized at D = 128: D_v = 256 compiles for a v5e in
  bfloat16 and, at blocks of 1024, not in float32 (Mosaic refuses it, as
  it refuses equal heads of 256). **A q.k width that is no whole lane
  tile** (D = 192 | D_v = 128, latent attention's 128 beside a rotary 64;
  compiled for a described v5e at (1, 32, 8192), PR 48,
  tests/perfbench/test_ling3_compile_tpu.py): bfloat16 compiles at blocks
  of 1024 and 512; float32 under `precision=HIGHEST` at 512 and 256 and is
  refused at 1024 (the forward's scoped VMEM, as at D_v = 256): a float32
  comparison of such a layer sets MXTPU_FLASH_BLOCK_Q/K=512. The operands
  go in as they are: the 192 columns lie in 256 lanes in HBM and VMEM and
  the MXU runs the half-empty second tile as a whole pass, which padding
  with zeros outside the call cannot save (timings below). The dQ slab's
  256 padded lanes are 2 * 8192 * 256 * 4 = 16.8 MB of _DQ_SLAB_BYTES: one
  backward call up to S = 32 768 where D <= 128 has 65 536
  (`_slab_bytes` rounds up, so `_dq_segments` knows). _BWD_TILE_BYTES
  still knows neither the operands' type nor the matmul precision: 24 MiB
  held the bf16 tiles at D = 192 as at 128. Under a window the grid's last axis
  counts the blocks of a row's BAND instead of all of them (at S = 16k,
  window 512, blocks of 512: 63 live pairs a head where the causal grid
  visits 528), the index maps are clamped from both sides so a step past
  the band moves nothing, and every live pair is masked. **Which rows can
  be empty:** in the causal and dense forward none (key 0 is in the first
  tile of every q-block and every query sees it), so `_fa_kernel` masks
  only the tiles the diagonal crosses (two bodies chosen from the program
  ids: *interior* and *diagonal*) and guards no row; under a window a
  row's first live tile may hold none of its keys, so `_fa_window_kernel`
  alone keeps the -inf guards of the online softmax (the body both had up
  to PR 46, value for value).
* **short** (flash_short_fwd, flash_short_bwd): narrow heads that tile 128
  lanes (D of 32 or 64, whole blocks of H*D) below S = 2048 whose whole
  (S, S) float32 tile fits VMEM and is worth a visit (_SHORT_MIN_S <= S <=
  _SHORT_MAX_S). One visit per head: no carry, no rescale, no second
  recompute; one backward kernel of five matmuls; operands in the input
  type, float32 accumulation. Reads and writes the projections' (B, S, H*D)
  layout in 128-lane blocks of whole heads: no transposes, no padded lanes.

Neither family ever writes an (S, S) tensor to HBM. Everything else (cross
attention, lengths no block divides, a window or a value width of its own
on a shape of the short family, whose lane layout knows one width, any
shape off the TPU) takes the XLA composite, whose einsums carry any D_v.
Used by models.bert MultiHeadAttention (attention='flash') and
models.phi4flash DifferentialAttention. A Mosaic refusal of a routed shape surfaces as the
compile error it is — nothing catches it to degrade. MXTPU_FLASH_INTERPRET=1 runs the kernels in
Pallas interpret mode (CPU tests only; chip_smoke.py refuses to
start with it set). Counters at /metrics, one increment per traced call:
mxtpu_attention_route_total{route} (the backward follows the forward's
route), mxtpu_attention_backward_total{kernel} (a streamed backward:
one call, or segmented; flash_window_bwd under a window),
mxtpu_attention_window_total{route} and
mxtpu_attention_wide_value_total{route} (v wider than k); the gauge
mxtpu_attention_live_block_pairs{kind} holds what the last traced forward
visits a head: "window" and "causal" (what the causal grid would) of a
windowed call, "interior" and "diagonal" of a causal or dense one (120 and
16 at S = 16 384 and blocks of 1024: how often the unmasked body engages).

Why two families (v5e, BERT-large's (16, 16, 512, 64) bf16, attention
alone, forward + backward, a call; PERF.md §6, PR 26): the composite takes
3.09 ms, the streamed kernels with 512-blocks 1.93 ms, the short family
1.11 ms, of which the two kernels are 0.89 ms in the compiled train step.
Why one streamed backward kernel (v5e, (1, 16, 16384, 128) bf16 causal, a
call with delta and the output casts; PERF.md §6, PR 30): the dK/dV + dQ
pair it replaced ran seven matmuls and the element-wise chain twice, 26.3
ms; one visit takes 18.6, every gradient equal to the pair's to the bit.
Segmented it ran at (1, 4, 131072, 128), two segments: 264.5 ms a call;
forced to two segments at 16k it takes 19.9 ms, dQ equal to the bit.
Why a value width of its own (v5e, (1, 20, 16384, 64) bf16 causal, a
differential layer of the SambaY stage; PERF.md §6, PR 37): a call's time
a head is the same at D = 64 as at D = 128 (half-filled lanes, rows
padded to 128 in HBM), so each softmax map against [v_1; v_2] 128 wide in
ONE call takes 14.2 ms forward and 37.7 with the backward where the two
calls of one width it replaces take 28.3 and 74.6 (window 512: 4.0 / 8.9
for 7.7 / 17.3), the output columns equal to the bit.
Why the forward has two bodies, sub-tiles and lane-kept statistics (v5e,
the kernel alone, ms a call; PERF.md §6, PR 47): (1, 16, 16384, 128) bf16
causal 10.60 -> 7.62, (1, 20, 16384, 64 | 128) 13.37 -> 9.64, EvaByte's
(256, 1, 2048, 128) 4.53 -> 3.45; the windowed call 3.27 both, every
output and gradient equal to the bit.
Why 192 goes in as it is (v5e, (1, 32, 8192, 192 | 128) bf16 causal, the
kernels alone, ms a call forward / forward + backward; PERF.md §6, PR 48):
as it is 6.69 / 23.18; q and k padded with zeros to 256 lanes 7.51 /
23.99, the outputs equal to the bit; 128 | 128 for scale 4.29 / 14.48:
1.6 times the time for 1.25 times the operations, the price of half a
lane tile. In the Ling step's capture the two calls take 20.2 ms, 51.9 %
of the roofline of the scores the model requires.
A ``scale`` of the caller's own at D 192 (v5e, PR 53: DeepSeek-V3's 192^-1/2
times YaRN's m^2 = 2.0047, five layers a step in the Xing cell) showed
nothing new: the scale is one scalar multiply in all three kernels' bodies
whatever its value; the ten calls take 102 ms of a 493 ms step at the same
51.8 % of the required scores' roofline as Ling's two, and a block alone
reads 11.7 ms forward, 30.9 forward + backward with its five maps.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .. import telemetry
from . import kernel_trace

__all__ = ["flash_attention", "flash_attention_supported",
           "flash_attention_legal", "flash_attention_lse",
           "attention_with_lse", "flash_attention_on_mesh",
           "attention_route", "ATTENDED_NAME"]

#: what a forward kernel hands its backward, for a `jax.checkpoint` policy:
#: the output and the log-sum-exp. A recomputed layer that saves the name
#: runs the forward kernel once a step; q, k and v are made again by XLA.
ATTENDED_NAME = "flash_attended"

# The short family visits one head's whole (S, S) float32 tile per grid step.
# 768 is the longest tile that compiles for a v5e within Mosaic's default
# scoped VMEM in every variant (causal or not, bf16 or float32; at 1024 only
# the causal bf16 one does). At 128 a head is seven 128-wide matmuls whose
# latencies nothing hides, and the composite wins. The router sends it what
# was timed against the composite on a v5e with these kernels (forward +
# backward, bf16, ms a call; PERF.md §6, PR 26): D = 64 at S = 256 2.09
# against 3.30, 384 0.78 / 1.44, 512 1.11 / 3.09, 640 1.68 / 4.74, 768
# 1.08 / 3.36 (causal 1.06 / 3.38); D = 32 at S = 512 1.88 / 6.00.
_SHORT_MIN_S, _SHORT_MAX_S, _SHORT_D = 256, 768, (32, 64)

_ROUTES = telemetry.counter(
    "mxtpu_attention_route_total",
    "flash_attention calls traced, by the path the shape was routed to "
    "(short / streamed Pallas kernels, or the XLA composite).", ("route",))
_BACKWARDS = telemetry.counter(
    "mxtpu_attention_backward_total",
    "Streamed attention backwards traced (the other routes' backwards "
    "follow mxtpu_attention_route_total): flash_bwd_dkvq (one call) or "
    "flash_bwd_dkvq_segmented (one call a q-segment: the dQ slab is over "
    "the VMEM budget).", ("kernel",))


_WINDOWS = telemetry.counter(
    "mxtpu_attention_window_total",
    "flash_attention calls traced with a sliding window, by the path they "
    "took (streamed: flash_window_fwd / flash_window_bwd; composite).",
    ("route",))
_WIDE_VALUES = telemetry.counter(
    "mxtpu_attention_wide_value_total",
    "flash_attention calls traced whose value is wider than its keys (a "
    "width D_v of its own: [v_1; v_2] of a differential pair), by the path "
    "they took (streamed: the same kernels, v / dO / O / dV D_v wide; "
    "composite).", ("route",))
_LIVE_PAIRS = telemetry.gauge(
    "mxtpu_attention_live_block_pairs",
    "Block pairs a head's streamed forward visits, set when a call is "
    "traced: under a window (window), and what the causal kernel would "
    "visit at the same blocks (causal); of a causal or dense call, the "
    "pairs that are never masked (interior) and the others (diagonal).",
    ("kind",))


def _interpret():
    from ..config import get_env
    return get_env("MXTPU_FLASH_INTERPRET")


def _kernels_run_here():
    return _interpret() or jax.devices()[0].platform == "tpu"


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


def _resolve_blocks(S, block_q, block_k, window=None):
    from ..config import get_env
    block_q = block_q or get_env("MXTPU_FLASH_BLOCK_Q") or None
    block_k = block_k or get_env("MXTPU_FLASH_BLOCK_K") or None
    auto = _auto_block(S) if window is None else _window_block(S, window)
    return (block_q or auto), (block_k or auto)


def _window_block(S, window):
    """Blocks under a window: the largest candidate dividing S that is no
    wider than the window (128 at least). A q-block of b rows sees b x
    (window + b / 2) keys and its band of kv-blocks holds about 2 b^2
    (window = b): at window 512 blocks of 512 compute 4 scores for 3 that
    are used, blocks of 1024 2 for 1."""
    for b in (1024, 512, 256, 128):
        if S % b == 0 and b <= max(window, 128):
            return b
    return None


def _band(n_blocks, first, last):
    """(most blocks any row of the grid visits, pairs visited in all):
    `first(i)`, `last(i)` the ends of row i's band (the index maps' own
    functions, here on Python integers)."""
    with jax.ensure_compile_time_eval():      # they are jnp arithmetic
        counts = [int(last(i)) - int(first(i)) + 1 for i in range(n_blocks)]
    return max(counts), sum(counts)


def _body_pairs(S, block_q, block_k, causal):
    """(interior, diagonal): the block pairs of a head that each body of
    the causal / dense forward kernel visits. Interior: the tile's last key
    is not past the q-block's first query (never masked; every pair when
    not causal); diagonal: the other live pairs."""
    rows = range(S // block_q)
    if not causal:
        return len(rows) * (S // block_k), 0
    interior = sum((i * block_q + 1) // block_k for i in rows)
    live = sum(((i + 1) * block_q - 1) // block_k + 1 for i in rows)
    return interior, live - interior


def _seen(Sq, Sk, window):
    """(Sq, Sk) bool: key j is seen from query i iff 0 <= i - j (< window)."""
    d = jnp.arange(Sq)[:, None] - jnp.arange(Sk)[None, :]
    return d >= 0 if window is None else (d >= 0) & (d < window)


def _blocked_reference(q, k, v, causal, scale, window=None):
    """XLA fallback with fp32 softmax (numerics match the kernel)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        s = jnp.where(_seen(q.shape[2], k.shape[2], window), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def flash_attention_legal(q_shape, block_q=None, block_k=None):
    """Capability: the streamed kernels can run this shape. D rides each
    BlockSpec as the FULL last dim (legal for any size when equal to the
    array dim); 8-alignment keeps sublanes packed."""
    B, H, S, D = q_shape
    block_q, block_k = _resolve_blocks(S, block_q, block_k)
    if block_q is None or block_k is None:
        return False
    if not _kernels_run_here():
        return False
    return S % block_q == 0 and S % block_k == 0 and D % 8 == 0


def _narrow_and_short(q_shape):
    """D = 64-style heads half-fill the MXU and, below S = 2048, the
    streamed kernels' carry and second recompute cost more than they save:
    these shapes belong to the short family (or the composite)."""
    return q_shape[3] % 128 != 0 and q_shape[2] < 2048


def flash_attention_supported(q_shape, block_q=None, block_k=None):
    """The STREAMED kernels take this self-attention shape: the same answer
    as attention_route(q_shape) == 'streamed', interpreted or on the chip.
    Ring and Ulysses ask this before calling flash_attention_lse, which has
    only the streamed kernels."""
    return attention_route(q_shape, block_q=block_q,
                           block_k=block_k) == "streamed"


def attention_route(q_shape, k_shape=None, v_shape=None, block_q=None,
                    block_k=None, window=None):
    """'short', 'streamed' or 'composite': which path flash_attention
    takes, from the shapes alone (and whether kernels can run here at all:
    a TPU, or interpret mode). Both kernel families assume self-attention
    (Sq == Sk); cross-attention takes the composite, which handles it.
    v may differ from q and k in its last dimension alone: the streamed
    family takes a D_v of its own (a multiple of 8) wherever it takes the
    equal shape, the short family's lane layout is D_v = D only.
    A sliding ``window`` adds no family: the streamed kernels take it (as
    flash_window_fwd / flash_window_bwd), the short family knows the
    diagonal only, so a windowed shape it would have taken goes to the
    composite."""
    k_shape, v_shape = k_shape or q_shape, v_shape or q_shape
    if not tuple(q_shape) == tuple(k_shape) \
            == tuple(v_shape[:-1]) + (q_shape[-1],):
        return "composite"
    _, H, S, D = q_shape
    if _narrow_and_short(q_shape):
        # whole heads in 128-lane blocks of the (B, S, H*D) layout
        fits = _SHORT_MIN_S <= S <= _SHORT_MAX_S and S % 128 == 0 \
            and D in _SHORT_D and (H * D) % 128 == 0 and window is None \
            and v_shape[-1] == D
        return "short" if fits and _kernels_run_here() else "composite"
    if window is not None:
        block_q, block_k = _resolve_blocks(S, block_q, block_k, window)
    return "streamed" if flash_attention_legal(q_shape, block_q, block_k) \
        and v_shape[-1] % 8 == 0 else "composite"


def _nt(a, b):
    """a @ b.T on the MXU, float32 result, no transpose materialized."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _tn(a, b):
    """a.T @ b, float32 result."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _mm(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


# --------------------------------------------------------------- forward
def _first_kv_block(qb, block_q, block_k, window):
    """The first kv-block a query of q-block ``qb`` sees under ``window``:
    its first query's oldest key is qb * block_q - window + 1."""
    return jnp.maximum(qb * block_q - window + 1, 0) // block_k


def _last_q_block(kb, block_q, block_k, window, n_q):
    """The last q-block that sees a key of kv-block ``kb`` under
    ``window``: its last key's youngest reader is that key + window - 1."""
    return jnp.minimum(((kb + 1) * block_k + window - 2) // block_q, n_q - 1)


#: keys a sub-tile of the forward body: a kv-block that is a larger multiple
#: of it is visited in sub-tiles, so that one sub-tile's Q K^T can run on the
#: MXU while the vector unit is at the previous one's softmax. Measured on a
#: v5e at (1, 16, 16384, 128), blocks of 1024 (PERF.md section 6, PR 47): 512
#: keys 7.63 ms a call, 256 keys 8.07, the whole block 8.91.
_SUB_K = 512


def _sub_tile(block_k):
    """(keys a sub-tile of the forward body, lanes its running max and sum
    are kept in): 512 and 128 at blocks of 1024."""
    width = _SUB_K if block_k % _SUB_K == 0 else block_k
    return width, math.gcd(width, 128)


def _lanes(x, width):
    """A statistic kept in every lane of (rows, lanes), as wide as an
    operand of ``width`` columns."""
    from jax.experimental.pallas import tpu as pltpu
    lanes = x.shape[1]
    if width == lanes:
        return x
    if width % lanes == 0:
        return pltpu.repeat(x, width // lanes, 1)
    return x[:, :1]


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s, *,
               block_k, causal, scale):
    """One (batch*head, q-block, k-block) program: K/V are STREAMED by the
    grid — VMEM holds only (block_q + 2*block_k) x D tiles (v's, the
    accumulator and the output D_v wide: the body reads every width off
    its refs) plus the online
    softmax carry (m/l/acc scratch, persisted across the sequential k-block
    steps), so sequence length is bounded by HBM, not VMEM (S=32k+ on one
    chip).  Writes the per-row LSE (m + log l) the backward kernels consume.

    **A tile does its own work and no more** (PR 47). Which of two bodies
    runs is read off the program ids: a live tile whose last key is not
    past the q-block's first query is *interior* and is never masked (no
    iota, no compare, no select: 120 of a head's 136 live pairs at S = 16k
    and blocks of 1024, every tile when not causal); the others are
    *diagonal* and take the mask by one select. **No row is ever empty
    here, so nothing guards one:** key 0 is in the first sub-tile of
    kv-block 0 and every query sees it, so after a q-block's first
    sub-tile every running max is finite; before it, alpha = exp(-inf -
    finite) = 0 scales sums that are 0, and a masked score is exp(-inf -
    finite) = 0 without help. A row of a diagonal sub-tile that sees none
    of its keys (block_q > block_k, or the later sub-tiles of a square
    diagonal block) keeps the finite max it came with. Only a window can
    start a row with no key: `_fa_window_kernel` keeps the guards.

    Nothing a q-block owns is made again a kv step: q, k go to the MXU in
    the type they came in (`_nt`, no transpose), the scale multiplies the
    float32 scores as the backward's does (so the backward's recomputed P
    is the forward's; q scaled once a q-block into a scratch measured 0.1
    ms a call less at 16k and keeps the rounding of a scaled bf16 q:
    PERF.md section 6, PR 47), P reaches the MXU as float32. The running max and sum live
    in every lane of (block_q, lanes) scratch: the sum as a lane's PARTIAL
    sum (adds of whole vregs; the one cross-lane sum is `_finish`'s), the
    max the row's in every lane. The kv-block is visited in sub-tiles of
    `_SUB_K` keys (`_sub_tile`), each with its own max / exp / P V step,
    traced once and unrolled where it is lowered.
    """
    from jax.experimental import pallas as pl

    qb, kb = pl.program_id(1), pl.program_id(2)
    block_q = q_ref.shape[1]
    width, lanes = _sub_tile(block_k)

    @pl.when(kb == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, -jnp.inf)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    def _tile(masked):
        q = q_ref[0]                                     # (block_q, D)

        def sub_tile(c, carry):
            m, l, acc = carry
            keys = pl.ds(pl.multiple_of(c * width, width), width)
            s = _nt(q, k_ref[0, keys, :]) * scale        # (block_q, width)
            if masked:
                qi = qb * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, 1), 0)
                ki = kb * block_k + c * width + jax.lax.broadcasted_iota(
                    jnp.int32, (1, width), 1)
                s = jnp.where(qi >= ki, s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - _lanes(m_new, width))
            alpha = jnp.exp(m - m_new)
            l = l * alpha + functools.reduce(
                jnp.add, [p[:, i:i + lanes] for i in range(0, width, lanes)])
            acc = acc * _lanes(alpha, acc.shape[1]) \
                + _mm(p, v_ref[0, keys, :].astype(jnp.float32))
            return m_new, l, acc

        m_s[...], l_s[...], acc_s[...] = jax.lax.fori_loop(
            0, block_k // width, sub_tile, (m_s[...], l_s[...], acc_s[...]),
            unroll=True)

    if causal:
        # K/V blocks fully above the diagonal contribute nothing
        interior = (kb + 1) * block_k - 1 <= qb * block_q
        live = (qb + 1) * block_q - 1 >= kb * block_k
        pl.when(interior)(lambda: _tile(masked=False))
        pl.when(live & jnp.logical_not(interior))(lambda: _tile(masked=True))
    else:
        _tile(masked=False)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finish():
        l = jnp.maximum(jnp.sum(l_s[...], axis=-1, keepdims=True), 1e-37)
        o_ref[0, :, :] = (acc_s[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0, :] = (m_s[:, :1] + jnp.log(l))[:, 0]


def _fa_window_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s, *,
                      block_k, scale, window):
    """`_fa_kernel`'s program under a ``window`` (key j seen from i iff
    0 <= i - j < window): the last grid axis counts the kv-blocks of the
    q-block's BAND, not all of them: step j is kv-block
    `_first_kv_block(qb) + j`, live while it is not past the diagonal, and
    every live tile is masked. A row may see no key of a live block (its
    window starts further right): the carry's -inf guards, which the causal
    kernel needs for no row, hold it at zero until its first key comes.
    The body is the one both kernels had up to PR 46, value for value.
    """
    from jax.experimental import pallas as pl

    qb, step = pl.program_id(1), pl.program_id(2)
    block_q = q_ref.shape[1]

    @pl.when(step == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, -jnp.inf)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    kb = _first_kv_block(qb, block_q, block_k, window) + step

    @pl.when((qb + 1) * block_q - 1 >= kb * block_k)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale        # (block_q, D)
        k_blk = k_ref[0].astype(jnp.float32)            # (block_k, D)
        v_blk = v_ref[0].astype(jnp.float32)
        s = q @ k_blk.T                                  # (block_q, block_k)
        qi = qb * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)
        ki = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        s = jnp.where((qi >= ki) & (qi - ki < window), s, -jnp.inf)
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

    @pl.when(step == pl.num_programs(2) - 1)
    def _finish():
        l = jnp.maximum(l_s[...], 1e-37)
        o_ref[0, :, :] = (acc_s[...] / l).astype(o_ref.dtype)
        # the diagonal is inside every window: no row ends with l = 0
        lse_ref[0, 0, :] = (m_s[...] + jnp.log(l))[:, 0]


def _fa_call(q, k, v, causal, scale, block_q, block_k, window=None):
    """Returns (out (B,H,S,Dv), lse (B*H,S) fp32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, S, D = q.shape
    Dv = v.shape[-1]
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * H, S, D)
    vf = v.reshape(B * H, S, Dv)
    grid = (B * H, S // block_q, S // block_k)
    if window is not None:
        # the grid's last axis spans a q-block's band of kv-blocks; the
        # index map is clamped from BOTH sides, so a step past the diagonal
        # re-uses the resident block and moves nothing
        def first(i):
            return _first_kv_block(i, block_q, block_k, window)

        def last(i):
            return ((i + 1) * block_q - 1) // block_k

        steps, pairs = _band(S // block_q, first, last)
        _LIVE_PAIRS.set(pairs, kind="window")
        _LIVE_PAIRS.set(_band(S // block_q, lambda i: 0, last)[1],
                        kind="causal")
        grid = grid[:2] + (steps,)
        kernel = functools.partial(_fa_window_kernel, block_k=block_k,
                                   scale=scale, window=window)
        lanes = 1                 # the guarded body's columns

        def kv_idx(b, i, j):
            return (b, jnp.minimum(first(i) + j, last(i)), 0)
    else:
        kernel = functools.partial(_fa_kernel, block_k=block_k,
                                   causal=causal, scale=scale)
        lanes = _sub_tile(block_k)[1]
        interior, diagonal = _body_pairs(S, block_q, block_k, causal)
        _LIVE_PAIRS.set(interior, kind="interior")
        _LIVE_PAIRS.set(diagonal, kind="diagonal")

        # dead blocks above the diagonal: clamp the index map so the grid
        # step re-uses the resident block instead of DMA-ing one it will
        # never read (compute is skipped by pl.when in the kernel)
        def kv_idx(b, i, j):
            last = ((i + 1) * block_q - 1) // block_k
            return (b, jnp.minimum(j, last) if causal else j, 0)
    out, lse = kernel_trace.pallas_call(
        kernel, (qf, kf, vf),
        out_shape=(jax.ShapeDtypeStruct((B * H, S, Dv), q.dtype),
                   jax.ShapeDtypeStruct((B * H, 1, S), jnp.float32)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), kv_idx),
            pl.BlockSpec((1, block_k, Dv), kv_idx),
        ],
        out_specs=(pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0)),
                   pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i))),
        scratch_shapes=[pltpu.VMEM((block_q, lanes), jnp.float32),
                        pltpu.VMEM((block_q, lanes), jnp.float32),
                        pltpu.VMEM((block_q, Dv), jnp.float32)],
        interpret=_interpret(),
        name="flash_fwd" if window is None else "flash_window_fwd",
    )
    return out.reshape(B, H, S, Dv), lse


# --------------------------------------------------------------- backward
# The streamed backward assumes a v5e-class VMEM: the one number below is
# what a v5e TensorCore has, and the two budgets follow from it. Compiled
# for a described v5e (PERF.md §6, PR 30): S = 65 536 at D = 128 fits in one
# call (two 32 MiB buffers of the slab), 131 072 does not.
_V5E_VMEM_BYTES = 128 << 20
# Both pipeline buffers of the resident dQ slab: half the core's VMEM.
_DQ_SLAB_BYTES = _V5E_VMEM_BYTES // 2
# The tiles and the kernel's temporaries beside the slab. Mosaic's scoped
# allocation at 1024-blocks and D = 128, less the slab (compiled for a
# described v5e, PR 30): 13.0 MiB with bf16 operands, 14.7 MiB with float32;
# 12 MiB refuses, 16 MiB compiles. The rest is room for wider heads.
_BWD_TILE_BYTES = 24 << 20


def _slab_bytes(rows, D):
    """Both buffers of a float32 (rows, D) block, D padded to 128 lanes."""
    return 2 * rows * -(-D // 128) * 128 * 4


def _dq_segments(S, D, block_q):
    """How many q-segments the backward is called for: the fewest that
    divide the q-blocks evenly and keep one segment's dQ slab within
    _DQ_SLAB_BYTES. One for every S <= 65 536 at D <= 128."""
    n_blocks = S // block_q
    return next((n for n in range(1, n_blocks + 1) if n_blocks % n == 0
                 and _slab_bytes(S // n, D) <= _DQ_SLAB_BYTES), n_blocks)


def _fa_bwd_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                   dk_ref, dv_ref, dq_ref, *, causal, scale, block_q,
                   block_k, q0, window=None, n_q=None):
    """Grid (bh, kv-block, q-block), each live pair visited ONCE: P and dS
    are recomputed from the saved LSE (FlashAttention-2) and all three
    gradients accumulate from them (v, dO and dV D_v wide, read off the
    refs: ONE dS from all D_v columns). dK/dV blocks accumulate over the inner
    q steps; dQ accumulates into dq_ref, the (batch*head)'s whole slab of
    this call's q rows, which stays in VMEM across both inner axes and is
    written back when bh changes. ``q0`` is the call's first q-block.

    Written in the transposed frame, as the short family's backward is
    (rows are keys, columns queries): LSE and delta broadcast as the (1,
    block_q) rows they are stored as, dV and dK are plain matmuls, and only
    dQ contracts over the tile's rows. The arithmetic is the replaced
    pair's, value for value: every operand is cast to float32 (lossless),
    the scale multiplies the float32 scores and dS and never an operand (a
    scaled q is no longer a bf16 value, and what the MXU keeps of it cost
    0.03-0.09 % of the gradients on a v5e: PERF.md §6, PR 30), P and dS
    reach the MXU as float32 and every matmul accumulates in float32.

    Under a ``window`` the last grid axis counts the q-blocks of the
    kv-block's BAND: step j is the call's q-block `first + j`, first the one
    that holds the kv-block's first key (or the call's first), live while
    it is not past `_last_q_block` (``n_q``: the call's q-blocks)."""
    from jax.experimental import pallas as pl

    kb, qb = pl.program_id(1), pl.program_id(2)

    @pl.when((kb == 0) & (qb == 0))
    def _new_head():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    @pl.when(qb == 0)
    def _new_kv_block():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    if window is not None:
        qb = jnp.maximum(kb * block_k // block_q - q0, 0) + qb
        live = q0 + qb <= _last_q_block(kb, block_q, block_k, window,
                                        q0 + n_q)
    else:
        # q-blocks fully above the diagonal contribute nothing when causal
        live = (q0 + qb + 1) * block_q - 1 >= kb * block_k if causal \
            else qb >= 0

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                  # (block_q, D)
        do = do_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)                  # (block_k, D)
        v = v_ref[0].astype(jnp.float32)
        pt = jnp.exp(_nt(k, q) * scale - lse_ref[0])      # (block_k, block_q)
        if causal:
            ki = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, 1), 0)
            qi = (q0 + qb) * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_q), 1)
            seen = qi >= ki if window is None \
                else (qi >= ki) & (qi - ki < window)
            pt = jnp.where(seen, pt, 0.0)
        dst = pt * (_nt(v, do) - delta_ref[0]) * scale
        dv_ref[0, :, :] += _mm(pt, do)
        dk_ref[0, :, :] += _mm(dst, q)
        rows = pl.ds(pl.multiple_of(qb * block_q, block_q), block_q)
        dq_ref[0, rows, :] += _tn(dst, k)


def _fa_bwd_segment(qf, kf, vf, dof, lse, delta, causal, scale, block_q,
                    block_k, q0, n_q, window=None):
    """dQ of q-blocks [q0, q0 + n_q) and their contribution to dK/dV (of
    the kv-blocks they can see), all float32, from one kernel call."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, S, D = qf.shape
    Dv = vf.shape[-1]
    n_kv = S // block_k
    steps = n_q
    kernel = functools.partial(_fa_bwd_kernel, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k, q0=q0)
    if causal:
        # kv-blocks past the segment's last row are dead for all of it
        n_kv = min(n_kv, -(-(q0 + n_q) * block_q // block_k))
    if window is not None:
        # the last axis spans a kv-block's band of q-blocks, clamped from
        # both sides: a step past the band moves nothing
        def first(i):
            return jnp.maximum(i * block_k // block_q, q0)

        def last(i):
            return _last_q_block(i, block_q, block_k, window, q0 + n_q)

        steps = max(1, _band(n_kv, first, last)[0])
        kernel = functools.partial(kernel, window=window, n_q=n_q)

        def q_blk(i, j):
            return jnp.clip(first(i) + j, q0, last(i))
    elif causal:

        # the grid streams q-blocks (j) per kv-block (i): q-blocks strictly
        # above the diagonal are dead — clamp to the first live one so no
        # DMA is issued for blocks pl.when will skip
        def q_blk(i, j):
            return jnp.maximum(q0 + j, (i * block_k) // block_q)
    else:
        def q_blk(i, j):
            return q0 + j
    def qspec(width):
        return pl.BlockSpec((1, block_q, width),
                            lambda b, i, j: (b, q_blk(i, j), 0))

    def kvspec(width):
        return pl.BlockSpec((1, block_k, width), lambda b, i, j: (b, i, 0))
    rowspec = pl.BlockSpec((1, 1, block_q),
                           lambda b, i, j: (b, 0, q_blk(i, j)))
    # an index that depends on b alone: resident across the inner axes
    slab = pl.BlockSpec((1, n_q * block_q, D), lambda b, i, j: (b, 0, 0))
    return kernel_trace.pallas_call(
        kernel, (qf, dof, lse, delta, kf, vf),
        out_shape=(jax.ShapeDtypeStruct((BH, n_kv * block_k, D), jnp.float32),
                   jax.ShapeDtypeStruct((BH, n_kv * block_k, Dv), jnp.float32),
                   jax.ShapeDtypeStruct((BH, n_q * block_q, D), jnp.float32)),
        grid=(BH, n_kv, steps),
        in_specs=[qspec(D), qspec(Dv), rowspec, rowspec, kvspec(D),
                  kvspec(Dv)],
        out_specs=(kvspec(D), kvspec(Dv), slab),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_slab_bytes(n_q * block_q, D) + _BWD_TILE_BYTES),
        interpret=_interpret(),
        name="flash_bwd_dkvq" if window is None else "flash_window_bwd",
    )


def _fa_bwd_call(q, k, v, o, lse, do, causal, scale, block_q, block_k,
                 g_lse=None, window=None):
    B, H, S, D = q.shape
    Dv = v.shape[-1]
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * H, S, D)
    vf = v.reshape(B * H, S, Dv)
    dof = do.reshape(B * H, S, Dv)
    # delta_i = sum_d dO_i * O_i — O(S*Dv), computed by XLA
    delta = jnp.sum(dof.astype(jnp.float32) *
                    o.reshape(B * H, S, Dv).astype(jnp.float32),
                    axis=-1)[:, None, :]                 # (B*H, 1, S)
    if g_lse is not None:
        # When LSE is a second primal output (flash_attention_lse), its
        # cotangent enters ds exactly as -delta does: d lse_i/d s_ij = p_ij,
        # so ds_ij = p_ij*(dp_ij - delta_i + g_lse_i)*scale — fold it in.
        delta = delta - g_lse.astype(jnp.float32)

    # One kernel call while a (batch*head)'s whole dQ slab fits VMEM; past
    # that the same kernel a q-segment, XLA summing the dK/dV partials.
    n_seg = _dq_segments(S, D, block_q)
    if window is None:
        _BACKWARDS.inc(kernel="flash_bwd_dkvq" if n_seg == 1
                       else "flash_bwd_dkvq_segmented")
    else:
        _BACKWARDS.inc(kernel="flash_window_bwd" if n_seg == 1
                       else "flash_window_bwd_segmented")
    n_q = S // block_q // n_seg
    parts = [_fa_bwd_segment(qf, kf, vf, dof, lse, delta, causal, scale,
                             block_q, block_k, s * n_q, n_q, window)
             for s in range(n_seg)]
    dk, dv, _ = parts[-1]                     # the last segment sees all of K
    for dk_s, dv_s, _ in parts[:-1]:
        dk = dk.at[:, :dk_s.shape[1]].add(dk_s)
        dv = dv.at[:, :dv_s.shape[1]].add(dv_s)
    dq = jnp.concatenate([dq_s for _, _, dq_s in parts], axis=1)

    shape = (B, H, S, D)
    return (dq.reshape(shape).astype(q.dtype),
            dk.reshape(shape).astype(k.dtype),
            dv.reshape(v.shape).astype(v.dtype))


# ------------------------------------------- short sequences: one visit
# The short kernels read and write the projections' own (B, S, H*D) layout in
# 128-lane blocks: 128 // D whole heads side by side (a pair at D = 64). A
# (.., S, 64) operand would be padded to 128 lanes in HBM and VMEM — twice
# the bytes of every q, k, v and gradient, 1.9 GB a chip of BERT-large's
# saved activations, which the dp4 cell does not have (PERF.md §6, PR 26) —
# and would need the (B,S,H,D) -> (B,H,S,D) transposes as ops of their own.
# One head of a block is picked by zeroing the other heads' lanes of q (and
# of dO): a contraction over all 128 lanes then sees that head alone and
# costs the MXU the pass a half-filled 64-deep one costs; results that come
# out 128 wide (o, dV, dQ) keep that head's lanes.
def _lane_blocks_per_step(n_blocks, heads_per_block, S):
    """128-lane blocks one grid step visits (the loops over blocks and heads
    are unrolled): the largest divisor of n_blocks that keeps a step at or
    under two (512, 512) tiles, so one block from S = 384 up and four (eight
    heads of 64) at S = 256. Measured on a v5e with these kernels at
    (64, 16, 256, 64), forward + backward (PERF.md §6, PR 26): four blocks
    a step take 2.09 ms where one takes 2.28."""
    want = max(1, 2 * (512 * 512) // (S * S) // heads_per_block)
    return max(g for g in range(1, min(want, n_blocks) + 1)
               if n_blocks % g == 0)


def _causal_tile(S, rows_are_queries):
    r = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
    return r >= c if rows_are_queries else c >= r


def _lane_blocks(ref):
    return [slice(128 * i, 128 * (i + 1)) for i in range(ref.shape[2] // 128)]


def _head_masks(D):
    """One (1, 128) mask per head of a 128-lane block: the head's lanes."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    return [(lane >= h * D) & (lane < (h + 1) * D) for h in range(128 // D)]


def _one_head(mine, x):
    """A 128-lane operand with every lane but one head's zeroed."""
    return jnp.where(mine, x, 0).astype(x.dtype)


def _short_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, D, causal,
                      scale):
    """Each head of the step, whole: s = (q*scale) k^T, row max, exp, row
    sum, o = p v / l. Operands stay in the input type and every matmul
    accumulates in float32; max, sum, exp and the LSE are float32. The
    whole key length is in the one tile, so there is no carry, no rescale
    and (the diagonal is always live) no row without a finite maximum."""
    S = q_ref.shape[1]
    for blk, lanes in enumerate(_lane_blocks(q_ref)):
        k, v = k_ref[0, :, lanes], v_ref[0, :, lanes]
        out = None
        for h, mine in enumerate(_head_masks(D)):
            q = _one_head(mine, q_ref[0, :, lanes] * scale)
            s = _nt(q, k)                                 # (S, S) float32
            if causal:
                s = jnp.where(_causal_tile(S, True), s, -jnp.inf)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=-1, keepdims=True)
            o = _mm(p.astype(v.dtype), v) / l             # every head's lanes
            out = o if out is None else jnp.where(mine, o, out)
            lse_ref[0, blk, h, :] = (m + jnp.log(l))[:, 0]
        o_ref[0, :, lanes] = out.astype(o_ref.dtype)


def _short_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                      dq_ref, dk_ref, dv_ref, *, D, causal, scale):
    """Each head of the step, whole, in the transposed frame (rows are
    keys, columns queries): the saved LSE is a (1, S) row and broadcasts as
    it lies, delta = sum_k p dP is a sum down the rows, and dV = p^T dO and
    dK = ds^T q are plain matmuls; only dQ contracts over the tile's rows.
    Five matmuls, the scores recomputed once, nothing accumulated across
    grid steps, each gradient written once in the operands' type."""
    S = q_ref.shape[1]
    for blk, lanes in enumerate(_lane_blocks(q_ref)):
        k, v = k_ref[0, :, lanes], v_ref[0, :, lanes]
        dq = dk = dv = None
        for h, mine in enumerate(_head_masks(D)):
            q = _one_head(mine, q_ref[0, :, lanes] * scale)
            do = _one_head(mine, do_ref[0, :, lanes])
            st = _nt(k, q)                                # (Sk, Sq) float32
            if causal:
                st = jnp.where(_causal_tile(S, False), st, -jnp.inf)
            pt = jnp.exp(st - lse_ref[0, blk, h:h + 1, :])
            dpt = _nt(v, do)
            # delta_q = dO_q . O_q = sum_k p_qk dP_qk, without reading O
            dst = (pt * (dpt - jnp.sum(pt * dpt, axis=0, keepdims=True))
                   ).astype(k.dtype)
            # q and dO are zero outside the head's lanes, so dV's and dK's
            # other lanes are too; dQ comes out of all of K's lanes.
            # s = (q*scale) k^T: dK takes the scaled q, dQ the factor itself
            dv_h, dk_h = _mm(pt.astype(k.dtype), do), _mm(dst, q)
            dq_h = _tn(dst, k) * scale
            dv = dv_h if dv is None else dv + dv_h
            dk = dk_h if dk is None else dk + dk_h
            dq = dq_h if dq is None else jnp.where(mine, dq_h, dq)
        dq_ref[0, :, lanes] = dq.astype(dq_ref.dtype)
        dk_ref[0, :, lanes] = dk.astype(dk_ref.dtype)
        dv_ref[0, :, lanes] = dv.astype(dv_ref.dtype)


def _short_specs(B, H, S, D):
    from jax.experimental import pallas as pl
    per_block = 128 // D
    n_blocks = H // per_block
    G = _lane_blocks_per_step(n_blocks, per_block, S)
    return (B, n_blocks // G), \
        pl.BlockSpec((1, S, 128 * G), lambda b, j: (b, 0, j)), \
        pl.BlockSpec((1, G, per_block, S), lambda b, j: (b, j, 0, 0))


def _to_rows(x):
    """(B, H, S, D) -> (B, S, H*D), the layout the projections produce:
    XLA cancels this against the model's own transpose."""
    B, H, S, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, S, H * D)


def _to_heads(x, H):
    B, S, U = x.shape
    return x.reshape(B, S, H, U // H).transpose(0, 2, 1, 3)


# Both calls are jitted: a model's layers then trace and lower each kernel
# once and call it N times, where N inline pallas_calls cost every start of
# BERT-large, warm cache or not, 4-6 s of host time (PERF.md §6, PR 26).
@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _short_call(q, k, v, causal, scale, interpret):
    """Returns (out (B,H,S,D), lse (B, H*D/128, 128/D, S) fp32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, S, D = q.shape
    grid, tile, rows = _short_specs(B, H, S, D)
    out, lse = kernel_trace.pallas_call(
        functools.partial(_short_fwd_kernel, D=D, causal=causal, scale=scale),
        (_to_rows(q), _to_rows(k), _to_rows(v)),
        out_shape=(jax.ShapeDtypeStruct((B, S, H * D), q.dtype),
                   jax.ShapeDtypeStruct((B, H * D // 128, 128 // D, S),
                                        jnp.float32)),
        grid=grid, in_specs=[tile, tile, tile], out_specs=(tile, rows),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="flash_short_fwd",
    )
    return _to_heads(out, H), lse


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _short_bwd_call(q, k, v, lse, do, causal, scale, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, S, D = q.shape
    grid, tile, rows = _short_specs(B, H, S, D)
    grads = kernel_trace.pallas_call(
        functools.partial(_short_bwd_kernel, D=D, causal=causal, scale=scale),
        (_to_rows(q), _to_rows(k), _to_rows(v), _to_rows(do), lse),
        out_shape=tuple(jax.ShapeDtypeStruct((B, S, H * D), x.dtype)
                        for x in (q, k, v)),
        grid=grid, in_specs=[tile, tile, tile, tile, rows],
        out_specs=(tile, tile, tile),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="flash_short_bwd",
    )
    return tuple(_to_heads(g, H) for g in grads)


# --------------------------------------------------------------- custom VJP
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, window=None):
    """q, k: (B, H, S, D), v: (B, H, S, D_v) → (B, H, S, D_v), by the
    path attention_route() names for the shapes; ``scale`` defaults to
    1 / sqrt(D) of q, whatever D_v. ``block_q``/``block_k`` size the streamed kernels'
    blocks (default: the largest of 1024/512/256/128 dividing S; under a
    window the largest no wider than it) and mean nothing on the other two
    paths. ``window`` (with ``causal``): key j is seen from query i iff
    0 <= i - j < window; None is plain causal or dense attention, the
    calls they always were."""
    return _fa_fwd(q, k, v, causal, scale, block_q, block_k, window)[0]


def _attended(*written):
    return tuple(checkpoint_name(t, ATTENDED_NAME) for t in written)


def _fa_fwd(q, k, v, causal, scale, block_q, block_k, window=None):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if window is not None and not causal:
        raise ValueError("a window is causal: key j is seen from i iff "
                         "0 <= i - j < window")
    route = attention_route(q.shape, k.shape, v.shape, block_q, block_k,
                            window)
    _ROUTES.inc(route=route)
    if window is not None:
        _WINDOWS.inc(route=route)
    if v.shape[-1] > k.shape[-1]:
        _WIDE_VALUES.inc(route=route)
    if route == "short":
        out, lse = _attended(*_short_call(q, k, v, causal, scale,
                                          _interpret()))
        return out, (q, k, v, None, lse)        # its backward needs no O
    if route == "streamed":
        block_q, block_k = _resolve_blocks(q.shape[2], block_q, block_k,
                                           window)
        out, lse = _attended(*_fa_call(q, k, v, causal, scale, block_q,
                                       block_k, window))
    else:
        out, lse = _blocked_reference(q, k, v, causal, scale, window), None
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, scale, block_q, block_k, window, res, do):
    q, k, v, o, lse = res
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    route = attention_route(q.shape, k.shape, v.shape, block_q, block_k,
                            window)
    if route == "short":
        return _short_bwd_call(q, k, v, lse, do, causal, scale,
                               _interpret())
    if route == "streamed":
        block_q, block_k = _resolve_blocks(q.shape[2], block_q, block_k,
                                           window)
        return _fa_bwd_call(q, k, v, o, lse, do, causal, scale, block_q,
                            block_k, window=window)
    # XLA composite (materializes (S,S)): off the TPU, cross-attention,
    # lengths no block divides, narrow heads past the short tile
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
    if causal:
        s = jnp.where(_seen(q.shape[2], k.shape[2], window), s, -jnp.inf)
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
                            causal=False, window=None):
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
        lambda q, k, v: flash_attention(q, k, v, causal, window=window),
        mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)(q, k, v)


# ------------------------------------------------- out + LSE (for SP paths)
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_lse(q, k, v, causal=False, scale=None, block_q=None,
                        block_k=None):
    """Like flash_attention but ALSO returns the per-row log-sum-exp
    (B, H, S) fp32 — the sufficient statistic ring attention's online
    combine needs. Both outputs are differentiable: the LSE cotangent
    folds into the existing backward kernels as a delta shift (see
    _fa_bwd_call). Requires flash_attention_supported(q.shape). Ring and
    Ulysses hand it q, k, v of ONE shape (attention_with_lse sends
    anything else to the dense form): a value width of its own is
    flash_attention's."""
    return _fa_lse_fwd(q, k, v, causal, scale, block_q, block_k)[0]


def _fa_lse_fwd(q, k, v, causal, scale, block_q, block_k):
    B, H, S, D = q.shape
    block_q, block_k = _resolve_blocks(S, block_q, block_k)
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    out, lse = _attended(*_fa_call(q, k, v, causal, scale, block_q, block_k))
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
