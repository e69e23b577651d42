"""Chunked LM cross-entropy: per-token CE over a (V, U) vocabulary head
without the (T, V) logits.

``chunked_lm_cross_entropy(hidden, head_w, labels, chunk)`` runs a lax.map
over chunks of tokens: (rows, U) @ (U, V) -> float32 LSE + label-logit
gather, so at most (rows, V) logits exist at a time. The backward is jax
autodiff through the map under ``jax.checkpoint`` (the chunk logits are
recomputed, the classic memory/compute trade), so every trip runs four
matmuls against the whole weight: logits, recomputed logits, input
gradient, and the weight gradient added into a (V, U) accumulator.

How many rows a trip takes decides what those matmuls are bound by
(``_auto_rows``). A matmul of r rows does r FLOP per byte of a bfloat16
weight, and a v5e's ridge is 197e12 / 819e9 = 240 FLOP/byte: under it the
trip is paced by reading the weight, and the accumulator's read and write
(2 x V x U x 2 bytes a trip) is paid at HBM rate as well. The rule that
was here before, 32 MiB of float32 logits a chunk, gave 166 rows at
V = 50257: 99 trips at T = 16384, every matmul under the ridge, 40.8 GB of
accumulator traffic a step, 147 ms in the four fusions for 73 ms of
matmul at the 185 TFLOP/s a large matmul reaches. At 1024 rows they take
76-79 ms in the compiled train steps (v5e; PERF.md S6, PR 28).

Numerics: LSE in fp32 with max subtraction; identical to dense softmax-CE
within bf16 matmul tolerance (tests/test_lm_ce.py pins parity and grads).

``multibyte_cross_entropy(hidden, head_w, labels, heads)`` is the same trade
for a head that predicts the next ``heads`` tokens of every position from
ONE (heads x V, U) map (EvaByte's `num_pred_heads`): a block of rows' float32
(rows, heads, V) logits at a time, head i of position t against
labels[t + i], nothing past the end of a sequence.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .. import telemetry

__all__ = ["chunked_lm_cross_entropy", "multibyte_cross_entropy"]


# Below DENSE_BYTES of float32 (T, V) logits: one chunk, which is the dense
# path (no map, no checkpoint, no recomputed matmul).
_DENSE_BYTES = 128 * 1024 * 1024
# Above it, rows a trip: whole MXU passes (a multiple of 256), never under
# twice the ridge, and 1024 where nothing stands against it. At 1024 rows
# a trip's traffic (three reads of the weight, the accumulator read and
# written: 5 x V x U x 2 bytes) is under a third of its matmul time, and
# more rows bought nothing on a v5e (PERF.md S6, PR 28): value_and_grad of
# the op alone at (T, V, U) = (16384, 50257, 2048) takes 153 ms at 166
# rows and 80.6 / 83.4 / 84.7 / 83.9 ms at 512 / 1024 / 2048 / 4096; at
# (8192, 128256, 4096) 228.6 / 207.1 / 213.9; and inside the compiled
# train steps 2048 rows give 1.8 % (GPT) and 3.7 % (OLMoE) fewer tokens a
# second than 1024, 512 rows +0.9 % and -4.2 %.
_ROW_ALIGN, _MIN_ROWS, _MAX_ROWS = 256, 512, 1024
# A chunk's float32 logits stay under this ceiling, except where V > 256 k
# leaves no count over the floor (the floor wins). It is what bounds the
# temporaries of a vocabulary over 128 k, where XLA holds up to three
# buffers of a chunk's logits in the backward (V = 262144: 1.6 GB at 1024
# rows, 0.8 GB at 512). At V = 50 k it does not bind: the fullest cell's
# whole step (OLMoE) compiled for a v5e holds 12.44 GB at 166 and at 1024
# rows, 12.50 GB at 2048.
_CEILING_BYTES = 512 * 1024 * 1024

_ROUTES = telemetry.counter(
    "mxtpu_lm_ce_route_total",
    "chunked_lm_cross_entropy calls traced, by route (dense: one chunk, no "
    "map; chunked: a checkpointed map over chunks of rows).", ("route",))


def _auto_rows(T, V):
    """Rows a trip for T tokens against a V-wide head, from the shape
    alone: of the aligned counts between the floor and the smaller of
    ``_MAX_ROWS`` and the ceiling, the one that leaves fewest zero-padded
    rows (none where the count divides T), and of equals the largest."""
    cap = max(_MIN_ROWS, min(
        _MAX_ROWS, _CEILING_BYTES // (4 * V) // _ROW_ALIGN * _ROW_ALIGN))
    return min(range(cap, _MIN_ROWS - 1, -_ROW_ALIGN),
               key=lambda rows: -(-T // rows) * rows)


def chunked_lm_cross_entropy(hidden, head_w, labels, chunk=None,
                             head_b=None):
    """hidden: (..., U) activations; head_w: (V, U) (embedding-tied or
    untied head); optional head_b: (V,) bias (BERT-style MLM decoders);
    labels: (...,) int. Returns per-token CE losses shaped like labels.

    ``chunk=None`` (default) auto-routes: the dense path when the full
    fp32 (T, V) logits block is under 128 MiB (no map overhead), else
    ``_auto_rows(T, V)`` rows a chunk, which keeps every matmul of a trip
    compute-bound. Token dims are flattened, chunked, and restored; when
    chunk does not divide T, the token stream is zero-PADDED up to the
    next chunk multiple and the pad losses discarded (a divisor fallback
    would collapse to tiny chunks for odd/prime T — e.g. T=8193 at chunk
    256 has largest divisor 3 — and a thousands-iteration map)."""
    shape = labels.shape
    U = hidden.shape[-1]
    h = hidden.reshape(-1, U)
    y = labels.reshape(-1).astype(jnp.int32)
    T = h.shape[0]
    V = head_w.shape[0]
    if chunk is None:
        chunk = T if T * V * 4 <= _DENSE_BYTES else _auto_rows(T, V)
    chunk = min(chunk, T)
    pad = (-T) % chunk
    if pad:
        h = jnp.concatenate([h, jnp.zeros((pad, U), h.dtype)])
        y = jnp.concatenate([y, jnp.zeros((pad,), y.dtype)])
    n = (T + pad) // chunk
    _ROUTES.inc(route="dense" if n == 1 else "chunked")
    hc = h.reshape(n, chunk, U)
    yc = y.reshape(n, chunk)

    def one(args):
        hb, yb = args
        logits = (hb @ head_w.T.astype(hb.dtype)).astype(jnp.float32)
        if head_b is not None:
            logits = logits + head_b.astype(jnp.float32)
        m = jnp.max(logits, axis=-1, keepdims=True)
        lse = (m + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1,
                                   keepdims=True)))[:, 0]
        lab = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
        return lse - lab

    if n == 1:
        # true dense path: no map, no checkpoint — a rematerializing
        # single-chunk map would re-run the full (T,U)@(U,V) head matmul
        # in the backward for zero memory benefit
        losses = one((hc[0], yc[0]))
    else:
        # checkpoint: WITHOUT it, grad-of-map stacks each chunk's softmax
        # residuals into an (n, chunk, V) buffer — full-logits-sized,
        # exactly what this op exists to avoid. With it, the backward
        # recomputes the chunk logits from the (chunk, U) inputs.
        losses = lax.map(jax.checkpoint(one), (hc, yc)).reshape(-1)
    if pad:
        losses = losses[:T]
    return losses.reshape(shape)


# Rows a trip of the multi-head loss: at EvaByte's 8 x 320 columns a trip's
# float32 logits are 21 MB and its matmuls (2048, U) x (U, 2560).
_MULTIBYTE_ROWS = 2048


def multibyte_cross_entropy(hidden, head_w, labels, heads, rows=None):
    """hidden (B, S, U); head_w (heads x V, U), head i's rows
    [V i, V (i + 1)); labels (B, S) int, labels[t] the token after position
    t. Head i of position t is scored against labels[t + i]; the last i
    positions of head i have no target. -> (the losses summed over a
    position's heads (B, S) float32, zeros where there is no target; the
    number of targets a sequence has, heads x S - heads (heads - 1) / 2).
    Logits are float32 out of the matmul (operands keep their type). ``rows``
    positions a trip (default 2048; one trip where it does not divide S),
    each trip recomputed in the backward."""
    b, s, u = hidden.shape
    vocab = head_w.shape[0] // heads
    rows = rows or _MULTIBYTE_ROWS
    if s % rows:
        rows = s
    at = jnp.arange(s)[:, None] + jnp.arange(heads)[None, :]      # (S, P)
    valid = at < s
    targets = labels.astype(jnp.int32)[:, jnp.minimum(at, s - 1)]  # (B, S, P)
    n = s // rows

    def one(args):
        hb, yb, ok = args              # (B, rows, U), (B, rows, P), (rows, P)
        logits = jnp.einsum("bru,vu->brv", hb, head_w.astype(hb.dtype),
                            preferred_element_type=jnp.float32) \
            .reshape(b, rows, heads, vocab)
        m = jnp.max(logits, axis=-1, keepdims=True)
        lse = (m + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1,
                                   keepdims=True)))[..., 0]
        lab = jnp.take_along_axis(logits, yb[..., None], axis=-1)[..., 0]
        return jnp.where(ok, lse - lab, 0.0).sum(-1)              # (B, rows)

    blocks = (jnp.moveaxis(hidden.reshape(b, n, rows, u), 1, 0),
              jnp.moveaxis(targets.reshape(b, n, rows, heads), 1, 0),
              valid.reshape(n, rows, heads))
    if n == 1:
        losses = one(tuple(x[0] for x in blocks))[None]
    else:
        losses = lax.map(jax.checkpoint(one), blocks)
    count = heads * s - heads * (heads - 1) // 2
    return jnp.moveaxis(losses, 0, 1).reshape(b, s), count
