"""Mixture-of-Experts with a sort-based DROPLESS dispatch — NEW capability
(SURVEY §2.5: no MoE ops in the reference).

One dispatch, whatever the expert count: the router's softmax and top-k run
in float32; the T x k (token, slot) assignments are sorted by expert, the
token rows gathered in that order, and every expert's matmul runs as ONE
grouped matmul over the stacked weights with the per-expert row counts as
data (``jax.lax.ragged_dot``: on a TPU XLA lowers it to a Mosaic grouped
matmul, on the CPU to plain ops); the rows are then un-sorted and summed
per token with the router's weights. Shapes are static — always exactly
T x k rows — and nothing is dropped, however uneven the routing: an expert
with no token costs nothing, one with all of them gets all of them. No
(T, E, C) or (E, T, H) tensor exists.

The stacked expert weights carry ``PartitionSpec(ep_axis, None, None)``,
so on a mesh with an ``ep`` axis GSPMD shards the experts' state; the
token exchange between chips (an all-to-all of the sorted rows) is not
written yet (ROADMAP R2), and GSPMD gathers what the grouped matmul needs.

A layer can be told which experts it HOLDS (``held=(first, count)``): one
chip's share of an expert-parallel group. It routes over all the experts,
as every rank does, and computes the part of the result its own experts
give; what the absent ones would add is some other chip's and is left out
(`dropless_moe_held`: windows of W sorted rows, W twice what a balanced
router sends the held experts, as many windows as hold a live row, none
dropped). On one chip that runs without its exchange; nothing stands in for
the other ranks.

An auxiliary load-balancing loss (Switch-Transformer form,
``E * sum_e fraction_routed_e * mean_gate_e``) and the ST-MoE router z-loss
are returned by ``forward_with_aux`` for the trainer to add to the task
loss. The dense O(T*E) form lives on in the tests as their reference
(tests/test_moe_dispatch.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import autograd, initializer, telemetry
from .. import ndarray as nd
from ..gluon import _functional
from ..gluon.block import HybridBlock
from ..ndarray import _apply

__all__ = ["MoELayer", "dropless_moe", "dropless_moe_held",
           "load_balancing_loss", "router_z_loss", "relu2"]

_DISPATCHES = telemetry.counter(
    "mxtpu_moe_dispatch_total",
    "MoE expert dispatches traced, by path (dropless: the sort-based "
    "grouped matmul over every expert; dropless_held: the same over the "
    "experts this chip holds) and by how the expert rows come back to "
    "their tokens (unsort: the inverse permutation's gather; a held "
    "window's by _rows_to_tokens, sort: sorted to their tokens and "
    "gathered, scatter: XLA's row scatter-add).", ("path", "combine"))
_GROUP_LIMITED = telemetry.counter(
    "mxtpu_moe_group_limited_total",
    "Routers traced with a group limit (MoELayer(n_group=, topk_group=): "
    "the experts chosen among the topk_group best of n_group groups).")
_WINDOW_ROWS = telemetry.gauge(
    "mxtpu_moe_window_rows",
    "W, the sorted rows one window of a layer's held dispatch handles "
    "(held_window_rows: from shapes, set when the layer is traced).",
    ("layer",))
# What a compiled train step's experts got, step by step: a layer's
# group_sizes leave the step as a step counter (MoELayer._fn) and
# _publish_load books them here once the host knows them, a step or two
# after the dispatch (jit.TrainStep). Labels are layers, never experts.
_ROWS = telemetry.counter(
    "mxtpu_moe_rows_total",
    "Live rows of a layer's expert dispatch over the resolved train steps: "
    "(token, expert) assignments its experts (the held ones of a held "
    "layer) got; T x k a step where every expert is held.", ("layer",))
_WINDOWS = telemetry.counter(
    "mxtpu_moe_windows_total",
    "Windows a held layer's dispatch ran a pass over the resolved train "
    "steps, ceil(live rows / W) a step: more than one a step is a router "
    "sending the held experts over twice their even share.", ("layer",))
_EXPERT_ROWS = telemetry.gauge(
    "mxtpu_moe_expert_rows",
    "Rows of a layer's least and most loaded expert in the last resolved "
    "train step (against the even load T x k / E).", ("layer", "stat"))
_STARVED = telemetry.gauge(
    "mxtpu_moe_starved_experts",
    "Experts of a layer that got no row in the last resolved train step.",
    ("layer",))


def _publish_load(layer, rows, held, window_rows, even_rows):
    """One resolved step's rows an expert of one layer into the registry
    (`collect_step_counter`'s ``publish``; ``rows`` a list of ints)."""
    live = sum(rows)
    _ROWS.inc(live, layer=layer)
    if held:
        _WINDOWS.inc(-(-live // window_rows), layer=layer)
    _EXPERT_ROWS.set(min(rows), layer=layer, stat="min")
    _EXPERT_ROWS.set(max(rows), layer=layer, stat="max")
    _STARVED.set(rows.count(0), layer=layer)


def load_balancing_loss(gates, top_idx, num_experts):
    """Switch-Transformer aux loss: E * sum_e f_e * p_e.

    gates: (T, E) softmax router probabilities; top_idx: (T, k) chosen experts.
    f_e = fraction of tokens whose FIRST choice is e; p_e = mean gate prob.
    """
    p = jnp.mean(gates, axis=0)                                   # (E,)
    f = jnp.mean(jax.nn.one_hot(top_idx[:, 0], num_experts,
                                dtype=gates.dtype), axis=0)       # (E,)
    return num_experts * jnp.sum(f * p)


def router_z_loss(logits):
    """ST-MoE router z-loss: mean(logsumexp(logits)^2) — keeps router
    logits small so the softmax stays out of its saturated/overflow-prone
    region (bf16 routers drift without it)."""
    z = jax.nn.logsumexp(logits, axis=-1)
    return jnp.mean(jnp.square(z))


# Both permutations move whole rows and are each other's inverse, so each
# one's gradient is a GATHER by the other; JAX's own rule for x[idx] is a
# scatter-add, which the TPU runs an order of magnitude slower.
@jax.custom_vjp
def _gather_sorted(tokens, order, inverse):
    """tokens (T, D) -> rows (T*k, D): row i is the token of the i-th
    assignment in expert order, tokens[order[i] // k]."""
    return tokens[order // (order.shape[0] // tokens.shape[0])]


def _gather_sorted_fwd(tokens, order, inverse):
    return _gather_sorted(tokens, order, inverse), (tokens.shape[0], inverse)


def _gather_sorted_bwd(res, g):
    n_tokens, inverse = res
    per_slot = g[inverse].reshape(n_tokens, -1, g.shape[-1])
    return per_slot.astype(jnp.float32).sum(1).astype(g.dtype), None, None


_gather_sorted.defvjp(_gather_sorted_fwd, _gather_sorted_bwd)


@jax.custom_vjp
def _unsort(rows, order, inverse):
    """rows in expert order -> rows in (token, slot) order."""
    return rows[inverse]


def _unsort_fwd(rows, order, inverse):
    return rows[inverse], order


def _unsort_bwd(order, g):
    return g[order], None, None


_unsort.defvjp(_unsort_fwd, _unsort_bwd)


def dropless_moe(tokens, top_vals, top_idx, w_up, w_down, act, w_gate=None):
    """y_t = sum_j top_vals[t, j] * FFN_{top_idx[t, j]}(tokens[t]).

    tokens (T, D); top_vals (T, k) float32; top_idx (T, k) int;
    w_up (E, D, H), w_down (E, H, D) stacked expert weights;
    FFN_e(x) = act(x w_up[e]) w_down[e], or with ``w_gate`` (E, D, H) the
    gated form (act(x w_gate[e]) * (x w_up[e])) w_down[e] (SwiGLU when
    ``act`` is silu). Every op is under one of the scopes `moe_dispatch`,
    `moe_experts`, `moe_combine`. -> (y, the rows each expert got: int32
    (E,), what the grouped matmuls ran on).
    """
    n_tokens, k = top_idx.shape
    num_experts = w_up.shape[0]
    _DISPATCHES.inc(path="dropless", combine="unsort")
    with jax.named_scope("moe_dispatch"):
        flat = top_idx.reshape(-1).astype(jnp.int32)              # (T*k,)
        slots = jnp.arange(flat.shape[0], dtype=jnp.int32)
        # two stable sorts: assignments by expert, and the way back
        _, order = jax.lax.sort_key_val(flat, slots)
        _, inverse = jax.lax.sort_key_val(order, slots)
        group_sizes = jnp.sum(
            flat[:, None] == jnp.arange(num_experts, dtype=jnp.int32),
            axis=0, dtype=jnp.int32)                              # (E,)
        rows = _gather_sorted(tokens, order, inverse)             # (T*k, D)
    with jax.named_scope("moe_experts"):
        grouped = functools.partial(jax.lax.ragged_dot,
                                    group_sizes=group_sizes)
        h = act(grouped(rows, w_up)) if w_gate is None \
            else act(grouped(rows, w_gate)) * grouped(rows, w_up)
        y = grouped(h, w_down)                                    # (T*k, D)
    with jax.named_scope("moe_combine"):
        y = _unsort(y, order, inverse).reshape(n_tokens, k, -1)
        out = jnp.sum(y.astype(jnp.float32) * top_vals[..., None], axis=1)
    return out.astype(tokens.dtype), group_sizes


def held_window_rows(n_tokens, k, count, num_experts):
    """W, the rows one window of the held dispatch handles: twice the
    T x k x count / E assignments a balanced router sends the held experts,
    in whole 1024s, and never more than the worst case T x min(k, count).
    From shapes alone; `held=(0, E)` gives T x k, one window."""
    return min(n_tokens * min(k, count),
               -(-2 * n_tokens * k * count // (1024 * num_experts)) * 1024)


def _over_windows(window, order, group_sizes, body, init):
    """`body(w, carry)` for every window w of `order` that holds a live
    row, ceil(group_sizes.sum() / window) of them (data): one `while_loop`,
    or where one window is all of `order` the body itself, once."""
    def named(w, carry):
        with jax.named_scope("moe_window"):
            return body(w, carry)

    if order.shape[0] == window:
        return named(0, init)
    n_windows = (group_sizes.sum() + window - 1) // window
    return jax.lax.while_loop(
        lambda c: c[0] < n_windows,
        lambda c: (c[0] + 1, named(c[0], c[1])),
        (jnp.int32(0), init))[1]


def _window_of(w, window, tokens, top_vals, order, group_sizes):
    """Sorted rows [w W, (w + 1) W) -> (slot, token, live, sizes, rows,
    weight): the (token, slot) assignment of each row as an index into
    T x k and its token, whether a held expert owns the row, each held
    expert's rows inside the window, the rows' tokens (W, D) and router
    weights. A dead row's token is its own position: a row to gather, and
    where its zeros are scattered no two dead rows land on one token."""
    with jax.named_scope("moe_dispatch"):
        lo = w * window
        iota = jnp.arange(window, dtype=jnp.int32)
        ends = jnp.cumsum(group_sizes)
        live = lo + iota < ends[-1]
        slot = jax.lax.dynamic_slice(order, (lo,), (window,))
        token = jnp.where(live, slot // top_vals.shape[1],
                          iota % tokens.shape[0])
        sizes = jnp.clip(ends, lo, lo + window) \
            - jnp.clip(ends - group_sizes, lo, lo + window)
        return slot, token, live, sizes, tokens[token], \
            top_vals.reshape(-1)[slot]


def _sorts_the_window(width):
    """Whether `_rows_to_tokens` sorts a window's rows to their tokens or
    leaves them to XLA's row scatter-add: from the rows' width alone, 2560
    and 3584 (the Ling and Xing cells'; any width with a prime factor over
    3) yes. On a v5e the scatter-add of W rows into (T, D) float32 takes
    0.05-0.1 us a row and 1024 elements at D = 512 .. 4096 in steps of 512,
    but 5.6 x that at 2560, 2 x at 3584 and 10 x at 5120, whatever the
    tokens; the sorted form's passes over W + T rows cost by the byte, win
    by little where the scatter-add is sound (and lose at D = 4096 and at
    W = 2 T), and so run only where it is not. Milliseconds a call, half
    the window live, bfloat16 | float32 rows, on one v5e (`chip_smoke.py
    --phases combine`; docs/PERF_NOTES.md, PR 54, also for W 4096 into T
    8192 at nine widths):

        (W, T, D, most)               scatter-add    sorted
        2048, 8192, 2560, 8 Ling      3.28 | 3.28    0.49 | 0.46
        8192, 8192, 3584, 4 Xing      2.84 | 2.48    1.49 | 1.82
        32768, 16384, 2048, 8 Keye    3.91 | 3.26    4.45 | 5.94
        4096, 8192, 4096, 8 Solar     1.19 | 1.19    1.17 | 1.80
        6144, 8192, 1024, 8 Nemotron  0.32 | 0.33    0.26 | 0.23
    """
    while width % 2 == 0:
        width //= 2
    while width % 3 == 0:
        width //= 3
    return width > 1


def _rows_to_tokens(total, rows, token, live, most):
    """total (T, D) float32 + zeros.at[token].add(rows) over the live rows
    of a window, rows (W, D), where no token has more than `most` of them.
    `rows` may be several (W, D) arrays whose float32 sum is meant (the
    backward's gradients a projection).

    One of two forms, by `_sorts_the_window`. XLA's row scatter-add, in
    place; a dead row is zeros and its token its own position, so it adds
    nothing and no two land on one token. Or one sort of the W token ids
    and two row gathers: the live rows sorted by token (a dead row's key
    is T: last, and in no token's run) lie as runs of at most `most`
    neighbours; a run is summed onto its first row by `most` - 1 shifted
    float32 adds under the mask "same key"; token t's run starts at the
    number of keys below t, a count by comparison (a binary search, or a
    gather or scatter of scalars, is serial on the TPU), and t reads that
    row where a key equals t. Each of `rows` is gathered in its own type
    and widened after. The same float32 sum either way: the order of a
    token's at most `most` additions is all that differs."""
    several = isinstance(rows, (tuple, list))
    if not _sorts_the_window(total.shape[1]):
        # (traced as up to PR 53: a pinned program stays the one it was)
        return total.at[token].add(
            sum(part.astype(jnp.float32) for part in rows) if several
            else rows.astype(jnp.float32))
    parts = rows if several else (rows,)
    window, n_tokens = parts[0].shape[0], total.shape[0]
    with jax.named_scope("rows_to_tokens"):
        key, at = jax.lax.sort_key_val(
            jnp.where(live, token, n_tokens),
            jnp.arange(window, dtype=jnp.int32))
        # most - 1 rows more, keyed as no token is, for the shifted reads
        key = jnp.pad(key, (0, most - 1), constant_values=-1)
        at = jnp.pad(at, (0, most - 1))
        parts = [part[at] for part in parts]                      # (W+, D)

        def shifted(j):
            return sum(part[j:j + window].astype(jnp.float32)
                       for part in parts)

        run = shifted(0)
        for j in range(1, most):
            run = run + jnp.where(
                (key[j:j + window] == key[:window])[:, None], shifted(j), 0)
        upto = jnp.sum(key[:window, None] <= jnp.arange(
            n_tokens, dtype=jnp.int32), axis=0, dtype=jnp.int32)   # (T,)
        below = jnp.pad(upto[:-1], (1, 0))
        return total + jnp.where((upto > below)[:, None],
                                 run[jnp.minimum(below, window - 1)], 0)


def _hidden(act, *pre):
    """The experts' hidden rows of their pre-activations: act(up), or gated
    act(gate) * up."""
    return act(pre[0]) if len(pre) == 1 else act(pre[0]) * pre[1]


def _weighted(y, weight):
    """Each expert row times its router weight, rounded to the rows' type."""
    return (y.astype(jnp.float32) * weight[:, None]).astype(y.dtype)


# A grouped matmul answers for the rows its groups own. What it leaves in
# the others is the kernel's business (zeros off the TPU, whatever was
# there on it), and those rows go on into sums: they are made zero here.
def _grouped(lhs, rhs, sizes, live):
    return jnp.where(live[:, None], jax.lax.ragged_dot(lhs, rhs, sizes), 0)


def _grouped_t(lhs, rhs, sizes, live, g):
    """Both transposes of ragged_dot(lhs, rhs, sizes) at its cotangent."""
    (d_lhs,) = jax.linear_transpose(
        lambda x: jax.lax.ragged_dot(x, rhs, sizes), lhs)(g)
    (d_rhs,) = jax.linear_transpose(
        lambda w: jax.lax.ragged_dot(lhs, w, sizes), rhs)(g)
    return jnp.where(live[:, None], d_lhs, 0), d_rhs


def _expert_rows(act, rows, projections, w_down, sizes, live):
    """A window's pre-activations (one a projection) and expert rows."""
    with jax.named_scope("moe_experts"):
        pre = tuple(_grouped(rows, m, sizes, live) for m in projections)
        return pre, _grouped(_hidden(act, *pre), w_down, sizes, live)


def _held_forward(act, window, keep, tokens, top_vals, projections, w_down,
                  order, group_sizes):
    """-> ((T, D) float32 sum, kept). With `keep` each window's
    pre-activations and expert rows are written into (windows x W)-row
    buffers for the backward; without, `kept` is empty."""
    most = min(top_vals.shape[1], w_down.shape[0])   # a token's rows

    def body(w, carry):
        total, kept = carry
        _, token, live, sizes, rows, weight = _window_of(
            w, window, tokens, top_vals, order, group_sizes)
        pre, y = _expert_rows(act, rows, projections, w_down, sizes,
                              live)                               # (W, H)
        with jax.named_scope("moe_combine"):
            total = _rows_to_tokens(total, _weighted(y, weight), token,
                                    live, most)
        return total, jax.tree.map(
            lambda buf, x: jax.lax.dynamic_update_slice(
                buf, x, (w * window, 0)), kept, (pre, y) if keep else ())

    def buffer(width):
        return jnp.zeros((order.shape[0], width), tokens.dtype)

    kept = ((buffer(w_down.shape[1]),) * len(projections),
            buffer(w_down.shape[2])) if keep else ()
    return _over_windows(window, order, group_sizes, body,
                         (jnp.zeros(tokens.shape, jnp.float32), kept))


# The held experts' sum, one window of live rows at a time. Forward and
# backward are each ONE loop over ONE body: the grouped matmuls are traced
# once a pass, however many windows run. The forward that is differentiated
# keeps what the backward reads (pre-activations and expert rows) in
# buffers a window writes its own rows of; a window that does not run
# costs those buffers' zeros and nothing else. Where those buffers would
# pass HELD_KEEP_BYTES nothing is kept and the backward's body computes its
# window's again. The moves between tokens and rows are a gather one way
# and `_rows_to_tokens` the other, W rows each: XLA's float32 row
# scatter-add, which stood here alone up to PR 53, or where that is slow
# (`_sorts_the_window`: the Ling and Xing cells' rows of 2560 and 3584) a
# sort of the window's token ids and gathers of W + T whole rows. The
# router weights' gradient is a scatter-add of W scalars at distinct slots.
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _held_sum(act, window, tokens, top_vals, projections, w_down, order,
              group_sizes):
    """tokens (T, D), top_vals (T, k), projections (w_up,) or (w_gate,
    w_up), order (windows x W,) the sorted assignments' slots, group_sizes
    (count,) -> (T, D) float32."""
    return _held_forward(act, window, False, tokens, top_vals, projections,
                         w_down, order, group_sizes)[0]


#: what the held dispatch may keep of its forward for the backward, in bytes
#: of the buffers of all the windows' rows. Beyond it the backward computes
#: a running window's pre-activations and expert rows again: W rows of
#: grouped matmuls a window, against buffers of T x min(k, count) rows that
#: a balanced router fills to a fortieth (8 of 320 SwiGLU experts 1280 wide
#: on 4096: 872 MB a layer, twice while the loop is entered; 8 of 512 in a
#: 1024-wide latent space keep their 486 MB)
HELD_KEEP_BYTES = 512 * 2 ** 20


def _keeps(tokens, projections, w_down, order):
    """Whether the forward's pre-activations and expert rows are kept for
    the backward (from shapes alone)."""
    widths = len(projections) * w_down.shape[1] + w_down.shape[2]
    return order.shape[0] * widths * tokens.dtype.itemsize <= HELD_KEEP_BYTES


def _held_sum_fwd(act, window, *args):
    tokens, _, projections, w_down, order, _ = args
    total, kept = _held_forward(
        act, window, _keeps(tokens, projections, w_down, order), *args)
    return total, args + (kept,)


def _held_sum_bwd(act, window, res, g):
    tokens, top_vals, projections, w_down, order, group_sizes, kept = res
    most = min(top_vals.shape[1], w_down.shape[0])

    def body(w, sums):
        d_tokens, d_vals, d_projections, d_w_down = sums
        slot, token, live, sizes, rows, weight = _window_of(
            w, window, tokens, top_vals, order, group_sizes)
        if kept:
            pre, y = jax.tree.map(
                lambda buf: jax.lax.dynamic_slice(
                    buf, (w * window, 0), (window, buf.shape[1])), kept)
        else:
            pre, y = _expert_rows(act, rows, projections, w_down, sizes,
                                  live)
        with jax.named_scope("moe_combine"):
            _, pull = jax.vjp(_weighted, y, weight)
            d_y, d_weight = pull(g[token].astype(y.dtype))
        with jax.named_scope("moe_experts"):
            h, pull = jax.vjp(functools.partial(_hidden, act), *pre)
            d_h, d_down = _grouped_t(h, w_down, sizes, live, d_y)
            d_rows, d_ms = zip(*(_grouped_t(rows, m, sizes, live, d)
                                 for m, d in zip(projections, pull(d_h))))
        with jax.named_scope("moe_dispatch"):
            d_tokens = _rows_to_tokens(d_tokens, d_rows, token, live,
                                       most)
            d_vals = d_vals.at[slot].add(d_weight)
        return d_tokens, d_vals, tuple(
            total + d.astype(jnp.float32)
            for total, d in zip(d_projections, d_ms)), \
            d_w_down + d_down.astype(jnp.float32)

    primals = (tokens, top_vals.reshape(-1), projections, w_down)
    sums = _over_windows(
        window, order, group_sizes, body,
        jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), primals))
    d_tokens, d_vals, d_projections, d_w_down = jax.tree.map(
        lambda d, x: d.astype(x.dtype), sums, primals)
    return (d_tokens, d_vals.reshape(top_vals.shape), d_projections,
            d_w_down, None, None)


_held_sum.defvjp(_held_sum_fwd, _held_sum_bwd)


def dropless_moe_held(tokens, top_vals, top_idx, w_up, w_down, act, first,
                      num_experts, w_gate=None):
    """The held experts' part of `dropless_moe`'s sum:
    y_t = sum_{j: first <= top_idx[t, j] < first + count} top_vals[t, j] *
    FFN_{top_idx[t, j]}(tokens[t]), with the stacked weights those of the
    `count` = w_up.shape[0] experts from `first` on, of `num_experts` the
    router chooses among.

    The T x k assignments are sorted so that the held experts' come first,
    in expert order, and handled in windows of W = `held_window_rows(T, k,
    count, num_experts)` sorted rows: a window gathers its rows' tokens,
    runs the grouped matmuls with each held expert's rows inside it as
    `group_sizes`, weighs and rounds each row to the tokens' type and adds
    it into its token's float32 sum (`_rows_to_tokens`: by the rows' width
    a sort of the window's token ids and two row gathers, or XLA's row
    scatter-add; the backward's sum of the rows' gradients into the
    tokens' is the same function). As many windows run as hold a live row
    (`lax.while_loop`: one where the router is anywhere near balanced,
    T x min(k, count) / W at worst), so nothing is dropped at any load, and
    every op works on W rows, the sorted form's count of keys below a token
    on W x T of their ids (what the backward reads of the forward is kept
    in buffers of all the windows' rows, which a window that runs writes
    its part of, or computed again where those buffers would pass
    `HELD_KEEP_BYTES`). Where W is the worst case there is no loop.
    Scopes as in `dropless_moe`, inside `moe_window`. -> (y, the rows each
    held expert got: int32 (count,), what the windows ran on).
    """
    n_tokens, k = top_idx.shape
    count = w_up.shape[0]
    worst = n_tokens * min(k, count)
    window = held_window_rows(n_tokens, k, count, num_experts)
    n_windows = -(-worst // window)
    _DISPATCHES.inc(path="dropless_held", combine="sort"
                    if _sorts_the_window(tokens.shape[1]) else "scatter")
    with jax.named_scope("moe_dispatch"):
        local = top_idx.astype(jnp.int32) - first                 # (T, k)
        key = jnp.where((local >= 0) & (local < count), local,
                        count).reshape(-1)                        # (T*k,)
        slots = jnp.arange(key.shape[0], dtype=jnp.int32)
        # a stable sort: held assignments first, by expert
        _, order = jax.lax.sort_key_val(key, slots)
        group_sizes = jnp.sum(
            key[:, None] == jnp.arange(count, dtype=jnp.int32),
            axis=0, dtype=jnp.int32)                              # (count,)
        # no live row lies past the worst case; whole windows
        order = jnp.pad(order[:worst], (0, n_windows * window - worst))
    out = _held_sum(act, window, tokens, top_vals,
                    (w_up,) if w_gate is None else (w_gate, w_up), w_down,
                    order, group_sizes)
    return out.astype(tokens.dtype), group_sizes


class _StackedXavier(initializer.Initializer):
    """Xavier (uniform, avg) for E stacked (fan_in, fan_out) matrices:
    every expert gets the scale its own matrix would. Xavier itself reads a
    3-D shape as a convolution kernel's and would scale 64 experts of
    2048 x 1024 down 26-fold."""

    def _init_weight(self, name, arr):
        _, fan_in, fan_out = arr.shape
        scale = math.sqrt(3.0 / ((fan_in + fan_out) / 2.0))
        arr._data = nd.random.uniform(-scale, scale, arr.shape).astype(
            arr.dtype)._data


def relu2(x):
    """relu(x)^2 (Nemotron-H's `mlp_hidden_act`)."""
    return jnp.square(jax.nn.relu(x))


_ACTIVATIONS = {"relu": jax.nn.relu, "gelu": jax.nn.gelu,
                "silu": jax.nn.silu, "relu2": relu2}
_ROUTERS = ("softmax", "sigmoid_bias")


def _is_chosen(top_idx, num_experts):
    """(T, k) expert ids -> (T, k, E) bool, True at [t, j, top_idx[t, j]]."""
    return top_idx[..., None] == jnp.arange(num_experts, dtype=top_idx.dtype)


# The chosen experts' scores, read out of `gates` by comparing expert ids:
# what `jnp.take_along_axis(gates, top_idx, -1)` and its transpose, a
# scatter-add into (T, E), give to the bit, because a row's ids are distinct
# and so each reduction below has ONE term that counts. Elementwise passes
# with a reduction, fused; XLA's gather and scatter step an element at a
# time (1.6 ms for 8192 x 22 of 512, fifteen times a Nemotron step). The
# value is a maximum and not a sum so that no sum over k behind it (the
# renormalisation's) can be folded into it and take another order.
@jax.custom_jvp
def _chosen_scores(gates, top_idx):
    """gates (T, E), top_idx (T, k) distinct a row -> (T, k):
    gates[t, top_idx[t, j]]."""
    return jnp.max(jnp.where(_is_chosen(top_idx, gates.shape[-1]),
                             gates[:, None, :], -jnp.inf), -1)


@_chosen_scores.defjvp
def _chosen_scores_jvp(primals, tangents):
    gates, top_idx = primals
    # linear in d gates; its transpose, which autodiff derives, is
    # d gates[t, e] = sum_j where(chosen, d top_vals[t, j], 0)
    return _chosen_scores(gates, top_idx), jnp.sum(
        jnp.where(_is_chosen(top_idx, gates.shape[-1]),
                  tangents[0][:, None, :], 0.0), -1)


class MoELayer(HybridBlock):
    """Top-k routed expert FFN, dropless: y = sum_k g_k * FFN_{e_k}(x).

    ``router="softmax"``: softmax(x Wr) over all experts in float32; the k
    largest probabilities weigh the chosen experts' outputs, renormalised
    to sum to one when ``norm_topk_prob`` (the Switch/GShard convention and
    the default) and used as they are otherwise (OLMoE, Mixtral-style
    configurations with ``norm_topk_prob: false``).
    ``router="sigmoid_bias"`` (DeepSeek-V3, Nemotron-H): s = sigmoid(x Wr)
    in float32; the k experts are those of the largest s + b, where b
    (``router_bias``, float32, not trained by the gradient: the source
    moves it by a load-balancing rule between steps) only CHOOSES; the
    weights are the chosen s themselves, over their sum (+ 1e-20) when
    ``norm_topk_prob``. Either router's weights are then times ``scale``
    (``routed_scaling_factor``).

    Weights, stacked over experts with E sharded over ``ep_axis``:
    ``w1`` (E, D, H) and ``w2`` (E, H, D); ``gated=True`` adds ``w3``
    (E, D, H) and the experts become act(x w1) * (x w3) -> w2 (SwiGLU with
    activation='silu'); ``activation="relu2"`` is relu(x)^2. ``forward``
    returns the output only; ``forward_with_aux`` also the load-balancing +
    z loss for the trainer to add to the task loss. Every token reaches all
    its k experts (`dropless_moe`): there is no capacity and no second
    dispatch. A shared expert is the caller's to add beside this layer
    (`models.nemotron_h.LatentMoE`, `models.solar_open2.SharedExpertMoE`).

    ``held=(first, count)``: this layer holds the experts first ..
    first + count - 1 of ``num_experts`` and the stacked weights are
    (count, ..). The router is as wide as ever and chooses among all; the
    output is the held experts' part of the sum (`dropless_moe_held`),
    normalised over all k chosen. ``held=None`` holds every expert and is
    the path above, unchanged. ``router_units`` is the width of what the
    router reads where that is not the experts' input (``forward(x,
    route_on)``: a latent mixture routes on the full-width activations).

    ``bias_rate`` (``sigmoid_bias`` only): the load-balancing rule itself.
    ``forward`` then returns (y, b') with b' = b + bias_rate * ln(even load
    / load) over this call's choices (an expert nobody chose counts as
    chosen once): what b becomes for the next step once the caller hands
    it to `move_bias`, which it does OUTSIDE any recomputed block. Without
    the rule Adam moves a router off its balance within tens of steps
    (PERF.md section 6, PR 38: a share's held experts lost every row).

    ``n_group``, ``topk_group`` (``sigmoid_bias`` only; DeepSeek-V3's
    `noaux_tc`): the experts are ``n_group`` groups of consecutive ones (a
    host's, in an expert-parallel layout), a group's score is the sum of
    its 2 largest s + b, and the k experts are the largest s + b of the
    ``topk_group`` best groups only: a token's experts lie on at most that
    many hosts. Ties go to the lower index, of groups as of experts. The
    weights are as ever the chosen s. ``held=`` and ``bias_rate=`` compose
    with it: the limit changes which k are chosen and nothing behind the
    choice. None (the default) is no limit, the router it was. What the
    node limit exists for, the exchange of rows between hosts, is not
    written (ROADMAP 2a). Scope `router_groups` inside `router`.
    """

    def __init__(self, num_experts, hidden_size, ffn_hidden, top_k=2,
                 ep_axis="ep", activation="relu", gated=False,
                 norm_topk_prob=True, z_loss_coef=1e-3,
                 capacity_factor=None, router="softmax", scale=1.0,
                 held=None, router_units=None, bias_rate=None, n_group=None,
                 topk_group=None, **kwargs):
        super().__init__(**kwargs)
        if capacity_factor is not None:
            import warnings
            warnings.warn(
                "MoELayer(capacity_factor=%r) is ignored: the one dispatch "
                "left is dropless — every token reaches its top-%d experts "
                "in T*k static rows, and no capacity can drop one"
                % (capacity_factor, top_k), stacklevel=2)
        if not 1 <= top_k <= num_experts:
            raise ValueError("top_k=%d of %d experts" % (top_k, num_experts))
        if router not in _ROUTERS:
            raise ValueError("router=%r (one of %s)" % (router, _ROUTERS))
        if bias_rate is not None and router != "sigmoid_bias":
            raise ValueError("bias_rate moves the selection bias of "
                             "router='sigmoid_bias'; router=%r has none"
                             % router)
        if (n_group is None) != (topk_group is None) or (
                n_group is not None and (
                    router != "sigmoid_bias" or num_experts % n_group
                    or not 1 <= topk_group <= n_group
                    or num_experts // n_group < 2
                    or top_k > topk_group * (num_experts // n_group))):
            raise ValueError(
                "n_group=%r, topk_group=%r: router='sigmoid_bias', groups "
                "of at least 2 that divide %d experts, and top_k=%d experts "
                "within topk_group of them" % (n_group, topk_group,
                                               num_experts, top_k))
        if held is not None and not (
                0 <= held[0] and held[1] >= 1
                and held[0] + held[1] <= num_experts):
            raise ValueError("held=%r of %d experts" % (held, num_experts))
        self.num_experts = num_experts
        self.top_k = top_k
        self.norm_topk_prob = norm_topk_prob
        self.z_loss_coef = z_loss_coef
        self.held = held
        self._act = activation
        self._gated = gated
        self._router = router
        self._scale = scale
        self._bias_rate = bias_rate
        self._groups = None if n_group is None else (n_group, topk_group)
        n_held = num_experts if held is None else held[1]
        stacked = {"w1": (n_held, hidden_size, ffn_hidden),
                   "w2": (n_held, ffn_hidden, hidden_size)}
        if gated:
            stacked["w3"] = stacked["w1"]
        with self.name_scope():
            self.gate_weight = self.params.get(
                "gate_weight", shape=(num_experts, router_units or hidden_size),
                init="xavier")
            if router == "sigmoid_bias":
                self.router_bias = self.params.get(
                    "router_bias", shape=(num_experts,), init="zeros",
                    grad_req="null")
            for name, shape in stacked.items():
                param = self.params.get(name, shape=shape,
                                        init=_StackedXavier())
                param.sharding = P(ep_axis, None, None)
                setattr(self, name, param)

    def cast(self, dtype):
        super().cast(dtype)
        if self._router == "sigmoid_bias":
            # it is added to float32 scores and only chooses
            self.router_bias.cast("float32")

    def _in_kept_groups(self, choosing):
        """choosing (T, E) = s + b -> the same with -inf outside each
        token's ``topk_group`` best groups. A group's score is the sum of
        its two largest entries (two maxima, the first's place masked for
        the second); a group is kept where fewer than ``topk_group`` others
        beat it, an equal score of a lower index counting as beating: what
        a stable top-k of the group scores keeps, without a sort."""
        n_group, topk_group = self._groups
        _GROUP_LIMITED.inc()
        with jax.named_scope("router_groups"):
            by_group = jax.lax.stop_gradient(choosing).reshape(
                choosing.shape[0], n_group, -1)
            place = jax.lax.broadcasted_iota(jnp.int32, by_group.shape, 2)
            first = jnp.max(by_group, -1, keepdims=True)
            at = jnp.min(jnp.where(by_group == first, place,
                                   by_group.shape[-1]), -1, keepdims=True)
            score = first[..., 0] + jnp.max(
                jnp.where(place == at, -jnp.inf, by_group), -1)   # (T, G)
            mine, other = score[:, :, None], score[:, None, :]
            g = jnp.arange(n_group)
            beaten_by = jnp.sum((other > mine) | (
                (other == mine) & (g[None, :] < g[:, None])), -1)
            kept = beaten_by < topk_group                         # (T, G)
            return jnp.where(kept[..., None], by_group, -jnp.inf).reshape(
                choosing.shape)

    def choose(self, gates, bias):
        """``sigmoid_bias``: gates (T, E) float32 -> the ids (T, k) of the
        k largest gates + bias, under the group limit where there is one
        (what a builder's balancing of the bias counts loads with)."""
        choosing = gates + bias.astype(jnp.float32)
        if self._groups is not None:
            choosing = self._in_kept_groups(choosing)
        return jax.lax.top_k(choosing, self.top_k)[1]

    def route(self, tokens, gw, bias=None):
        """tokens (T, D), gw (E, D) -> (logits, gates, top_vals, top_idx),
        all float32 but the indices: the matmul accumulates in float32 and
        the scores and top-k never see a narrower type."""
        logits = jnp.einsum("td,ed->te", tokens, gw,
                            preferred_element_type=jnp.float32)
        if self._router == "softmax":
            gates = jax.nn.softmax(logits, axis=-1)
            top_vals, top_idx = jax.lax.top_k(gates, self.top_k)
            if self.norm_topk_prob:
                top_vals = top_vals / jnp.sum(top_vals, -1, keepdims=True)
        else:
            gates = jax.nn.sigmoid(logits)
            top_idx = self.choose(gates, bias)
            top_vals = _chosen_scores(gates, top_idx)
            if self.norm_topk_prob:
                top_vals = top_vals / (jnp.sum(top_vals, -1, keepdims=True)
                                       + 1e-20)
        if self._scale != 1.0:
            top_vals = top_vals * self._scale
        return logits, gates, top_vals, top_idx

    def _fn(self, arrays, compute_aux):
        """``arrays``: {name: data} of the call's inputs (``x``, where the
        router reads something else ``route_on``) and ``_weight_names``."""
        xd = arrays["x"]
        shape = xd.shape
        tokens = xd.reshape(-1, shape[-1])                        # (T, D)
        seen = arrays.get("route_on")
        seen = tokens if seen is None else seen.reshape(-1, seen.shape[-1])
        # the softmax router is called as it always was: (tokens, gw)
        bias = (arrays["router_bias"],) if "router_bias" in arrays else ()
        with jax.named_scope("router"):
            logits, gates, top_vals, top_idx = self.route(
                seen, arrays["gate_weight"], *bias)
        # gated: w1 is the activated (gate) projection, w3 the linear one
        w_gate, w_up = (arrays["w1"], arrays["w3"]) if self._gated \
            else (None, arrays["w1"])
        act = _ACTIVATIONS[self._act]
        if self.held is None:
            window = top_idx.size       # all T x k rows, one pass over them
            out, rows = dropless_moe(tokens, top_vals, top_idx, w_up,
                                     arrays["w2"], act, w_gate)
        else:
            window = held_window_rows(*top_idx.shape, self.held[1],
                                      self.num_experts)
            _WINDOW_ROWS.set(window, layer=self.name)
            out, rows = dropless_moe_held(tokens, top_vals, top_idx, w_up,
                                          arrays["w2"], act, self.held[0],
                                          self.num_experts, w_gate)
        # a compiled train step hands this step's rows out (jit.TrainStep)
        _functional.collect_step_counter(
            self.name, rows, _publish_load, held=self.held is not None,
            window_rows=window, even_rows=top_idx.size / self.num_experts)
        out = out.reshape(shape)
        if compute_aux:
            with jax.named_scope("router"):
                aux = load_balancing_loss(gates, top_idx, self.num_experts) \
                    + self.z_loss_coef * router_z_loss(logits)
            return out, aux
        if self._bias_rate is not None:
            with jax.named_scope("router"):
                load = jnp.sum(
                    top_idx.reshape(-1, 1) == jnp.arange(
                        self.num_experts, dtype=top_idx.dtype),
                    axis=0, dtype=jnp.float32)
                moved = arrays["router_bias"] + self._bias_rate * jnp.log(
                    top_idx.size / self.num_experts / jnp.maximum(load, 1.0))
            return out, moved
        return out

    def _weight_names(self):
        return ("gate_weight",) \
            + (("router_bias",) if self._router == "sigmoid_bias" else ()) \
            + ("w1", "w2") + (("w3",) if self._gated else ())

    def _weights(self):
        return [getattr(self, n).data() for n in self._weight_names()]

    def _call(self, x, route_on, compute_aux):
        given = {"x": x} if route_on is None else {"x": x,
                                                   "route_on": route_on}
        names = tuple(given) + self._weight_names()
        return _apply(
            lambda *datas: self._fn(dict(zip(names, datas)), compute_aux),
            *given.values(), *self._weights())

    def forward(self, x, route_on=None):
        """x: (..., D) → (..., D); the router reads ``route_on``
        (..., router_units) where given, else x. With ``bias_rate`` →
        (y, the selection bias as the rule moves it)."""
        return self._call(x, route_on, False)

    def move_bias(self, moved):
        """``moved`` (``forward``'s second output) becomes the selection
        bias: when the step ends inside a compiled train step (an
        auxiliary update, as BatchNorm's running statistics are), at once
        in eager training, never outside training."""
        if not autograd.is_training():
            return
        bias = self.router_bias.data()
        if _functional.in_functional_mode():
            _functional.collect_aux_update(bias, moved._data)
        else:
            bias._data = moved._data

    def forward_with_aux(self, x, route_on=None):
        """Returns (y, aux) where aux = Switch load-balancing loss +
        z_loss_coef * ST-MoE router z-loss (add to the task loss)."""
        return self._call(x, route_on, True)
