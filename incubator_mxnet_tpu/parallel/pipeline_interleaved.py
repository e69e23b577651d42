"""Interleaved (virtual-stage) 1F1B pipeline schedule — NEW capability
(SURVEY §2.5; the reference has no pipeline parallelism at all).

Megatron-LM-style interleaving (arXiv:2104.04473 §2.2): each of the p
devices hosts ``v`` model CHUNKS (virtual stages), so the model is cut into
V = v*p stages of w/v work each.  The pipeline fill still takes ~p*w of
wall-clock, but during it every device works on OTHER microbatches' chunks,
so the idle (bubble) time per device shrinks ~v-fold:
bubble ≈ (p-1)/(v*m) of the step vs (p-1)/m non-interleaved.

Implementation: the schedule is computed AT TRACE TIME by a greedy list
scheduler over the op DAG (one op per device per tick, +1-ring activation /
-1-ring gradient hops with 1-tick latency, 1F1B drain priority: backwards
run as soon as ready).  The resulting static tick tables (op / chunk /
micro / arrival per device) ride the compiled program as small int32
arrays; the SPMD body just indexes them with (tick, axis_index) and runs
the predicated F/B — so the schedule is data, not control flow, and XLA
compiles ONE tick body (lax.fori_loop) regardless of m, p, v.

``schedule_stats`` exposes the exact bubble fraction of any schedule
(idle device-ticks / total device-ticks) — the committed numbers in
docs/PERF_PIPELINE.md come from it, weighted by measured F/B tick costs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as onp
from jax import lax, shard_map
from jax.lax import axis_size, pcast
from jax.sharding import PartitionSpec as P

__all__ = ["interleaved_schedule", "schedule_stats",
           "pipeline_interleaved_grads", "schedule_1f1b", "schedule_gpipe"]


# ------------------------------------------------------------- scheduler
def interleaved_schedule(m, p, v):
    """Greedy 1F1B list schedule for m microbatches, p devices, v chunks.

    Returns a list of ticks; each tick is a list of p entries
    ``None | ('F'|'B', chunk, micro)``.  Dependency model (1-tick ring
    latency, matching the executor's ppermute placement):

    * F(S, i) needs F(S-1, i) to have finished by tick t-1 (activation
      arrives at t); F(0, i) is always ready.
    * B(S, i) needs B(S+1, i) finished by t-1 (cotangent arrives at t);
      B(V-1, i) needs F(V-1, i) finished by t-1 (its stash slot written).
    * One op per device per tick; B preferred over F (1F1B drain rule),
      lower micro first, then lower chunk (FIFO).
    """
    V = v * p
    done_F = {}   # (S, i) -> finish tick
    done_B = {}
    ticks = []
    total = 2 * V * m
    ndone = 0
    t = 0
    while ndone < total:
        row = [None] * p
        for d in range(p):
            best = None
            # backwards first (1F1B), FIFO by micro then chunk
            for c in range(v - 1, -1, -1):
                S = c * p + d
                for i in range(m):
                    if (S, i) in done_B:
                        continue
                    if S == V - 1:
                        ready = done_F.get((S, i), t) < t
                    else:
                        ready = done_B.get((S + 1, i), t) < t
                    if ready:
                        cand = ("B", c, i)
                        if best is None or (cand[2], cand[1]) < \
                                (best[2], best[1]):
                            best = cand
                        break   # FIFO in i for this chunk
            if best is None:
                for c in range(v):
                    S = c * p + d
                    for i in range(m):
                        if (S, i) in done_F:
                            continue
                        ready = S == 0 or done_F.get((S - 1, i), t) < t
                        if ready:
                            cand = ("F", c, i)
                            if best is None or (cand[2], cand[1]) < \
                                    (best[2], best[1]):
                                best = cand
                            break
            if best is not None:
                typ, c, i = best
                S = c * p + d
                if typ == "F":
                    done_F[(S, i)] = t
                else:
                    done_B[(S, i)] = t
                ndone += 1
                row[d] = best
        ticks.append(row)
        t += 1
        assert t < 8 * total + 64, "scheduler livelock"
    return ticks


def schedule_1f1b(m, p):
    """Non-interleaved 1F1B = interleaved with v=1 (same dependency model)."""
    return interleaved_schedule(m, p, 1)


def schedule_gpipe(m, p):
    """GPipe: all forwards, then all backwards (synchronous flush) —
    expressed in the same tick table format for comparable stats."""
    ticks = []
    # forward wave
    for t in range(m + p - 1):
        row = [None] * p
        for d in range(p):
            i = t - d
            if 0 <= i < m:
                row[d] = ("F", 0, i)
        ticks.append(row)
    # backward wave (reverse ring)
    for t in range(m + p - 1):
        row = [None] * p
        for d in range(p):
            i = t - (p - 1 - d)
            if 0 <= i < m:
                row[d] = ("B", 0, i)
        ticks.append(row)
    return ticks


def schedule_stats(ticks, p, f_cost=1.0, b_cost=2.0):
    """Bubble fraction of a schedule, cost-weighted (backward ≈ 2x forward).

    Tick duration = the max op cost issued that tick (devices are
    lock-stepped by the ring); idle time = Σ_device (step − busy)."""
    step = 0.0
    busy = [0.0] * p
    for row in ticks:
        dur = max([f_cost if op[0] == "F" else b_cost
                   for op in row if op] or [0.0])
        step += dur
        for d in range(p):
            if row[d]:
                busy[d] += f_cost if row[d][0] == "F" else b_cost
    total = step * p
    return {
        "ticks": len(ticks),
        "step_cost": step,
        "bubble_fraction": (total - sum(busy)) / total,
        "per_device_busy": busy,
    }


# ------------------------------------------------------------- executor
def _stash_bound(ticks, p, v, m):
    """Exact stash-slot bound from the schedule: the max number of
    microbatches simultaneously in flight through any (device, chunk)'s
    forward-input / arrived-activation / arrived-cotangent windows.  The
    greedy scheduler issues FIFO per stage, so in-flight micros form a
    contiguous index range and ``i % K`` slots never collide for
    K >= the window size.  This is what makes interleaved memory bounded
    by the SCHEDULE depth (~p + v) instead of n_microbatches."""
    V = v * p
    fin_F, fin_B = {}, {}
    for t, row in enumerate(ticks):
        for d, op in enumerate(row):
            if op:
                typ, c, i = op
                (fin_F if typ == "F" else fin_B)[(c * p + d, i)] = t
    bound = 1
    T = len(ticks)
    for S in range(V):
        windows = [(lambda i: fin_F[(S, i)], lambda i: fin_B[(S, i)]),
                   (lambda i: (fin_B[(S + 1, i)] + 1) if S < V - 1
                    else fin_F[(S, i)], lambda i: fin_B[(S, i)])]
        if S > 0:
            # arrived-activation window; stage 0 has NO ring arrival (its
            # input is read straight from the replicated x_mb at F time),
            # so no window — counting one would make the bound linear in m
            windows.append((lambda i: fin_F[(S - 1, i)] + 1,
                            lambda i: fin_F[(S, i)]))
        for lo_fn, hi_fn in windows:
            events = [(lo_fn(i), hi_fn(i)) for i in range(m)]
            for t in range(T):
                live = sum(1 for lo, hi in events if lo <= t <= hi)
                bound = max(bound, live)
    return bound


def _tables(ticks, p, v, m):
    """Static numpy tick tables for the SPMD body (+ arrival decode)."""
    T = len(ticks)
    V = v * p
    op = onp.zeros((T, p), onp.int32)       # 0 none, 1 F, 2 B
    chk = onp.zeros((T, p), onp.int32)
    mic = onp.zeros((T, p), onp.int32)
    for t, row in enumerate(ticks):
        for d in range(p):
            if row[d]:
                typ, c, i = row[d]
                op[t, d] = 1 if typ == "F" else 2
                chk[t, d] = c
                mic[t, d] = i
    # arrivals at tick t on device d = neighbour's op at t-1
    arrF = onp.zeros((T, p), onp.int32)     # 1 if an activation arrives
    arrF_c = onp.zeros((T, p), onp.int32)   # destination chunk
    arrF_i = onp.zeros((T, p), onp.int32)
    arrB = onp.zeros((T, p), onp.int32)
    arrB_c = onp.zeros((T, p), onp.int32)
    arrB_i = onp.zeros((T, p), onp.int32)
    for t in range(1, T):
        for d in range(p):
            src = (d - 1) % p
            if op[t - 1, src] == 1:
                S = chk[t - 1, src] * p + src
                if S < V - 1:               # last stage's output: no consumer
                    arrF[t, d] = 1
                    arrF_c[t, d] = (S + 1) // p
                    arrF_i[t, d] = mic[t - 1, src]
            src = (d + 1) % p
            if op[t - 1, src] == 2:
                S = chk[t - 1, src] * p + src
                if S > 0:
                    arrB[t, d] = 1
                    arrB_c[t, d] = (S - 1) // p
                    arrB_i[t, d] = mic[t - 1, src]
    return [onp.asarray(a) for a in
            (op, chk, mic, arrF, arrF_c, arrF_i, arrB, arrB_c, arrB_i)]


def _interleaved_sharded(x_mb, y_mb, stacked_params, tables, stage_fn,
                         loss_fn, axis_name, v, m, kslots):
    """SPMD body: execute the static tick tables on the pp ring."""
    p = axis_size(axis_name)
    d = lax.axis_index(axis_name)
    V = v * p
    # local params: (v, 1, ...) -> per-chunk pytree list indexed by c
    params = jax.tree_util.tree_map(lambda q: q[:, 0], stacked_params)
    mb_shape = x_mb.shape[1:]
    (opT, chkT, micT, arrF, arrFc, arrFi, arrB, arrBc, arrBi) = [
        jnp.asarray(a) for a in tables]
    T = opT.shape[0]

    def tick(t, carry):
        (a_in, g_in, a_stash, f_stash, g_stash, pgrads, dx_buf,
         loss_acc) = carry
        # ---- bank arrivals (activation from d-1, cotangent from d+1)
        a_stash = lax.cond(
            arrF[t, d] == 1,
            lambda st: st.at[arrFc[t, d], arrFi[t, d] % kslots].set(a_in),
            lambda st: st, a_stash)
        g_stash = lax.cond(
            arrB[t, d] == 1,
            lambda st: st.at[arrBc[t, d], arrBi[t, d] % kslots].set(g_in),
            lambda st: st, g_stash)

        c, i = chkT[t, d], micT[t, d]
        S = c * p + d
        prm = jax.tree_util.tree_map(lambda q: q[c], params)

        def do_F(f_stash):
            inp = jnp.where(S == 0, x_mb[i], a_stash[c, i % kslots])
            out = stage_fn(prm, inp)
            return out, f_stash.at[c, i % kslots].set(inp)

        def no_F(f_stash):
            return jnp.zeros(mb_shape, x_mb.dtype), f_stash

        a_out, f_stash = lax.cond(opT[t, d] == 1, do_F, no_F, f_stash)

        def do_B(pgrads, dx_buf, loss_acc):
            binp = f_stash[c, i % kslots]

            def last_branch(binp):
                lv, vjp = jax.vjp(
                    lambda q, x: loss_fn(stage_fn(q, x), y_mb[i]),
                    prm, binp)
                dpar, dx = vjp(jnp.ones_like(lv))
                return lv.astype(jnp.float32), dpar, dx

            def mid_branch(binp):
                lv, vjp = jax.vjp(
                    lambda q, x: jnp.vdot(
                        stage_fn(q, x).astype(jnp.float32),
                        lax.stop_gradient(g_stash[c, i % kslots]).astype(
                            jnp.float32)),
                    prm, binp)
                dpar, dx = vjp(jnp.float32(1.0))
                return jnp.float32(0.0), dpar, dx

            lv, dpar, dx = lax.cond(S == V - 1, last_branch, mid_branch,
                                    binp)
            pgrads = jax.tree_util.tree_map(
                lambda g, dp: g.at[c].add(dp), pgrads, dpar)
            dx_buf = jnp.where(S == 0, dx_buf.at[i].set(dx), dx_buf)
            return dx, pgrads, dx_buf, loss_acc + lv

        def no_B(pgrads, dx_buf, loss_acc):
            return (jnp.zeros(mb_shape, x_mb.dtype), pgrads, dx_buf,
                    loss_acc)

        g_out, pgrads, dx_buf, loss_acc = lax.cond(
            opT[t, d] == 2, do_B, no_B, pgrads, dx_buf, loss_acc)

        a_in = lax.ppermute(a_out, axis_name,
                            [(j, (j + 1) % p) for j in range(p)])
        g_in = lax.ppermute(g_out.astype(x_mb.dtype), axis_name,
                            [(j, (j - 1) % p) for j in range(p)])
        return (a_in, g_in, a_stash, f_stash, g_stash, pgrads, dx_buf,
                loss_acc)

    zeros_mb = jnp.zeros(mb_shape, x_mb.dtype)

    def vary(x):
        return pcast(x, (axis_name,), to="varying")

    carry0 = (
        vary(zeros_mb), vary(zeros_mb),
        vary(jnp.zeros((v, kslots) + mb_shape, x_mb.dtype)),
        vary(jnp.zeros((v, kslots) + mb_shape, x_mb.dtype)),
        vary(jnp.zeros((v, kslots) + mb_shape, x_mb.dtype)),
        jax.tree_util.tree_map(
            lambda q: vary(jnp.zeros_like(q, jnp.float32)), params),
        vary(jnp.zeros((m,) + mb_shape, x_mb.dtype)),
        vary(jnp.float32(0.0)),
    )
    out = lax.fori_loop(0, T, tick, carry0)
    pgrads, dx_buf, loss_acc = out[5], out[6], out[7]
    loss = lax.psum(jnp.where(d == p - 1, loss_acc, 0.0), axis_name) / m
    dx_buf = lax.psum(jnp.where(d == 0, dx_buf, jnp.zeros_like(dx_buf)),
                      axis_name)
    pgrads = jax.tree_util.tree_map(lambda g: g[:, None], pgrads)
    return loss, pgrads, dx_buf


def pipeline_interleaved_grads(stage_fn, loss_fn, stacked_params, x, y,
                               mesh, n_microbatches, v, axis="pp"):
    """Interleaved-1F1B train-step core.

    ``stacked_params``: leading dims (v, p) — chunk-major; virtual stage
    S = c*p + d runs chunk c's slice on device d, so a microbatch flows
    device 0..p-1 through chunk 0, wraps the ring, then chunk 1, etc.
    Returns (mean loss, param grads (v, p, ...), input grads) — the same
    contract as pipeline_1f1b_grads, which is this with v=1.
    """
    from jax.sharding import NamedSharding

    p = int(mesh.shape[axis])
    leaves = jax.tree_util.tree_leaves(stacked_params)
    if leaves and leaves[0].shape[:2] != (v, p):
        raise ValueError("stacked_params leading dims must be (v=%d, p=%d)"
                         % (v, p))
    if x.shape[0] % n_microbatches:
        raise ValueError("batch %d not divisible by n_microbatches %d"
                         % (x.shape[0], n_microbatches))
    m = n_microbatches
    mb = x.shape[0] // m
    x_mb = x.reshape((m, mb) + x.shape[1:])
    y_mb = y.reshape((m, mb) + y.shape[1:])
    ticks = interleaved_schedule(m, p, v)
    tables = _tables(ticks, p, v, m)
    kslots = _stash_bound(ticks, p, v, m)
    param_specs = jax.tree_util.tree_map(
        lambda q: P(None, axis, *([None] * (q.ndim - 2))), stacked_params)
    x_mb = jax.device_put(x_mb, NamedSharding(mesh, P()))
    y_mb = jax.device_put(y_mb, NamedSharding(mesh, P()))
    stacked_params = jax.tree_util.tree_map(
        lambda q, sp: jax.device_put(q, NamedSharding(mesh, sp)),
        stacked_params, param_specs)
    fn = functools.partial(_interleaved_sharded, stage_fn=stage_fn,
                           loss_fn=loss_fn, axis_name=axis, v=v, m=m,
                           kslots=kslots)
    loss, pgrads, dx = shard_map(
        lambda a, b, c: fn(a, b, c, tables), mesh=mesh,
        in_specs=(P(), P(), param_specs),
        out_specs=(P(), param_specs, P()), check_vma=False)(
            x_mb, y_mb, stacked_params)
    return loss, pgrads, dx.reshape((x.shape[0],) + dx.shape[2:])
