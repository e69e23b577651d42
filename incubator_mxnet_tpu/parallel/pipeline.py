"""Pipeline parallelism — NEW capability (SURVEY §2.5: absent in reference).

GPipe-style microbatching over structurally-identical stages expressed with
shard_map + ppermute over the ``pp`` mesh axis: stage weights are stacked on
a leading stage dim sharded over ``pp``; activations circulate the ring once
per microbatch tick. XLA overlaps the permute with stage compute on ICI.

The whole transform is differentiable (ppermute/scan have transposes), so
loss and gradients flow through the pipeline — see parallel.gluon_pipeline
for the Gluon block that pipelines a trunk between an embedding and a head
with TrainStep/Trainer integration.

``data_axis`` composes pp with data parallelism: the microbatch dim stays
sharded over ``dp`` while activations ring over ``pp``. ``key`` threads PRNG
randomness into stages (folded per-stage and per-tick) for dropout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.lax import axis_size, pcast
from jax.sharding import PartitionSpec as P

__all__ = ["PipelineParallel", "pipeline_spmd", "pipeline_1f1b_grads"]


def _pipeline_sharded(x_mb, stacked_params, key, stage_fn, axis_name,
                      n_microbatches, vary_axes=None):
    """Inside shard_map: each device holds ONE stage's params (leading stage
    dim of size 1 locally) and processes the stream of microbatches.

    x_mb: (n_micro, mb, ...) — full microbatch stream, replicated.
    Returns (n_micro, mb, ...) outputs (valid on the last stage; all-gathered).
    """
    n_stages = axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    params = jax.tree_util.tree_map(lambda p: p[0], stacked_params)
    mb_shape = x_mb.shape[1:]
    total_ticks = n_microbatches + n_stages - 1
    stage_key = None if key is None else jax.random.fold_in(key, stage)

    def tick(t, carry):
        state, outputs = carry  # state: activation currently held (mb, ...)
        # stage 0 injects microbatch t (if any); others use what arrived
        inject = jnp.where(t < n_microbatches, t, n_microbatches - 1)
        fresh = x_mb[inject]
        cur = jnp.where(stage == 0, fresh, state)
        if stage_key is None:
            out = stage_fn(params, cur)
        else:
            out = stage_fn(params, cur, jax.random.fold_in(stage_key, t))
        # last stage records its result for microbatch (t - n_stages + 1)
        done_idx = t - (n_stages - 1)
        record = jnp.logical_and(stage == n_stages - 1, done_idx >= 0)
        write_idx = jnp.clip(done_idx, 0, n_microbatches - 1)
        outputs = jnp.where(record, outputs.at[write_idx].set(out), outputs)
        # shift activations to the next stage on the ring
        perm = [(j, (j + 1) % n_stages) for j in range(n_stages)]
        state = lax.ppermute(out, axis_name, perm)
        return state, outputs

    axes = vary_axes or (axis_name,)
    out0 = pcast(jnp.zeros((n_microbatches,) + mb_shape, x_mb.dtype),
                     axes, to="varying")
    state0 = pcast(jnp.zeros(mb_shape, x_mb.dtype), axes, to="varying")
    _, outputs = lax.fori_loop(0, total_ticks, tick, (state0, out0))
    # only the last stage holds real outputs; broadcast them to all stages
    return _bcast_from_last(outputs, axis_name, n_stages)


def _bcast_from_last(x, axis_name, n_stages):
    # psum with a mask selects the last stage's copy on every device
    stage = lax.axis_index(axis_name)
    mask = (stage == n_stages - 1).astype(x.dtype)
    return lax.psum(x * mask, axis_name)


def pipeline_spmd(stage_fn, stacked_params, x, mesh, n_microbatches, axis="pp",
                  data_axis=None, key=None):
    """Run a structurally-identical-stage pipeline.

    stage_fn(params, x[, key])->y with identical in/out shapes; stacked_params
    has a leading dim = n_stages sharded over ``axis``; x: (batch, ...) split
    into n_microbatches along dim 0. With ``data_axis``, the microbatch dim
    stays sharded over that mesh axis (pp x dp composition). ``key`` (optional
    PRNG key) is folded per-stage/per-tick and passed as stage_fn's 3rd arg.
    """
    from jax.sharding import NamedSharding

    n_stages = int(mesh.shape[axis])
    leaves = jax.tree_util.tree_leaves(stacked_params)
    if leaves and leaves[0].shape[0] != n_stages:
        raise ValueError(
            "stacked_params leading dim (%d stages) must equal the %r mesh "
            "axis size (%d) — a divisible mismatch would silently drop "
            "stages" % (leaves[0].shape[0], axis, n_stages))
    if x.shape[0] % n_microbatches:
        raise ValueError("batch %d not divisible by n_microbatches %d"
                         % (x.shape[0], n_microbatches))
    mb = x.shape[0] // n_microbatches
    x_mb = x.reshape((n_microbatches, mb) + x.shape[1:])
    fn = functools.partial(
        _pipeline_sharded, stage_fn=stage_fn, axis_name=axis,
        n_microbatches=n_microbatches,
        vary_axes=(axis, data_axis) if data_axis else (axis,))
    param_specs = jax.tree_util.tree_map(
        lambda p: P(axis, *([None] * (p.ndim - 1))), stacked_params)
    io_spec = P(None, data_axis) if data_axis else P()
    # operands may arrive committed to a single device (eager NDArray data);
    # lay them out on the mesh so shard_map accepts them (no-op under jit
    # steady state — becomes a sharding constraint)
    x_mb = jax.device_put(x_mb, NamedSharding(mesh, io_spec))
    stacked_params = jax.tree_util.tree_map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
        stacked_params, param_specs)
    if key is not None:
        key = jax.device_put(key, NamedSharding(mesh, P()))
    if key is None:
        out = shard_map(
            lambda xm, sp: fn(xm, sp, None), mesh=mesh,
            in_specs=(io_spec, param_specs),
            out_specs=io_spec)(x_mb, stacked_params)
    else:
        out = shard_map(
            fn, mesh=mesh,
            in_specs=(io_spec, param_specs, P()),
            out_specs=io_spec)(x_mb, stacked_params, key)
    return out.reshape((x.shape[0],) + out.shape[2:])


class PipelineParallel:
    """Convenience wrapper: pipeline a stack of identical HybridBlocks.

    Used for transformer-layer stacks: all stages share one structure; their
    parameters are stacked on a leading dim and sharded over ``pp``.
    """

    def __init__(self, stage_fn, n_stages, mesh, axis="pp", n_microbatches=None):
        self.stage_fn = stage_fn
        self.n_stages = n_stages
        self.mesh = mesh
        self.axis = axis
        self.n_microbatches = n_microbatches or n_stages

    def __call__(self, stacked_params, x):
        return pipeline_spmd(self.stage_fn, stacked_params, x, self.mesh,
                             self.n_microbatches, self.axis)


# ----------------------------------------------------------------- 1F1B
def _pipeline_1f1b_sharded(x_mb, y_mb, stacked_params, stage_fn, loss_fn,
                           axis_name):
    """Hand-scheduled 1F1B (PipeDream-flush) inside shard_map.

    Non-interleaved 1F1B timing on the ring: stage s runs F_i at global
    tick t = s + 2i and B_i at t = 2(p+i) - s - 1 — per stage the two
    predicates have opposite tick parity, so each tick is one F, one B, or
    idle. Activations shift +1 on the ring every tick, gradients shift -1;
    a value produced at tick t is consumed by its neighbour at exactly
    t+1 in both directions (ticks on other parities carry garbage that no
    predicate ever reads). Total ticks 2(m+p-1): the SAME bubble fraction
    as GPipe-by-autodiff — 1F1B's win is the activation stash, which is
    bounded by p slots per stage instead of GPipe's m (in-flight
    microbatches at stage s: ceil((2(p-s)-1)/2) <= p).

    The backward recomputes each stage under jax.vjp from the stashed
    INPUT at its B tick (activation recompute, the standard memory/compute
    trade); the last stage folds loss_fn into its vjp so the loss gradient
    needs no self-handoff on the ring.

    Returns (mean loss over microbatches, param grads summed over
    microbatches (each stage holds its own slice), dx per microbatch for
    composing with an upstream embedding).
    """
    p = axis_size(axis_name)
    s = lax.axis_index(axis_name)
    params = jax.tree_util.tree_map(lambda q: q[0], stacked_params)
    m = x_mb.shape[0]
    mb_shape = x_mb.shape[1:]
    K = p  # stash slots: the 1F1B in-flight bound
    total_ticks = 2 * (m + p - 1)
    fwd_perm = [(j, (j + 1) % p) for j in range(p)]
    bwd_perm = [(j, (j - 1) % p) for j in range(p)]

    def tick(t, carry):
        a_reg, g_reg, stash, pgrads, dx_buf, loss_acc = carry
        iF = (t - s) // 2
        is_F = ((t - s) % 2 == 0) & (t >= s) & (iF < m)
        iF = jnp.clip(iF, 0, m - 1)
        iB = (t + s + 1 - 2 * p) // 2
        is_B = ((t + s + 1 - 2 * p) % 2 == 0) & (iB >= 0) & (iB < m)
        iB = jnp.clip(iB, 0, m - 1)

        finp = jnp.where(s == 0, x_mb[iF], a_reg)

        def do_F(stash):
            out = stage_fn(params, finp)
            return out, stash.at[iF % K].set(finp)

        def no_F(stash):
            return jnp.zeros(mb_shape, x_mb.dtype), stash

        a_out, stash = lax.cond(is_F, do_F, no_F, stash)

        def do_B(pgrads, dx_buf, loss_acc):
            binp = stash[iB % K]

            def last_branch(binp):
                # fold the loss into the stage vjp: the loss gradient needs
                # no self-handoff on the ring
                lv, vjp = jax.vjp(
                    lambda q, x: loss_fn(stage_fn(q, x), y_mb[iB]),
                    params, binp)
                dpar, dx = vjp(jnp.ones_like(lv))
                return lv.astype(jnp.float32), dpar, dx

            def mid_branch(binp):
                # vjp at cotangent g_reg, phrased as a scalar vdot so both
                # branches share the (loss, dpar, dx) structure
                lv, vjp = jax.vjp(
                    lambda q, x: jnp.vdot(
                        stage_fn(q, x).astype(jnp.float32),
                        lax.stop_gradient(g_reg).astype(jnp.float32)),
                    params, binp)
                dpar, dx = vjp(jnp.float32(1.0))
                return jnp.float32(0.0), dpar, dx

            lv, dpar, dx = lax.cond(s == p - 1, last_branch, mid_branch,
                                    binp)
            pgrads = jax.tree_util.tree_map(lambda g, d: g + d, pgrads,
                                            dpar)
            dx_buf = jnp.where(s == 0, dx_buf.at[iB].set(dx), dx_buf)
            return dx, pgrads, dx_buf, loss_acc + lv

        def no_B(pgrads, dx_buf, loss_acc):
            return (jnp.zeros(mb_shape, x_mb.dtype), pgrads, dx_buf,
                    loss_acc)

        g_out, pgrads, dx_buf, loss_acc = lax.cond(
            is_B, do_B, no_B, pgrads, dx_buf, loss_acc)

        a_reg = lax.ppermute(a_out, axis_name, fwd_perm)
        g_reg = lax.ppermute(g_out.astype(x_mb.dtype), axis_name, bwd_perm)
        return a_reg, g_reg, stash, pgrads, dx_buf, loss_acc

    zeros_mb = jnp.zeros(mb_shape, x_mb.dtype)
    carry0 = (
        pcast(zeros_mb, (axis_name,), to="varying"),
        pcast(zeros_mb, (axis_name,), to="varying"),
        pcast(jnp.zeros((K,) + mb_shape, x_mb.dtype), (axis_name,),
                  to="varying"),
        jax.tree_util.tree_map(
            lambda q: pcast(jnp.zeros_like(q, jnp.float32),
                                (axis_name,), to="varying"), params),
        pcast(jnp.zeros((m,) + mb_shape, x_mb.dtype), (axis_name,),
                  to="varying"),
        pcast(jnp.float32(0.0), (axis_name,), to="varying"),
    )
    _, _, _, pgrads, dx_buf, loss_acc = lax.fori_loop(
        0, total_ticks, tick, carry0)
    # loss lives on the last stage; dx on stage 0 — broadcast both
    loss = lax.psum(jnp.where(s == p - 1, loss_acc, 0.0), axis_name) / m
    dx_buf = lax.psum(jnp.where(s == 0, dx_buf, jnp.zeros_like(dx_buf)),
                      axis_name)
    # re-stack param grads: each stage contributes its own slice
    pgrads = jax.tree_util.tree_map(lambda g: g[None], pgrads)
    return loss, pgrads, dx_buf


def pipeline_1f1b_grads(stage_fn, loss_fn, stacked_params, x, y, mesh,
                        n_microbatches, axis="pp"):
    """1F1B pipeline train-step core: returns (loss, stage param grads,
    input grads). Same bubble as the GPipe/autodiff path (2(m+p-1) ticks);
    activation stash bounded by n_stages slots per stage instead of
    n_microbatches — the 1F1B memory win (see _pipeline_1f1b_sharded).

    stage_fn(params, x)->y shape-preserving; loss_fn(out, y_mb)->scalar
    (applied on the last stage); stacked_params leading dim = pp axis size;
    x/y: (batch, ...) split into n_microbatches on dim 0.
    """
    from jax.sharding import NamedSharding

    p = int(mesh.shape[axis])
    leaves = jax.tree_util.tree_leaves(stacked_params)
    if leaves and leaves[0].shape[0] != p:
        raise ValueError("stacked_params leading dim must equal the %r "
                         "axis size %d" % (axis, p))
    if x.shape[0] % n_microbatches:
        raise ValueError("batch %d not divisible by n_microbatches %d"
                         % (x.shape[0], n_microbatches))
    mb = x.shape[0] // n_microbatches
    x_mb = x.reshape((n_microbatches, mb) + x.shape[1:])
    y_mb = y.reshape((n_microbatches, mb) + y.shape[1:])
    param_specs = jax.tree_util.tree_map(
        lambda q: P(axis, *([None] * (q.ndim - 1))), stacked_params)
    x_mb = jax.device_put(x_mb, NamedSharding(mesh, P()))
    y_mb = jax.device_put(y_mb, NamedSharding(mesh, P()))
    stacked_params = jax.tree_util.tree_map(
        lambda q, sp: jax.device_put(q, NamedSharding(mesh, sp)),
        stacked_params, param_specs)
    fn = functools.partial(_pipeline_1f1b_sharded, stage_fn=stage_fn,
                           loss_fn=loss_fn, axis_name=axis)
    loss, pgrads, dx = shard_map(
        fn, mesh=mesh, in_specs=(P(), P(), param_specs),
        out_specs=(P(), param_specs, P()), check_vma=False)(
            x_mb, y_mb, stacked_params)
    return loss, pgrads, dx.reshape((x.shape[0],) + dx.shape[2:])
