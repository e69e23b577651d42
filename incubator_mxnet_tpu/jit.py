"""Fused training step — whole-step compilation with donated buffers.

This is the TPU-native analog of the reference's hot path: GraphExecutor op
bulking (src/executor/graph_executor.cc:1368 BulkOpSegs + :1449 bulk segments)
plus optimizer-as-op (src/operator/optimizer_op.cc multi_sgd): ONE XLA program
computes forward, backward, and every parameter/optimizer-state update, with
input buffers donated so updates are in-place on device (kWriteInplace analog).

Usage::

    step = TrainStep(net, loss_fn, trainer)
    loss = step(x, y)          # one compiled step; params/state updated

Data-parallel over a mesh: see parallel.DataParallelTrainStep, which shards
the batch axis of this same program.
"""
from __future__ import annotations

import collections
import logging
import threading as _threading
import time as _time
import weakref

import jax
import jax.numpy as jnp
import numpy as _onp

from . import aot
from . import autograd
from . import config
from . import telemetry
from .telemetry import devstats, flightrec, numwatch, spans, watchdog
from .gluon import _functional
from .ndarray import NDArray
from .ndarray import random as _rnd

_LOG = logging.getLogger(__name__)


def _donate(argnums):
    """Buffer donation unless MXTPU_NO_DONATE (debugging) is set."""
    return () if config.get_env("MXTPU_NO_DONATE") else argnums


# Per-net trace/dispatch synchronization. Tracing a step/eval program
# swaps TRACERS into the live Parameter NDArrays' ``_data`` and restores
# them after (gluon/_functional pure_fn, TrainStep._build inner) — so for
# the duration of a trace, the net's params hold tracers, and any other
# thread reading ``a._data`` (a concurrent trace of another bucket, or a
# HIT dispatch capturing its argument list) would hand a tracer to a
# compiled executable. The registry's prewarm thread made this reachable:
# after the early cutover the batcher worker dispatches the same net the
# warm thread is still tracing bigger buckets of. Discipline: every TRACE
# window holds the net's lock exclusively; every dispatch captures its
# ``_data`` snapshot under the same lock (sub-µs when uncontended) and
# executes outside it. The lock lives on the net object itself so every
# component tracing one net (EvalStep, TrainStep, multiple instances)
# shares it; it is keyed per net, so one model's compile never stalls
# another model's traffic.
_TRACE_LOCK_REGISTRY = _threading.Lock()


def _net_trace_lock(net):
    lock = getattr(net, "_mxtpu_trace_lock", None)
    if lock is None:
        with _TRACE_LOCK_REGISTRY:      # double-checked: one lock per net
            lock = getattr(net, "_mxtpu_trace_lock", None)
            if lock is None:
                lock = _threading.RLock()
                net._mxtpu_trace_lock = lock
    return lock

__all__ = ["TrainStep", "EvalStep", "compiled_train_programs",
           "flush_step_counters"]

# Compile observability: each shared-cache (aot.CACHE) miss that cannot be
# satisfied by a persisted artifact is one model trace + XLA compile.
# Train programs, on one device or a mesh, compile ahead inside the build
# (jit().lower().compile() with the step's arg specs — which also hands
# devstats the compiled program's cost/memory analysis). The miss's whole
# first step — trace + compile + run — is what gets attributed to compile
# time. Watching compiles_total climb under bucketed variable-shape
# traffic is how an undersized MXTPU_AOT_CACHE_SIZE shows itself (so is
# mxtpu_aot_evictions_total, its direct cause).
# The PARTS of that lump have homes of their own (telemetry/setup_phases.py
# books JAX's own pipeline events to the span open around them): creating
# the optimizer state is the span train:init_states; placing the state
# train:layout; the model's trace, jaxpr -> MLIR and the XLA compile or the
# read from the persistent cache are the children train:trace / :lower /
# :backend_compile / :cache_read of train:build (eval:* of eval:build) and
# mxtpu_compile_phase_seconds_total{phase, owner}; whether the cache
# answered is mxtpu_compile_cache_total{result, owner}; the Pallas kernel
# bodies traced on the way are mxtpu_kernel_trace_seconds_total{kernel};
# the first run is the first train:dispatch (eval:step).
_COMPILES = telemetry.counter(
    "mxtpu_jit_compiles_total",
    "Shape-keyed executable-cache misses (one XLA compile each).",
    ("kind",))
_COMPILE_SECONDS = telemetry.counter(
    "mxtpu_jit_compile_seconds_total",
    "Wall seconds spent in cache-miss first steps (trace+compile+run).",
    ("kind",))
_STEP_SECONDS = telemetry.histogram(
    "mxtpu_train_step_seconds",
    "Wall time per TrainStep call (cache-hit steady state included).",
    buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
             1.0, 2.5, 5.0, 10.0, 30.0, 60.0))
_STEPS = telemetry.counter(
    "mxtpu_train_steps_total", "Completed TrainStep calls.")
_EXAMPLES = telemetry.counter(
    "mxtpu_train_examples_total",
    "Examples consumed by TrainStep (batch-size sum); rate() of this is "
    "examples/sec.")


# Step counters (gluon/_functional.collect_step_counter): small integer
# values the blocks of a step compute anyway (a MoELayer's rows an expert)
# leave the compiled step as ONE int32 vector beside the loss. The host
# never waits for it: a TrainStep keeps (step number, the step's
# train:dispatch span, the vector) of the steps it has not resolved and,
# inside each later call, books those whose vector has arrived: the
# retroactive record train:counters in the span ring, on that step's own
# train:dispatch clock, and each counter's own series (its ``publish``).
# flush_step_counters() resolves what is left, waiting.
#: unresolved steps a TrainStep keeps; past it the oldest is dropped
_COUNTERS_KEPT = 64
_COUNTERS_DROPPED = telemetry.counter(
    "mxtpu_step_counters_dropped_total",
    "Steps whose counters were dropped unread: more than %d steps of one "
    "TrainStep were waiting for their values." % _COUNTERS_KEPT)
#: one resolver at a time: a step's own call, or a reader's flush
_COUNTERS_LOCK = _threading.Lock()
#: the live TrainSteps, for flush_step_counters
_COUNTING = weakref.WeakSet()


def flush_step_counters():
    """Resolve every step counter a live TrainStep still holds, waiting for
    the device where it must: for a reader after the loop (the steps' own
    calls never wait). Returns the number of steps resolved."""
    return sum(step._resolve_counters(wait=True) for step in list(_COUNTING))


def _record_compile_span(name, dur_s):
    """Retroactive span for a just-finished compile window (it ends with
    the miss's first run, so it is only measurable after the fact),
    parented onto the ambient step span. It is the lump, kept for the
    operators' uses docs/OBSERVABILITY.md, AOT.md and GENERATE.md give it;
    train:compile = train:host_transfer + train:init_states + train:build
    (train:layout, train:trace, train:lower, train:backend_compile or
    train:cache_read) + train:schedule + the first train:dispatch, and
    eval:compile = eval:build (eval:trace, eval:lower, eval:backend_compile
    or eval:cache_read): read those to know which part moved."""
    try:
        from . import profiler
        spans.record_span(name, profiler.now_us() - dur_s * 1e6,
                          dur_s * 1e6, parent=spans.current_span())
    except Exception:   # tracing must never fail the step
        pass


def _tree_to_data(state):
    """Nested optimizer state (NDArrays in tuples) -> pytree of jax arrays."""
    if state is None:
        return None
    if isinstance(state, NDArray):
        return state._data
    if isinstance(state, (tuple, list)):
        return tuple(_tree_to_data(s) for s in state)
    return state


def _tree_wrap(data):
    """pytree of jax arrays -> nested NDArrays (fresh wrappers)."""
    if data is None:
        return None
    if isinstance(data, (tuple, list)):
        return tuple(_tree_wrap(d) for d in data)
    return NDArray(data)


def _placed(x, sharding):
    """``x`` on ``sharding``: ``x`` itself where the step has no layout
    (None) or ``x`` is laid out so already. Equivalence, not ``==``: a
    step's outputs come back as P('dp') where the rule says P('dp', None)."""
    if sharding is None or x.sharding.is_equivalent_to(sharding, x.ndim):
        return x
    return jax.device_put(x, sharding)


def _with_layout(f, tree, shardings):
    """``tree`` with ``f(leaf, sharding)`` in place of each leaf;
    ``shardings`` is flat, in ``tree_flatten``'s order."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return treedef.unflatten([f(x, s) for x, s in zip(leaves, shardings)])


class TrainStep:
    """Compile net forward + loss + backward + optimizer update into one program."""

    def __init__(self, net, loss_fn, trainer, batch_axis=0, grad_postprocess=None,
                 mesh=None, data_axis="dp", remat=None, zero=False,
                 model_id=None):
        self.net = net
        self.loss_fn = loss_fn
        self.trainer = trainer
        self._grad_postprocess = grad_postprocess
        # shared-executable-cache identity: train entries carry per-call
        # python state (param/aux NDArray lists bound to THIS net), so the
        # default id is instance-scoped — entries are released in __del__
        self._model_id = model_id
        self._cache_keys = set()
        self._trace_lock = _net_trace_lock(net)
        self._step_count = 0
        self.mesh = mesh
        self.data_axis = data_axis
        self.batch_axis = batch_axis
        # remat: rematerialize the forward during backward (jax.checkpoint)
        # — trades ~1 extra forward of FLOPs for O(layer) activation memory,
        # the long-sequence HBM lever (SURVEY §7 guidance)
        self.remat = config.get_env("MXTPU_REMAT") if remat is None else remat
        # zero: ZeRO-1 / automatic cross-replica sharding of the weight
        # update (arXiv:2004.13336, the GSPMD-annotation form): optimizer
        # states (incl. fp32 masters) are SHARDED over the dp axis on dim 0,
        # so state memory and update FLOPs divide by |dp|; the sharding
        # mismatch makes XLA lower the grad all-reduce to reduce-scatter and
        # all-gather the updated weights — no hand-written collectives.
        # Params themselves stay replicated (ZeRO-1, not 2/3).
        self.zero = zero
        # device truth of the most recently dispatched program (aot entry
        # stats: flops / bytes_accessed / peak_bytes / output_bytes), or
        # None pre-dispatch; nothing in the package reads it, the tests
        # do
        self._last_stats = None
        # watchdog bookkeeping: counts once this instance starts stepping
        self._hb_registered = False
        # the steps whose counters the host does not know yet, oldest first:
        # (step, its train:dispatch span, the vector, the vector's layout)
        self._unresolved = collections.deque()
        _COUNTING.add(self)

    # ------------------------------------------------------------------
    def _build(self, n_inputs):
        params = list(self.net.collect_params().values())
        trainable = [p for p in params if p.grad_req != "null"]
        frozen = [p for p in params if p.grad_req == "null"]
        t_arrs = [p.data() for p in trainable]
        f_arrs = [p.data() for p in frozen]
        net, loss_fn = self.net, self.loss_fn
        optimizer = self.trainer._optimizer
        aux_box = []
        counter_box = []   # (name, length, publish, static) of each counter
        # blocks whose kernels GSPMD cannot partition (models' flash
        # attention) ask which mesh they are being traced for (imported
        # here: parallel/ imports this module)
        from .parallel.mesh import step_mesh_scope
        mesh, data_axis = self.mesh, self.data_axis

        def inner(t_datas, f_datas, input_datas, key):
            saved_t = [a._data for a in t_arrs]
            saved_f = [a._data for a in f_arrs]
            for a, d in zip(t_arrs, t_datas):
                a._data = d
            for a, d in zip(f_arrs, f_datas):
                a._data = d
            try:
                with step_mesh_scope(mesh, data_axis), \
                        _functional.FunctionalScope(key) as st:
                    with autograd.pause(train_mode=True):
                        nd_inputs = [NDArray(d) for d in input_datas]
                        # bypass hybridize's own cache: trace the eager
                        # forward; Block.__call__ names every child's scope,
                        # these two direct calls name their own
                        with jax.named_scope(net.name):
                            out = net.forward(*nd_inputs[:n_inputs])
                        outs = out if isinstance(out, (list, tuple)) else (out,)
                        with jax.named_scope("loss"):
                            loss = loss_fn.forward(
                                outs[0] if len(outs) == 1 else outs,
                                *nd_inputs[n_inputs:])
                    # seed-of-ones semantics: grads of the SUM; Trainer's
                    # rescale_grad (1/batch) then normalises — matches eager
                    loss_scalar = loss._data.sum()
                    aux_pairs = list(st.aux_updates)
                    counters = list(st.step_counters)
            finally:
                for a, s in zip(t_arrs, saved_t):
                    a._data = s
                for a, s in zip(f_arrs, saved_f):
                    a._data = s
            aux_box[:] = [a for a, _ in aux_pairs]
            counter_box[:] = [(name, int(v.size), publish, static)
                              for name, v, publish, static in counters]
            return loss_scalar, (loss._data, [v for _, v in aux_pairs],
                                 [v for _, v, _, _ in counters])

        fwd = jax.checkpoint(inner) if self.remat else inner

        # step_fn must NOT close over self: the compiled entry lives in
        # the process-wide aot.CACHE, and an entry pinning its TrainStep
        # would keep __del__ (which releases the entry) from ever running
        # — capture the needed config as plain locals instead
        grad_postprocess = self._grad_postprocess
        layout = self._layout(trainable, frozen)
        constrain_update = self._make_constrainer(layout)
        replicated = layout[4]

        def step_fn(t_datas, f_datas, opt_states, input_datas, key, lrs, wds, t,
                    rescale):
            (loss_scalar, (loss_full, aux_vals, counted)), grads = \
                jax.value_and_grad(fwd, argnums=0, has_aux=True)(
                    t_datas, f_datas, input_datas, key)
            if grad_postprocess is not None:
                grads = grad_postprocess(grads)
            new_t, new_opt = [], []
            lowp = (jnp.bfloat16, jnp.float16)
            with jax.named_scope("optimizer"):
                for i, (w, g, s) in enumerate(zip(t_datas, grads, opt_states)):
                    g = g * rescale
                    if optimizer.clip_gradient is not None:
                        g = jnp.clip(g, -optimizer.clip_gradient, optimizer.clip_gradient)
                    gf = g.astype(jnp.float32)
                    mp = optimizer.multi_precision and w.dtype in lowp
                    if mp:
                        # fp32 master-weight flow (ref optimizer.py:320): state is
                        # (master, inner); update the master, cast down the copy
                        master, inner_state = s
                        state_nd = _tree_wrap(inner_state)
                        new_w, new_state_nd = optimizer.update_rule(
                            master, gf, state_nd, lrs[i], wds[i], t)
                        new_t.append(new_w.astype(w.dtype))
                        new_opt.append((new_w, _tree_to_data(new_state_nd)))
                    else:
                        state_nd = _tree_wrap(s)
                        new_w, new_state_nd = optimizer.update_rule(
                            w.astype(jnp.float32), gf, state_nd, lrs[i], wds[i], t)
                        new_t.append(new_w.astype(w.dtype))
                        new_opt.append(_tree_to_data(new_state_nd))
            if constrain_update is not None:
                new_t, new_opt = constrain_update(new_t, new_opt)
            if not counted:
                # no block counts anything: the program it always was
                return loss_full, new_t, new_opt, aux_vals
            # one small transfer a step, every chip of a mesh its own copy
            counted = jnp.concatenate(
                [v.reshape(-1).astype(jnp.int32) for v in counted])
            if replicated is not None:
                counted = jax.lax.with_sharding_constraint(counted,
                                                           replicated)
            return loss_full, new_t, new_opt, aux_vals, counted

        return step_fn, layout, trainable, t_arrs, f_arrs, aux_box, \
            counter_box

    def _build_entry(self, n_inputs, arrs, key):
        """aot.compile_cached build hook: (compiled program, instance
        extras, no exported artifact — train programs stay in-memory).

        One path, whatever the mesh: the state is laid out once, HERE —
        each parameter, frozen array and optimizer-state leaf is put onto
        its sharding of ``_layout`` and written back into its NDArray /
        ``trainer._states`` slot, so a dispatch passes what it holds —
        and the program is compiled ahead, ``jit().lower(specs)
        .compile()`` under the net's trace lock, the same explicit
        pipeline EvalStep uses. The XLA compile lands inside the
        train:build span and the cache entry is an analyzable compiled
        program (devstats harvests its cost/memory analysis at insert).
        Inside train:build the lay-out is the span train:layout and
        JAX's trace / lower / compile-or-cache-read events become its
        other children (telemetry/setup_phases.py).
        A failed spec, lower or compile raises to the caller: a lazy
        retry would compile the same program again, and swallowing the
        first error is how a compiler refusal (a Mosaic kernel over its
        VMEM budget, an HBM OOM) gets hidden."""
        step_fn, layout, trainable, t_arrs, f_arrs, aux_box, counter_box = \
            self._build(n_inputs)
        data_sh, repl = layout[3:]
        # the lay-out writes, and the trace swaps tracers into, the live
        # param NDArrays (inner's _data swap) — hold the net's trace lock
        # for the whole window, exactly like the eval build
        with self._trace_lock:
            with spans.span("train:layout"):
                slots, state, state_sh = self._state(
                    layout, trainable, t_arrs, f_arrs)
                specs = self._arg_specs(state, arrs, key, state_sh, data_sh,
                                        repl)
                # rebound, so that nothing holds what was there before: the
                # program loads beside the laid-out state alone
                state = _with_layout(_placed, state, state_sh)
                self._write_back(t_arrs + f_arrs, slots,
                                 state[0] + state[1], state[2])
                # the eager tape's gradient buffers: this step never reads
                # them, and they are 2 bytes a parameter beside its state
                for p in trainable:
                    p.release_grad()
            compiled = jax.jit(
                step_fn, donate_argnums=_donate((0, 2))).lower(*specs).compile()
        return compiled, (slots, t_arrs, f_arrs, aux_box, state_sh,
                          data_sh, counter_box), None

    def _state(self, layout, trainable, t_arrs, f_arrs):
        """-> (each trainable parameter's slot in ``trainer._states``;
        (parameters, frozen arrays, optimizer states) as one step_fn call
        takes them; the layout's sharding of every leaf of those)."""
        t_sh, f_sh, state_rules = layout[:3]
        trainer = self.trainer
        slots = [trainer._param2idx.get(p.name, i)
                 for i, p in enumerate(trainable)]
        state = ([a._data for a in t_arrs], [a._data for a in f_arrs],
                 [_tree_to_data(trainer._states[idx]) for idx in slots])
        return slots, state, t_sh + f_sh + [
            rule(leaf) for st, rule in zip(state[2], state_rules)
            for leaf in jax.tree_util.tree_leaves(st)]

    def lower(self, *inputs, n_net_inputs=1, sharding=None):
        """The step's program for ``inputs`` (NDArrays, or anything with
        a shape and a dtype: `jax.ShapeDtypeStruct`s), lowered and not
        compiled: nothing is laid out, cached or run, and ``.compile()``
        gives the program a first call would build. ``sharding`` replaces
        every sharding of the layout: the `SingleDeviceSharding` of a
        DESCRIBED device (`jax.experimental.topologies`) compiles the
        step for a chip that is not there
        (tests/test_kernels_compile_v5e.py)."""
        trainer = self.trainer
        if not trainer._kv_initialized:
            trainer._init_kvstore()
        if not trainer._states_initialized:
            trainer._init_states()
        step_fn, layout, trainable, t_arrs, f_arrs = self._build(
            n_net_inputs)[:5]
        _, state, state_sh = self._state(layout, trainable, t_arrs, f_arrs)
        data_sh, repl = layout[3:]
        if sharding is not None:
            state_sh = [sharding] * len(state_sh)
            data_sh = repl = sharding
        specs = self._arg_specs(
            state, inputs, jax.ShapeDtypeStruct((2,), jnp.uint32), state_sh,
            data_sh, repl)
        with self._trace_lock:
            return jax.jit(step_fn,
                           donate_argnums=_donate((0, 2))).lower(*specs)

    def _arg_specs(self, state, arrs, key, state_sh, data_sh, repl):
        """jax.ShapeDtypeStruct tree matching one step_fn call, every
        leaf with its sharding of the layout (None without a mesh) —
        what _build_entry lowers with."""
        def sds(x, sharding):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

        def scalar(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

        t_specs, f_specs, opt_specs = _with_layout(sds, state, state_sh)
        vec = scalar((len(t_specs),), jnp.float32)
        return (t_specs, f_specs, opt_specs,
                [sds(getattr(a, "_data", a), data_sh) for a in arrs],
                sds(key, repl),
                vec, vec, scalar((), jnp.int32), scalar((), jnp.float32))

    def _write_back(self, p_arrs, slots, new_p, new_opt):
        """Parameters and optimizer state into their NDArray slots."""
        for a, d in zip(p_arrs, new_p):
            a._data = d
        states = self.trainer._states
        for idx, new in zip(slots, new_opt):
            states[idx] = _rewrap_state(states[idx], new)

    def _layout(self, trainable, frozen):
        """THE layout of this step: which sharding each leaf of one
        step_fn call has, as ``(per trainable parameter, per frozen one,
        per trainable parameter a rule leaf -> sharding for its optimizer
        state, the inputs', the scalars' and key's)``. Nothing else
        builds a sharding; without a mesh every answer is None.

        On a mesh (SPMD data(+tensor)-parallel) the batch is sharded over
        ``data_axis`` and XLA inserts the gradient all-reduce (psum over
        dp) itself — this IS the kvstore dist_device_sync path on ICI
        (SURVEY §2.5 north star). A parameter has its ``p.sharding`` (the
        PartitionSpec a tensor/expert-parallel layer set) or is
        replicated, and its optimizer state follows it unless ``zero``
        shards that: dim 0 over the dp axis when divisible
        (masters/momenta share the param shape); scalars and indivisible
        leaves replicate; params a tensor/expert-parallel layer already
        sharded keep their spec. The rules close over the mesh, never
        over self: step_fn's constrainer holds them."""
        mesh = self.mesh
        if mesh is None:
            return ([None] * len(trainable), [None] * len(frozen),
                    [lambda leaf: None] * len(trainable), None, None)
        from jax.sharding import NamedSharding, PartitionSpec
        repl = NamedSharding(mesh, PartitionSpec())
        dp = self.data_axis
        n = mesh.shape.get(dp, 1)

        def of_param(p):
            spec = getattr(p, "sharding", None)
            if spec is None:
                return repl
            if isinstance(spec, NamedSharding):
                return spec
            return NamedSharding(mesh, spec)

        def zero_rule(leaf):
            shape = getattr(leaf, "shape", ())
            if len(shape) >= 1 and shape[0] and shape[0] % n == 0:
                return NamedSharding(
                    mesh, PartitionSpec(dp, *([None] * (len(shape) - 1))))
            return repl

        def of_state(p):
            if self.zero and n > 1 and getattr(p, "sharding", None) is None:
                return zero_rule
            base = of_param(p)
            return lambda leaf: base

        return ([of_param(p) for p in trainable],
                [of_param(p) for p in frozen],
                [of_state(p) for p in trainable],
                NamedSharding(mesh, PartitionSpec(dp)), repl)

    def _make_constrainer(self, layout):
        """Build the update-sharding constrainer (zero mode): new states
        stay dp-sharded, new weights return to their (replicated/TP) param
        sharding — the mismatch is what GSPMD lowers to
        reduce-scatter + sharded update + all-gather. Returns None when
        inactive; the returned closure is SELF-FREE (the layout is
        resolved at build time) so the shared-cache entry never pins
        this instance."""
        if not self.zero or self.mesh is None:
            return None
        shards, _, rules, _, _ = layout

        def constrain(new_t, new_opt):
            out_t, out_opt = [], []
            for w, s, rule, shard in zip(new_t, new_opt, rules, shards):
                out_t.append(jax.lax.with_sharding_constraint(w, shard))
                out_opt.append(jax.tree_util.tree_map(
                    lambda leaf, _r=rule: jax.lax.with_sharding_constraint(
                        leaf, _r(leaf)), s))
            return out_t, out_opt

        return constrain

    # ------------------------------------------------------------------
    #: live instances that have stepped at least once — the shared
    #: "train_step" heartbeat channel is unregistered when the LAST one is
    #: dropped, so a finished training loop (step object released) does
    #: not read as a stall forever after
    _hb_live = 0

    def __call__(self, *inputs, batch_size=None, n_net_inputs=1):
        """inputs = (*net_inputs, *loss_extra_args); returns per-sample loss."""
        if not self._hb_registered:
            # register on FIRST step, not construction: a step built long
            # before training starts must not page while idle
            self._hb_registered = True
            TrainStep._hb_live += 1
        watchdog.heartbeat("train_step")
        with spans.span("train:step"):
            return self._call_traced(inputs, batch_size, n_net_inputs)

    def __del__(self):
        try:
            if self._hb_registered:
                TrainStep._hb_live -= 1
                if TrainStep._hb_live <= 0:
                    watchdog.unregister("train_step")
            # train entries are instance-scoped (their extras pin THIS
            # net's param arrays): release them instead of waiting for LRU
            for key in self._cache_keys:
                aot.CACHE.discard(key)
            # a loop that ends leaves its last steps' counters here: book
            # those that have arrived, and let the rest go without a wait
            self._resolve_counters(wait=False)
            self._unresolved.clear()
        except Exception:
            pass          # interpreter-teardown __del__ must never raise

    def _call_traced(self, inputs, batch_size, n_net_inputs):
        # host-transfer child span: raw host arrays become device arrays
        # here (a no-op wrap for inputs already on device)
        with spans.span("train:host_transfer"):
            arrs = [a if isinstance(a, NDArray) else NDArray(jnp.asarray(a))
                    for a in inputs]
        if batch_size is None:
            batch_size = arrs[0].shape[0]
        trainer = self.trainer
        # trigger any deferred parameter init with one eager forward
        if any(p._data is None for p in self.net.collect_params().values()):
            with autograd.pause(train_mode=True):
                self.net.forward(*arrs[:n_net_inputs])
        if not (trainer._kv_initialized and trainer._states_initialized):
            # fp32 masters and the moments of every parameter: a first
            # call's own set-up, a small program or two a parameter
            with spans.span("train:init_states"):
                if not trainer._kv_initialized:
                    trainer._init_kvstore()
                if not trainer._states_initialized:
                    trainer._init_states()

        if self._model_id is None:
            self._model_id = aot.model_id_for(
                self.net,
                extra=("train", type(self.trainer._optimizer).__name__,
                       type(self.loss_fn).__name__))
        # the instance token lives in the KEY, not the model_id, and is
        # applied even to an explicit model_id: train entries carry this
        # instance's param/aux NDArray lists, so two TrainSteps must never
        # share one (a hit would silently train the builder's net)
        cache_key = aot.cache_key(
            self._model_id,
            tuple((a.shape, str(a.dtype)) for a in arrs),
            kind="train", mesh=aot.mesh_sig(self.mesh),
            extra=(n_net_inputs, "i%x" % id(self)))
        step_t0 = _time.perf_counter()
        # the per-step RNG key is drawn BEFORE the build so a compile
        # miss can shape its arg specs from it (one draw per step either way)
        key = _rnd._next_key()
        entry = aot.CACHE.lookup(cache_key)
        compile_miss = entry is None
        flightrec.record("step_begin", step=self._step_count + 1,
                         compile=compile_miss)
        if compile_miss:
            flightrec.record("compile_begin", kind="train")
            # The program compiles ahead inside this build span
            # (_build_entry), so the entry is an analyzable compiled
            # program (donated-buffer programs are never
            # jax.export-persisted). The retroactive train:compile span
            # below covers the whole trace+compile+first-run window (same
            # definition as the mxtpu_jit_compile_seconds_total counter),
            # which is what separates "slow step" from "recompiling
            # every step".
            with spans.span("train:build"):
                entry = aot.compile_cached(
                    cache_key,
                    lambda: self._build_entry(n_net_inputs, arrs, key))
                self._cache_keys.add(cache_key)
        self._last_stats = entry.stats
        slots, t_arrs, f_arrs, aux_box, state_sh, data_sh, counter_box = \
            entry.extras

        optimizer = trainer._optimizer
        # python-side schedule state (lr scheduler, update counts) advances
        # here; the span covers the step's only per-parameter host loops
        with spans.span("train:schedule"):
            self._step_count += 1
            lrs, wds, opt_states = [], [], []
            for idx in slots:
                optimizer._update_count(idx)
                lrs.append(optimizer._get_lr(idx))
                wds.append(optimizer._get_wd(idx))
                opt_states.append(_tree_to_data(trainer._states[idx]))
            t = self._step_count
            rescale = optimizer.rescale_grad / batch_size

        # the dispatch + write-back holds the net's trace lock: it reads
        # and then writes the live param NDArrays' ``_data`` slots —
        # interleaved with a concurrent eval/warm trace of this net it
        # would capture tracers or lose the step's update to the trace's
        # finally-restore. Uncontended (the common case: nothing else
        # traces this net) the RLock costs sub-µs per step.
        with spans.span("train:dispatch", compile=compile_miss,
                        step=self._step_count) as dispatch, self._trace_lock:
            dispatch_t0 = _time.perf_counter()
            # the state is passed as it is held: a leaf is put again only
            # where it left its layout between two steps (set_data,
            # load_parameters, trainer.load_states), which a compiled
            # program refuses; the inputs go onto the data sharding; the
            # scalars stay host arrays: on a mesh every chip gets its copy
            # from the host, none crosses from chip 0 between two programs
            loss_full, new_t, new_opt, aux_vals, *counted = entry.fn(
                *_with_layout(_placed, (
                    [a._data for a in t_arrs], [a._data for a in f_arrs],
                    opt_states), state_sh),
                [_placed(a._data, data_sh) for a in arrs], key,
                _onp.asarray(lrs, _onp.float32), _onp.asarray(wds, _onp.float32),
                _onp.asarray(t, _onp.int32), _onp.asarray(rescale, _onp.float32))
            # device-truth MFU: opt-in sync (the block defeats
            # donated-buffer step chaining — docs/OBSERVABILITY.md);
            # unsynced, the observed span is the host dispatch window
            # and the rolling train MFU can read high while steps
            # pipeline
            if config.get_env("MXTPU_DEVSTATS_TRAIN_SYNC"):
                try:
                    jax.block_until_ready(loss_full)
                except Exception:
                    pass
            devstats.observe_dispatch(
                "train", entry.stats, _time.perf_counter() - dispatch_t0,
                model=self._model_id)

            self._write_back(t_arrs, slots, new_t, new_opt)
            for a, v in zip(aux_box, aux_vals):
                a._data = v
        if counted:
            self._count(dispatch, counted[0], counter_box)
        # numerics sentinel (stride-sampled, default off): on-device
        # stats taps over the per-sample loss and the updated parameter
        # tree — grads are fused inside the step program, so a NaN storm
        # in them surfaces here as non-finite loss/updates. tap() never
        # raises and costs a dict increment when unsampled.
        numwatch.tap(self._model_id, "train:loss", (loss_full,))
        numwatch.tap(self._model_id, "train:params", new_t)
        step_dur = _time.perf_counter() - step_t0
        _STEP_SECONDS.observe(step_dur)
        _STEPS.inc()
        _EXAMPLES.inc(int(batch_size))
        if compile_miss:
            _COMPILES.inc(kind="train")
            _COMPILE_SECONDS.inc(step_dur, kind="train")
            # retroactive: the compile window IS this whole cache-miss
            # step (trace + XLA compile + first run — see the note above),
            # emitted as a child of the open train:step span
            _record_compile_span("train:compile", step_dur)
            flightrec.record("compile_end", kind="train",
                             dur_s=round(step_dur, 6))
        flightrec.record("step_end", step=self._step_count,
                         dur_s=round(step_dur, 6))
        return NDArray(loss_full)

    # -- step counters ----------------------------------------------------
    def _count(self, dispatch, vector, layout):
        """Keep this step's counters for later and book the earlier steps'
        that have arrived; never waits for the device."""
        vector.copy_to_host_async()
        self._unresolved.append((self._step_count, dispatch, vector, layout))
        if len(self._unresolved) > _COUNTERS_KEPT:
            self._unresolved.popleft()
            _COUNTERS_DROPPED.inc()
        self._resolve_counters(wait=False)

    def _resolve_counters(self, wait):
        """Book the unresolved steps in order, up to the first whose vector
        has not arrived (``wait``: all of them, waiting). Per step one
        retroactive record train:counters, a child of that step's
        train:dispatch with its ``start_us`` and ``dur_us`` = how much later
        the values were known, args ``step`` and ``counters`` (per counter
        its name, values and static facts), and each counter's ``publish``.
        Returns the number of steps booked."""
        from . import profiler
        done = 0
        with _COUNTERS_LOCK:
            while self._unresolved:
                step, dispatch, vector, layout = self._unresolved[0]
                if not (wait or vector.is_ready()):
                    break
                self._unresolved.popleft()
                try:
                    values = _onp.asarray(vector).tolist()
                    counters, at = [], 0
                    for name, length, publish, static in layout:
                        mine = values[at:at + length]
                        at += length
                        counters.append(dict(static, name=name, values=mine))
                        if publish is not None:
                            publish(name, mine, **static)
                    spans.record_span(
                        "train:counters", dispatch.start_us,
                        profiler.now_us() - dispatch.start_us,
                        parent=dispatch, step=step, counters=counters)
                except Exception:   # tracing must never fail the step
                    _LOG.debug("step %d: its counters were not booked",
                               step, exc_info=True)
                done += 1
        return done


def compiled_train_programs():
    """``[(model_id, optimised HLO text)]`` of the live train programs in
    ``aot.CACHE``. Every instruction of the text carries its scope path in
    ``metadata={op_name="jit(step_fn)/.../<scopes>/<primitive>"}`` (block
    names, ``ffn``, ``loss``, ``optimizer``), which is how a profiler
    capture's device events — named by instruction — are booked to a block.
    The text is rendered only here, on demand."""
    out = []
    for key in aot.CACHE.keys():
        entry = aot.CACHE.peek(key) if key.kind == "train" else None
        if entry is not None:
            out.append((key.model_id, entry.fn.as_text()))
    return out


def _rewrap_state(old, new_data):
    """Write new jax arrays back into the existing NDArray state structure."""
    if old is None:
        return None
    if isinstance(old, NDArray):
        old._data = new_data
        return old
    if isinstance(old, (tuple, list)):
        return tuple(_rewrap_state(o, n) for o, n in zip(old, new_data))
    return new_data


class EvalStep:
    """Compiled inference step (train_mode=False): net(*inputs) in one
    program, dispatched through the process-wide aot.CACHE.

    The compiled program takes params as runtime inputs, so instances
    built on an identical model (aot.model_id_for content digest — or an
    explicit ``model_id``) SHARE executables: a hot-reloaded same-model
    version, a second BlockServable, or a second EvalStep never recompile
    a bucket this process already compiled. Misses use the explicit AOT
    pipeline (``jit(fn).lower(args).compile()``) so the XLA compile lands
    inside the eval:build span — never lazily inside a later dispatch —
    and the traced program is persisted via jax.export when
    MXTPU_AOT_CACHE_DIR is set, letting a fresh process load the
    executable instead of re-tracing the model (artifact hit, zero
    eval:compile spans).
    """

    def __init__(self, net, model_id=None):
        self.net = net
        self._model_id = model_id
        self._trace_lock = _net_trace_lock(net)
        self._pure = None       # (param_arrs, pure_fn): built once, no trace
        # device truth of the most recently dispatched program (aot entry
        # stats), None pre-dispatch
        self._last_stats = None

    def _ensure_pure(self):
        if self._pure is None:
            _params, param_arrs, pure_fn, _aux = \
                _functional.make_pure_fn(self.net, train_mode=False)
            self._pure = (param_arrs, pure_fn)
        return self._pure

    def _builder(self, arg_specs, persist):
        """aot.compile_cached build hook. With the artifact layer on
        (``persist``): trace ONCE via jax.export, AOT-compile the exported
        module, and hand the export back for persistence; with it off
        (MXTPU_AOT_CACHE_DIR unset — the default) go straight to the
        direct AOT pipeline and never pay the export round-trip for a
        file that would not be written. Compile-window metrics and the
        retroactive eval:compile span are emitted here so only the thread
        that actually built pays (and counts) the compile."""
        def build():
            t0 = _time.perf_counter()
            flightrec.record("compile_begin", kind="eval")
            # the net's trace lock is held EXCLUSIVELY for the whole
            # trace: the live params hold tracers until the export/lower
            # restores them, and no dispatch may capture _data meanwhile
            with spans.span("eval:build"), self._trace_lock:
                _param_arrs, pure_fn = self._ensure_pure()
                exported, fn = None, None
                if persist:
                    try:
                        # NB `from` form: a bare `import jax.export` here
                        # would make `jax` function-local and break the
                        # persist=False path below (UnboundLocalError)
                        from jax import export as jax_export
                        exported = jax_export.export(
                            jax.jit(pure_fn))(*arg_specs)
                        fn = jax.jit(exported.call).lower(
                            *arg_specs).compile()
                    except Exception:
                        # non-exportable program (custom calls, platform
                        # quirks): fall back to direct AOT compile,
                        # in-memory only — the drop must be diagnosable
                        _LOG.debug("jax.export failed; eval program stays "
                                   "in-memory", exc_info=True)
                        exported = None
                if fn is None:
                    fn = jax.jit(pure_fn).lower(*arg_specs).compile()
            compile_dur = _time.perf_counter() - t0
            _COMPILES.inc(kind="eval")
            _COMPILE_SECONDS.inc(compile_dur, kind="eval")
            _record_compile_span("eval:compile", compile_dur)
            flightrec.record("compile_end", kind="eval",
                             dur_s=round(compile_dur, 6))
            return fn, None, exported
        return build

    def __call__(self, *inputs):
        arrs = [a if isinstance(a, NDArray) else NDArray(jnp.asarray(a)) for a in inputs]
        if self._model_id is None:
            self._model_id = aot.model_id_for(self.net, extra=("eval",))
        cache_key = aot.cache_key(self._model_id, aot.input_signature(arrs),
                                  kind="eval")
        key = jax.random.PRNGKey(0)
        entry = aot.CACHE.lookup(cache_key)
        compile_miss = entry is None
        if compile_miss:
            param_arrs, _pure_fn = self._ensure_pure()
            arg_specs = (
                [jax.ShapeDtypeStruct(a._data.shape, a._data.dtype)
                 for a in param_arrs],
                [jax.ShapeDtypeStruct(a._data.shape, a._data.dtype)
                 for a in arrs],
                key)
            persist = aot.artifact_path(cache_key) is not None
            entry = aot.compile_cached(cache_key,
                                       self._builder(arg_specs, persist),
                                       exportable=persist,
                                       arg_specs=arg_specs)
            # an artifact load is NOT a compile: no trace happened, no
            # eval:compile span was recorded, the compile counter is
            # untouched — the dispatch below is an ordinary warm step
            compile_miss = entry.source == "build"
        else:
            param_arrs, _pure_fn = self._ensure_pure()
        # capture the param snapshot under the net's trace lock (a
        # concurrent trace of ANOTHER bucket has tracers swapped into
        # these NDArrays for its whole window; sub-µs when uncontended),
        # then execute outside it — captured real arrays can't be
        # corrupted by a trace that starts later
        with self._trace_lock:
            param_datas = [a._data for a in param_arrs]
        self._last_stats = entry.stats
        # the device leg of the serving span chain: under the batcher this
        # nests inside the worker's serve:batch span (same thread)
        with spans.span("eval:step", compile=compile_miss):
            dispatch_t0 = _time.perf_counter()
            out_datas, _aux = entry.fn(param_datas,
                                       [a._data for a in arrs], key)
            # MFU observation needs a block-until-ready span (device
            # time, not enqueue time). Under the batcher (an ambient
            # dispatch context) the very next step is a host
            # materialization anyway, so the sync moves cost rather than
            # adding any — always observe there. STANDALONE eval loops
            # overlap host prep with device execution, and an
            # unconditional block would serialize them: opt in via
            # MXTPU_DEVSTATS_EVAL_SYNC (mirror of the train knob).
            if entry.stats is not None and (
                    devstats.in_dispatch_context()
                    or config.get_env("MXTPU_DEVSTATS_EVAL_SYNC")):
                try:
                    jax.block_until_ready(out_datas)
                except Exception:
                    pass
                devstats.observe_dispatch(
                    "eval", entry.stats,
                    _time.perf_counter() - dispatch_t0,
                    model=self._model_id)
        outs = [NDArray(o) for o in out_datas]
        return outs[0] if len(outs) == 1 else tuple(outs)
