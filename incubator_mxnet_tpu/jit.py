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

import logging
import threading as _threading
import time as _time

import jax
import jax.numpy as jnp

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

__all__ = ["TrainStep", "EvalStep", "compiled_train_programs"]

# Compile observability: each shared-cache (aot.CACHE) miss that cannot be
# satisfied by a persisted artifact is one model trace + XLA compile.
# Single-device train programs AOT-compile inside the build (jit().lower()
# .compile() with the step's arg specs — which also hands devstats the
# compiled program's cost/memory analysis); mesh-train wrappers still
# compile lazily on the first dispatch. Either way the miss's whole
# first step — trace + compile + run — is what gets attributed to compile
# time. Watching compiles_total climb under bucketed variable-shape
# traffic is how an undersized MXTPU_AOT_CACHE_SIZE shows itself (so is
# mxtpu_aot_evictions_total, its direct cause).
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


def _record_compile_span(name, dur_s):
    """Retroactive span for a just-finished compile window (jax.jit
    compiles lazily inside the first call, so the window is only
    measurable after the fact), parented onto the ambient step span."""
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
        from .config import get_env
        self.remat = get_env("MXTPU_REMAT") if remat is None else remat
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
        # None pre-dispatch / on the lazy mesh path — what bench.py's
        # cost-analysis-derived MFU reads
        self._last_stats = None
        # watchdog bookkeeping: counts once this instance starts stepping
        self._hb_registered = False

    # ------------------------------------------------------------------
    def _split_params(self):
        params = list(self.net.collect_params().values())
        trainable = [p for p in params if p.grad_req != "null"]
        frozen = [p for p in params if p.grad_req == "null"]
        return trainable, frozen

    def _build(self, meta, n_inputs):
        trainable, frozen = self._split_params()
        t_arrs = [p.data() for p in trainable]
        f_arrs = [p.data() for p in frozen]
        net, loss_fn = self.net, self.loss_fn
        optimizer = self.trainer._optimizer
        aux_box = []
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
            finally:
                for a, s in zip(t_arrs, saved_t):
                    a._data = s
                for a, s in zip(f_arrs, saved_f):
                    a._data = s
            aux_box[:] = [a for a, _ in aux_pairs]
            return loss_scalar, (loss._data, [v for _, v in aux_pairs])

        fwd = jax.checkpoint(inner) if self.remat else inner

        # step_fn must NOT close over self: the compiled entry lives in
        # the process-wide aot.CACHE, and an entry pinning its TrainStep
        # would keep __del__ (which releases the entry) from ever running
        # — capture the needed config as plain locals instead
        grad_postprocess = self._grad_postprocess
        constrain_update = self._make_constrainer(trainable)

        def step_fn(t_datas, f_datas, opt_states, input_datas, key, lrs, wds, t,
                    rescale):
            (loss_scalar, (loss_full, aux_vals)), grads = jax.value_and_grad(
                fwd, argnums=0, has_aux=True)(t_datas, f_datas, input_datas, key)
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
            return loss_full, new_t, new_opt, aux_vals

        if self.mesh is not None:
            jitted = self._jit_sharded(step_fn, trainable, frozen)
        else:
            jitted = jax.jit(step_fn, donate_argnums=_donate((0, 2)))
        return jitted, trainable, frozen, t_arrs, f_arrs, aux_box

    def _build_entry(self, n_inputs, arg_specs=None):
        """aot.compile_cached build hook: (compiled callable, instance
        extras, no exported artifact — train programs stay in-memory).

        With ``arg_specs`` (the single-device path), the program is
        AOT-compiled HERE — ``jit().lower(specs).compile()`` under the
        net's trace lock, the same explicit pipeline EvalStep uses — so
        the XLA compile lands inside the train:build span instead of
        lazily inside the first dispatch, and the cache entry is an
        analyzable compiled program (devstats harvests its cost/memory
        analysis at insert). A failed lower/compile raises to the
        caller: a lazy retry would compile the same program again, and
        swallowing the first error is how a compiler refusal (a Mosaic
        kernel over its VMEM budget, an HBM OOM) gets hidden."""
        jitted, trainable, frozen, t_arrs, f_arrs, aux_box = \
            self._build(None, n_inputs)
        if arg_specs is not None and self.mesh is None:
            # the trace swaps tracers into the live param NDArrays
            # (inner's _data swap) — hold the net's trace lock for
            # the whole window, exactly like the eval build
            with self._trace_lock:
                jitted = jitted.lower(*arg_specs).compile()
        return jitted, (trainable, frozen, t_arrs, f_arrs, aux_box), None

    def _arg_specs(self, arrs, key):
        """jax.ShapeDtypeStruct tree matching one step_fn call — what
        _build_entry AOT-lowers with. None (→ lazy compile, no program
        stats) on the mesh path or when any piece is unavailable."""
        if self.mesh is not None:
            return None
        try:
            def sds(x):
                return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)

            trainer = self.trainer
            trainable, frozen = self._split_params()
            t_specs = [sds(p.data()._data) for p in trainable]
            f_specs = [sds(p.data()._data) for p in frozen]
            opt_specs = []
            for i, p in enumerate(trainable):
                idx = trainer._param2idx.get(p.name, i)
                opt_specs.append(jax.tree_util.tree_map(
                    sds, _tree_to_data(trainer._states[idx])))
            in_specs = [sds(a._data) for a in arrs]
            vec = jax.ShapeDtypeStruct((len(trainable),), jnp.float32)
            return (t_specs, f_specs, opt_specs, in_specs, sds(key),
                    vec, vec, jax.ShapeDtypeStruct((), jnp.int32),
                    jax.ShapeDtypeStruct((), jnp.float32))
        except Exception:
            _LOG.debug("train arg-spec construction failed; program "
                       "compiles lazily on first dispatch", exc_info=True)
            return None

    def _zero_leaf_sharding(self, p):
        """Per-leaf optimizer-state sharding rule under zero=True: shard
        dim 0 over the dp axis when divisible (masters/momenta share the
        param shape); scalars and indivisible leaves replicate; params a
        tensor/expert-parallel layer already sharded keep their spec."""
        from jax.sharding import NamedSharding, PartitionSpec
        repl = NamedSharding(self.mesh, PartitionSpec())
        if not self.zero or self.mesh is None \
                or self.mesh.shape.get(self.data_axis, 1) <= 1 \
                or getattr(p, "sharding", None) is not None:
            base = self._param_sharding(p)
            return lambda leaf: base
        n = self.mesh.shape[self.data_axis]
        dp = self.data_axis

        def rule(leaf):
            shape = getattr(leaf, "shape", ())
            if len(shape) >= 1 and shape[0] and shape[0] % n == 0:
                return NamedSharding(
                    self.mesh,
                    PartitionSpec(dp, *([None] * (len(shape) - 1))))
            return repl

        return rule

    def _make_constrainer(self, trainable):
        """Build the update-sharding constrainer (zero mode): new states
        stay dp-sharded, new weights return to their (replicated/TP) param
        sharding — the mismatch is what GSPMD lowers to
        reduce-scatter + sharded update + all-gather. Returns None when
        inactive; the returned closure is SELF-FREE (sharding rules are
        resolved here, at build time) so the shared-cache entry never pins
        this instance."""
        if not self.zero or self.mesh is None:
            return None
        rules = [self._zero_leaf_sharding(p) for p in trainable]
        shards = [self._param_sharding(p) for p in trainable]

        def constrain(new_t, new_opt):
            out_t, out_opt = [], []
            for w, s, rule, shard in zip(new_t, new_opt, rules, shards):
                out_t.append(jax.lax.with_sharding_constraint(w, shard))
                out_opt.append(jax.tree_util.tree_map(
                    lambda leaf, _r=rule: jax.lax.with_sharding_constraint(
                        leaf, _r(leaf)), s))
            return out_t, out_opt

        return constrain

    def _param_sharding(self, p):
        """Per-parameter sharding: p.sharding (a PartitionSpec) if set by a
        tensor/expert-parallel layer, else fully replicated."""
        from jax.sharding import NamedSharding, PartitionSpec
        if getattr(p, "sharding", None) is not None:
            spec = p.sharding
            if isinstance(spec, NamedSharding):
                return spec
            return NamedSharding(self.mesh, spec)
        return NamedSharding(self.mesh, PartitionSpec())

    def _jit_sharded(self, step_fn, trainable, frozen):
        """SPMD data(+tensor)-parallel: inputs sharded on the batch axis over
        ``data_axis``; params/optimizer state follow their own shardings. XLA
        inserts the gradient all-reduce (psum over dp) automatically — this IS
        the kvstore dist_device_sync path on ICI (SURVEY §2.5 north star)."""
        from jax.sharding import NamedSharding, PartitionSpec

        repl = NamedSharding(self.mesh, PartitionSpec())
        t_sh = [self._param_sharding(p) for p in trainable]
        f_sh = [self._param_sharding(p) for p in frozen]
        data_sh = NamedSharding(self.mesh, PartitionSpec(self.data_axis))
        jitted = jax.jit(step_fn, donate_argnums=_donate((0, 2)))

        state_rules = [self._zero_leaf_sharding(p) for p in trainable]

        def wrapper(t_datas, f_datas, opt_states, input_datas, *rest):
            # lay out operands on the mesh; no-op once steady-state shardings
            # are established (outputs inherit them), so the reshard cost is
            # first-step-only
            t_datas = [jax.device_put(d, s) for d, s in zip(t_datas, t_sh)]
            f_datas = [jax.device_put(d, s) for d, s in zip(f_datas, f_sh)]
            opt_states = [jax.tree_util.tree_map(
                lambda x, _r=r: jax.device_put(x, _r(x)), st)
                for st, r in zip(opt_states, state_rules)]
            input_datas = [jax.device_put(d, data_sh) for d in input_datas]
            rest = [jax.device_put(r, repl) for r in rest]
            return jitted(t_datas, f_datas, opt_states, input_datas, *rest)

        return wrapper

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
        # miss can shape its arg specs from it (one draw per step either
        # way — only the draw's position moved)
        key = _rnd._next_key()
        entry = aot.CACHE.lookup(cache_key)
        compile_miss = entry is None
        flightrec.record("step_begin", step=self._step_count + 1,
                         compile=compile_miss)
        if compile_miss:
            flightrec.record("compile_begin", kind="train")
            # Single-device train programs AOT-compile inside this build
            # span (jit().lower(arg_specs).compile() in _build_entry) so
            # the entry is an analyzable compiled program; the mesh-train
            # wrapper (and any spec-construction failure) still
            # jax.jit-compiles LAZILY inside the first train:dispatch
            # (donated-buffer programs are never jax.export-persisted
            # either way). The retroactive train:compile span below
            # covers the whole trace+compile+first-run window (same
            # definition as the mxtpu_jit_compile_seconds_total counter),
            # which is what separates "slow step" from "recompiling
            # every step".
            arg_specs = self._arg_specs(arrs, key)
            with spans.span("train:build"):
                entry = aot.compile_cached(
                    cache_key,
                    lambda: self._build_entry(n_net_inputs, arg_specs))
                self._cache_keys.add(cache_key)
        jitted = entry.fn
        self._last_stats = entry.stats
        trainable, frozen, t_arrs, f_arrs, aux_box = entry.extras

        optimizer = trainer._optimizer
        # python-side schedule state (lr scheduler, update counts) advances
        # here; the span covers the step's only per-parameter host loops
        with spans.span("train:schedule"):
            self._step_count += 1
            lrs, wds, opt_states = [], [], []
            for i, p in enumerate(trainable):
                idx = trainer._param2idx.get(p.name, i)
                optimizer._update_count(idx)
                lrs.append(optimizer._get_lr(idx))
                wds.append(optimizer._get_wd(idx))
                opt_states.append(_tree_to_data(trainer._states[idx]))
            t = self._step_count
            rescale = optimizer.rescale_grad / batch_size

        # the whole dispatch + write-back holds the net's trace lock: a
        # mesh-path MISS dispatch IS the lazy train trace (inner swaps
        # tracers into the live param NDArrays), a HIT dispatch reads and
        # then writes those same ``_data`` slots — either interleaved
        # with a concurrent eval/warm trace of this net would capture
        # tracers or lose the step's update to the trace's
        # finally-restore. Uncontended (the common case: nothing else
        # traces this net) the RLock costs sub-µs per step.
        with spans.span("train:dispatch", compile=compile_miss), \
                self._trace_lock:
            dispatch_t0 = _time.perf_counter()
            loss_full, new_t, new_opt, aux_vals = jitted(
                [a._data for a in t_arrs], [a._data for a in f_arrs],
                opt_states, [a._data for a in arrs], key,
                jnp.asarray(lrs, jnp.float32), jnp.asarray(wds, jnp.float32),
                jnp.asarray(t, jnp.int32), jnp.asarray(rescale, jnp.float32))
            if entry.stats is not None:
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
                    "train", entry.stats,
                    _time.perf_counter() - dispatch_t0,
                    model=self._model_id)

            for a, d in zip(t_arrs, new_t):
                a._data = d
            for i, p in enumerate(trainable):
                idx = trainer._param2idx.get(p.name, i)
                trainer._states[idx] = _rewrap_state(trainer._states[idx],
                                                     new_opt[i])
            for a, v in zip(aux_box, aux_vals):
                a._data = v
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
            # step (trace + XLA compile + first run — see the lazy-compile
            # note above), emitted as a child of the open train:step span
            _record_compile_span("train:compile", step_dur)
            flightrec.record("compile_end", kind="train",
                             dur_s=round(step_dur, 6))
        flightrec.record("step_end", step=self._step_count,
                         dur_s=round(step_dur, 6))
        return NDArray(loss_full)


def compiled_train_programs():
    """``[(model_id, optimised HLO text)]`` of the live train programs in
    ``aot.CACHE``. Every instruction of the text carries its scope path in
    ``metadata={op_name="jit(step_fn)/.../<scopes>/<primitive>"}`` (block
    names, ``ffn``, ``loss``, ``optimizer``), which is how a profiler
    capture's device events — named by instruction — are booked to a block.
    The text is rendered only here, on demand. Only AOT-compiled entries
    have one: the mesh path's lazily compiling wrapper yields nothing."""
    out = []
    for key in aot.CACHE.keys():
        entry = aot.CACHE.peek(key) if key.kind == "train" else None
        as_text = getattr(entry.fn, "as_text", None) if entry else None
        if as_text is not None:
            out.append((key.model_id, as_text()))
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
        # stats), None pre-dispatch — bench.py's cost-analysis MFU source
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
