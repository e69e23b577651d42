"""Driver of the training cells: gluon.Trainer (multi-precision Adam) ->
jit.TrainStep on one chip, or parallel.DataParallelTrainStep over a mesh
when the cell's workload file has a `mesh` group.

Set-up (everything before the window): build the model from the seed,
check it against the configuration's float32 reference on the first batch
(the outputs of a compiled forward, the first train step's own loss and,
where the cell's `check` asks for it, the direction of the first update
against the reference's gradient), and warm the step. The loop: a fresh
batch every step from a generator thread, uploaded and dispatched by this
thread, with at most `IN_FLIGHT` steps not yet finished on the device — a
training loop that reads its loss a step or two late. The clock starts
with that pipeline full, at the instant a warm step's loss arrives, and
stops when the last step's does; tokens per second is the steps that
finished in between over that time, so filling the pipeline is set-up and
not rate.
"""
import math
import queue
import shutil
import threading
import time

#: steps dispatched ahead of the one being waited for: the device always
#: has the next step queued, and the host cannot run away from it
IN_FLIGHT = 2
#: batches the generator thread keeps ready
PREFETCH = 4
#: steps dispatched before the window, after the compiling one: the last
#: of them waits for the first, so the window opens on a full pipeline
WARM_STEPS = IN_FLIGHT + 1
#: a traced window is this long at most (traces are large)
TRACE_SECONDS = 3.0
#: any lowering in the window means a program was compiled there
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class Prefetcher:
    """The input pipeline: one daemon thread draws batches from the
    generator into a bounded queue."""

    def __init__(self, source):
        self._source = source
        self._queue = queue.Queue(PREFETCH)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._fill, daemon=True,
                                        name="perfbench-input")
        self._thread.start()

    def _fill(self):
        for batch in self._source:
            while not self._stop.is_set():
                try:
                    self._queue.put(batch, timeout=0.1)
                    break
                except queue.Full:
                    continue
            if self._stop.is_set():
                return

    def next(self):
        return self._queue.get(timeout=60.0)

    def close(self):
        self._stop.set()
        self._thread.join(10.0)
        if self._thread.is_alive():
            raise RuntimeError("input thread did not stop")


def rel_rms(got, want):
    import numpy as np
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


def update_agreement(before, after, grads):
    """How far the first update went down the reference's gradient, per
    checked parameter. Adam's first step moves every weight by the learning
    rate against the sign of its gradient, so the sign is all an update
    shows; a bfloat16 weight too coarse to move shows nothing and is left
    out. -> {name: (the share of the reference gradient's magnitude, over
    the weights that moved, that sits on weights that moved against it —
    1 is the same direction everywhere, 0.5 unrelated —, the share of the
    weights that moved)}."""
    import numpy as np
    out = {}
    for name, g in grads.items():
        g = np.asarray(g, np.float32)
        delta = np.asarray(after[name], np.float32) \
            - np.asarray(before[name], np.float32)
        moved = delta != 0
        total = np.abs(g)[moved].sum()
        down = np.abs(g)[moved & (np.sign(delta) == -np.sign(g))].sum()
        out[name] = (float(down / total) if total else 0.0,
                     float(moved.mean()))
    return out


def run(run):
    import jax
    import numpy as np
    from incubator_mxnet_tpu import gluon, jit, nd, parallel

    cfg, wl, log = run.config, run.workload, run.log
    traffic, check = wl["traffic"], wl["check"]
    chips = run.cell["chips"]
    compile_s = 0.0           # every first call of a shape, summed
    t_phase = [run.t_process]

    def phase(what):
        """Where set-up goes, on an earlier line of every run."""
        now = time.perf_counter()
        log("set-up: %s %.1f s" % (what, now - t_phase[0]))
        t_phase[0] = now

    phase("start of process, imports, device")

    lowerings = [0]
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, _secs, **_kw: lowerings.__setitem__(
            0, lowerings[0] + (name == LOWERING_EVENT)))

    # ---- the system ------------------------------------------------------
    builder = run.load_module("builders", cfg["builder"])
    built = builder.build(cfg, run.seed, traffic["seq_len"])
    source = run.load_module("traffic", traffic["generator"]).generate(
        traffic, run.seed, cfg)
    first_tokens, first_labels = next(source)
    phase("model built and initialised")

    # ---- float32 reference on the first batch, before any update ---------
    n_ref, tail = check["sequences"], check["tail_positions"]
    reference = run.load_module("reference", cfg["reference"])
    ref_fn = jax.jit(lambda p, t, y: reference.forward(p, cfg, t, y, tail))
    ref_out, ref_loss = jax.device_get(ref_fn(
        builder.reference_params(built["model"]),
        first_tokens[:n_ref], first_labels[:n_ref]))
    phase("float32 reference forward, %d x %d tokens"
          % (n_ref, traffic["seq_len"]))
    t0 = time.perf_counter()
    sys_out = jit.EvalStep(built["eval_net"])(
        nd.array(first_tokens[:n_ref])).asnumpy()
    compile_s += time.perf_counter() - t0
    out_err = rel_rms(sys_out[:, -tail:], ref_out)
    del ref_fn, sys_out, ref_out
    phase("first call of the compiled forward")
    min_agreement = check.get("update_agreement")
    if min_agreement is not None:
        # the step donates its parameters: keep the checked ones on the
        # host; the update is the whole batch's, so the gradient is too
        live = builder.reference_params(built["model"])
        before = jax.device_get(reference.update_checked(live))
        ref_grads = jax.device_get(jax.jit(
            lambda p, t, y: reference.checked_grads(p, cfg, t, y))(
                live, first_tokens, first_labels))
        del live
        phase("float32 reference gradient of %s" % sorted(ref_grads))

    # ---- the step --------------------------------------------------------
    trainer = gluon.Trainer(built["train_net"].collect_params(), "adam",
                            {"learning_rate": 1e-4, "multi_precision": True})
    if "mesh" in wl:
        mesh = parallel.make_mesh(wl["mesh"]["axes"],
                                  devices=jax.devices()[:chips])
        step = parallel.DataParallelTrainStep(
            built["train_net"], built["loss"], trainer, mesh=mesh,
            zero=wl["mesh"]["zero"])
    else:
        step = jit.TrainStep(built["train_net"], built["loss"], trainer)
    t0 = time.perf_counter()
    first_loss = step(nd.array(first_tokens), nd.array(first_labels)) \
        .asnumpy().astype(np.float32)
    compile_s += time.perf_counter() - t0
    phase("first call of the train step")
    loss_err = float(np.max(np.abs(first_loss[:n_ref] - ref_loss)
                            / np.abs(ref_loss)))
    agrees = out_err <= check["outputs_rel_rms"] \
        and loss_err <= check["loss_rel"]
    update = ""
    if min_agreement is not None:
        after = jax.device_get(reference.update_checked(
            builder.reference_params(built["model"])))
        per_param = update_agreement(before, after, ref_grads)
        agrees = agrees and min(a for a, _ in per_param.values()) \
            >= min_agreement
        update = ", first update down the reference gradient (share of " \
            "its magnitude, limit %g; weights that moved): %s" % (
                min_agreement, ", ".join(
                    "%s %.4f (%.0f %%)" % (n, a, 100 * m)
                    for n, (a, m) in sorted(per_param.items())))
        del before, after, ref_grads
    log("agreement with reference/%s: outputs rel-rms error %.4g (limit %g), "
        "first-step loss %s vs %s, rel error %.4g (limit %g)%s: %s"
        % (cfg["reference"], out_err, check["outputs_rel_rms"],
           first_loss[:n_ref].tolist(), ref_loss.tolist(), loss_err,
           check["loss_rel"], update, "ok" if agrees else "DISAGREES"))

    feed = Prefetcher(source)
    losses = [first_loss]
    annotate = jax.profiler.TraceAnnotation
    wait_ms, call_ms, block_ms = [], [], []

    def one_step(pending):
        """next batch -> upload -> dispatch; then wait for the step
        IN_FLIGHT back."""
        t_a = time.perf_counter()
        with annotate("bench:next_batch"):
            tokens, labels = feed.next()
            tokens, labels = nd.array(tokens), nd.array(labels)
        t_b = time.perf_counter()
        with annotate("bench:step_call"):
            loss = step(tokens, labels)
        t_c = time.perf_counter()
        pending.append(loss)
        if len(pending) > IN_FLIGHT:
            with annotate("bench:block"):
                losses.append(pending.pop(0).asnumpy())
        t_d = time.perf_counter()
        wait_ms.append((t_b - t_a) * 1e3)
        call_ms.append((t_c - t_b) * 1e3)
        block_ms.append((t_d - t_c) * 1e3)

    def drain(pending):
        with annotate("bench:block"):
            while pending:
                losses.append(pending.pop(0).asnumpy())

    tracing = False
    try:
        seconds = min(run.seconds, TRACE_SECONDS) if run.trace \
            else run.seconds
        if run.trace:
            shutil.rmtree(run.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(run.trace_dir, profiler_options=opts)
            tracing = True
        pending = []
        for _ in range(WARM_STEPS):
            one_step(pending)       # the last waits for the first's loss
        del wait_ms[:], call_ms[:], block_ms[:]
        phase("%d warm steps, %d still in flight" % (WARM_STEPS,
                                                     len(pending)))

        # ---- the window: from one loss's arrival to the last one's -------
        lowerings_before = lowerings[0]
        steps_before = len(losses)
        setup_s = time.perf_counter() - run.t_process
        t_window = time.perf_counter()
        with annotate("bench:traced_window"):
            while time.perf_counter() - t_window < seconds:
                one_step(pending)
            drain(pending)
            window_s = time.perf_counter() - t_window
    finally:
        if tracing:
            jax.profiler.stop_trace()
        feed.close()
    steps = len(losses) - steps_before
    compiled_in_window = lowerings[0] - lowerings_before

    # ---- what came out ---------------------------------------------------
    per_step = [float(np.mean(np.asarray(x, np.float32))) for x in losses]
    failed = sum(1 for v in per_step[steps_before:] if not math.isfinite(v))
    tokens_per_step = traffic["batch"] * traffic["seq_len"]
    tok_per_s = steps * tokens_per_step / window_s
    log("window: %d steps of %d tokens finished in %.3f s; steps with a "
        "non-finite loss: %d; programs lowered inside the window: %d"
        % (steps, tokens_per_step, window_s, failed, compiled_in_window))
    if len(per_step) > 20:
        log("loss: step 1 %.4f, step 20 %.4f, last (step %d) %.4f"
            % (per_step[0], per_step[19], len(per_step), per_step[-1]))
    model_flops = builder.model_flops_per_token(cfg, traffic["seq_len"])
    if run.peaks is not None:
        log("MFU %.4f = %.1f tokens/s x %d model FLOP/token / (%d chip(s) "
            "x %.4g FLOP/s)" % (
                tok_per_s * model_flops
                / (chips * run.peaks["bf16_flops_per_s"]), tok_per_s,
                model_flops, chips, run.peaks["bf16_flops_per_s"]))

    context = {
        "config": cfg, "workload": wl, "peaks": run.peaks, "chips": chips,
        "steps": steps, "tokens_per_step": tokens_per_step,
        "attention_flops_per_token": builder.attention_flops_per_token(
            cfg, traffic["seq_len"]),
        "window_s": window_s, "setup_compile_s": compile_s,
        "input_wait_ms": wait_ms, "step_call_ms": call_ms,
        "block_ms": block_ms, "trace": None,
    }
    if run.trace:
        reducer = run.load_module("trace", "reduce")
        context["trace"] = reducer.reduce_capture(run.trace_dir)
    return {
        "correct": agrees and failed == 0 and compiled_in_window == 0
        and steps > 0,
        "attempted": steps, "failed": failed,
        "end_to_end": {"train_tok_per_s": tok_per_s, "setup_s": setup_s},
        "context": context,
    }
