"""Set-up seen from inside the program (telemetry/setup_phases.py,
ops/kernel_trace.py, the initialisation and lay-out spans): JAX's own
compile-pipeline events, fed here through JAX's own recording calls so the
cases are exact, land on the span open around them; a real tiny TrainStep's
first call is covered by its children and its warm steps touch nothing;
kernel traces are counted where Pallas makes them, once a shape under
`traced_once`."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import monitoring

from incubator_mxnet_tpu import gluon, jit, nd, telemetry
from incubator_mxnet_tpu.ops import attention, kernel_trace
from incubator_mxnet_tpu.ops import selective_scan as scan_mod
from incubator_mxnet_tpu.telemetry import setup_phases, spans

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"

COUNTERS = ("mxtpu_compile_phase_seconds_total",
            "mxtpu_compile_phase_events_total", "mxtpu_compile_cache_total",
            "mxtpu_kernel_trace_seconds_total", "mxtpu_kernel_traces_total",
            "mxtpu_import_seconds")


def jax_event(event, start, end, inside=None, fun_name="f"):
    """What jax._src.dispatch.log_elapsed_time tells its listeners, in its
    order, with times of the test's choosing."""
    monitoring.record_scalar(event, start, fun_name=fun_name)
    if inside is not None:
        inside()
    monitoring.record_event_duration_secs(event, end - start,
                                          fun_name=fun_name)
    monitoring.record_event_time_span(event, start, end, fun_name=fun_name)


def seconds(phase, owner):
    return setup_phases._SECONDS.value(phase=phase, owner=owner)


def events(phase, owner):
    return setup_phases._EVENTS.value(phase=phase, owner=owner)


def everything_of_this_pr():
    """Every series of the PR's counters and gauge."""
    return {name: telemetry.REGISTRY.get(name).series() for name in COUNTERS}


@pytest.fixture(autouse=True)
def fresh_ring():
    spans.reset()
    yield
    spans.reset()


def test_an_event_under_a_build_span_is_its_child_on_the_spans_clock():
    before = seconds("trace", "train:build"), events("trace", "train:build")
    with spans.span("train:build") as parent:
        # JAX's clock, as JAX stamps its events
        t0 = time.time()  # mxtpulint: disable=R006
        time.sleep(0.02)
        t1 = time.time()  # mxtpulint: disable=R006
        jax_event(TRACE, t0, t1, fun_name="step_fn")
    child, build = spans.snapshot()
    assert (child["name"], build["name"]) == ("train:trace", "train:build")
    assert child["parent_id"] == parent.span_id
    assert child["args"] == {"fun_name": "step_fn"}
    assert child["dur_us"] == pytest.approx((t1 - t0) * 1e6)
    # inside its parent to within a millisecond, though JAX's clock is
    # time.time() and the spans' is anchored elsewhere
    assert child["start_us"] >= build["start_us"] - 1e3
    assert child["start_us"] + child["dur_us"] \
        <= build["start_us"] + build["dur_us"] + 1e3
    assert seconds("trace", "train:build") - before[0] \
        == pytest.approx(t1 - t0)
    assert events("trace", "train:build") - before[1] == 1


@pytest.mark.parametrize("owner,event,child", [
    ("train:build", LOWER, "train:lower"),
    ("eval:build", TRACE, "eval:trace"),
    ("aot:load", COMPILE, "aot:backend_compile"),
    ("gluon:initialize", COMPILE, "init:backend_compile"),
    ("gluon:cast", LOWER, "init:lower"),
    ("train:init_states", TRACE, "init:trace"),
])
def test_children_are_named_after_their_owner(owner, event, child):
    phase = child.split(":")[1]
    before = events(phase, owner)
    with spans.span(owner):
        now = time.time()
        jax_event(event, now - 0.001, now)
    assert [r["name"] for r in spans.snapshot()] == [child, owner]
    assert events(phase, owner) == before + 1


def test_with_no_span_open_the_owner_is_other_and_no_span_is_made():
    before = seconds("lower", "other"), events("lower", "other")
    jax_event(LOWER, 100.0, 100.5)
    assert spans.snapshot() == []
    assert seconds("lower", "other") - before[0] == pytest.approx(0.5)
    assert events("lower", "other") - before[1] == 1


def test_a_nested_trace_adds_its_events_but_not_its_seconds():
    """An inner jax.jit traced inside an outer one (every `traced_once`
    call): three events, the outer's 1.0 s once."""
    before = seconds("trace", "train:build"), events("trace", "train:build")
    with spans.span("train:build"):
        jax_event(TRACE, 10.0, 11.0, inside=lambda: (
            jax_event(TRACE, 10.1, 10.3), jax_event(TRACE, 10.5, 10.9)))
    assert seconds("trace", "train:build") - before[0] == pytest.approx(1.0)
    assert events("trace", "train:build") - before[1] == 3
    # the inner events lie inside the outer one's span and get none
    assert [r["name"] for r in spans.snapshot()] \
        == ["train:trace", "train:build"]


def test_the_phases_partition_the_time_in_the_pipeline():
    """An eager op inside a trace runs its own whole pipeline there: each
    phase gets its own seconds and the trace what is left."""
    owner = "eval:build"
    before = {p: seconds(p, owner) for p in setup_phases.PHASES}

    def eager():
        jax_event(TRACE, 20.1, 20.2)
        jax_event(LOWER, 20.2, 20.4)
        jax_event(COMPILE, 20.4, 20.9)

    with spans.span(owner):
        jax_event(TRACE, 20.0, 22.0, inside=eager)
    got = {p: seconds(p, owner) - before[p] for p in setup_phases.PHASES}
    # the outer trace's 2.0 s less the 0.8 inside it, and the eager trace
    assert got == pytest.approx({"trace": 1.2 + 0.1, "lower": 0.2,
                                 "backend_compile": 0.5, "cache_read": 0.0})
    assert sum(got.values()) == pytest.approx(2.0)


@pytest.mark.parametrize("cache_event,phase,result", [
    (HIT, "cache_read", "hit"), (MISS, "backend_compile", "miss"),
    (None, "backend_compile", None)])
def test_a_compile_the_cache_answered_is_a_cache_read(cache_event, phase,
                                                      result):
    owner = "train:build"
    before = {p: events(p, owner) for p in setup_phases.PHASES}
    cache = {r: setup_phases._CACHE.value(result=r, owner=owner)
             for r in ("hit", "miss")}
    with spans.span(owner):
        jax_event(COMPILE, 30.0, 31.0, inside=None if cache_event is None
                  else lambda: monitoring.record_event(cache_event))
        # the flag is spent: the next compile is judged on its own
        jax_event(COMPILE, 31.0, 31.5)
    got = {p: events(p, owner) - before[p] for p in setup_phases.PHASES}
    want = dict.fromkeys(setup_phases.PHASES, 0)
    want["backend_compile"] += 1
    want[phase] += 1
    assert got == want
    first = spans.snapshot()[0]
    assert first["name"] == "train:" + phase
    assert first["args"]["cache_hit"] is (result == "hit")
    for r in ("hit", "miss"):
        assert setup_phases._CACHE.value(result=r, owner=owner) \
            - cache[r] == (r == result)


def test_a_second_threads_events_leave_the_first_threads_owner_alone():
    mine = events("trace", "train:build")
    others = events("trace", "other")

    def elsewhere():
        jax_event(TRACE, 40.0, 40.1)

    with spans.span("train:build"):
        t = threading.Thread(target=elsewhere)
        t.start()
        t.join()
    assert events("trace", "train:build") == mine
    assert events("trace", "other") == others + 1


def test_registering_twice_listens_once():
    setup_phases.install()
    setup_phases.install()
    before = events("trace", "other")
    jax_event(TRACE, 50.0, 50.1)
    assert events("trace", "other") == before + 1


def test_other_events_and_lone_durations_count_nothing():
    before = everything_of_this_pr()
    monitoring.record_event("/jax/compilation_cache/compile_requests_use_cache")
    monitoring.record_event_duration_secs(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.5)
    monitoring.record_event_time_span("/jax/some/other_duration", 1.0, 2.0)
    monitoring.record_scalar("/jax/some/scalar", 3)
    # JAX tells a pipeline event twice, as a duration and as a time span:
    # only the time span is booked
    monitoring.record_event_duration_secs(TRACE, 0.5, fun_name="f")
    assert everything_of_this_pr() == before


def test_the_import_is_timed_in_two_parts():
    import incubator_mxnet_tpu as mx
    # the package's __init__ took both readings (another test's
    # telemetry.reset() may have dropped the gauge's series since)
    assert mx._T_IMPORT > 0 and mx.random.BACKEND_TOUCH_S >= 0
    gauge = telemetry.REGISTRY.get("mxtpu_import_seconds")
    saved = gauge.series()
    try:
        setup_phases.record_import(5.5, 3.25)
        assert dict((labels["part"], v) for labels, v in gauge.series()) \
            == {"modules": 2.25, "backend": 3.25}
    finally:
        for labels, v in saved:
            gauge.set(v, **labels)


# ---- the program's own set-up spans, with real programs --------------------
def _tiny_step():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, in_units=8, activation="relu"),
            gluon.nn.Dense(4, in_units=16))
    net.initialize()
    net.cast("bfloat16")
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-3, "multi_precision": True})
    step = jit.TrainStep(net, gluon.loss.L2Loss(), trainer)
    x = nd.array(np.ones((4, 8), "float32")).astype("bfloat16")
    y = nd.array(np.ones((4, 4), "float32")).astype("bfloat16")
    return net, step, x, y


def _children(records, parent):
    return [r for r in records if r["parent_id"] == parent["span_id"]
            and not r["name"].endswith(":compile")]


def _outside(records, parent):
    """Microseconds of `parent` inside no child (the retroactive
    train:compile lump is no part of the split)."""
    covered, edge = 0.0, parent["start_us"]
    for r in sorted(_children(records, parent), key=lambda r: r["start_us"]):
        end = r["start_us"] + r["dur_us"]
        if end > edge:
            covered += end - max(r["start_us"], edge)
            edge = end
    return parent["dur_us"] - covered


def test_initialize_and_cast_are_spans_that_own_their_programs():
    before = {o: events("backend_compile", o)
              for o in ("gluon:initialize", "gluon:cast")}
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(24, in_units=12), gluon.nn.Dense(6, in_units=24))
    net.initialize()
    net.cast("float16")
    by_name = {}
    for r in spans.snapshot():
        by_name.setdefault(r["name"], []).append(r)
    (init,), (cast,) = by_name["gluon:initialize"], by_name["gluon:cast"]
    assert init["args"] == {"params": 4}
    # ONE span for the tree, though every child block casts itself
    assert cast["args"] == {"dtype": "float16", "params": 4}
    for owner, span in (("gluon:initialize", init), ("gluon:cast", cast)):
        assert events("backend_compile", owner) > before[owner]
        kids = {r["name"] for r in _children(spans.snapshot(), span)}
        assert kids and kids <= {"init:trace", "init:lower",
                                 "init:backend_compile", "init:cache_read"}


def test_a_first_train_step_is_covered_by_its_children():
    _net, step, x, y = _tiny_step()
    spans.reset()
    before = {p: events(p, "train:build") for p in setup_phases.PHASES}
    step(x, y).asnumpy()
    records = spans.snapshot()
    by_name = {r["name"]: r for r in records}
    first = by_name["train:step"]
    assert {r["name"] for r in _children(records, first)} == {
        "train:host_transfer", "train:init_states", "train:build",
        "train:schedule", "train:dispatch"}
    assert _outside(records, first) < 0.05 * first["dur_us"]
    build = by_name["train:build"]
    assert [r["name"] for r in _children(records, build)] == [
        "train:layout", "train:trace", "train:lower",
        "train:backend_compile", "aot:analyze"]
    assert _outside(records, build) < 0.05 * build["dur_us"]
    # the optimizer state's small programs belong to their own span
    assert {r["name"] for r in _children(records,
                                         by_name["train:init_states"])} \
        <= {"init:trace", "init:lower", "init:backend_compile",
            "init:cache_read"}
    got = {p: events(p, "train:build") - before[p]
           for p in setup_phases.PHASES}
    assert got["lower"] == 1 and got["trace"] >= 1
    assert got["backend_compile"] + got["cache_read"] == 1


def test_warm_steps_touch_no_counter_gauge_or_span_of_the_setup():
    """The window: nothing of this PR moves between its first and its last
    instant."""
    _net, step, x, y = _tiny_step()
    step(x, y).asnumpy()
    step(x, y).asnumpy()
    spans.reset()
    before = everything_of_this_pr()
    for _ in range(5):
        step(x, y).asnumpy()
    assert everything_of_this_pr() == before
    assert {r["name"] for r in spans.snapshot()} == {
        "train:step", "train:host_transfer", "train:schedule",
        "train:dispatch"}


def test_a_first_eval_call_builds_under_eval_build():
    net = gluon.nn.Dense(5, in_units=7)
    net.initialize()
    spans.reset()
    before = events("lower", "eval:build")
    jit.EvalStep(net)(nd.array(np.ones((2, 7), "float32"))).asnumpy()
    records = spans.snapshot()
    build = next(r for r in records if r["name"] == "eval:build")
    assert [r["name"] for r in _children(records, build)] == [
        "eval:trace", "eval:lower", "eval:backend_compile"]
    # (a 30 ms build: make_pure_fn's own milliseconds are all that is
    # left, and they stretch under the six workers' load)
    assert _outside(records, build) < max(0.05 * build["dur_us"], 5e4)
    assert events("lower", "eval:build") == before + 1


# ---- kernel traces ---------------------------------------------------------
@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")


def traces(kernel):
    return kernel_trace._TRACES.value(kernel=kernel)


def test_two_equal_shape_calls_of_the_scan_count_one_trace(interpreted):
    """`traced_once` still holds: a second layer of the same shapes
    re-binds the kernel jaxpr the first one traced."""
    from test_phi4flash import _chunked, _scan_inputs
    args = _scan_inputs(1, 1, 32, scan_mod._KERNEL_CHANNELS, 16)
    before = {k: traces(k) for k in ("selective_scan_fwd",
                                     "selective_scan_bwd")}
    secs = kernel_trace._SECONDS.value(kernel="selective_scan_fwd")

    def two_layers(*a):
        return jnp.sum(_chunked(*a, 16) ** 2) + jnp.sum(_chunked(*a, 16))

    jax.jit(jax.grad(two_layers, tuple(range(len(args))))).trace(*args)
    # a forward that keeps the chunk starts (differentiated), traced once
    # for both layers, and one backward
    assert traces("selective_scan_fwd") == before["selective_scan_fwd"] + 1
    assert traces("selective_scan_bwd") == before["selective_scan_bwd"] + 1
    assert kernel_trace._SECONDS.value(kernel="selective_scan_fwd") > secs
    # another shape is another trace
    more = _scan_inputs(1, 1, 48, scan_mod._KERNEL_CHANNELS, 16)
    jax.jit(lambda *a: _chunked(*a, 16)).trace(*more)
    assert traces("selective_scan_fwd") == before["selective_scan_fwd"] + 2


@pytest.mark.parametrize("shape,window,kernels", [
    ((1, 2, 256, 128), None, ("flash_fwd", "flash_bwd_dkvq")),
    ((1, 2, 256, 128), 128, ("flash_window_fwd", "flash_window_bwd")),
    # (the short calls are jitted: a shape another test file has traced in
    # this process would count nothing, as it should)
    ((3, 2, 384, 64), None, ("flash_short_fwd", "flash_short_bwd")),
])
def test_attention_kernels_are_counted_under_their_own_names(
        interpreted, shape, window, kernels):
    q = jnp.ones(shape, jnp.bfloat16)
    before = [traces(k) for k in kernels]
    everyone = dict((labels["kernel"], v)
                    for labels, v in kernel_trace._TRACES.series())

    def loss(q, k, v):
        return jnp.sum(attention.flash_attention(
            q, k, v, True, None, None, None, window).astype(jnp.float32))

    jax.jit(jax.grad(loss, (0, 1, 2))).trace(q, q, q)
    assert [traces(k) for k in kernels] == [b + 1 for b in before]
    now = dict((labels["kernel"], v)
               for labels, v in kernel_trace._TRACES.series())
    assert {k for k in now if now[k] != everyone.get(k, 0)} == set(kernels)


def test_an_eager_kernel_call_is_no_trace(interpreted):
    """Eagerly the call also compiles and runs: not a kernel's trace."""
    before = traces("flash_short_fwd"), traces("flash_fwd")
    q = jnp.ones((1, 1, 128, 128), jnp.bfloat16)
    attention._fa_call(q, q, q, True, 0.1, 128, 128)
    assert (traces("flash_short_fwd"), traces("flash_fwd")) == before


def test_every_kernel_call_goes_by_the_door_under_one_of_ten_names():
    import inspect
    from incubator_mxnet_tpu.ops import delta_rule
    for mod, calls in ((attention, 4), (scan_mod, 2), (delta_rule, 2)):
        src = inspect.getsource(mod)
        assert "pl.pallas_call(" not in src
        assert src.count("kernel_trace.pallas_call(") == calls
    assert {labels["kernel"] for labels, _ in
            kernel_trace._TRACES.series()} <= {
        "flash_fwd", "flash_bwd_dkvq", "flash_window_fwd",
        "flash_window_bwd", "flash_short_fwd", "flash_short_bwd",
        "selective_scan_fwd", "selective_scan_bwd",
        "delta_rule_fwd", "delta_rule_bwd"}
