"""Span-tracing tier: nesting/parenting semantics, explicit queue-boundary
propagation, chrome-trace mirroring, the 16-thread race, the opt-in
histogram bridge — and the e2e acceptance: one serving request followed as
a parented span chain (HTTP -> queue -> bucket -> device) inside a single
chrome-trace dump."""
import json
import queue
import threading
import urllib.request

import pytest

from incubator_mxnet_tpu import profiler, telemetry
from incubator_mxnet_tpu.telemetry import spans


@pytest.fixture(autouse=True)
def _fresh_spans():
    spans.reset()
    yield
    spans.reset()
    spans.set_histogram_bridge(None)


def by_name(recs=None):
    out = {}
    for r in (recs if recs is not None else spans.snapshot()):
        out.setdefault(r["name"], r)
    return out


# ------------------------------------------------------------- semantics
def test_nesting_parent_links_and_order():
    with spans.span("outer") as outer:
        with spans.span("mid") as mid:
            assert spans.current_span() is mid
            with spans.span("inner", k=7):
                pass
        assert spans.current_span() is outer
    assert spans.current_span() is None
    recs = spans.snapshot()
    # children finish (and land) before their parents
    assert [r["name"] for r in recs] == ["inner", "mid", "outer"]
    b = by_name(recs)
    assert b["outer"]["parent_id"] is None
    assert b["mid"]["parent_id"] == b["outer"]["span_id"]
    assert b["inner"]["parent_id"] == b["mid"]["span_id"]
    assert b["inner"]["args"] == {"k": 7}
    assert b["inner"]["dur_us"] >= 0
    # start ordering: outer began first
    assert b["outer"]["start_us"] <= b["mid"]["start_us"]


def test_exception_closes_span_and_stack():
    with pytest.raises(RuntimeError):
        with spans.span("boom"):
            raise RuntimeError("x")
    assert spans.current_span() is None
    rec = spans.snapshot()[-1]
    assert rec["name"] == "boom" and rec["args"]["error"] == "RuntimeError"


def test_request_id_flows_from_ambient_trace():
    with telemetry.request_scope("rid123"):
        with spans.span("a"):
            with spans.span("b"):
                pass
    b = by_name()
    assert b["a"]["request_id"] == "rid123"
    assert b["b"]["request_id"] == "rid123"


def test_sibling_spans_share_parent():
    with spans.span("root") as root:
        with spans.span("s1"):
            pass
        with spans.span("s2"):
            pass
    b = by_name()
    assert b["s1"]["parent_id"] == b["s2"]["parent_id"] == root.span_id


# ------------------------------------------- queue-boundary propagation
def test_cross_thread_propagation_via_context():
    """The batcher pattern in miniature: producer captures its context,
    a consumer THREAD parents both a live child and a retroactive
    record_span onto it."""
    q = queue.Queue()
    done = threading.Event()

    def consumer():
        ctx = q.get()
        with spans.span("consume", parent=ctx):
            pass
        spans.record_span("queue_wait", 1000.0, 50.0, parent=ctx)
        done.set()

    t = threading.Thread(target=consumer, daemon=True)
    t.start()
    with spans.span("produce") as prod:
        q.put(spans.current_context())
        assert done.wait(10)
    t.join(10)
    b = by_name()
    assert b["consume"]["parent_id"] == prod.span_id
    assert b["queue_wait"]["parent_id"] == prod.span_id
    # the consumer thread's ambient stack was not involved
    assert b["consume"]["thread"] != b["produce"]["thread"]


def test_record_span_inherits_request_id_from_context():
    with telemetry.request_scope("ridQ"):
        with spans.span("root"):
            ctx = spans.current_context()
    spans.record_span("later", 0.0, 1.0, parent=ctx)
    assert by_name()["later"]["request_id"] == "ridQ"


def test_context_is_identity_not_liveness():
    # a context captured from a finished span still parents correctly
    with spans.span("gone") as sp:
        ctx = sp.context()
    spans.record_span("orphan", 0.0, 1.0, parent=ctx)
    assert by_name()["orphan"]["parent_id"] == sp.span_id


# --------------------------------------------------- chrome-trace mirror
def test_chrome_trace_parenting(tmp_path):
    out = tmp_path / "trace.json"
    profiler.set_config(filename=str(out))
    profiler.set_state("run")
    try:
        with spans.span("outer"):
            with spans.span("inner"):
                pass
    finally:
        profiler.set_state("stop")
    profiler.dump()
    events = {e["name"]: e for e in json.load(open(out))["traceEvents"]
              if e.get("cat") == "span"}
    assert {"outer", "inner"} <= set(events)
    assert events["inner"]["args"]["parent_id"] \
        == events["outer"]["args"]["span_id"]
    assert events["inner"]["ph"] == "X" and events["inner"]["dur"] >= 0


def test_spans_not_mirrored_when_profiler_stopped(tmp_path):
    assert profiler.state() == "stop"
    with spans.span("quiet"):
        pass
    # ...but the span ring still has it (always-on causality buffer)
    assert "quiet" in by_name()


# -------------------------------------------------------------- export
def _host_events(capture_dir):
    """{event name: [duration_ns]} of a jax.profiler capture's host planes."""
    import glob
    import jax
    [path] = glob.glob(str(capture_dir / "**" / "*.xplane.pb"),
                       recursive=True)
    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out.setdefault(ev.name, []).append(ev.duration_ns)
    return out


@pytest.mark.parametrize("session", [True, False],
                         ids=["under-a-capture", "no-session"])
def test_spans_are_trace_annotations_too(tmp_path, session):
    """Under a jax.profiler capture a span is on the host plane under its
    own name, on the profiler's clock; with no session active it costs a
    flag test, and either way it lands in the ring."""
    import jax
    if session:
        jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("train:step"):
            with spans.span("train:dispatch", compile=False):
                pass
            leaked = spans.span("train:leaked").start()
        # the parent's end closed the leaked child's stack entry, and the
        # child's own late end() still exits its annotation once
        leaked.end()
    finally:
        if session:
            jax.profiler.stop_trace()
    assert [r["name"] for r in spans.snapshot()] == [
        "train:dispatch", "train:step", "train:leaked"]
    assert spans.current_span() is None
    if session:
        events = _host_events(tmp_path)
        for name in ("train:step", "train:dispatch", "train:leaked"):
            assert len(events[name]) == 1, name
        assert events["train:step"][0] >= events["train:dispatch"][0]


def test_train_step_emits_its_spans_with_schedule():
    """train:schedule (update counts, lrs, wds, optimizer states) is one of
    train:step's children, between the build and the dispatch."""
    import numpy as np
    from incubator_mxnet_tpu import gluon, jit, nd
    net = gluon.nn.Dense(4, in_units=4)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 1e-2})
    step = jit.TrainStep(net, gluon.loss.L2Loss(), trainer)
    x = nd.array(np.ones((4, 4), "float32"))
    step(x, x)
    spans.reset()
    step(x, x)
    recs = spans.snapshot()
    names = [r["name"] for r in recs]
    assert names == ["train:host_transfer", "train:schedule",
                     "train:dispatch", "train:step"]
    parent = by_name(recs)["train:step"]["span_id"]
    assert all(r["parent_id"] == parent for r in recs[:-1])


def test_jsonl_export_and_dump(tmp_path):
    with spans.span("a", n=1):
        pass
    text = spans.export_jsonl()
    lines = [json.loads(l) for l in text.splitlines()]
    assert lines and lines[-1]["name"] == "a"
    p = tmp_path / "spans.jsonl"
    spans.dump_jsonl(str(p))
    assert [json.loads(l) for l in open(p)] == lines


def test_buffer_is_bounded(monkeypatch):
    monkeypatch.setenv("MXTPU_SPANS_BUFFER", "8")
    spans.reset()
    for i in range(50):
        with spans.span("s%d" % (i % 4)):
            pass
    assert len(spans.snapshot()) == 8


# ------------------------------------------------------- histogram bridge
def test_histogram_bridge_opt_in():
    telemetry.reset()
    with spans.span("bridged_off"):
        pass
    hist = telemetry.REGISTRY.get("mxtpu_span_seconds")
    if hist is not None:
        assert hist.value(span="bridged_off") == (0.0, 0)
    spans.set_histogram_bridge(True)
    try:
        with spans.span("bridged_on"):
            pass
    finally:
        spans.set_histogram_bridge(None)
    hist = telemetry.REGISTRY.get("mxtpu_span_seconds")
    s, c = hist.value(span="bridged_on")
    assert c == 1 and s >= 0
    assert hist.value(span="bridged_off") == (0.0, 0)


# ------------------------------------------------------- 16-thread race
def test_sixteen_thread_race_keeps_stacks_isolated():
    """Each thread runs its own nested chain; thread-local stacks must
    never cross: every child's parent is its OWN thread's root."""
    N, PER = 16, 25
    barrier = threading.Barrier(N)
    errors = []

    def work(tid):
        try:
            barrier.wait(timeout=30)
            for i in range(PER):
                with spans.span("root-%d" % tid) as root:
                    with spans.span("child-%d" % tid) as child:
                        assert child.parent_id == root.span_id, \
                            (tid, i, child.parent_id, root.span_id)
                    assert spans.current_span() is root
                assert spans.current_span() is None
        except Exception as e:  # surfaced below; bare assert dies silently
            errors.append(e)

    threads = [threading.Thread(target=work, args=(t,), daemon=True)
               for t in range(N)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not errors, errors
    recs = spans.snapshot()
    assert len(recs) == N * PER * 2
    roots = {}          # span_id -> tid, from the records themselves
    for r in recs:
        if r["name"].startswith("root-"):
            roots[r["span_id"]] = r["name"].split("-")[1]
    for r in recs:
        if r["name"].startswith("child-"):
            tid = r["name"].split("-")[1]
            assert roots.get(r["parent_id"]) == tid, r
    # no span id was ever reused across threads
    ids = [r["span_id"] for r in recs]
    assert len(ids) == len(set(ids))


# --------------------------------------------------- e2e serving chain
def test_e2e_request_span_chain_in_one_chrome_dump(tmp_path):
    """Acceptance: one HTTP request is followable as a PARENTED span chain
    HTTP -> queue -> bucket(batch) -> device in a single chrome-trace
    dump."""
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.serving import ModelRegistry, ServingServer

    net = gluon.nn.Dense(3, in_units=4)
    net.initialize()
    reg = ModelRegistry()
    reg.load("m", net, max_batch_size=4, batch_timeout_ms=2.0)

    out = tmp_path / "trace.json"
    profiler.set_config(filename=str(out))
    profiler.set_state("run")
    try:
        with ServingServer(reg, port=0) as srv:
            body = json.dumps({"inputs": [[1.0, 2.0, 3.0, 4.0]]}).encode()
            req = urllib.request.Request(
                srv.url + "/v1/models/m:predict", data=body,
                headers={"Content-Type": "application/json",
                         "X-Request-Id": "feedc0de"})
            with urllib.request.urlopen(req, timeout=60) as r:
                assert r.status == 200
                assert r.headers["X-Request-Id"] == "feedc0de"
            # the always-on span ring serves the same chain over HTTP
            with urllib.request.urlopen(srv.url + "/debug/spans",
                                        timeout=30) as r:
                served = [json.loads(l)
                          for l in r.read().decode().splitlines()]
    finally:
        profiler.set_state("stop")
    profiler.dump()

    trace = json.load(open(out))["traceEvents"]
    ev = {}
    for e in trace:
        if e.get("cat") == "span":
            ev.setdefault(e["name"], e)
    chain = ["http:predict", "serve:queue", "serve:batch",
             "serve:dispatch", "eval:step"]
    assert set(chain) <= set(ev), sorted(ev)
    root_id = ev["http:predict"]["args"]["span_id"]
    # HTTP -> queue and HTTP -> batch are direct parent links
    assert ev["serve:queue"]["args"]["parent_id"] == root_id
    assert ev["serve:batch"]["args"]["parent_id"] == root_id
    # batch -> replica dispatch -> device: the servable call runs inside
    # the per-replica serve:dispatch span, and the compiled eval step
    # nests under THAT (the replica link the loadgen join reads)
    assert ev["serve:dispatch"]["args"]["parent_id"] \
        == ev["serve:batch"]["args"]["span_id"]
    assert ev["serve:dispatch"]["args"]["replica"] == 0
    assert ev["eval:step"]["args"]["parent_id"] \
        == ev["serve:dispatch"]["args"]["span_id"]
    # the request id rides the whole chain
    assert ev["http:predict"]["args"]["request_id"] == "feedc0de"
    assert ev["serve:queue"]["args"]["request_id"] == "feedc0de"
    assert "feedc0de" in ev["serve:batch"]["args"]["request_ids"]
    assert "feedc0de" in ev["serve:dispatch"]["args"]["request_ids"]
    # and the HTTP debug export shows the same parented chain
    sv = {}
    for r in served:
        sv.setdefault(r["name"], r)
    assert sv["serve:queue"]["parent_id"] == sv["http:predict"]["span_id"]


# ----------------------------------------------- profiler dump satellites
def test_profiler_dump_degrades_without_jax(tmp_path, monkeypatch):
    """dump() must still write a trace when `import jax` fails (host-only
    analysis box). device_memory() now delegates to the devstats sampler
    snapshot (PR 10 satellite): instead of a bare {}, the memory appendix
    degrades to the host-RSS report-only fallback (or the sampler's last
    known device snapshot) — never a per-device sample, never a crash."""
    import sys
    out = tmp_path / "nojax.json"
    profiler.set_config(filename=str(out))
    profiler.set_state("run")
    try:
        profiler.record_event("ev", dur_us=5.0)
    finally:
        profiler.set_state("stop")
    monkeypatch.setitem(sys.modules, "jax", None)   # import jax -> error
    profiler.dump()
    payload = json.load(open(out))
    # no live jax: no per-device entries; the host fallback (stable keys
    # rss_bytes / peak_rss_bytes) may stand in
    assert set(payload["deviceMemory"]) <= {"host"}
    assert any(e["name"] == "ev" for e in payload["traceEvents"])


def test_profiler_dump_concurrent_records_survive(tmp_path):
    """Events recorded while dump() writes the file are NOT lost: only the
    snapshotted prefix is cleared."""
    out = tmp_path / "concurrent.json"
    profiler.set_config(filename=str(out))
    profiler.set_state("run")
    try:
        profiler.record_event("before", dur_us=1.0)
        real_open = open

        class _SlowFile:
            def __init__(self, f):
                self._f = f

            def write(self, data):
                # a late event arrives mid-write
                profiler.record_event("during", dur_us=1.0)
                return self._f.write(data)

            def __enter__(self):
                return self

            def __exit__(self, *a):
                self._f.close()

        import builtins
        orig = builtins.open

        def patched(path, *a, **kw):
            f = orig(path, *a, **kw)
            if str(path) == str(out):
                return _SlowFile(f)
            return f

        builtins.open = patched
        try:
            profiler.dump(finished=True)
        finally:
            builtins.open = orig
    finally:
        profiler.set_state("stop")
    first = json.load(real_open(out))
    assert any(e["name"] == "before" for e in first["traceEvents"])
    profiler.dump()
    second = json.load(real_open(out))
    names = [e["name"] for e in second["traceEvents"]]
    assert "during" in names and "before" not in names
