"""Serving export round-trip (ref c_predict_api.cc predictor workflow)."""
import pytest
import numpy as onp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd, gluon
from incubator_mxnet_tpu.contrib import serving
from incubator_mxnet_tpu.test_utils import assert_almost_equal


def _net():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(4, 3, padding=1, in_channels=1),
            gluon.nn.BatchNorm(in_channels=4),
            gluon.nn.Activation("relu"),
            gluon.nn.Flatten(),
            gluon.nn.Dense(10, in_units=4 * 8 * 8))
    mx.random.seed(0)
    net.initialize(mx.init.Xavier())
    return net


def test_export_load_predict_roundtrip(tmp_path):
    net = _net()
    x = nd.random.normal(shape=(2, 1, 8, 8))
    ref = net(x)
    path = str(tmp_path / "model.mxtpu")
    served = serving.export_model(net, x, path)
    assert served.input_shapes == [(2, 1, 8, 8)]
    assert served.output_shapes == [(2, 10)]

    loaded = serving.load(path)
    out = loaded.predict(x)
    assert_almost_equal(out.asnumpy(), ref.asnumpy(), rtol=1e-5, atol=1e-6)
    # params are baked: predictions don't depend on the live net
    net.collect_params()  # (still alive, but unused by the artifact)


def test_export_mlir_is_stablehlo(tmp_path):
    net = _net()
    x = nd.random.normal(shape=(1, 1, 8, 8))
    path = str(tmp_path / "model.mxtpu")
    serving.export_model(net, x, path)
    mlir = serving.export_mlir(path)
    assert "module @" in mlir and ("stablehlo." in mlir or "func.func" in mlir)


def test_load_rejects_garbage(tmp_path):
    import pytest
    p = tmp_path / "bad.mxtpu"
    p.write_bytes(b"not a model")
    with pytest.raises(ValueError):
        serving.load(str(p))


def test_contrib_data_interval_sampler_and_wikitext():
    # (placed here to avoid a new jit-heavy test module)
    from incubator_mxnet_tpu.gluon.contrib import data as cdata
    assert list(cdata.IntervalSampler(13, interval=3)) == \
        [0, 3, 6, 9, 12, 1, 4, 7, 10, 2, 5, 8, 11]
    s = cdata.IntervalSampler(13, interval=3, rollover=False)
    assert list(s) == [0, 3, 6, 9, 12]
    assert len(s) == 5
    ds = cdata.WikiText2(segment="train", seq_len=35)
    x, y = ds[0]
    assert x.shape == (35,) and y.shape == (35,)
    # label is the next-token shift of the same stream
    x1, _ = ds[1]
    assert y[-1] == x1[0] or len(ds) == 1
    assert len(cdata.WikiText2(segment="val", seq_len=35)) < len(ds)


def test_standalone_predict_tool(tmp_path):
    """Amalgamation analog: the single-file predictor runs an artifact
    WITHOUT importing the framework (subprocess keeps it honest)."""
    import subprocess
    import sys as _sys
    import os as _os
    net = _net()
    x = nd.random.normal(shape=(2, 1, 8, 8))
    path = str(tmp_path / "m.mxtpu")
    serving.export_model(net, x, path)
    expected = serving.load(path).predict(x).asnumpy()
    inp = str(tmp_path / "x.npy")
    outp = str(tmp_path / "y.npy")
    onp.save(inp, x.asnumpy())
    root = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    env = dict(_os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    code = ("import sys; sys.argv=['sp', %r, %r, %r]; "
            "import jax; jax.config.update('jax_platforms','cpu'); "
            "exec(open(%r).read())"
            % (path, inp, outp,
               _os.path.join(root, "tools", "standalone_predict.py")))
    r = subprocess.run([_sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    got = onp.load(outp)
    onp.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-6)


# ======================================================================
# serving/ subsystem: dynamic batcher, registry, metrics, HTTP front-end
# ======================================================================
import json as _json
import threading as _threading
import time as _time
import urllib.error as _urlerror
import urllib.request as _urlreq

from incubator_mxnet_tpu.serving import (
    BlockServable, DeadlineExceededError, DynamicBatcher, ModelNotFoundError,
    ModelRegistry, QueueFullError, ServingClosedError, ServingMetrics,
    ServingServer, default_buckets, percentile)


class _EchoServable:
    """predict_batch = identity + 1; records every dispatched batch size.
    Optional gate: when armed, dispatch blocks until released — the lever
    the robustness tests use to pile up / expire / hot-swap requests."""

    def __init__(self, bias=1.0):
        self.bias = bias
        self.batch_sizes = []
        self.gate = _threading.Event()
        self.gate.set()                  # open unless a test arms it
        self.entered = _threading.Event()

    def predict_batch(self, x):
        self.batch_sizes.append(x.shape[0])
        self.entered.set()
        assert self.gate.wait(30.0), "test gate never released"
        return (x + self.bias,)


def test_default_buckets():
    assert default_buckets(8) == [1, 2, 4, 8]
    assert default_buckets(6) == [1, 2, 4, 6]
    assert default_buckets(1) == [1]


def test_percentile_nearest_rank():
    assert percentile([], 99) is None
    assert percentile([5.0], 50) == 5.0
    vals = sorted(range(1, 101))  # 1..100
    assert percentile(vals, 50) == 50
    assert percentile(vals, 99) == 99
    assert percentile(vals, 100) == 100


def test_percentile_small_window_edges():
    """Nearest-rank edges at tiny windows (the serving latency ring starts
    life with 1-2 samples): q=50 of one element is that element; q=99 of
    two elements is the max; q=50 of two is the LOWER (rank ceil(1.0)=1);
    and exact-integer rank products must not float-round UP a rank."""
    assert percentile([7.0], 50) == 7.0
    assert percentile([7.0], 99) == 7.0
    assert percentile([7.0], 1) == 7.0
    assert percentile([1.0, 2.0], 99) == 2.0
    assert percentile([1.0, 2.0], 50) == 1.0
    assert percentile([1.0, 2.0], 100) == 2.0
    # n*q/100 exactly integral: n=70, q=30 -> rank 21, not 22 (float
    # 70*30/100 = 21.000000000000004 would ceil to 22)
    vals = list(range(1, 71))
    assert percentile(vals, 30) == 21
    # clamping: out-of-range q never indexes out of the window
    assert percentile([3.0], 0) == 3.0
    assert percentile([1.0, 2.0], 150) == 2.0
    assert percentile([1.0, 2.0], -5) == 1.0


def test_batcher_coalesces_concurrent_requests():
    """N requests submitted inside one batch window -> fewer dispatches
    than requests, mean dispatched batch > 1 (the coalescing proof)."""
    sv = _EchoServable()
    b = DynamicBatcher(sv, max_batch_size=8, batch_timeout_ms=100.0,
                       queue_size=64, name="coalesce")
    try:
        reqs = [b.submit(onp.full((3,), float(i), "float32"))
                for i in range(8)]
        outs = [r.result(30.0) for r in reqs]
        for i, out in enumerate(outs):
            onp.testing.assert_allclose(out[0], onp.full((3,), i + 1.0))
        assert len(sv.batch_sizes) < 8, sv.batch_sizes
        assert b.metrics.mean_batch_size > 1.0
        assert b.metrics.ok_count == 8
        hist = b.metrics.batch_size_hist
        assert sum(k * v for k, v in hist.items()) == 8
    finally:
        b.close()


def test_batcher_timeout_flushes_partial_batch():
    """3 requests << max_batch_size still dispatch once the window closes."""
    sv = _EchoServable()
    b = DynamicBatcher(sv, max_batch_size=64, batch_timeout_ms=25.0,
                       queue_size=64, name="flush")
    try:
        t0 = _time.monotonic()
        reqs = [b.submit(onp.zeros((2,), "float32")) for _ in range(3)]
        for r in reqs:
            r.result(30.0)
        elapsed = _time.monotonic() - t0
        assert sv.batch_sizes and max(sv.batch_sizes) <= 4  # bucket of 3 -> 4
        assert sum(sv.batch_sizes) <= 4                     # padded, not split
        assert elapsed < 10.0
        # padding rode along: bucket 4 vs 3 real items
        assert b.metrics.padded_items >= 1
    finally:
        b.close()


def test_batcher_queue_full_rejects():
    """A full bounded queue rejects AT SUBMIT TIME (backpressure), and the
    rejection is counted."""
    sv = _EchoServable()
    sv.gate.clear()                      # worker will block mid-dispatch
    b = DynamicBatcher(sv, max_batch_size=1, batch_timeout_ms=1.0,
                       queue_size=2, name="full")
    try:
        first = b.submit(onp.zeros((1,), "float32"))
        assert sv.entered.wait(10.0)     # worker is inside dispatch
        b.submit(onp.zeros((1,), "float32"))
        b.submit(onp.zeros((1,), "float32"))
        with pytest.raises(QueueFullError):
            for _ in range(8):           # queue drain is async; keep pushing
                b.submit(onp.zeros((1,), "float32"))
        assert b.metrics.rejected_count >= 1
        sv.gate.set()
        first.result(30.0)               # queued work still completes
    finally:
        sv.gate.set()
        b.close()


def test_batcher_deadline_expires_queued_request():
    """A request whose deadline passes while queued fails with
    DeadlineExceededError and is never dispatched."""
    sv = _EchoServable()
    sv.gate.clear()
    b = DynamicBatcher(sv, max_batch_size=1, batch_timeout_ms=1.0,
                       queue_size=8, name="deadline")
    try:
        blocker = b.submit(onp.zeros((1,), "float32"))
        assert sv.entered.wait(10.0)
        doomed = b.submit(onp.zeros((1,), "float32"), deadline_ms=20.0)
        _time.sleep(0.08)                # let the deadline lapse while queued
        sv.gate.set()
        blocker.result(30.0)
        with pytest.raises(DeadlineExceededError):
            doomed.result(30.0)
        assert b.metrics.expired_count == 1
        # the doomed request never reached the servable
        assert sum(sv.batch_sizes) == 1
    finally:
        sv.gate.set()
        b.close()


def test_batcher_close_rejects_and_drains():
    sv = _EchoServable()
    b = DynamicBatcher(sv, max_batch_size=4, batch_timeout_ms=5.0,
                       queue_size=8, name="closing")
    reqs = [b.submit(onp.zeros((1,), "float32")) for _ in range(3)]
    b.close(drain=True)
    for r in reqs:                       # drained, not dropped
        r.result(5.0)
    with pytest.raises(ServingClosedError):
        b.submit(onp.zeros((1,), "float32"))
    assert not b.alive


def test_batcher_dispatch_error_propagates_to_every_waiter():
    def bad(_x):
        raise RuntimeError("servable exploded")
    b = DynamicBatcher(bad, max_batch_size=4, batch_timeout_ms=20.0,
                       queue_size=8, name="err")
    try:
        reqs = [b.submit(onp.zeros((1,), "float32")) for _ in range(3)]
        for r in reqs:
            with pytest.raises(RuntimeError, match="exploded"):
                r.result(30.0)
        assert b.metrics.error_count == 3
    finally:
        b.close()


def test_registry_load_predict_unload():
    reg = ModelRegistry()
    assert reg.load("echo", _EchoServable()) == 1
    out = reg.predict("echo", onp.asarray([2.0], "float32"))
    onp.testing.assert_allclose(out[0], [3.0])
    assert reg.models()[0]["name"] == "echo"
    with pytest.raises(ModelNotFoundError):
        reg.predict("nope", onp.zeros((1,), "float32"))
    with pytest.raises(ValueError, match="fixed at first load"):
        reg.load("echo", _EchoServable(), max_batch_size=2)
    reg.unload("echo")
    with pytest.raises(ModelNotFoundError):
        reg.predict("echo", onp.zeros((1,), "float32"))
    reg.close()


def test_registry_hot_reload_drains_in_flight():
    """load() on a live name repoints NEW batches at the new version while
    the in-flight batch finishes on the old servable (connection drain)."""
    v1, v2 = _EchoServable(bias=1.0), _EchoServable(bias=100.0)
    v1.gate.clear()                      # first batch will hang inside v1
    reg = ModelRegistry()
    assert reg.load("m", v1, max_batch_size=1, batch_timeout_ms=1.0) == 1
    inflight = reg.submit("m", onp.asarray([5.0], "float32"))
    assert v1.entered.wait(10.0)         # dispatched on v1, now blocked
    assert reg.load("m", v2) == 2        # hot swap while v1 is mid-batch
    fresh = reg.submit("m", onp.asarray([5.0], "float32"))
    v1.gate.set()                        # unblock the ONE worker thread
    # the in-flight batch finished on the OLD servable (drain), the batch
    # dispatched after the swap on the new one
    onp.testing.assert_allclose(inflight.result(30.0)[0], [6.0])   # on v1
    onp.testing.assert_allclose(fresh.result(30.0)[0], [105.0])    # on v2
    reg.unload("m", version=1, drain=True)   # v1 idle -> drops immediately
    desc = reg.models()[0]
    assert desc["versions"] == [2] and desc["current_version"] == 2
    reg.close()


def test_registry_unload_drain_times_out_on_stuck_batch():
    sv = _EchoServable()
    sv.gate.clear()
    reg = ModelRegistry()
    reg.load("m", sv, max_batch_size=1, batch_timeout_ms=1.0)
    req = reg.submit("m", onp.zeros((1,), "float32"))
    assert sv.entered.wait(10.0)
    with pytest.raises(TimeoutError, match="in-flight"):
        reg.unload("m", drain=True, timeout=0.1)
    sv.gate.set()
    req.result(30.0)
    reg.close()


def test_metrics_snapshot_counters_and_percentiles():
    m = ServingMetrics(latency_window=8)
    for ms in (1.0, 2.0, 3.0, 4.0, 100.0):
        m.observe_latency_ms(ms)
    m.observe_batch(3, 4)
    m.observe_batch(1, 1)
    m.inc("request_count", 4)
    m.inc("ok_count", 4)
    snap = m.snapshot()
    assert snap["request_count"] == 4 and snap["ok_count"] == 4
    assert snap["batch_count"] == 2 and snap["batched_items"] == 4
    assert snap["padded_items"] == 1
    assert snap["batch_size_hist"] == {3: 1, 1: 1}
    assert snap["mean_batch_size"] == 2.0
    assert snap["latency_ms"]["p50"] == 3.0
    assert snap["latency_ms"]["p99"] == 100.0
    # ring buffer bounds memory: the window slides
    for _ in range(20):
        m.observe_latency_ms(7.0)
    assert m.latency_percentiles_ms()["p99"] == 7.0


def test_block_servable_buckets_hit_executable_cache():
    """A live Gluon block behind the batcher compiles once per bucket
    (the shared AOT executable cache), not once per batch size."""
    from incubator_mxnet_tpu import aot

    net = gluon.nn.Dense(3, in_units=4)
    net.initialize()
    sv = BlockServable(net)
    reg = ModelRegistry()
    reg.load("dense", sv, max_batch_size=4, batch_timeout_ms=5.0)
    for _ in range(3):
        out = reg.predict("dense", onp.ones((4,), "float32"))
        assert out[0].shape == (3,)
    # every dispatch was a 1-item batch padded to bucket 1 -> ONE shared-
    # cache entry at the bucket-1 signature for this model id (other
    # suites may have compiled the same architecture at other shapes)
    mid = sv._step._model_id
    entries = [k for k in aot.CACHE.keys()
               if k.model_id == mid and k.input_sig == (((1, 4), "float32"),)]
    assert len(entries) == 1
    reg.close()


def test_registry_health_transitions():
    sv = _EchoServable()
    sv.gate.clear()
    reg = ModelRegistry()
    assert reg.health()["status"] == "healthy"
    reg.load("m", sv, max_batch_size=1, batch_timeout_ms=1.0, queue_size=5)
    req = reg.submit("m", onp.zeros((1,), "float32"))
    assert sv.entered.wait(10.0)
    for _ in range(4):                   # 4/5 queued >= 80% -> degraded
        reg.submit("m", onp.zeros((1,), "float32"))
    assert reg.health()["status"] == "degraded"
    sv.gate.set()
    req.result(30.0)
    reg.close()
    assert reg.health()["status"] == "unhealthy"


# ---------------------------------------------------------------- HTTP tier
def _post_json(url, payload, timeout=60.0):
    body = _json.dumps(payload).encode("utf-8")
    req = _urlreq.Request(url, data=body,
                          headers={"Content-Type": "application/json"})
    try:
        with _urlreq.urlopen(req, timeout=timeout) as resp:
            return resp.status, _json.loads(resp.read())
    except _urlerror.HTTPError as e:
        return e.code, _json.loads(e.read())


def _get_json(url, timeout=30.0):
    try:
        with _urlreq.urlopen(url, timeout=timeout) as resp:
            return resp.status, _json.loads(resp.read())
    except _urlerror.HTTPError as e:
        return e.code, _json.loads(e.read())


def test_http_error_contract():
    """400 malformed body, 404 unknown model/route, 504 deadline, 503 after
    shutdown — the robustness story over the wire."""
    sv = _EchoServable()
    reg = ModelRegistry()
    reg.load("echo", sv, max_batch_size=2, batch_timeout_ms=5.0)
    with ServingServer(reg, port=0) as srv:
        code, body = _post_json(srv.url + "/v1/models/echo:predict",
                                {"inputs": "not-a-list"})
        assert code == 400 and "error" in body
        code, _b = _post_json(srv.url + "/v1/models/ghost:predict",
                              {"inputs": [[1.0]]})
        assert code == 404
        code, _b = _get_json(srv.url + "/v1/models/ghost")
        assert code == 404
        code, _b = _post_json(srv.url + "/nowhere", {})
        assert code == 404
        # expired-on-arrival deadline surfaces as 504, not a hang
        sv.gate.clear()
        blocker = reg.submit("echo", onp.zeros((1,), "float32"))
        assert sv.entered.wait(10.0)
        code, body = _post_json(srv.url + "/v1/models/echo:predict",
                                {"inputs": [[1.0]], "deadline_ms": 10})
        assert code == 504 and "deadline" in body["error"].lower()
        sv.gate.set()
        blocker.result(30.0)
        # happy path still good
        code, body = _post_json(srv.url + "/v1/models/echo:predict",
                                {"inputs": [[41.0]]})
        assert code == 200 and body["outputs"][0] == [42.0]
        code, body = _get_json(srv.url + "/v1/models")
        assert code == 200 and body["models"][0]["name"] == "echo"
        code, body = _get_json(srv.url + "/v1/models/echo")
        assert code == 200 and body["metrics"]["ok_count"] >= 2


def test_http_backpressure_returns_429():
    """Overload comes back as an explicit 429 rejection, never a hang."""
    sv = _EchoServable()
    sv.gate.clear()
    reg = ModelRegistry()
    reg.load("tiny", sv, max_batch_size=1, batch_timeout_ms=1.0,
             queue_size=2)
    with ServingServer(reg, port=0) as srv:
        blocker = reg.submit("tiny", onp.zeros((1,), "float32"))
        assert sv.entered.wait(10.0)
        codes, threads = [], []
        lock = _threading.Lock()

        def fire():
            code, _b = _post_json(srv.url + "/v1/models/tiny:predict",
                                  {"inputs": [[0.0]]}, timeout=60.0)
            with lock:
                codes.append(code)

        for _ in range(8):               # queue holds 2; the rest must 429
            t = _threading.Thread(target=fire)
            t.start()
            threads.append(t)
        deadline = _time.monotonic() + 20.0
        while _time.monotonic() < deadline:
            with lock:
                if codes.count(429) >= 1:
                    break
            _time.sleep(0.01)
        sv.gate.set()
        blocker.result(30.0)
        for t in threads:
            t.join(30.0)
        assert codes.count(429) >= 1, codes
        assert all(c in (200, 429) for c in codes), codes
        code, h = _get_json(srv.url + "/healthz")
        assert code == 200 and h["status"] == "healthy"
    assert reg.health()["status"] == "unhealthy"  # stopped -> unhealthy


def test_metrics_routes_prometheus_and_json_backcompat():
    """GET /metrics now serves Prometheus text; GET /metrics.json serves
    the EXACT JSON payload /metrics used to (byte-compatible with
    json.dumps(registry.metrics_snapshot()))."""
    import urllib.request as _u
    from incubator_mxnet_tpu import telemetry as _tel
    sv = _EchoServable()
    reg = ModelRegistry()
    reg.load("echo2", sv, max_batch_size=2, batch_timeout_ms=5.0)
    with ServingServer(reg, port=0) as srv:
        code, body = _post_json(srv.url + "/v1/models/echo2:predict",
                                {"inputs": [[1.0]]})
        assert code == 200
        # ---- /metrics: Prometheus text, validated by the stdlib parser
        # The predict handler decrements the inflight gauge AFTER the
        # response bytes land, so an immediate scrape can truthfully
        # capture inflight=1 while the settled in-process export shows
        # 0 — re-scrape until the two views converge.
        for _ in range(100):
            with _u.urlopen(srv.url + "/metrics", timeout=30.0) as resp:
                assert resp.status == 200
                assert resp.headers["Content-Type"].startswith("text/plain")
                text = resp.read().decode("utf-8")
            if text == _tel.export_text():
                break
            _time.sleep(0.02)
        assert "# TYPE mxtpu_serving_requests_total counter" in text
        assert 'mxtpu_serving_requests_total{model="echo2"}' in text
        assert "# TYPE mxtpu_serving_batch_size histogram" in text
        import os as _os
        import sys as _sys
        root = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
        if root not in _sys.path:
            _sys.path.insert(0, root)
        from tools import promcheck   # one module identity repo-wide
        promcheck.validate(text)
        # the exposition matches the in-process registry's view
        assert text == _tel.export_text()
        # ---- /metrics.json: byte-compatible with the old JSON route
        with _u.urlopen(srv.url + "/metrics.json", timeout=30.0) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"] == "application/json"
            raw = resp.read()
        assert raw == _json.dumps(reg.metrics_snapshot()).encode("utf-8")
        snap = _json.loads(raw)
        assert snap["echo2"]["ok_count"] >= 1


def test_http_predict_echoes_request_id_header():
    """Every predict response carries X-Request-Id: a client-supplied id
    is echoed verbatim; otherwise the server assigns one."""
    import urllib.request as _u
    reg = ModelRegistry()
    reg.load("echo3", _EchoServable(), max_batch_size=2, batch_timeout_ms=5.0)
    with ServingServer(reg, port=0) as srv:
        body = _json.dumps({"inputs": [[1.0]]}).encode("utf-8")
        req = _u.Request(srv.url + "/v1/models/echo3:predict", data=body,
                         headers={"Content-Type": "application/json",
                                  "X-Request-Id": "trace-abc-123"})
        with _u.urlopen(req, timeout=30.0) as resp:
            assert resp.status == 200
            assert resp.headers["X-Request-Id"] == "trace-abc-123"
        req = _u.Request(srv.url + "/v1/models/echo3:predict", data=body,
                         headers={"Content-Type": "application/json"})
        with _u.urlopen(req, timeout=30.0) as resp:
            assert resp.status == 200
            assigned = resp.headers["X-Request-Id"]
            assert assigned and len(assigned) == 16


def test_http_end_to_end_64_concurrent_over_exported_model(tmp_path):
    """The acceptance demo: >= 64 concurrent single-item HTTP requests
    against a real exported .mxtpu artifact on CPU. Proves (1) real
    coalescing — mean dispatched batch > 1 in the histogram, (2) every
    response is numerically right, (3) p99 latency is served from the
    metrics endpoint."""
    net = _net()
    xb = nd.random.normal(shape=(4, 1, 8, 8))   # exported batch axis B=4
    path = str(tmp_path / "m.mxtpu")
    serving.export_model(net, xb, path)
    served = serving.load(path)

    N = 64
    rng = onp.random.RandomState(7)
    items = rng.randn(N, 1, 8, 8).astype("float32")
    ref = net(nd.array(items)).asnumpy()

    reg = ModelRegistry()
    reg.load("cnn", served, max_batch_size=8, batch_timeout_ms=50.0,
             queue_size=128)
    with ServingServer(reg, port=0) as srv:
        results = [None] * N
        barrier = _threading.Barrier(N)

        def client(i):
            barrier.wait()               # all 64 hit the server together
            try:
                results[i] = _post_json(srv.url + "/v1/models/cnn:predict",
                                        {"inputs": [items[i].tolist()]},
                                        timeout=120.0)
            except Exception as e:       # surface transport-level failures
                results[i] = (None, {"error": repr(e)})

        threads = [_threading.Thread(target=client, args=(i,))
                   for i in range(N)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120.0)

        for i, (code, body) in enumerate(results):
            assert code == 200, (i, code, body)
            onp.testing.assert_allclose(
                onp.asarray(body["outputs"][0]), ref[i],
                rtol=1e-4, atol=1e-4)

        code, metrics = _get_json(srv.url + "/metrics.json")
        assert code == 200
        m = metrics["cnn"]
        assert m["request_count"] == N and m["ok_count"] == N
        assert m["rejected_count"] == 0
        # the coalescing proof: fewer dispatches than requests
        assert m["batch_count"] < N, m["batch_size_hist"]
        assert m["mean_batch_size"] > 1.0, m["batch_size_hist"]
        assert sum(k * int(v) for k, v in
                   ((int(k), v) for k, v in m["batch_size_hist"].items())) == N
        # p99 latency reported over the wire
        assert m["latency_ms"]["p99"] is not None
        assert m["latency_ms"]["p99"] >= m["latency_ms"]["p50"] > 0.0
        assert m["queue_depth"] == 0


def test_serving_profiler_batch_hook(tmp_path):
    """With the profiler running, each dispatched batch lands in the trace
    as a serve:<model>:batch<bucket> event carrying the real item count."""
    from incubator_mxnet_tpu import profiler
    out = str(tmp_path / "serve_trace.json")
    profiler.set_config(filename=out)
    sv = _EchoServable()
    b = DynamicBatcher(sv, max_batch_size=4, batch_timeout_ms=20.0,
                       queue_size=16, name="prof")
    profiler.set_state("run")
    try:
        reqs = [b.submit(onp.zeros((2,), "float32")) for _ in range(3)]
        for r in reqs:
            r.result(30.0)
        assert "serve:prof:batch" in profiler.dumps()  # aggregate table
        profiler.dump()
        with open(out) as f:
            trace = _json.load(f)
        evs = [e for e in trace["traceEvents"]
               if e.get("name", "").startswith("serve:prof:batch")]
        assert evs, "no serving batch events in the profiler trace"
        assert any(e.get("args", {}).get("batch_size", 0) >= 1 for e in evs)
    finally:
        profiler.set_state("stop")
        profiler.set_config(filename="profile.json")
        b.close()


def test_serve_convenience_boots_from_artifact_path(tmp_path):
    """serving.serve({'name': '<path>.mxtpu'}) loads + registers + starts."""
    from incubator_mxnet_tpu.serving import serve as _serve
    net = _net()
    x = nd.random.normal(shape=(2, 1, 8, 8))
    path = str(tmp_path / "m.mxtpu")
    serving.export_model(net, x, path)
    ref = net(x).asnumpy()
    srv = _serve({"cnn": path}, port=0, batch_timeout_ms=5.0)
    try:
        code, body = _post_json(srv.url + "/v1/models/cnn:predict",
                                {"inputs": [x.asnumpy()[0].tolist()]})
        assert code == 200
        onp.testing.assert_allclose(onp.asarray(body["outputs"][0]), ref[0],
                                    rtol=1e-4, atol=1e-4)
    finally:
        srv.stop()


# ------------------------------------------------- review-hardening tier
def test_batcher_mixed_shapes_isolated_per_group():
    """Shape-mismatched requests sharing a gather window are dispatched as
    separate shape-homogeneous groups: neither fails the other, and the
    worker survives regardless."""
    sv = _EchoServable()
    b = DynamicBatcher(sv, max_batch_size=4, batch_timeout_ms=30.0,
                       queue_size=16, name="mixed")
    try:
        r1 = b.submit(onp.zeros((2,), "float32"))
        r2 = b.submit(onp.ones((3,), "float32"))    # same window, other shape
        onp.testing.assert_allclose(r1.result(30.0)[0], [1.0, 1.0])
        onp.testing.assert_allclose(r2.result(30.0)[0], [2.0, 2.0, 2.0])
        assert b.alive
        # two dispatches happened (one per signature), not one merged stack
        assert b.metrics.batch_count >= 2
        out = b.predict(onp.asarray([1.0, 2.0], "float32"), timeout=30.0)
        onp.testing.assert_allclose(out[0], [2.0, 3.0])
    finally:
        b.close()


def test_batcher_deadline_zero_means_already_expired():
    """deadline_ms=0 is an expired deadline, not 'no deadline'."""
    sv = _EchoServable()
    sv.gate.clear()                      # ensure the 0ms request queues
    b = DynamicBatcher(sv, max_batch_size=1, batch_timeout_ms=1.0,
                       queue_size=8, name="zerodl")
    try:
        blocker = b.submit(onp.zeros((1,), "float32"))
        assert sv.entered.wait(10.0)
        doomed = b.submit(onp.zeros((1,), "float32"), deadline_ms=0)
        sv.gate.set()
        blocker.result(30.0)
        with pytest.raises(DeadlineExceededError):
            doomed.result(30.0)
    finally:
        sv.gate.set()
        b.close()


def test_server_stop_without_start_does_not_hang():
    reg = ModelRegistry()
    srv = ServingServer(reg, port=0)
    done = _threading.Event()

    def stopper():
        srv.stop()
        done.set()

    t = _threading.Thread(target=stopper, daemon=True)
    t.start()
    assert done.wait(10.0), "stop() hung without a prior start()"


def test_registry_failed_drain_keeps_version_routable():
    """A drain-timeout unload must NOT leave the model 404ing with its
    only version still loaded."""
    sv = _EchoServable()
    sv.gate.clear()
    reg = ModelRegistry()
    reg.load("m", sv, max_batch_size=1, batch_timeout_ms=1.0)
    stuck = reg.submit("m", onp.zeros((1,), "float32"))
    assert sv.entered.wait(10.0)
    with pytest.raises(TimeoutError):
        reg.unload("m", drain=True, timeout=0.1)
    assert reg.models()[0]["current_version"] == 1   # still routable
    sv.gate.set()
    stuck.result(30.0)
    out = reg.predict("m", onp.asarray([1.0], "float32"))
    onp.testing.assert_allclose(out[0], [2.0])
    reg.close()


def test_registry_concurrent_hot_reloads_get_distinct_versions():
    reg = ModelRegistry()
    reg.load("m", _EchoServable(), max_batch_size=2, batch_timeout_ms=1.0)
    versions, threads = [], []
    lock = _threading.Lock()

    def reload_one(k):
        v = reg.load("m", _EchoServable(bias=float(k)))
        with lock:
            versions.append(v)

    for k in range(8):
        t = _threading.Thread(target=reload_one, args=(k,))
        t.start()
        threads.append(t)
    for t in threads:
        t.join(30.0)
    assert sorted(versions) == list(range(2, 10))    # no duplicates
    assert reg.models()[0]["current_version"] == 9
    reg.close()


def test_unload_drain_serves_already_queued_requests():
    """Graceful unload of the last version: requests ACCEPTED before the
    unload are served by the departing version, never 404ed."""
    sv = _EchoServable()
    sv.gate.clear()
    reg = ModelRegistry()
    reg.load("m", sv, max_batch_size=1, batch_timeout_ms=1.0)
    reqs = [reg.submit("m", onp.asarray([float(i)], "float32"))
            for i in range(3)]          # 1 in flight, 2 queued
    assert sv.entered.wait(10.0)
    done = _threading.Event()

    def unloader():
        reg.unload("m", drain=True)
        done.set()

    t = _threading.Thread(target=unloader, daemon=True)
    t.start()
    sv.gate.set()
    assert done.wait(30.0)
    for i, r in enumerate(reqs):        # all served, by the old version
        onp.testing.assert_allclose(r.result(30.0)[0], [i + 1.0])
    assert reg.models() == []           # and the name is gone
    reg.close()


def test_unload_no_drain_in_flight_results_still_delivered():
    """unload(drain=False) while a batch is mid-dispatch must not destroy
    that batch's computed results (the in-flight accounting slot is gone,
    but the waiters aren't)."""
    sv = _EchoServable()
    sv.gate.clear()
    reg = ModelRegistry()
    reg.load("m", sv, max_batch_size=1, batch_timeout_ms=1.0)
    inflight = reg.submit("m", onp.asarray([7.0], "float32"))
    assert sv.entered.wait(10.0)
    done = _threading.Event()

    def unloader():
        reg.unload("m", drain=False)
        done.set()

    t = _threading.Thread(target=unloader, daemon=True)
    t.start()
    _time.sleep(0.05)
    sv.gate.set()
    assert done.wait(30.0)
    onp.testing.assert_allclose(inflight.result(30.0)[0], [8.0])
    reg.close()


def test_batcher_malformed_servable_output_fails_batch_not_worker():
    """A servable returning a scalar / too-short dim 0 fails THAT batch
    loudly; the worker survives and later requests still serve."""
    calls = {"n": 0}

    def flaky(x):
        calls["n"] += 1
        if calls["n"] == 1:
            return onp.float32(1.0)      # 0-d: not sliceable per-request
        return (x + 1.0,)

    b = DynamicBatcher(flaky, max_batch_size=2, batch_timeout_ms=5.0,
                       queue_size=8, name="malformed")
    try:
        with pytest.raises(Exception):
            b.predict(onp.zeros((2,), "float32"), timeout=30.0)
        assert b.alive
        out = b.predict(onp.asarray([1.0], "float32"), timeout=30.0)
        onp.testing.assert_allclose(out[0], [2.0])
        assert b.metrics.error_count >= 1
    finally:
        b.close()


def test_http_malformed_deadline_is_400():
    reg = ModelRegistry()
    reg.load("echo", _EchoServable(), max_batch_size=2, batch_timeout_ms=5.0)
    with ServingServer(reg, port=0) as srv:
        code, body = _post_json(srv.url + "/v1/models/echo:predict",
                                {"inputs": [[1.0]], "deadline_ms": "soon"})
        assert code == 400 and "error" in body
