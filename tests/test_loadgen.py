"""Load harness tier (tools/loadgen.py and the serving-side saturation
gauges it scrapes) — docs/LOADGEN.md."""
import json
import threading
import time

import numpy as onp
import pytest

from tools import loadgen, promcheck


# ------------------------------------------------------------ fakes
class FakeClock:
    """Virtual time: sleep() advances instantly — the whole scheduling
    path runs with zero real sleeps."""

    def __init__(self):
        self.t = 0.0
        self.sleeps = 0

    def now(self):
        return self.t

    def sleep(self, s):
        self.sleeps += 1
        self.t += max(0.0, float(s))


class FakeTransport:
    """Scripted per-stage (status, service_s); advances the fake clock
    inside send() so the engine's latency math is what's measured."""

    def __init__(self, clock, script):
        self.clock = clock
        self.script = script          # stage idx -> (status, service_s)
        self.sent = []

    def send(self, rid):
        stage = int(rid.split("-s")[-1].split("-")[0])
        status, service_s = self.script[stage]
        self.clock.t += service_s
        self.sent.append(rid)
        return status

    def scrape(self):
        return ""

    def spans(self):
        return ""


# ------------------------------------------------------- arrival process
def test_arrival_constant_exact():
    offs = loadgen.arrival_offsets("constant", 100, 2.0)
    assert len(offs) == 200
    assert offs[0] == 0.0
    deltas = [b - a for a, b in zip(offs, offs[1:])]
    assert all(abs(d - 0.01) < 1e-12 for d in deltas)
    assert loadgen.arrival_offsets("constant", 0, 2.0) == []


def test_arrival_poisson_seeded_deterministic():
    import random
    a = loadgen.arrival_offsets("poisson", 200, 3.0, random.Random(7))
    b = loadgen.arrival_offsets("poisson", 200, 3.0, random.Random(7))
    c = loadgen.arrival_offsets("poisson", 200, 3.0, random.Random(8))
    assert a == b and a != c
    assert all(0.0 <= t < 3.0 for t in a)
    assert a == sorted(a)
    # law of large numbers sanity: ~600 arrivals within 20%
    assert 480 < len(a) < 720
    with pytest.raises(ValueError):
        loadgen.arrival_offsets("uniform", 10, 1.0)


# ------------------------------------------------------ engine, no sleeps
def test_engine_fake_clock_runs_without_real_sleeps():
    clock = FakeClock()
    tr = FakeTransport(clock, {0: (200, 0.005), 1: (429, 0.001)})
    lg = loadgen.LoadGen(tr, [{"rps": 100, "duration_s": 1.0},
                              {"rps": 200, "duration_s": 1.0}],
                         arrival="constant", clock=clock, settle_s=0.0,
                         run_id="t", seed=0)
    wall0 = time.perf_counter()
    report = lg.run(sync=True)
    assert time.perf_counter() - wall0 < 5.0    # no real 2s soak happened
    assert clock.t > 1.9                        # ...but virtual time did
    s0, s1 = report["stages"]
    assert s0["offered"] == 100 and s1["offered"] == 200
    assert s0["ok"] == 100 and s0["goodput_rps"] == pytest.approx(100.0)
    assert s0["latency_ms"]["p50"] == pytest.approx(5.0)
    assert s0["error_rate"] == 0.0
    # stage 1 is pure shed: zero goodput, shed rate 1.0, no OK percentiles
    assert s1["ok"] == 0 and s1["shed"] == 200
    assert s1["shed_rate"] == pytest.approx(1.0)
    assert s1["latency_ms"]["p50"] is None
    assert s1["status_counts"] == {"429": 200}
    # the goodput plateau + shed divergence IS the saturation definition
    assert report["saturation"] and report["saturation"]["stage"] == 1
    gm = report["gate_metrics"]
    assert gm["schema"] == loadgen.METRICS_SCHEMA
    assert gm["metrics"]["loadgen_saturation_detected"] == 1.0


def test_engine_transport_exception_is_transport_error():
    clock = FakeClock()

    class Boom(FakeTransport):
        def send(self, rid):
            raise OSError("refused")

    lg = loadgen.LoadGen(Boom(clock, {}), [{"rps": 10, "duration_s": 1.0}],
                         arrival="constant", clock=clock, settle_s=0.0,
                         run_id="t", seed=0)
    report = lg.run(sync=True)
    s0 = report["stages"][0]
    assert s0["errors"] == 10 and s0["error_rate"] == 1.0
    assert s0["status_counts"] == {str(loadgen.TRANSPORT_ERROR): 10}


# --------------------------------------------------- saturation detection
def _stage(offered, goodput, p99, shed):
    return {"offered_rps": offered, "goodput_rps": goodput,
            "latency_ms": {"p99": p99}, "shed_rate": shed}


def test_detect_saturation_on_synthetic_knee():
    stages = [_stage(100, 100, 8.0, 0.0),
              _stage(400, 395, 9.0, 0.0),
              _stage(1600, 520, 40.0, 0.55)]   # plateau + tail + shed
    sat = loadgen.detect_saturation(stages)
    assert sat["stage"] == 2 and "shed" in sat["reason"]
    assert sat["goodput_rps"] == 520


def test_detect_saturation_requires_both_legs():
    # goodput plateaus but the tail/shed never diverge (a measurement
    # floor, not a knee) -> no saturation call
    flat = [_stage(100, 100, 8.0, 0.0), _stage(400, 150, 8.5, 0.0)]
    assert loadgen.detect_saturation(flat) is None
    # tail grows but goodput keeps converting -> still not saturated
    healthy = [_stage(100, 100, 8.0, 0.0), _stage(400, 390, 20.0, 0.0)]
    assert loadgen.detect_saturation(healthy) is None
    # clean linear ramp -> None
    ramp = [_stage(100, 100, 8.0, 0.0), _stage(200, 200, 8.2, 0.0),
            _stage(400, 400, 8.4, 0.0)]
    assert loadgen.detect_saturation(ramp) is None


# --------------------------------------------------------- span joining
def test_summarize_stage_joins_request_ids_to_spans():
    rids = ["lg-t-s0-%d" % i for i in range(4)]
    # rids 0-2 succeeded; rid 3 was dispatched but 504'd (it still left a
    # serve:queue span server-side)
    results = [{"rid": r, "status": 200, "latency_ms": 10.0}
               for r in rids[:3]]
    results.append({"rid": rids[3], "status": 504, "latency_ms": 50.0})
    lines = []
    for r in (rids[0], rids[1], rids[3]):
        lines.append(json.dumps({"name": "serve:queue", "request_id": r,
                                 "dur_us": 2000.0}))
    lines.append(json.dumps({"name": "serve:batch",
                             "request_id": rids[0], "dur_us": 6000.0,
                             "args": {"request_ids": rids[:3]}}))
    lines.append(json.dumps({"name": "eval:step", "request_id": rids[0],
                             "dur_us": 4000.0}))
    lines.append(json.dumps({"name": "serve:queue",
                             "request_id": "other-run", "dur_us": 9e6}))
    s = loadgen.summarize_stage({"rps": 4, "duration_s": 1.0}, 4, results,
                                span_text="\n".join(lines))
    srv = s["server"]
    assert srv["queue_ms"]["count"] == 3    # the 504's wait still counts
    assert srv["queue_ms"]["p50"] == pytest.approx(2.0)
    assert srv["batch_ms"]["count"] == 1
    assert srv["batch_ms"]["p99"] == pytest.approx(6.0)
    assert srv["device_ms"]["count"] == 1
    # coverage is over OK responses only: 2 of the 3 200s have a queue
    # span; the dispatched-then-504'd request must not inflate it past 1
    assert srv["join_coverage"] == pytest.approx(2 / 3)


def test_summarize_stage_breaks_out_dispatch_time_per_replica():
    rids = ["lg-r-s0-%d" % i for i in range(4)]
    results = [{"rid": r, "status": 200, "latency_ms": 5.0} for r in rids]
    lines = []
    # two replicas: replica 0 fast (2 ms), replica 1 slow (20 ms) — the
    # per-replica breakout must attribute the skew to replica 1 alone
    for r, rep, dur in ((rids[0], 0, 2000.0), (rids[1], 0, 2000.0),
                        (rids[2], 1, 20000.0), (rids[3], 1, 20000.0)):
        lines.append(json.dumps({"name": "serve:dispatch",
                                 "dur_us": dur,
                                 "args": {"replica": rep,
                                          "request_ids": [r]}}))
    s = loadgen.summarize_stage({"rps": 4, "duration_s": 1.0}, 4, results,
                                span_text="\n".join(lines))
    srv = s["server"]
    assert srv["dispatch_ms"]["count"] == 4
    assert srv["replica_ms"]["0"]["p50"] == pytest.approx(2.0)
    assert srv["replica_ms"]["1"]["p50"] == pytest.approx(20.0)
    assert srv["replica_ms"]["0"]["count"] == 2


def test_parse_prom_values_and_labels():
    text = ('# TYPE x counter\nx{model="m"} 3\nx{model="n"} 4\n'
            '# TYPE g gauge\ng 2.5\nh_bucket{le="+Inf"} 7\n')
    snap = loadgen.parse_prom(text)
    assert snap[("x", (("model", "m"),))] == 3.0
    assert snap[("g", ())] == 2.5
    assert loadgen._prom_sum(snap, "x") == 7.0


# ----------------------------------------------- one-parser CI report shape
def test_ci_report_shape_parity_across_tools(tmp_path):
    prom_rep = promcheck.report("garbage line {", path="m.txt")
    clock = FakeClock()
    lg = loadgen.LoadGen(FakeTransport(clock, {0: (500, 0.001)}),
                         [{"rps": 5, "duration_s": 1.0}],
                         arrival="constant", clock=clock, settle_s=0.0,
                         run_id="t", seed=0)
    load_rep = loadgen.report_ci(lg.run(sync=True), "r.json")
    assert not load_rep["ok"]          # 500s are hard errors -> L001
    for rep in (prom_rep, load_rep):
        assert set(rep) == {"tool", "ok", "findings", "counts", "baselined"}
        for f in rep["findings"]:
            assert set(f) == {"path", "line", "rule", "message"}
    assert load_rep["findings"][0]["rule"] == "L001"


def test_require_saturation_finding():
    clock = FakeClock()
    lg = loadgen.LoadGen(FakeTransport(clock, {0: (200, 0.001)}),
                         [{"rps": 5, "duration_s": 1.0}],
                         arrival="constant", clock=clock, settle_s=0.0,
                         run_id="t", seed=0)
    rep = lg.run(sync=True)
    ci = loadgen.report_ci(rep, "r.json", require_saturation=True)
    assert not ci["ok"] and "saturation" in ci["findings"][0]["message"]


def test_env_defaults_match_config_registry():
    from incubator_mxnet_tpu import config
    for name, default in loadgen.ENV_DEFAULTS.items():
        typ, cfg_default, _doc = config.ENV_VARS[name]
        assert typ is type(default), name
        assert cfg_default == default, name


# --------------------------------------------------- serving-side gauges
class _GatedEcho:
    """predict_batch blocks on .gate so the dispatch-stage depth is
    observable mid-flight."""

    def __init__(self):
        self.entered = threading.Event()
        self.gate = threading.Event()
        self.gate.set()

    def predict_batch(self, x):
        self.entered.set()
        assert self.gate.wait(30.0)
        return (x,)


def test_bucket_depth_gauge_tracks_dispatch_and_detaches():
    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.serving.batcher import DynamicBatcher

    sv = _GatedEcho()
    b = DynamicBatcher(sv, max_batch_size=4, batch_timeout_ms=150.0,
                       queue_size=16, name="lg-bucket-m")
    assert b.bucket_depths() == {1: 0, 2: 0, 4: 0}
    sv.gate.clear()
    reqs = [b.submit(onp.full((3,), i, "float32")) for i in range(3)]
    assert sv.entered.wait(10.0)
    # 3 requests gathered into the bucket-4 dispatch, still in flight
    assert b.bucket_depths()[4] == 3
    text = telemetry.export_text()
    assert ('mxtpu_serving_bucket_queue_depth'
            '{model="lg-bucket-m",bucket="4"} 3') in text
    sv.gate.set()
    for r in reqs:
        r.result(30.0)
    assert b.bucket_depths()[4] == 0
    b.close()
    # detach on close: a dead model must not export stale depth (its
    # cumulative counters/histograms legitimately stay — Prometheus
    # convention; only the live gauge callbacks must go)
    after = telemetry.export_text()
    assert ('mxtpu_serving_bucket_queue_depth{model="lg-bucket-m"'
            not in after)
    assert 'mxtpu_serving_queue_depth{model="lg-bucket-m"}' not in after


# ------------------------------------------------------------ e2e soak
class _SlowEcho:
    """~20 ms per dispatched batch: capacity is timer-bound (~150 rps at
    max_batch 4), so the saturating stage is deterministic across
    machines."""

    def predict_batch(self, x):
        time.sleep(0.02)
        return (x,)


def test_e2e_soak_report_joins_request_ids_to_spans():
    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.serving import ModelRegistry, ServingServer

    reg = ModelRegistry()
    reg.load("lg-soak", _SlowEcho(), max_batch_size=4, batch_timeout_ms=2.0,
             queue_size=4)
    with ServingServer(reg, port=0) as srv:
        tr = loadgen.HttpTransport(srv.url, "lg-soak", [0.0, 0.0])
        lg = loadgen.LoadGen(tr, [{"rps": 40, "duration_s": 0.6},
                                  {"rps": 600, "duration_s": 0.6}],
                             arrival="poisson", seed=0, max_clients=64,
                             settle_s=0.3)
        report = lg.run()
    s0, s1 = report["stages"]
    # stage 0 is under capacity: everything converts, nothing fails
    assert s0["ok"] > 0 and s0["errors"] == 0 and s0["client_dropped"] == 0
    # no server/transport errors anywhere (429 shed is not an error;
    # client drops at the overload stage are harness capacity, reported
    # separately and excluded from error_rate)
    assert s1["errors"] == 0
    assert s0["goodput_rps"] == pytest.approx(s0["offered_rps"], rel=0.05)
    # stage 1 is 4x over the timer-bound capacity: shed + plateau
    assert s1["shed"] > 0
    assert report["saturation"] and report["saturation"]["stage"] == 1
    # the X-Request-Id join: client latency attributed server-side
    assert s0["server"]["queue_ms"]["count"] > 0
    assert s0["server"]["batch_ms"]["count"] > 0
    assert s0["server"]["join_coverage"] > 0.5
    # scrape deltas rode along, including the two new saturation gauges
    m = s0["server"]["metrics"]
    assert m["delta"]["mxtpu_serving_ok_total"] >= s0["ok"]
    assert "mxtpu_http_inflight_requests" in m["gauges"]
    assert "mxtpu_serving_bucket_queue_depth" in m["gauges"]
    # the run's flat summary
    gm = report["gate_metrics"]["metrics"]
    assert gm["loadgen_error_rate"] == 0.0
    assert gm["loadgen_saturation_detected"] == 1.0
    assert gm["loadgen_stage0_p50_ms"] > 0
    # inflight gauge balanced back to zero after the soak
    snap = loadgen.parse_prom(telemetry.export_text())
    assert loadgen._prom_sum(snap, "mxtpu_http_inflight_requests") == 0


def test_parse_stages_cli_grammar():
    assert loadgen._parse_stages("100x1.5,400x2") == [
        {"rps": 100.0, "duration_s": 1.5}, {"rps": 400.0, "duration_s": 2.0}]
    with pytest.raises(ValueError):
        loadgen._parse_stages("100")


# --------------------------------------------------- tenant mix (PR 11)
class TenantTransport(FakeTransport):
    """FakeTransport that accepts the optional tenant arg and records it,
    plus a scripted /debug/slo payload for the between-stage scrape."""

    def __init__(self, clock, script, slo_payload=None):
        super().__init__(clock, script)
        self.tenants = []
        self.slo_payload = slo_payload

    def send(self, rid, tenant=None):
        self.tenants.append(tenant)
        return super().send(rid)

    def slo(self):
        return json.dumps(self.slo_payload) if self.slo_payload else ""


def test_parse_tenants_cli_grammar():
    assert loadgen._parse_tenants("alice:3,bob:1") == [
        ("alice", 3.0), ("bob", 1.0)]
    assert loadgen._parse_tenants("solo") == [("solo", 1.0)]  # bare weighs 1
    assert loadgen._parse_tenants("") is None
    assert loadgen._parse_tenants(None) is None
    with pytest.raises(ValueError):
        loadgen._parse_tenants(":2")
    with pytest.raises(ValueError):
        loadgen.LoadGen(FakeTransport(FakeClock(), {}),
                        [{"rps": 1, "duration_s": 1.0}],
                        tenants=[("a", 0.0)])     # weights must be > 0


def test_tenant_mix_weighted_and_schedule_invariant():
    """The weighted mix reaches the wire per-request, the stage report
    gains per-tenant columns, and adding --tenants leaves the arrival
    schedule byte-identical (a separate RNG stream draws tenants)."""
    def run(tenants):
        clock = FakeClock()
        tr = TenantTransport(clock, {0: (200, 0.002)})
        lg = loadgen.LoadGen(tr, [{"rps": 200, "duration_s": 1.0}],
                             arrival="poisson", clock=clock, settle_s=0.0,
                             run_id="t", seed=7, tenants=tenants)
        return tr, lg.run(sync=True)

    tr_mix, rep_mix = run([("alice", 3.0), ("bob", 1.0)])
    tr_none, rep_none = run(None)
    # identical rid sequence: the tenant draw never perturbs arrivals
    assert tr_mix.sent == tr_none.sent
    assert all(t is None for t in tr_none.tenants)
    assert set(tr_mix.tenants) == {"alice", "bob"}
    cols = rep_mix["stages"][0]["tenants"]
    assert set(cols) == {"alice", "bob"}
    offered = rep_mix["stages"][0]["offered"]
    assert cols["alice"]["offered"] + cols["bob"]["offered"] == offered
    assert cols["alice"]["offered"] > cols["bob"]["offered"]  # 3:1 mix
    for c in cols.values():
        assert c["ok"] == c["offered"] and c["shed"] == 0
        assert c["latency_ms"]["p50"] == pytest.approx(2.0)
        assert c["goodput_rps"] > 0
    # deterministic: same seed, same mix -> same per-tenant split
    tr_again, rep_again = run([("alice", 3.0), ("bob", 1.0)])
    assert tr_again.tenants == tr_mix.tenants
    # no mix -> no tenants key (report shape is backward compatible)
    assert "tenants" not in rep_none["stages"][0]
    assert rep_mix["config"]["tenants"] == [("alice", 3.0), ("bob", 1.0)]


def test_stage_report_carries_slo_scrape():
    payload = {"slos": [{"name": "m/availability", "budget_remaining": 0.5,
                         "burn_rates": {"300s": 2.0}, "alerts": []}]}
    clock = FakeClock()
    tr = TenantTransport(clock, {0: (200, 0.001)}, slo_payload=payload)
    lg = loadgen.LoadGen(tr, [{"rps": 10, "duration_s": 1.0}],
                         arrival="constant", clock=clock, settle_s=0.0,
                         run_id="t", seed=0)
    rep = lg.run(sync=True)
    assert rep["stages"][0]["slo"] == payload
    # a transport without .slo() (older fakes) degrades to no key
    tr2 = FakeTransport(clock, {0: (200, 0.001)})
    lg2 = loadgen.LoadGen(tr2, [{"rps": 10, "duration_s": 1.0}],
                          arrival="constant", clock=clock, settle_s=0.0,
                          run_id="t", seed=0)
    assert "slo" not in lg2.run(sync=True)["stages"][0]


# ------------------------------------------------------------ chaos soak
class FaultTransport(FakeTransport):
    """FakeTransport + the arm_faults verb, recording every spec."""

    def __init__(self, clock, script):
        super().__init__(clock, script)
        self.armed = []

    def arm_faults(self, spec):
        self.armed.append(spec)
        return {"armed": bool(spec), "faults": []}


def test_faults_armed_per_stage_and_disarmed_after_run():
    clock = FakeClock()
    tr = FaultTransport(clock, {0: (200, 0.001), 1: (200, 0.001),
                                2: (200, 0.001)})
    lg = loadgen.LoadGen(
        tr, [{"rps": 5, "duration_s": 1.0}] * 3,
        arrival="constant", clock=clock, settle_s=0.0, run_id="t", seed=0,
        faults={1: "batcher.dispatch:exception:stride=2"})
    rep = lg.run(sync=True)
    # armed entering stage 1, then disarmed once after the last stage —
    # a soak never leaves the server poisoned
    assert tr.armed == ["batcher.dispatch:exception:stride=2", ""]
    # the arming persists into stage 2 (no entry replaces it), and every
    # stage summary says what chaos it ran under
    specs = [s["fault_spec"] for s in rep["stages"]]
    assert specs == [None, "batcher.dispatch:exception:stride=2",
                     "batcher.dispatch:exception:stride=2"]
    assert rep["config"]["faults"] == {
        1: "batcher.dispatch:exception:stride=2"}


def test_faults_empty_spec_disarms_mid_ramp():
    clock = FakeClock()
    tr = FaultTransport(clock, {0: (200, 0.001), 1: (200, 0.001)})
    lg = loadgen.LoadGen(
        tr, [{"rps": 5, "duration_s": 1.0}] * 2,
        arrival="constant", clock=clock, settle_s=0.0, run_id="t", seed=0,
        faults={0: "a:exception", 1: ""})
    rep = lg.run(sync=True)
    # the stage-1 '' already disarmed: no redundant trailing disarm
    assert tr.armed == ["a:exception", ""]
    assert [s["fault_spec"] for s in rep["stages"]] == ["a:exception", None]


def test_faults_require_a_capable_transport():
    with pytest.raises(ValueError):
        loadgen.LoadGen(FakeTransport(FakeClock(), {}),
                        [{"rps": 1, "duration_s": 1.0}],
                        faults={0: "a:exception"})


def test_parse_faults_cli_forms():
    assert loadgen._parse_faults(None) is None
    # a bare spec targets stage 0 — the '=' inside stride=2 never parses
    # as a stage split because 'site:kind:stride' is not an integer
    assert loadgen._parse_faults(["b.d:exception:stride=2"]) == {
        0: "b.d:exception:stride=2"}
    assert loadgen._parse_faults(["1=a:exception", "2="]) == {
        1: "a:exception", 2: ""}
    with pytest.raises(ValueError):
        loadgen._parse_faults(["1=a:exception", "1=b:exception"])
