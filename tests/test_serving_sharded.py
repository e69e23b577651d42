"""Mesh-sharded serving: data-parallel replicas + tensor-parallel predict.

Subsystem tier for the replica router and MeshServable (conftest forces
an 8-device CPU mesh via ``--xla_force_host_platform_device_count=8``,
so the dp x tp topologies here are real multi-executable programs):

- least-depth routing balance under concurrent clients (no replica ever
  more than 2x the minimum),
- dead-replica drain-back (requests re-routed, never stranded; depth
  gauge detached; /healthz degraded),
- (bucket x replica) prewarm + hot reload with ZERO dropped requests and
  zero compile spans between swap-begin and drain-complete,
- tp=2 MeshServable vs the single-device model at float32 tolerance,
  through the batcher,
- a mini 1-vs-4-replica goodput-scaling smoke on a timer-bound servable
  (the hard-gated 1-vs-8 soak lives in ``ci/run.sh sharded``).
"""
import threading
import time

import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import parallel, telemetry
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.serving import (
    DynamicBatcher, MeshServable, ModelRegistry, ServingClosedError)
from incubator_mxnet_tpu.telemetry import spans


class _Echo:
    def predict_batch(self, x):
        return (x,)


class _SlowEcho:
    """Timer-bound servable: capacity set by the sleep, not the host."""

    def __init__(self, delay_s=0.005):
        self.delay_s = delay_s

    def predict_batch(self, x):
        time.sleep(self.delay_s)
        return (x,)


class _Die(BaseException):
    """Escapes the batcher's per-batch Exception guards -> worker death
    (the defect class the drain-back contract exists for)."""


class _PoisonableEcho:
    """Replica-aware echo that kills the worker on a poison value."""

    def __init__(self, delay_s=0.0):
        self.delay_s = delay_s
        self.calls = []         # (replica, batch) log

    def predict_batch(self, x, replica=0):
        if float(onp.asarray(x).ravel()[0]) == -1.0:
            raise _Die("poison")
        self.calls.append((replica, int(x.shape[0])))
        if self.delay_s:
            time.sleep(self.delay_s)
        return (x,)


# ---------------------------------------------------------------- router
def test_replicas_default_is_one_and_validated():
    b = DynamicBatcher(_Echo(), max_batch_size=2, batch_timeout_ms=1.0,
                       queue_size=4, name="one")
    assert b.replicas == 1
    b.close()
    with pytest.raises(ValueError):
        DynamicBatcher(_Echo(), max_batch_size=2, queue_size=4, replicas=0)


def test_least_depth_router_prefers_empty_replica():
    gate = threading.Event()

    class Gated:
        def predict_batch(self, x):
            gate.wait(10.0)
            return (x,)

    b = DynamicBatcher(Gated(), max_batch_size=1, batch_timeout_ms=1.0,
                       queue_size=8, replicas=2, name="router")
    try:
        # first submit lands on some replica and blocks its worker; the
        # next submits must prefer the other (lower-depth) replica
        reqs = [b.submit(onp.float32([i])) for i in range(4)]
        time.sleep(0.1)
        depths = b.replica_depths()
        assert sum(depths) == 4
        # 4 requests over 2 replicas with least-depth routing: 2 each
        assert depths == [2, 2], depths
        gate.set()
        for r in reqs:
            r.result(10.0)
        assert sum(b.replica_dispatch_counts()) == 4
    finally:
        gate.set()
        b.close()


def test_balanced_dispatch_four_replicas_concurrent_clients():
    b = DynamicBatcher(_SlowEcho(0.002), max_batch_size=4,
                       batch_timeout_ms=1.0, queue_size=32, replicas=4,
                       name="balance")
    try:
        errs = []

        def client(i):
            try:
                for j in range(8):
                    out = b.predict(onp.float32([i * 100 + j]), timeout=30.0)
                    assert out[0][0] == i * 100 + j
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
        assert not errs, errs
        counts = b.replica_dispatch_counts()
        assert sum(counts) == 16 * 8
        assert min(counts) > 0, counts
        # the acceptance bound: no replica handles > 2x the minimum
        assert max(counts) <= 2 * min(counts), counts
    finally:
        b.close()


def test_replica_depth_gauges_exported_and_detached_on_close():
    b = DynamicBatcher(_Echo(), max_batch_size=2, batch_timeout_ms=1.0,
                       queue_size=4, replicas=3, name="gauges")
    text = telemetry.export_text()
    for r in range(3):
        assert ('mxtpu_serving_replica_queue_depth{model="gauges",'
                'replica="%d"}' % r) in text
    b.close()
    text = telemetry.export_text()
    leftover = [ln for ln in text.splitlines()
                if ln.startswith("mxtpu_serving_replica_queue_depth")
                and 'model="gauges"' in ln]
    assert not leftover, leftover


# ---------------------------------------------------------- dead replicas
def test_dead_replica_drains_back_to_router():
    gate = threading.Event()

    class GatedPoison:
        def predict_batch(self, x):
            v = float(onp.asarray(x).ravel()[0])
            if v == -1.0:
                time.sleep(0.15)    # let the queues build behind us
                raise _Die("poison")
            gate.wait(10.0)
            return (x,)

    b = DynamicBatcher(GatedPoison(), max_batch_size=1, batch_timeout_ms=1.0,
                       queue_size=8, replicas=2, name="drain")
    try:
        poison = b.submit(onp.float32([-1.0]))     # replica 0, dies slowly
        time.sleep(0.05)
        normal = [b.submit(onp.float32([float(i)])) for i in range(3)]
        # wait for the death + drain-back
        deadline = time.monotonic() + 10.0
        while not b.dead_replicas() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert b.dead_replicas(), "worker never died"
        gate.set()
        # every NORMAL request must still complete on the survivor —
        # including any that had been queued behind the poison
        for r in normal:
            out = r.result(15.0)
            assert out[0].shape == (1,)
        with pytest.raises(_Die):
            poison.result(5.0)
        # the dead replica's depth gauge is detached, the survivor's stays
        text = telemetry.export_text()
        lines = [ln for ln in text.splitlines()
                 if ln.startswith("mxtpu_serving_replica_queue_depth")
                 and 'model="drain"' in ln]
        assert len(lines) == 1, lines
        assert b.alive       # the survivor keeps serving
    finally:
        gate.set()
        b.close()


def test_all_replicas_dead_fails_new_and_queued_requests():
    class AlwaysDie:
        def predict_batch(self, x):
            raise _Die("always")

    b = DynamicBatcher(AlwaysDie(), max_batch_size=1, batch_timeout_ms=1.0,
                       queue_size=4, replicas=1, name="alldead")
    try:
        req = b.submit(onp.float32([1.0]))
        with pytest.raises((_Die, ServingClosedError)):
            req.result(10.0)
        deadline = time.monotonic() + 5.0
        while b.alive and time.monotonic() < deadline:
            time.sleep(0.01)
        with pytest.raises(ServingClosedError):
            b.submit(onp.float32([2.0]))
    finally:
        b.close()


def test_registry_health_degraded_on_dead_replica():
    reg = ModelRegistry()
    sv = _PoisonableEcho()
    reg.load("hdeg", sv, max_batch_size=2, batch_timeout_ms=1.0,
             queue_size=8, replicas=2, prewarm=False)
    try:
        assert reg.health()["status"] == "healthy"
        req = reg.submit("hdeg", onp.float32([-1.0]))
        with pytest.raises((_Die, ServingClosedError)):
            req.result(10.0)
        deadline = time.monotonic() + 5.0
        while not reg._entry("hdeg").batcher.dead_replicas() \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        h = reg.health()
        assert h["status"] == "degraded", h
        assert "replica" in h["reason"]
        # the survivor still serves
        out = reg.predict("hdeg", onp.float32([3.0]))
        assert out[0][0] == 3.0
    finally:
        reg.close()


# ------------------------------------------------------- replica prewarm
def test_prewarm_covers_every_bucket_replica_pair():
    reg = ModelRegistry()
    sv = _PoisonableEcho()
    reg.load("warmpairs", sv, max_batch_size=4, batch_timeout_ms=1.0,
             replicas=3, warm_spec=[((2,), "float32")])
    try:
        # buckets 1,2,4 x replicas 0,1,2 — every pair warmed pre-cutover
        assert sorted(set(sv.calls)) == sorted(
            {(r, b) for b in (1, 2, 4) for r in (0, 1, 2)}), sv.calls
        assert reg.metrics("warmpairs").prewarm_count == 9
    finally:
        reg.close()


def test_prewarm_replica_unaware_servable_warms_each_bucket_once():
    reg = ModelRegistry()
    calls = []

    class Plain:
        def predict_batch(self, x):
            calls.append(int(x.shape[0]))
            return (x,)

    reg.load("warmplain", Plain(), max_batch_size=4, batch_timeout_ms=1.0,
             replicas=3, warm_spec=[((2,), "float32")])
    try:
        assert sorted(calls) == [1, 2, 4]
        assert reg.metrics("warmplain").prewarm_count == 3
    finally:
        reg.close()


# ------------------------------------------------- hot reload, no drops
def test_hot_reload_with_replicas_no_drops_no_compiles():
    mx.random.seed(0)
    net1 = nn.Dense(3, in_units=6)
    net1.initialize(mx.init.Xavier())
    net2 = nn.Dense(3, in_units=6)
    net2.initialize(mx.init.Xavier())
    reg = ModelRegistry()
    reg.load("hotrep", net1, max_batch_size=4, batch_timeout_ms=2.0,
             queue_size=64, replicas=4, warm_spec=[((6,), "float32")])
    try:
        stop = threading.Event()
        errs, oks = [], [0]

        def client(i):
            x = onp.full((6,), float(i), "float32")
            while not stop.is_set():
                try:
                    reg.predict("hotrep", x, timeout=30.0)
                    oks[0] += 1
                except Exception as e:  # noqa: BLE001
                    errs.append(e)
                    return

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        time.sleep(0.3)
        mark = len(spans.snapshot())
        reg.load("hotrep", net2)                  # prewarmed hot reload
        reg.unload("hotrep", version=1, drain=True, timeout=30.0)
        time.sleep(0.3)
        stop.set()
        for t in threads:
            t.join(30.0)
        assert not errs, errs[:3]
        assert oks[0] > 0
        # zero compile spans between swap-begin and drain-complete
        bad = [s["name"] for s in spans.snapshot()[mark:]
               if s["name"] in ("eval:compile", "eval:build",
                                "train:compile", "train:build")]
        assert not bad, bad
    finally:
        reg.close()


# -------------------------------------------------------- tensor parallel
def _col_parallel_net(seed=3):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    # column-parallel only: every output feature is computed entirely on
    # one shard (full contraction, no cross-shard psum), so the sharded
    # program is BIT-IDENTICAL to the single-device one
    net.add(parallel.ColParallelDense(12, in_units=8))
    net.initialize(mx.init.Xavier())
    return net


def test_tp2_mesh_servable_matches_unsharded_through_batcher():
    """The tp=2 program splits each contraction over two devices, so its
    float32 sums associate differently from the single-device forward: XLA
    promises no bit-equality across partitionings (CPU or TPU). Agreement
    is held to float32 rounding of these O(1) activations instead."""
    net = _col_parallel_net()
    rng = onp.random.RandomState(0)
    x = rng.randn(6, 8).astype("float32")
    ref = net(mx.nd.array(x)).asnumpy()
    sv = MeshServable(net, tp=2)
    assert sv.replicas == 1
    reg = ModelRegistry()
    reg.load("tp2", sv, max_batch_size=4, batch_timeout_ms=2.0,
             warm_spec=[((8,), "float32")])
    try:
        for i in range(6):
            out = reg.predict("tp2", x[i])
            onp.testing.assert_allclose(onp.asarray(out[0]), ref[i],
                                        rtol=1e-5, atol=1e-6)
    finally:
        reg.close()


def test_tp2_replica_groups_compose_and_stay_bit_exact():
    net = _col_parallel_net()
    rng = onp.random.RandomState(1)
    x = rng.randn(8, 8).astype("float32")
    ref = net(mx.nd.array(x)).asnumpy()
    sv = MeshServable(net, tp=2, replicas=4)     # 4 groups x tp=2 = 8 devs
    assert sv.replicas == 4
    reg = ModelRegistry()
    reg.load("dptp", sv, max_batch_size=2, batch_timeout_ms=1.0,
             replicas=4, prewarm=False)
    try:
        reqs = [reg.submit("dptp", x[i]) for i in range(8)]
        for i, r in enumerate(reqs):
            out = r.result(60.0)
            assert onp.array_equal(onp.asarray(out[0]), ref[i]), i
        counts = reg._entry("dptp").batcher.replica_dispatch_counts()
        assert sum(counts) == 8
    finally:
        reg.close()


def test_mesh_servable_validates_device_budget():
    net = _col_parallel_net()
    with pytest.raises(ValueError):
        MeshServable(net, tp=2, replicas=5)      # 10 > 8 devices
    with pytest.raises(ValueError):
        MeshServable(net, tp=99)


def test_mesh_servable_sharded_artifact_roundtrip(tmp_path, monkeypatch):
    from incubator_mxnet_tpu import aot
    monkeypatch.setenv("MXTPU_AOT_CACHE_DIR", str(tmp_path))
    net = _col_parallel_net()
    x = onp.random.RandomState(2).randn(4, 8).astype("float32")
    # an earlier test may have compiled this (model, sig, mesh) without
    # the artifact layer on — force a fresh build so one is written
    for key in [k for k in aot.CACHE.keys() if k.mesh is not None]:
        aot.CACHE.discard(key)
    sv = MeshServable(net, tp=2)
    ref = onp.asarray(sv.predict_batch(x)[0])
    files = list(tmp_path.rglob("*.mxtpu-aot"))
    assert files, "sharded serve program was not persisted"
    # a reconstructed servable (cache cleared) loads the partitioned
    # artifact instead of re-tracing the model
    for key in [k for k in aot.CACHE.keys() if k.mesh is not None]:
        aot.CACHE.discard(key)
    sv2 = MeshServable(net, tp=2)
    out = onp.asarray(sv2.predict_batch(x)[0])
    assert onp.array_equal(out, ref)
    entry = next(aot.CACHE.peek(k) for k in aot.CACHE.keys()
                 if k.mesh is not None)
    assert entry.source == "artifact"


def test_train_and_mesh_key_artifact_rules(tmp_path, monkeypatch):
    from incubator_mxnet_tpu import aot
    monkeypatch.setenv("MXTPU_AOT_CACHE_DIR", str(tmp_path))
    train = aot.cache_key("m", [((4,), "float32")], kind="train",
                          mesh=(("tp", 2),))
    assert aot.artifact_path(train) is None
    serve = aot.cache_key("m", [((4,), "float32")], kind="serve",
                          mesh=(("tp", 2),))
    p_sharded = aot.artifact_path(serve)
    assert p_sharded is not None
    # the mesh signature participates in the digest: a different
    # topology must resolve a DIFFERENT file, never misload
    other = aot.cache_key("m", [((4,), "float32")], kind="serve",
                          mesh=(("tp", 4),))
    assert aot.artifact_path(other) != p_sharded


# ------------------------------------------------------ scaling (smoke)
def test_replica_goodput_scales_smoke():
    """1 vs 4 replicas on a timer-bound servable: wall time for the same
    request set must improve well past noise (the hard >=3x 1->8 gate
    with saturation detection runs in ci/run.sh sharded)."""
    def run(replicas):
        b = DynamicBatcher(_SlowEcho(0.010), max_batch_size=4,
                           batch_timeout_ms=1.0, queue_size=64,
                           replicas=replicas, name="scale%d" % replicas)
        try:
            t0 = time.monotonic()
            reqs = [b.submit(onp.float32([float(i)])) for i in range(48)]
            for r in reqs:
                r.result(60.0)
            return time.monotonic() - t0
        finally:
            b.close()

    t1 = run(1)
    t4 = run(4)
    assert t1 / t4 >= 2.0, (t1, t4)


def test_serve_dispatch_spans_carry_replica_and_request_ids():
    b = DynamicBatcher(_Echo(), max_batch_size=2, batch_timeout_ms=1.0,
                       queue_size=8, replicas=2, name="spansrep")
    try:
        # reset, don't mark-and-slice: once the bounded ring is at
        # capacity (a long test session gets it there), len() stays
        # constant while old records evict, so a [mark:] slice of the
        # post-predict snapshot would read empty
        spans.reset()
        b.predict(onp.float32([1.0]), request_id="rid-1", timeout=10.0)
        recs = [s for s in spans.snapshot()
                if s["name"] == "serve:dispatch"]
        assert recs, "no serve:dispatch span"
        args = recs[-1]["args"]
        assert args["replica"] in (0, 1)
        assert "rid-1" in args["request_ids"]
    finally:
        b.close()
