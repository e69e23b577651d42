"""AOT executable-cache subsystem tier (incubator_mxnet_tpu/aot.py +
the zero-recompile serving integration — ROADMAP item 3, docs/AOT.md).

Covers: cache-key correctness (same model+bucket+dtype+mesh hits, any
delta misses), LRU-by-last-dispatch eviction with the evictions counter,
cross-instance executable sharing (params stay runtime inputs), the
persistent artifact round-trip in a FRESH subprocess (zero train:/
eval:compile spans, artifact-hit counter > 0, compile counter untouched),
registry prewarm (smallest bucket first, aot:warm spans, prewarm
metrics), and the e2e hot-reload acceptance: no compile span lands
between swap-begin and drain-complete while concurrent predicts keep
succeeding.
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import aot, gluon, jit, nd
from incubator_mxnet_tpu.serving import ModelRegistry
from incubator_mxnet_tpu.telemetry import spans


def _dense(units, in_units=4):
    net = gluon.nn.Dense(units, in_units=in_units)
    net.initialize(mx.init.Xavier())
    return net


# ------------------------------------------------------------- cache keys
def test_cache_key_identity_and_deltas():
    base = aot.cache_key("m1", [((4, 8), "float32")], kind="eval")
    same = aot.cache_key("m1", (((4, 8), "float32"),), kind="eval")
    assert base == same and hash(base) == hash(same)
    assert base != aot.cache_key("m2", [((4, 8), "float32")], kind="eval")
    assert base != aot.cache_key("m1", [((2, 8), "float32")], kind="eval")
    assert base != aot.cache_key("m1", [((4, 8), "bfloat16")], kind="eval")
    assert base != aot.cache_key("m1", [((4, 8), "float32")], kind="train")
    assert base != aot.cache_key("m1", [((4, 8), "float32")], kind="eval",
                                 mesh=((("dp", 8),), 8))
    assert base != aot.cache_key("m1", [((4, 8), "float32")], kind="eval",
                                 extra=(2,))


def test_model_id_structural_sharing_and_deltas():
    a, b = _dense(3), _dense(3)
    assert aot.model_id_for(a) == aot.model_id_for(b)   # same architecture
    assert aot.model_id_for(a) != aot.model_id_for(_dense(5))
    assert aot.model_id_for(a) != aot.model_id_for(a, extra=("train",))
    # baked (non-Parameter) array state participates: differently-baked
    # instances of one class must not share a compiled program
    c, d = _dense(3), _dense(3)
    c._baked = onp.ones(4, "float32")
    d._baked = onp.zeros(4, "float32")
    assert aot.model_id_for(c) != aot.model_id_for(d)
    # dict-valued baked config participates too (calibration tables)
    e, f = _dense(3), _dense(3)
    e._calib = {"scale": 0.5}
    f._calib = {"scale": 2.0}
    assert aot.model_id_for(e) != aot.model_id_for(f)


# ------------------------------------------------------------------- LRU
def test_lru_eviction_by_last_dispatch(monkeypatch):
    monkeypatch.setenv("MXTPU_AOT_CACHE_SIZE", "2")
    cache = aot.AOTCache()
    k = [aot.cache_key("m", [((i, 4), "float32")], kind="eval")
         for i in range(3)]
    ev0 = aot._EVICTIONS.value(kind="eval")
    cache.insert(k[0], "fn0")
    cache.insert(k[1], "fn1")
    assert cache.lookup(k[0]) is not None   # touch: k0 is now the hot one
    cache.insert(k[2], "fn2")
    # dict-order eviction would have dropped k0; LRU drops the cold k1
    assert cache.peek(k[0]) is not None
    assert cache.peek(k[1]) is None
    assert cache.peek(k[2]) is not None
    assert aot._EVICTIONS.value(kind="eval") == ev0 + 1


def test_single_flight_build():
    """Concurrent misses on one key run build() exactly once."""
    cache = aot.AOTCache()
    key = aot.cache_key("sf", [((1,), "float32")], kind="eval")
    builds, barrier = [], threading.Barrier(4)
    def build():
        builds.append(1)
        time.sleep(0.05)
        return "fn", None, None
    out = []
    def worker():
        barrier.wait()
        out.append(cache.get_or_build(key, build))
    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert len(builds) == 1 and len(out) == 4
    assert all(e.fn == "fn" for e in out)


# ------------------------------------------------- cross-instance sharing
def test_evalstep_instances_share_executable():
    net1, net2 = _dense(7), _dense(7)
    s1, s2 = jit.EvalStep(net1), jit.EvalStep(net2)
    o1 = s1(nd.ones((2, 4)))
    c0 = jit._COMPILES.value(kind="eval")
    o2 = s2(nd.ones((2, 4)))                 # same arch -> shared program
    assert jit._COMPILES.value(kind="eval") == c0
    # params are runtime inputs: different weights give different outputs
    assert not onp.allclose(o1.asnumpy(), o2.asnumpy())


def test_concurrent_shape_builds_same_net_are_safe():
    """Two threads compile-missing DIFFERENT shapes of one net at once
    (the warm-thread-vs-worker shape after early cutover): every trace
    swaps tracers into the same live param NDArrays, so builds must
    serialize on jit._TRACE_LOCK — no leaked tracer, params intact."""
    net = _dense(3)
    step = jit.EvalStep(net)
    errs = []

    def build(n):
        try:
            out = step(nd.ones((n, 4)))
            assert out.shape == (n, 3)
        except Exception as e:   # noqa: BLE001 — surfaced after join
            errs.append(repr(e))

    threads = [threading.Thread(target=build, args=(n,))
               for n in (9, 10, 11, 12)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not errs, errs
    # params survived every concurrent trace window un-corrupted
    assert step(nd.ones((9, 4))).shape == (9, 3)


def test_train_kind_never_persisted(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_AOT_CACHE_DIR", str(tmp_path))
    key = aot.cache_key("m", [((4, 4), "float32")], kind="train")
    assert aot.artifact_path(key) is None
    assert aot.artifact_path(
        aot.cache_key("m", [((4, 4), "float32")], kind="eval")) is not None


def test_trainstep_entries_released_on_del():
    import gc
    net = _dense(3)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    step = jit.TrainStep(net, gluon.loss.L2Loss(), trainer)
    step(nd.ones((4, 4)), nd.ones((4, 3)))
    mid = step._model_id
    assert any(k.model_id == mid for k in aot.CACHE.keys())
    del step
    gc.collect()
    assert not any(k.model_id == mid for k in aot.CACHE.keys())


def test_trainstep_explicit_model_id_never_shares_entries():
    """Train entries carry instance-bound state: even with one explicit
    model_id, two TrainSteps must NOT share — each step must update its
    OWN net (the instance token lives in the cache key)."""
    net_a, net_b = _dense(3), _dense(3)
    tr_a = gluon.Trainer(net_a.collect_params(), "sgd",
                         {"learning_rate": 0.1})
    tr_b = gluon.Trainer(net_b.collect_params(), "sgd",
                         {"learning_rate": 0.1})
    s_a = jit.TrainStep(net_a, gluon.loss.L2Loss(), tr_a, model_id="shared")
    s_b = jit.TrainStep(net_b, gluon.loss.L2Loss(), tr_b, model_id="shared")
    w_a0 = list(net_a.collect_params().values())[0].data().asnumpy().copy()
    w_b0 = list(net_b.collect_params().values())[0].data().asnumpy().copy()
    s_a(nd.ones((4, 4)), nd.ones((4, 3)))
    s_b(nd.ones((4, 4)), nd.ones((4, 3)))
    w_a1 = list(net_a.collect_params().values())[0].data().asnumpy()
    w_b1 = list(net_b.collect_params().values())[0].data().asnumpy()
    assert not onp.allclose(w_a0, w_a1), "net A did not train"
    assert not onp.allclose(w_b0, w_b1), "net B did not train (hit A's entry)"


def test_servedmodel_shares_compiled_chunks(tmp_path):
    from incubator_mxnet_tpu.contrib import serving as artifact
    net = _dense(3)
    path = str(tmp_path / "m.mxtpu")
    artifact.export_model(net, nd.ones((2, 4)), path)
    sm1, sm2 = artifact.load(path), artifact.load(path)
    assert sm1._model_id == sm2._model_id
    sm1.predict_batch(onp.ones((5, 4), "float32"))
    misses = aot._MISSES.value(kind="serve")
    # second instance of the same artifact + same bucket: pure hits
    sm2.predict_batch(onp.ones((5, 4), "float32"))
    assert aot._MISSES.value(kind="serve") == misses


# ------------------------------------------------------ artifact round-trip
_CHILD = """
import json
import jax
jax.config.update("jax_platforms", "cpu")
import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import aot, gluon, jit, nd, telemetry
from incubator_mxnet_tpu.telemetry import spans

net = gluon.nn.Dense(3, in_units=4)
net.initialize(mx.init.Xavier())
step = jit.EvalStep(net)
out = step(nd.ones((2, 4)))
names = [s["name"] for s in spans.snapshot()]
prog_flops = [float(l.rsplit(None, 1)[1])
              for l in telemetry.export_text().splitlines()
              if l.startswith("mxtpu_aot_program_flops{")]
entry_stats = [e["stats"] for e in aot.CACHE.snapshot()]
print(json.dumps({
    "artifact_hits": aot._ARTIFACT_HITS.value(kind="eval"),
    "compiles": jit._COMPILES.value(kind="eval"),
    "compile_spans": [n for n in names
                      if n in ("eval:compile", "train:compile")],
    "program_flops": prog_flops,
    "entry_stats": entry_stats,
    "shape": list(out.shape)}))
"""


def test_artifact_roundtrip_fresh_subprocess(tmp_path, monkeypatch):
    """A fresh process pointed at a populated MXTPU_AOT_CACHE_DIR serves
    its first request without tracing: artifact-hit counter > 0, compile
    counter unchanged, ZERO train:/eval:compile spans recorded — AND
    device truth survives the zero-compile load: the entry carries the
    v2 header's program stats and /metrics reports nonzero
    mxtpu_aot_program_flops."""
    cache_dir = str(tmp_path / "aotcache")
    monkeypatch.setenv("MXTPU_AOT_CACHE_DIR", cache_dir)
    # populate: same architecture as the child builds
    net = _dense(3)
    step = jit.EvalStep(net)
    step(nd.ones((2, 4)))
    written = [os.path.join(dp, f) for dp, _dn, fs in os.walk(cache_dir)
               for f in fs if f.endswith(".mxtpu-aot")]
    assert written, "no artifact persisted"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXTPU_AOT_CACHE_DIR=cache_dir)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _CHILD], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode == 0, (r.stdout, r.stderr)
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["artifact_hits"] >= 1, rec
    assert rec["compiles"] == 0, rec
    assert rec["compile_spans"] == [], rec
    assert rec["shape"] == [2, 3]
    # zero-compile device truth: program FLOPs from the artifact header
    assert rec["program_flops"] and max(rec["program_flops"]) > 0, rec
    assert any(s and s.get("flops", 0) > 0 for s in rec["entry_stats"]), rec


def test_old_version_artifact_header_rebuilds_with_reanalysis(
        tmp_path, monkeypatch):
    """An artifact written by an OLDER format version (v1 magic, no
    stats header) must fall back to a fresh build WITH re-analysis: no
    artifact hit, one compile, and the rebuilt entry carries program
    stats — never a misparse, never an entry without device truth."""
    cache_dir = str(tmp_path / "aotcache")
    monkeypatch.setenv("MXTPU_AOT_CACHE_DIR", cache_dir)
    net = _dense(3)
    # (6, 4) is unique to this test: the in-memory entry cannot pre-exist
    jit.EvalStep(net)(nd.ones((6, 4)))
    files = [os.path.join(dp, f) for dp, _dn, fs in os.walk(cache_dir)
             for f in fs if f.endswith(".mxtpu-aot")]
    assert files
    for path in files:
        buf = open(path, "rb").read()
        assert buf.startswith(aot.ARTIFACT_MAGIC)
        # rewrite as a v1-era file: old magic, payload directly after it
        with open(path, "wb") as f:
            f.write(b"MXTPUAOT\x001" + buf[len(aot.ARTIFACT_MAGIC):])
    for k in list(aot.CACHE.keys()):   # force re-resolution from disk
        if k.input_sig and k.input_sig[0][0] == (6, 4):
            aot.CACHE.discard(k)
    hits0 = aot._ARTIFACT_HITS.value(kind="eval")
    c0 = jit._COMPILES.value(kind="eval")
    step = jit.EvalStep(_dense(3))
    out = step(nd.ones((6, 4)))        # must not raise, must not misload
    assert out.shape == (6, 3)
    assert aot._ARTIFACT_HITS.value(kind="eval") == hits0
    assert jit._COMPILES.value(kind="eval") == c0 + 1
    assert step._last_stats and step._last_stats["flops"] > 0


def test_truncated_v2_header_rebuilds(tmp_path, monkeypatch):
    """A v2 file whose header length overruns the payload is corrupt:
    rebuild, never misparse."""
    cache_dir = str(tmp_path / "aotcache")
    monkeypatch.setenv("MXTPU_AOT_CACHE_DIR", cache_dir)
    net = _dense(3)
    # (13, 4) is unique to this test: the in-memory entry cannot pre-exist
    jit.EvalStep(net)(nd.ones((13, 4)))
    files = [os.path.join(dp, f) for dp, _dn, fs in os.walk(cache_dir)
             for f in fs if f.endswith(".mxtpu-aot")]
    assert files
    for path in files:
        with open(path, "wb") as f:
            f.write(aot.ARTIFACT_MAGIC + b"\xff\xff\xff\xff{}")
    for k in list(aot.CACHE.keys()):
        if k.input_sig and k.input_sig[0][0] == (13, 4):
            aot.CACHE.discard(k)
    c0 = jit._COMPILES.value(kind="eval")
    out = jit.EvalStep(_dense(3))(nd.ones((13, 4)))
    assert out.shape == (13, 3)
    assert jit._COMPILES.value(kind="eval") == c0 + 1


def test_corrupt_artifact_falls_back_to_build(tmp_path, monkeypatch):
    cache_dir = str(tmp_path / "aotcache")
    monkeypatch.setenv("MXTPU_AOT_CACHE_DIR", cache_dir)
    net = _dense(3)
    # (7, 4) is unique to this test: the in-memory entry cannot pre-exist
    jit.EvalStep(net)(nd.ones((7, 4)))
    files = [os.path.join(dp, f) for dp, _dn, fs in os.walk(cache_dir)
             for f in fs if f.endswith(".mxtpu-aot")]
    assert files
    for path in files:
        with open(path, "wb") as f:
            f.write(b"garbage")
    for k in list(aot.CACHE.keys()):   # force re-resolution from disk
        if k.input_sig and k.input_sig[0][0] == (7, 4):
            aot.CACHE.discard(k)
    hits0 = aot._ARTIFACT_HITS.value(kind="eval")
    c0 = jit._COMPILES.value(kind="eval")
    out = jit.EvalStep(_dense(3))(nd.ones((7, 4)))   # must not raise
    assert out.shape == (7, 3)
    assert aot._ARTIFACT_HITS.value(kind="eval") == hits0
    assert jit._COMPILES.value(kind="eval") == c0 + 1


# ------------------------------------------------------------- prewarm e2e
def test_first_load_warm_spec_prewarms_all_buckets():
    reg = ModelRegistry()
    reg.load("warm0", _dense(3), max_batch_size=4, batch_timeout_ms=2.0,
             warm_spec=[((4,), "float32")])
    assert reg.metrics("warm0").prewarm_count == 3    # buckets 1, 2, 4
    # by the model's name, which is this test's alone: a position in the
    # span ring means nothing once earlier tests of the worker have filled it
    warm = [s for s in spans.snapshot() if s["name"] == "aot:warm"
            and s["args"].get("model") == "warm0"]
    assert [s["args"]["bucket"] for s in warm] == [1, 2, 4]  # smallest first
    c0 = jit._COMPILES.value(kind="eval")
    out = reg.predict("warm0", onp.ones((4,), "float32"))
    assert out[0].shape == (3,)
    assert jit._COMPILES.value(kind="eval") == c0, \
        "first request after warm must not compile"
    reg.close()


class _TupleServable:
    def predict_batch(self, *xs):
        return xs


def test_repoint_superseded_warm_cannot_roll_back():
    """Overlapping hot-reloads: only the NEWEST registered version's warm
    may repoint — a slower older warm finishing last must not drag
    dispatch back to a stale model."""
    from incubator_mxnet_tpu.serving.registry import _ModelEntry
    entry = _ModelEntry("rp", max_batch_size=2, batch_timeout_ms=1.0)
    try:
        v1 = entry.install(_TupleServable(), None)
        v2 = entry.add_version(_TupleServable(), None)
        v3 = entry.add_version(_TupleServable(), None)  # newest target
        entry.repoint(v2)                # stale warm finishing late
        assert entry.current_version == v1
        entry.repoint(v3)
        assert entry.current_version == v3
        entry.install(_TupleServable(), None)           # direct install...
        entry.repoint(v3)                # ...supersedes v3's warm too
        assert entry.current_version == 4
    finally:
        entry.batcher.close()


def test_first_load_routable_while_warming():
    """A FIRST load's warm must not leave the model 404ing: with no
    routable predecessor, add_version makes the version current
    immediately (warming requests compile lazily instead of erroring)."""
    from incubator_mxnet_tpu.serving.registry import _ModelEntry
    entry = _ModelEntry("fl", max_batch_size=2, batch_timeout_ms=1.0)
    try:
        v = entry.add_version(_TupleServable(), None)
        assert entry.current_version == v
    finally:
        entry.batcher.close()


def test_prewarm_failure_degrades_to_lazy_swap():
    """A servable that cannot take the observed signature still swaps in
    (old lazy behavior), never leaves the model unroutable."""
    class Broken:
        def predict_batch(self, *xs):
            raise RuntimeError("boom")
    reg = ModelRegistry()
    reg.load("deg", _dense(3), max_batch_size=2, batch_timeout_ms=2.0)
    reg.predict("deg", onp.ones((4,), "float32"))     # observe the sig
    v2 = reg.load("deg", Broken())                    # warm fails, swaps
    assert reg._entry("deg").describe()["current_version"] == v2
    reg.close()


def test_hot_reload_no_compile_window_under_traffic():
    """The acceptance e2e: concurrent predicts stay successful through a
    hot reload to a DIFFERENT architecture, and no compile span lands
    between swap-begin (prewarmed load returned) and drain-complete —
    the new version's compiles all happened inside aot:warm, pre-swap."""
    reg = ModelRegistry()
    v1 = reg.load("hot", _dense(3), max_batch_size=4, batch_timeout_ms=2.0,
                  warm_spec=[((4,), "float32")])
    stop, errors, oks = threading.Event(), [], []

    def client():
        while not stop.is_set():
            try:
                out = reg.predict("hot", onp.ones((4,), "float32"),
                                  timeout=30.0)
                assert out[0].shape[0] in (3, 6)
                oks.append(1)
            except Exception as e:   # noqa: BLE001 — surfaced after join
                errors.append(repr(e))
                return

    threads = [threading.Thread(target=client) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        time.sleep(0.3)                      # steady traffic on v1
        v2 = reg.load("hot", _dense(6))      # warm (real compiles) + swap
        mark = len(spans.snapshot())         # swap-begin
        reg.unload("hot", version=v1, drain=True, timeout=30.0)
        time.sleep(0.3)                      # post-drain traffic on v2
        window = spans.snapshot()[mark:]     # ...drain-complete and after
    finally:
        stop.set()
        for t in threads:
            t.join(30.0)
    assert not errors, errors
    assert len(oks) > 20
    assert v2 == v1 + 1
    compiles = [s for s in window
                if s["name"] in ("eval:compile", "eval:build",
                                 "train:compile", "train:build")]
    assert not compiles, compiles
    # and the traffic in the window really exercised the new version
    assert reg._entry("hot").describe()["current_version"] == v2
    reg.close()


def test_debug_aot_endpoint():
    from incubator_mxnet_tpu.serving import ServingServer
    import urllib.request
    reg = ModelRegistry()
    reg.load("dbg", _dense(3), max_batch_size=2, batch_timeout_ms=2.0,
             warm_spec=[((4,), "float32")])
    with ServingServer(reg, port=0) as srv:
        with urllib.request.urlopen(srv.url + "/debug/aot",
                                    timeout=30) as r:
            payload = json.loads(r.read())
    kinds = {e["kind"] for e in payload["entries"]}
    assert "eval" in kinds
    assert all({"model_id", "kind", "input_sig", "source", "idle_s"}
               <= set(e) for e in payload["entries"])
