"""Process-wide AOT compiled-executable cache (ROADMAP item 3: p99 must
not see a compile).

Before this module, three independent lazy caches each paid their own
trace+compile+first-run window inside the first hot-path call: TrainStep
``self._cache``, EvalStep ``self._cache``, and the per-ServedModel
``Exported.call`` path (which re-built its call wrapper every chunk).
Under bucketed serving that is one full compile *per bucket, per model
version, per component, per process* — and a registry hot-reload put that
window straight into user-visible p99.

This module replaces them with ONE shared cache:

- **Key**: ``(model_id, kind, input signature, mesh, extra)`` — the
  shape-bucket × dtype × mesh identity of a compiled program
  (``cache_key()``). ``model_id`` is a stable digest (``model_id_for()``)
  so two components serving the same architecture share executables
  instead of recompiling per component.
- **Compilation**: JAX's explicit AOT pipeline —
  ``jit(fn).lower(*args).compile()`` — instead of first-call lazy
  compilation, so the compile lands where the caller schedules it
  (a prewarm thread, a build span), never inside a later dispatch.
- **Artifacts** (``MXTPU_AOT_CACHE_DIR``): exportable programs (the
  eval/serve forward paths) are serialized via ``jax.export`` (StableHLO)
  per cache key — including MESH-SHARDED serving programs, whose
  partitioned module jax.export records with its GSPMD shardings (the
  key's mesh signature is in the file digest, so topology mismatches
  miss instead of misload). A fresh process pointed at a populated cache
  dir LOADS the program instead of re-tracing the Python model — the
  first request pays zero trace time and records an artifact hit, and
  with registry prewarm the XLA compile of the loaded module also lands
  pre-traffic. Train-kind entries (donated-buffer programs,
  instance-bound state) stay in-memory only.
- **Eviction**: LRU by last-dispatch time, bounded by
  ``MXTPU_AOT_CACHE_SIZE``, with every eviction counted on
  ``mxtpu_aot_evictions_total`` so silent thrash is visible (dict-order
  eviction could silently drop the hottest bucket).

- **Device truth** (telemetry/devstats.py): every executable entering the
  cache — fresh build OR artifact load — has its XLA ``cost_analysis()``
  + ``memory_analysis()`` harvested ONCE into ``entry.stats``
  (``{flops, bytes_accessed, peak_bytes, output_bytes}``), persisted in
  the artifact header (format v2) so a zero-compile load in a fresh
  process still knows its program's FLOPs, and published on
  ``mxtpu_aot_program_flops`` / ``mxtpu_aot_program_peak_bytes``
  ``{model,kind,bucket}``. The hot paths divide these FLOPs by measured
  dispatch spans for MFU attribution — analysis happens here, at
  build/load time, never per dispatch (mxtpulint R001 models the
  per-dispatch form as a defect).

Observability: ``mxtpu_aot_{hits,misses,evictions,artifact_hits,
artifact_writes}_total`` counters, the ``mxtpu_aot_entries`` gauge, and
``aot:load`` spans around artifact deserialization (prewarm emits
``aot:warm`` spans from serving/registry.py). See docs/AOT.md.
"""
from __future__ import annotations

import hashlib
import json as _json
import logging
import os
import struct
import threading
import time as _time
from collections import namedtuple

from . import config
from . import telemetry
from .telemetry import devstats, faultlab, spans

__all__ = ["CacheKey", "cache_key", "AOTCache", "CACHE", "compile_cached",
           "model_id_for", "input_signature", "mesh_sig", "artifact_path",
           "ARTIFACT_MAGIC", "FORMAT_VERSION", "collect_inserts",
           "ProgramFactsRef", "program_digest", "facts_for_key"]

_LOG = logging.getLogger(__name__)

#: bump when the artifact payload layout changes — old files are ignored,
#: never misparsed (the version participates in the file digest AND the
#: magic, so a stale same-named file is rejected at the magic check).
#: v2: a length-prefixed JSON header (program stats from cost/memory
#: analysis) sits between the magic and the jax.export payload, so a
#: zero-compile artifact load still carries device truth.
FORMAT_VERSION = 2
ARTIFACT_MAGIC = b"MXTPUAOT\x002"

_HITS = telemetry.counter(
    "mxtpu_aot_hits_total",
    "Shared executable-cache hits (dispatch found a compiled program).",
    ("kind",))
_MISSES = telemetry.counter(
    "mxtpu_aot_misses_total",
    "Shared executable-cache misses (artifact load or fresh build).",
    ("kind",))
_EVICTIONS = telemetry.counter(
    "mxtpu_aot_evictions_total",
    "LRU evictions from the shared executable cache past "
    "MXTPU_AOT_CACHE_SIZE — a climbing rate under steady traffic means "
    "the bound is too small for the live bucket set (cache thrash).",
    ("kind",))
_ARTIFACT_HITS = telemetry.counter(
    "mxtpu_aot_artifact_hits_total",
    "Cache misses satisfied by a persisted jax.export artifact "
    "(MXTPU_AOT_CACHE_DIR) instead of re-tracing the model.", ("kind",))
_ARTIFACT_WRITES = telemetry.counter(
    "mxtpu_aot_artifact_writes_total",
    "Serialized executables written to MXTPU_AOT_CACHE_DIR.", ("kind",))
_ENTRIES = telemetry.gauge(
    "mxtpu_aot_entries",
    "Live entries in the process-wide AOT executable cache.")
_PROG_FLOPS = telemetry.gauge(
    "mxtpu_aot_program_flops",
    "XLA cost_analysis FLOPs of one execution of a cached program, "
    "harvested at build/load time (artifact loads carry it in the v2 "
    "header). The numerator of every mxtpu_device_mfu observation — "
    "nonzero after a zero-compile artifact-only load is the device-truth "
    "survival contract (docs/AOT.md).", ("model", "kind", "bucket"))
_PROG_PEAK_BYTES = telemetry.gauge(
    "mxtpu_aot_program_peak_bytes",
    "memory_analysis peak live bytes of one execution of a cached "
    "program (arguments + outputs + XLA temp buffers, donated/aliased "
    "bytes deducted) — compare against mxtpu_device_memory_bytes "
    "bytes_limit before sizing batch buckets.", ("model", "kind",
                                                 "bucket"))

#: (model_id, kind, input_sig, mesh, extra) — the full identity of one
#: compiled program. kind is 'train' | 'eval' | 'serve'; input_sig is a
#: tuple of (shape tuple, dtype string) per input; mesh is mesh_sig();
#: extra carries caller-specific statics (e.g. TrainStep's n_net_inputs).
CacheKey = namedtuple("CacheKey", ("model_id", "kind", "input_sig", "mesh",
                                   "extra"))


def input_signature(arrs):
    """(shape, dtype) tuple per input — accepts NDArrays, jax or numpy
    arrays (anything with .shape/.dtype)."""
    return tuple((tuple(a.shape), str(a.dtype)) for a in arrs)


def mesh_sig(mesh):
    """Hashable identity of a mesh (None for single-device): axis sizes +
    device count, enough to distinguish programs compiled for different
    layouts."""
    if mesh is None:
        return None
    return (tuple(sorted(mesh.shape.items())), len(mesh.devices.flat))


def cache_key(model_id, input_sig, kind="eval", mesh=None, extra=()):
    """Build the canonical CacheKey. ``input_sig`` comes from
    ``input_signature()`` (already normalized) or any iterable of
    (shape, dtype) pairs."""
    sig = tuple((tuple(s), str(d)) for s, d in input_sig)
    return CacheKey(str(model_id), str(kind), sig,
                    mesh if (mesh is None or isinstance(mesh, tuple))
                    else mesh_sig(mesh), tuple(extra))


def _iter_blocks(net, path="net", seen=None):
    """Depth-first (path, block) walk over a Gluon block tree."""
    if seen is None:
        seen = set()
    if id(net) in seen:
        return
    seen.add(id(net))
    yield path, net
    children = getattr(net, "_children", None)
    if isinstance(children, dict):
        for name, child in sorted(children.items()):
            yield from _iter_blocks(child, "%s.%s" % (path, name), seen)


def _is_array(val):
    return hasattr(val, "shape") and hasattr(val, "dtype") \
        and hasattr(val, "__array__")


def _baked_state_tokens(net):
    """Digest tokens for TRACE-TIME-BAKED block state: instance attributes
    that are Python scalars or raw arrays (NOT registered Parameters —
    those stay runtime inputs). A quantized wrapper's int8 weights and
    calibration ranges live here; two differently-calibrated instances of
    one architecture must NOT share a compiled program, and a reloaded
    identical one must."""
    import numpy as onp
    scalars = (bool, int, float, str, bytes, type(None))
    skip = ("_children", "_reg_params", "_forward_hooks", "_cached_fn",
            "_forward_pre_hooks", "_prefix", "_name", "_scope")
    for path, block in _iter_blocks(net):
        try:
            items = sorted(vars(block).items())
        except TypeError:
            continue
        for name, val in items:
            if name in skip or type(val).__name__ == "Parameter" \
                    or hasattr(val, "_children"):
                continue
            if isinstance(val, dict):
                # sort by repr: mixed-type keys (int vs str) make the
                # natural sort raise mid-generator, which would silently
                # truncate the digest and merge differently-baked models
                items = tuple(sorted(
                    ((k, v) for k, v in val.items()
                     if isinstance(v, scalars)),
                    key=repr))
                yield "%s.%s=%r" % (path, name, items)
                continue
            if isinstance(val, (tuple, list)) \
                    and all(isinstance(v, scalars) for v in val):
                yield "%s.%s=%r" % (path, name, tuple(val))
            elif isinstance(val, scalars):
                yield "%s.%s=%r" % (path, name, val)
            elif _is_array(val) or hasattr(val, "_data"):
                try:
                    arr = onp.asarray(getattr(val, "_data", val))
                    yield "%s.%s@%s" % (path, name, hashlib.sha256(
                        arr.tobytes()).hexdigest()[:16])
                except Exception:
                    yield "%s.%s@<unhashable>" % (path, name)


def model_id_for(net, extra=()):
    """Stable content digest of a Gluon block: class, repr (layer
    hyperparameters), the parameter (name, shape, dtype) list, and a hash
    of any trace-time-baked instance state (raw arrays / scalars that are
    not Parameters), plus caller ``extra`` tokens. Components
    (EvalStep/BlockServable) built on an identical model produce the same
    id and SHARE compiled executables — and a fresh process reconstructing
    the same model resolves the same persisted artifact. Registered
    Parameters stay runtime inputs, so sharing is weight-safe.

    The digest cannot see forward() semantics hidden from repr, the
    parameter structure, and the baked-state walk (e.g. state tucked in
    nested custom containers) — pass an explicit ``model_id`` to the
    caller (EvalStep/TrainStep/export) when such models must not share
    (docs/AOT.md invalidation rules).
    """
    import jax
    parts = [jax.__version__, type(net).__qualname__]
    try:
        parts.append(repr(net))
    except Exception:
        parts.append("<repr-failed>")
    try:
        # POSITIONAL (index, shape, dtype) — never the parameter names:
        # gluon auto-naming makes every instance's prefix unique
        # (dense0_ vs dense1_), and two instances of one architecture
        # must produce the same id; collect_params() walk order is
        # structure-deterministic, which is what make_pure_fn's input
        # ordering relies on too
        for i, p in enumerate(net.collect_params().values()):
            shape = getattr(p, "shape", None)
            dtype = getattr(p, "dtype", None)
            parts.append("p%d:%s:%s" % (i, shape, dtype))
    except Exception:
        parts.append("<params-unavailable>")
    try:
        parts.extend(_baked_state_tokens(net))
    except Exception:
        parts.append("<baked-state-unavailable>")
    parts.extend(str(e) for e in extra)
    return "g" + hashlib.sha256("\x00".join(parts).encode()).hexdigest()[:20]


class _Entry:
    """One compiled program + its caller extras and LRU bookkeeping.
    ``stats`` is the program's device truth (devstats.program_stats dict:
    flops / bytes_accessed / peak_bytes / output_bytes) or None when the
    program is not analyzable (a lazily-jitted or wrapped callable)."""

    __slots__ = ("key", "fn", "extras", "last_used", "source", "created",
                 "stats")

    def __init__(self, key, fn, extras, source, stats=None):
        self.key = key
        self.fn = fn
        self.extras = extras
        self.source = source            # 'build' | 'artifact'
        self.stats = stats
        self.created = _time.monotonic()
        self.last_used = self.created


_collector = threading.local()


class collect_inserts:
    """Record every cache entry THIS THREAD inserts while the context is
    active. The serving registry wraps each prewarm bucket's warm
    dispatches in one so the hlolint load gate can lint exactly the
    programs the warm just produced (build or artifact load) before it
    repoints traffic at them — no cache-wide diffing, no cross-thread
    attribution guesswork (warm dispatches run on the one warm thread).
    Nests: the inner context collects; the outer resumes afterwards."""

    def __enter__(self):
        self._prev = getattr(_collector, "sink", None)
        self.entries = []
        _collector.sink = self.entries
        return self.entries

    def __exit__(self, *exc):
        _collector.sink = self._prev
        return False


class AOTCache:
    """Thread-safe LRU map CacheKey -> _Entry (the process-wide instance
    is ``aot.CACHE``). Lookups touch last_used; inserts evict
    least-recently-DISPATCHED entries past MXTPU_AOT_CACHE_SIZE and count
    each eviction."""

    def __init__(self):
        self._lock = threading.RLock()
        self._entries = {}
        self._building = {}   # key -> Event (single-flight build guard)

    # ------------------------------------------------------------------
    def lookup(self, key):
        """Hit -> entry (last_used touched, hit counted); miss -> None."""
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                entry.last_used = _time.monotonic()
        if entry is not None:
            _HITS.inc(kind=key.kind)
        return entry

    def peek(self, key):
        """lookup() without touching LRU order or counters (tests,
        inspection)."""
        with self._lock:
            return self._entries.get(key)

    def insert(self, key, fn, extras=None, source="build", stats=None):
        if stats is None:
            # device truth is harvested HERE, once per cache entry — the
            # one place every executable (train/eval/serve, build or
            # artifact) passes through on its way to a dispatch
            # (a span of its own: inside train:build it is what the
            # trace / lower / compile children leave over)
            with spans.span("aot:analyze", kind=key.kind):
                stats = devstats.program_stats(fn)
        entry = _Entry(key, fn, extras, source, stats)
        with self._lock:
            self._entries[key] = entry
            self._evict_locked()
            _ENTRIES.set(len(self._entries))
            # publish INSIDE the lock: outside it, a concurrent
            # clear()/discard() could unpublish first and this late
            # publish would resurrect a series with no backing entry
            # (lock order cache->gauge matches _unpublish_locked)
            if stats:
                _publish_program_stats(key, stats)
        sink = getattr(_collector, "sink", None)
        if sink is not None:
            sink.append(entry)
        return entry

    def _evict_locked(self):
        bound = max(1, config.get_env("MXTPU_AOT_CACHE_SIZE"))
        while len(self._entries) > bound:
            victim = min(self._entries.values(),
                         key=lambda e: e.last_used)
            self._entries.pop(victim.key)
            _EVICTIONS.inc(kind=victim.key.kind)
            self._unpublish_locked(victim.key)

    def _unpublish_locked(self, key):
        """Drop the departed entry's program-stats gauge series — a dead
        program must not export frozen FLOPs forever (same discipline as
        serving's detach_telemetry). Several entries can share one
        (model, kind, bucket) label set (per-replica device pins, dtype
        variants): when a live entry still maps onto it, the gauges are
        RE-published from that survivor's stats (the departed entry may
        have published last, and the label must describe a program that
        is actually in the cache). Caller holds self._lock."""
        label = (key.model_id, key.kind, _bucket_of(key))
        for other_key, other in self._entries.items():
            if (other_key.model_id, other_key.kind,
                    _bucket_of(other_key)) == label and other.stats:
                _publish_program_stats(other_key, other.stats)
                return
        try:
            _PROG_FLOPS.remove(model=label[0], kind=label[1],
                               bucket=label[2])
            _PROG_PEAK_BYTES.remove(model=label[0], kind=label[1],
                                    bucket=label[2])
        except Exception:
            _LOG.debug("program stats gauge removal dropped",
                       exc_info=True)

    def discard(self, key):
        with self._lock:
            gone = self._entries.pop(key, None) is not None
            if gone:
                self._unpublish_locked(key)
            _ENTRIES.set(len(self._entries))
        return gone

    def clear(self):
        with self._lock:
            keys = list(self._entries)
            self._entries.clear()
            for key in keys:
                self._unpublish_locked(key)
            _ENTRIES.set(0)

    def __len__(self):
        with self._lock:
            return len(self._entries)

    def keys(self):
        with self._lock:
            return list(self._entries)

    def snapshot(self):
        """JSON-able view (GET /debug/aot): one record per entry, most
        recently dispatched first."""
        now = _time.monotonic()
        with self._lock:
            entries = sorted(self._entries.values(),
                             key=lambda e: -e.last_used)
            return [{"model_id": e.key.model_id, "kind": e.key.kind,
                     "input_sig": [[list(s), d] for s, d in e.key.input_sig],
                     "mesh": e.key.mesh if e.key.mesh is None
                     else list(e.key.mesh),
                     "source": e.source,
                     "stats": dict(e.stats) if e.stats else None,
                     "age_s": round(now - e.created, 3),
                     "idle_s": round(now - e.last_used, 3)}
                    for e in entries]

    # ------------------------------------------------------------------
    def get_or_build(self, key, build, exportable=False, arg_specs=None):
        """Single-flight miss path: at most one thread builds a given key;
        the rest wait on its completion event and then hit. ``build()``
        returns ``(fn, extras, exported_or_None)``; the exported program
        (when present and ``exportable``) is persisted to
        MXTPU_AOT_CACHE_DIR. A persisted artifact, when present, is
        loaded INSTEAD of calling build() — no Python tracing."""
        while True:
            entry = self.lookup(key)
            if entry is not None:
                return entry
            with self._lock:
                entry = self._entries.get(key)
                if entry is not None:
                    entry.last_used = _time.monotonic()
                    _HITS.inc(kind=key.kind)
                    return entry
                event = self._building.get(key)
                if event is None:
                    event = self._building[key] = threading.Event()
                    builder = True
                else:
                    builder = False
            if not builder:
                # another thread owns the build — wait, then re-lookup
                # (bounded so a crashed builder cannot strand waiters)
                event.wait(timeout=600.0)
                continue
            try:
                _MISSES.inc(kind=key.kind)
                if exportable:
                    loaded = _load_artifact(key, arg_specs)
                    if loaded is not None:
                        fn, stats = loaded
                        _ARTIFACT_HITS.inc(kind=key.kind)
                        # header stats win (they survive even when the
                        # loaded module was not XLA-compiled yet); insert
                        # re-analyzes only when the header carried none
                        return self.insert(key, fn, source="artifact",
                                           stats=stats)
                fn, extras, exported = build()
                entry = self.insert(key, fn, extras, source="build")
                if exportable and exported is not None:
                    _write_artifact(key, exported, stats=entry.stats)
                return entry
            finally:
                with self._lock:
                    self._building.pop(key, None)
                event.set()


CACHE = AOTCache()


def compile_cached(key, build, exportable=False, arg_specs=None):
    """THE module entry point every hot path dispatches through (jit.py
    TrainStep/EvalStep, contrib.serving.ServedModel, serving prewarm).
    ``build()`` is traced/compiled on a miss — the same retrace-hazard
    surface as a direct ``jax.jit`` call site (mxtpulint R011 models this
    boundary). Returns the cache entry (``entry.fn`` is the compiled
    program, ``entry.source`` says whether it came from a build or a
    persisted artifact)."""
    return CACHE.get_or_build(key, build, exportable=exportable,
                              arg_specs=arg_specs)


def _bucket_of(key):
    """Batch-bucket label for the program gauges: dim 0 of the first
    input (the batcher's bucket axis), '-' for rank-0/inputless keys."""
    try:
        return int(key.input_sig[0][0][0])
    except Exception:
        return "-"


def _publish_program_stats(key, stats):
    """Mirror one entry's device truth onto the program gauges. Guarded:
    a telemetry failure must not fail the build/load that produced the
    executable."""
    try:
        bucket = _bucket_of(key)
        _PROG_FLOPS.set(stats.get("flops", 0.0), model=key.model_id,
                        kind=key.kind, bucket=bucket)
        _PROG_PEAK_BYTES.set(stats.get("peak_bytes", 0.0),
                             model=key.model_id, kind=key.kind,
                             bucket=bucket)
    except Exception:
        _LOG.debug("program stats gauge update dropped", exc_info=True)


# --------------------------------------------------------------------------
# Persistent artifact layer (MXTPU_AOT_CACHE_DIR)
def _key_digest(key):
    raw = repr((FORMAT_VERSION, key.model_id, key.kind, key.input_sig,
                key.mesh, key.extra))
    return hashlib.sha256(raw.encode()).hexdigest()[:32]


def artifact_path(key, cache_dir=None):
    """Artifact file for a key, or None when the layer is disabled
    (no MXTPU_AOT_CACHE_DIR) or the key is not persistable (train
    programs stay in-memory).

    Mesh-sharded eval/serve programs ARE persisted: jax.export records
    the partitioned module (GSPMD shardings included), and the key's
    ``mesh`` signature — axis layout + device count — participates in the
    file digest, so a process with a different topology can never load a
    mismatched partitioning (it misses and rebuilds). This is the
    sharded-serving counterpart of the single-device zero-retrace
    cold start (docs/AOT.md "Sharded artifacts")."""
    if cache_dir is None:
        cache_dir = config.get_env("MXTPU_AOT_CACHE_DIR")
    # train programs are NEVER persisted (donated buffers + instance-bound
    # state) — enforced here, not just at today's call sites
    if not cache_dir or key.kind == "train":
        return None
    import jax
    return os.path.join(cache_dir, "jax-%s" % jax.__version__,
                        "%s-%s.mxtpu-aot" % (key.kind, _key_digest(key)))


def _pack_header(stats):
    """v2 header: 4-byte big-endian length + JSON metadata. The metadata
    carries the program's device truth so a fresh process's artifact load
    never needs to re-run XLA analysis to know its FLOPs."""
    meta = _json.dumps({"format": FORMAT_VERSION,
                        "stats": stats if stats else None},
                       sort_keys=True).encode("utf-8")
    return struct.pack(">I", len(meta)) + meta


def _unpack_header(buf):
    """(stats_or_None, payload_offset) for a v2 body (magic stripped).
    Raises on truncation/garbage — the caller treats that as a corrupt
    artifact and rebuilds."""
    if len(buf) < 4:
        raise ValueError("truncated artifact header")
    (n,) = struct.unpack(">I", buf[:4])
    if n > len(buf) - 4:
        raise ValueError("artifact header length %d overruns file" % n)
    meta = _json.loads(buf[4:4 + n].decode("utf-8"))
    stats = meta.get("stats") if isinstance(meta, dict) else None
    if stats is not None and not isinstance(stats, dict):
        stats = None
    return stats, 4 + n


def _load_artifact(key, arg_specs):
    """Deserialize the persisted StableHLO for ``key`` and AOT-compile it
    (``aot:load`` span). Returns ``(compiled, stats)`` — the header's
    device truth rides along — or None (missing / corrupt / wrong-version
    magic / unloadable: the caller falls back to a fresh build WITH
    re-analysis; the drop is debug-logged, never raised into a hot
    path)."""
    path = artifact_path(key)
    if path is None or not os.path.exists(path):
        return None
    try:
        # faultlab site "aot.artifact_read": artifact_corrupt injects an
        # unreadable artifact (identical to the real corrupt path — the
        # caller rebuilds with re-analysis); exception-kind lands in the
        # except-all below, exercising the same fallback
        if faultlab.armed and faultlab.fire(
                "aot.artifact_read", kind=key.kind,
                model_id=key.model_id) == "artifact_corrupt":
            _LOG.debug("aot artifact read for %s: injected corrupt", path)
            return None
        import jax
        import jax.export  # jax>=0.4.30 does not re-export lazily
        with open(path, "rb") as f:
            buf = f.read()
        if not buf.startswith(ARTIFACT_MAGIC):
            # wrong magic OR an old format version (the version byte is
            # part of the magic): rebuild + re-analyze, never misparse
            raise ValueError("bad magic/version in %s" % path)
        stats, off = _unpack_header(buf[len(ARTIFACT_MAGIC):])
        with spans.span("aot:load", kind=key.kind,
                        model_id=key.model_id):
            exported = jax.export.deserialize(
                buf[len(ARTIFACT_MAGIC) + off:])
            fn = jax.jit(exported.call)
            if arg_specs is not None:
                # explicit AOT: XLA-compile the loaded module NOW (inside
                # the aot:load span / prewarm window) — never lazily
                # inside a later dispatch
                fn = fn.lower(*arg_specs).compile()
        return fn, stats
    except Exception:
        _LOG.debug("aot artifact load failed for %s", path, exc_info=True)
        return None


def _write_artifact(key, exported, stats=None):
    """Persist a jax.export program atomically (tmp + rename; pid+tid in
    the tmp name so concurrent writers never interleave), with the
    program's device truth in the v2 header. Failures are debug-logged
    and swallowed — a full disk must not fail the dispatch that just
    compiled successfully."""
    path = artifact_path(key)
    if path is None:
        return None
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = "%s.%d.%d.tmp" % (path, os.getpid(), threading.get_ident())
        with open(tmp, "wb") as f:
            f.write(ARTIFACT_MAGIC + _pack_header(stats)
                    + exported.serialize())
        os.replace(tmp, path)
        _ARTIFACT_WRITES.inc(kind=key.kind)
        return path
    except Exception:
        _LOG.debug("aot artifact write failed for %s", path, exc_info=True)
        return None


# --------------------------------------------------------------------------
# Per-program fact digests (the hlodiff contract)
#
# ``program_digest`` is the stable identity of one artifact's BYTES (magic
# + header + payload): two byte-identical deploys share it, so the
# differential analyzer (tools/hlodiff) can prove "empty diff" without
# walking either module. ``facts_for_key`` resolves a cache key to the
# persisted artifact's header facts + digest WITHOUT deserializing the
# payload — the differ and any future planner cost model read device
# truth from here instead of re-deriving the header parsing.

#: (path, digest, stats): one persisted program's identity + header
#: device truth. ``digest`` is program_digest of the file bytes; ``stats``
#: is the v2 header dict ({flops, bytes_accessed, peak_bytes,
#: output_bytes}) or None for statless artifacts.
ProgramFactsRef = namedtuple("ProgramFactsRef", ("path", "digest", "stats"))

_FACTS_MEMO = {}                  # path -> (mtime_ns, size, ProgramFactsRef)
_FACTS_MEMO_LOCK = threading.Lock()
_FACTS_MEMO_MAX = 512


def program_digest(buf):
    """Stable digest of one artifact's full bytes — the same 32-hex-char
    width as the cache-key digest in the filename, but content-addressed:
    it changes iff the deployed bytes change."""
    return hashlib.sha256(bytes(buf)).hexdigest()[:32]


def facts_for_key(key, cache_dir=None):
    """Header facts for the persisted artifact of ``key`` ->
    ``ProgramFactsRef(path, digest, stats)``, or None when the key has no
    readable artifact (train kind, disabled layer, missing/corrupt file).
    Reads magic + header only — never the jax.export payload — and memos
    per (path, mtime, size), so a gate that re-checks the routed
    version's facts on every deploy costs one ``stat()``."""
    path = artifact_path(key, cache_dir)
    if path is None:
        return None
    try:
        st = os.stat(path)
    except OSError:
        return None
    with _FACTS_MEMO_LOCK:
        memo = _FACTS_MEMO.get(path)
        if memo is not None and memo[0] == st.st_mtime_ns \
                and memo[1] == st.st_size:
            return memo[2]
    try:
        with open(path, "rb") as f:
            buf = f.read()
        if not buf.startswith(ARTIFACT_MAGIC):
            return None
        stats, _off = _unpack_header(buf[len(ARTIFACT_MAGIC):])
    except Exception:
        _LOG.debug("aot facts_for_key failed for %s", path, exc_info=True)
        return None
    ref = ProgramFactsRef(path, program_digest(buf), stats)
    with _FACTS_MEMO_LOCK:
        if len(_FACTS_MEMO) >= _FACTS_MEMO_MAX:
            _FACTS_MEMO.clear()
        _FACTS_MEMO[path] = (st.st_mtime_ns, st.st_size, ref)
    return ref
