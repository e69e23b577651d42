"""Device-truth observability: XLA cost/memory analysis, live HBM gauges,
and per-dispatch MFU attribution.

Every signal the stack exported before this module was host wall-clock —
good enough to say a step got slower, useless to say WHY. Production
frameworks judge runs by achieved utilization against hardware peaks
(TensorFlow, arXiv 1605.08695; MLPerf TPU-pod scaling, arXiv 1909.09756),
and the attribution chain needs device facts at three timescales:

- **Per program** (``program_stats``): at AOT build/load time the compiled
  executable's ``cost_analysis()`` + ``memory_analysis()`` are harvested
  ONCE into ``{flops, bytes_accessed, peak_bytes, output_bytes}`` and
  stored on the aot.CACHE entry (and in the persisted artifact header, so
  a zero-compile artifact load in a fresh process still has them —
  docs/AOT.md). Exposed as ``mxtpu_aot_program_flops`` /
  ``mxtpu_aot_program_peak_bytes{model,kind,bucket}`` and on
  ``GET /debug/aot``. Harvesting per DISPATCH instead would put an XLA
  analysis walk into the hot path — mxtpulint R001 models exactly that
  defect.
- **Per dispatch** (``observe_dispatch``): the hot paths (TrainStep,
  EvalStep, ServedModel / MeshServable under the batcher) divide the
  entry's FLOPs by the measured block-until-ready dispatch span, driving
  rolling ``mxtpu_device_mfu{model,kind,replica}`` and
  ``mxtpu_device_hbm_bw_util{model,kind,replica}`` gauges against the
  per-backend peak table, plus ``mxtpu_device_flops_total`` /
  ``mxtpu_device_bytes_accessed_total`` /
  ``mxtpu_device_dispatch_seconds_total`` counters so a scrape WINDOW
  (a loadgen stage, a CI soak) can compute its own achieved utilization
  from deltas. Whether a step is compute-bound (MFU high), HBM-bound
  (bw_util high, MFU low) or host-overhead-bound (both low while
  wall-clock is busy) is now a scrape, not a guess.
- **Continuous** (the HBM sampler): a watchdog-style daemon polls
  ``device.memory_stats()`` into ``mxtpu_device_memory_bytes{device,stat}``
  and files a flight-recorder ``hbm_pressure`` event once per episode
  when a device crosses 90% of its memory limit. Backends whose PJRT
  client reports no memory stats (CPU) degrade to host-RSS report-only
  samples under ``device="host"`` so the series never silently vanishes.

Peaks come from ``MXTPU_DEVICE_PEAK_FLOPS`` / ``MXTPU_DEVICE_PEAK_HBM_BPS``
when set, else a built-in table keyed on ``jax.devices()[0].device_kind``;
unknown kinds (CPU) fall back to nominal constants and the utilization
numbers become report-only ratios (internally consistent, not meaningful
against real hardware — ``peaks()[2]`` says which).

``capture_profile(seconds)`` is the on-demand ``jax.profiler`` capture
behind ``GET /debug/profile?seconds=N``: single-flight (concurrent
captures get ``ProfileCaptureBusy`` → HTTP 409), bounded output dir
(``MXTPU_PROFILE_KEEP`` newest captures survive).

See docs/OBSERVABILITY.md "Device truth".
"""
from __future__ import annotations

import contextlib
import itertools
import logging
import os
import shutil
import tempfile
import threading
import time as _time

from . import flightrec
from . import watchdog
from .registry import counter, gauge

__all__ = ["program_stats", "peaks", "observe_dispatch", "dispatch_context",
           "start", "stop", "running", "sample_now", "device_memory",
           "set_memory_source", "capture_profile", "ProfileCaptureBusy",
           "capture_in_progress", "dispatch_totals",
           "PEAK_TABLE", "device_peaks", "reset_peaks", "HBM_TABLE",
           "hbm_capacity"]

_LOG = logging.getLogger(__name__)

#: THE peak table: device_kind prefix -> (peak dense bf16 FLOP/s, peak
#: int8 OP/s, peak HBM bytes/s) per chip. Source: Google Cloud TPU
#: documentation, system-architecture pages per generation ("TPU v5e":
#: 197 TFLOP/s bf16, 393 TOP/s int8, 819 GB/s). Every rate the repo
#: prints against a hardware peak (chip_smoke.py, the MFU gauges)
#: divides by a row of this
#: table; a device_kind with no row is an error for those callers
#: (device_peaks), never a default.
PEAK_TABLE = {
    "TPU v5 lite": (197e12, 393e12, 819e9),
    "TPU v5e": (197e12, 393e12, 819e9),
    "TPU v4": (275e12, 275e12, 1228e9),
    "TPU v5p": (459e12, 918e12, 2765e9),
    "TPU v5": (459e12, 918e12, 2765e9),
    "TPU v6 lite": (918e12, 1836e12, 1640e9),
    "TPU v6e": (918e12, 1836e12, 1640e9),
}

#: report-only stand-in (bf16 FLOP/s, HBM bytes/s) for backends with no
#: PEAK_TABLE row (the CPU test backend): utilization gauges stay live
#: and internally consistent but are NOT meaningful against hardware
#: peaks (peaks()[2] == "fallback"); nothing prints a device rate from it
_FALLBACK_PEAKS = (1e12, 100e9)

#: device_kind prefix -> per-chip HBM CAPACITY in bytes (spec sheets —
#: the capacity companion of PEAK_TABLE's rate numbers). Consumed by the
#: hlolint H004 gate: an artifact whose header peak_bytes exceeds this
#: is rejected before deploy instead of OOMing after cutover. No
#: fallback entry on purpose — predicting an OOM against a made-up
#: capacity would reject valid programs, so unknown kinds (CPU) return
#: None and the H004 rule skips (MXTPU_HLOLINT_HBM_BUDGET overrides).
HBM_TABLE = {
    "TPU v4i": 8e9,
    "TPU v5 lite": 16e9,
    "TPU v5e": 16e9,
    "TPU v4": 32e9,
    "TPU v5p": 95e9,
    "TPU v5": 95e9,
    "TPU v6 lite": 32e9,
    "TPU v6e": 32e9,
}


def hbm_capacity():
    """(per-chip HBM bytes, source) for this process's backend: the
    HBM_TABLE entry keyed on ``jax.devices()[0].device_kind`` (source
    'table'), or (None, 'unknown') for backends the table doesn't know —
    callers that would otherwise guess (hlolint H004) must skip
    instead."""
    row = _table_row(HBM_TABLE, _device_kind())
    if row is None:
        return None, "unknown"
    return float(row), "table"


def _device_kind():
    """``device_kind`` of this process's first device ('' when no
    backend can be initialised)."""
    try:
        import jax
        return jax.devices()[0].device_kind or ""
    except RuntimeError:
        return ""


def _table_row(table, kind):
    """The ``table`` row whose device_kind prefix matches ``kind``, or
    None. Longest prefix wins, so e.g. a v5e chip can never fall through
    to the broader "TPU v5" entry regardless of table ordering — and a
    prefix hit only counts at a word boundary: an unlisted sub-variant
    ("TPU v7x") must come back unknown, never inherit a sibling's row
    (H004 would wave a predicted OOM through; an MFU would divide by the
    wrong peak)."""
    for prefix in sorted(table, key=len, reverse=True):
        if kind == prefix or (kind.startswith(prefix)
                              and not kind[len(prefix)].isalnum()):
            return table[prefix]
    return None


def device_peaks(kind):
    """(peak bf16 FLOP/s, peak int8 OP/s, peak HBM bytes/s) of one chip
    of ``kind`` from PEAK_TABLE. Raises LookupError for a kind with no
    row: whoever prints a rate against a peak (chip_smoke.py)
    must fail on an unknown device, not assume one."""
    row = _table_row(PEAK_TABLE, kind)
    if row is None:
        raise LookupError(
            "device_kind %r has no row in devstats.PEAK_TABLE (known: %s) "
            "— add its published peaks with their source before "
            "reporting a utilization on it" % (kind, sorted(PEAK_TABLE)))
    return row


# --------------------------------------------------------------- program facts
def program_stats(compiled):
    """Harvest ``{flops, bytes_accessed, peak_bytes, output_bytes}`` from a
    compiled executable's XLA ``cost_analysis()`` + ``memory_analysis()``.

    Returns None when the object is not an analyzable compiled program
    (a lazily-jitted wrapper, a plain python callable) or when both
    analyses come back empty — callers store the result on the AOT cache
    entry at build/load time; NEVER call this per dispatch (mxtpulint
    R001 flags analysis calls in hot paths).
    """
    if not hasattr(compiled, "cost_analysis"):
        return None
    flops = bytes_accessed = 0.0
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if isinstance(ca, dict):
            flops = float(ca.get("flops") or 0.0)
            bytes_accessed = float(ca.get("bytes accessed") or 0.0)
    except Exception:
        _LOG.debug("cost_analysis failed", exc_info=True)
    peak_bytes = output_bytes = 0.0
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            output_bytes = float(getattr(ma, "output_size_in_bytes", 0) or 0)
            # peak live footprint of one execution: arguments + outputs +
            # compiler temp buffers, minus donated/aliased input bytes
            # (those are reused, not additional)
            peak_bytes = (
                float(getattr(ma, "argument_size_in_bytes", 0) or 0)
                + output_bytes
                + float(getattr(ma, "temp_size_in_bytes", 0) or 0)
                - float(getattr(ma, "alias_size_in_bytes", 0) or 0))
    except Exception:
        _LOG.debug("memory_analysis failed", exc_info=True)
    if flops <= 0.0 and bytes_accessed <= 0.0 and peak_bytes <= 0.0:
        return None
    return {"flops": flops, "bytes_accessed": bytes_accessed,
            "peak_bytes": max(0.0, peak_bytes),
            "output_bytes": output_bytes}


# ------------------------------------------------------------------ peak table
_peaks_lock = threading.Lock()
_peaks = None            # (flops_per_s, hbm_bytes_per_s, source)

_PEAK_FLOPS_G = gauge(
    "mxtpu_device_peak_flops",
    "Per-chip peak FLOP/s the MFU gauges divide by (MXTPU_DEVICE_PEAK_"
    "FLOPS override, else the built-in table keyed on device_kind, else "
    "a report-only fallback — docs/OBSERVABILITY.md 'Device truth').")
_PEAK_BW_G = gauge(
    "mxtpu_device_peak_hbm_bps",
    "Per-chip peak HBM bytes/s the bandwidth-utilization gauges divide "
    "by (MXTPU_DEVICE_PEAK_HBM_BPS override, else the device_kind "
    "table, else a report-only fallback).")


def peaks():
    """(peak_flops_per_s, peak_hbm_bytes_per_s, source) for this process's
    backend; source is 'env' | 'table' | 'fallback'. Resolved once and
    published on the mxtpu_device_peak_* gauges."""
    global _peaks
    if _peaks is not None:
        return _peaks
    with _peaks_lock:
        if _peaks is not None:
            return _peaks
        from .. import config
        env_f = config.get_env("MXTPU_DEVICE_PEAK_FLOPS")
        env_b = config.get_env("MXTPU_DEVICE_PEAK_HBM_BPS")
        row = _table_row(PEAK_TABLE, _device_kind())
        if row is not None:
            flops_p, _int8, bw_p = row
        else:
            flops_p, bw_p = _FALLBACK_PEAKS
        base = "table" if row is not None else "fallback"
        if env_f is not None and env_b is not None:
            source = "env"
        elif env_f is not None or env_b is not None:
            # only ONE peak overridden: the other is still `base` — the
            # composite source keeps "fallback" visible so a consumer
            # checking for report-only mode is not lied to
            source = "env+" + base
        else:
            source = base
        if env_f is not None:
            flops_p = float(env_f)
        if env_b is not None:
            bw_p = float(env_b)
        flops_p = max(1.0, float(flops_p))
        bw_p = max(1.0, float(bw_p))
        _PEAK_FLOPS_G.set(flops_p)
        _PEAK_BW_G.set(bw_p)
        _peaks = (flops_p, bw_p, source)
        return _peaks


def reset_peaks():
    """Forget the resolved peaks (tests changing MXTPU_DEVICE_PEAK_*)."""
    global _peaks
    with _peaks_lock:
        _peaks = None


# ------------------------------------------------------- per-dispatch rolling
_MFU = gauge(
    "mxtpu_device_mfu",
    "Rolling (EMA) model-FLOPs utilization per dispatch: the cached "
    "program's cost_analysis FLOPs over the measured block-until-ready "
    "dispatch span, against mxtpu_device_peak_flops. Labels: serving "
    "model (or model digest outside serving), entry kind "
    "(train|eval|serve), data-parallel replica.",
    ("model", "kind", "replica"))
_BW_UTIL = gauge(
    "mxtpu_device_hbm_bw_util",
    "Rolling (EMA) HBM bandwidth utilization per dispatch: the program's "
    "cost_analysis bytes-accessed over the dispatch span, against "
    "mxtpu_device_peak_hbm_bps. High here with low mxtpu_device_mfu "
    "means the program is memory-bound, not compute-bound.",
    ("model", "kind", "replica"))
_FLOPS_TOTAL = counter(
    "mxtpu_device_flops_total",
    "Cost-analysis FLOPs dispatched (sum over instrumented dispatches). "
    "delta(this)/window/mxtpu_device_peak_flops is a scrape window's "
    "achieved MFU — what loadgen stage reports and the devstats CI soak "
    "compute.", ("model", "kind"))
_BYTES_TOTAL = counter(
    "mxtpu_device_bytes_accessed_total",
    "Cost-analysis HBM bytes accessed by instrumented dispatches "
    "(window deltas give achieved bandwidth).", ("model", "kind"))
_DISPATCH_SECONDS = counter(
    "mxtpu_device_dispatch_seconds_total",
    "Measured (block-until-ready) device dispatch seconds — the device "
    "leg of a scrape window, to set against wall-clock for host-overhead "
    "attribution.", ("model", "kind"))
_CHIP_SECONDS = counter(
    "mxtpu_device_chip_seconds_total",
    "Dispatch seconds x participating chips (a K-chip tensor-parallel "
    "program burns K chip-seconds per wall second). "
    "delta(mxtpu_device_flops_total) / delta(this) / "
    "mxtpu_device_peak_flops is a scrape window's achieved PER-CHIP MFU "
    "while executing — exact under any replica/tp topology, which a "
    "wall-window division is not.", ("model", "kind"))

#: EMA smoothing for the rolling gauges: ~last 10 dispatches dominate
_EMA_ALPHA = 0.2
_ema_lock = threading.Lock()
_ema = {}                # (model, kind, replica) -> [mfu, bw]

_ctx = threading.local()


class dispatch_context:
    """Thread-scoped serving context: the batcher worker wraps its
    servable call in ``dispatch_context(model, replica)`` so the MFU
    observation — which happens levels deeper, where the compiled entry
    and its FLOPs are known (EvalStep, ServedModel._run) — is labeled
    with the serving model name and replica index instead of a digest."""

    def __init__(self, model, replica):
        self.model = model
        self.replica = replica

    def __enter__(self):
        self._saved = getattr(_ctx, "value", None)
        _ctx.value = (self.model, self.replica)
        return self

    def __exit__(self, *exc):
        _ctx.value = self._saved


def detach_model(model):
    """Drop one model's rolling per-dispatch gauge series (mxtpu_device_
    mfu / _hbm_bw_util) and their EMA state — the batcher close/unload
    hook, mirroring ServingMetrics.detach_telemetry: a dead model must
    not export its last MFU forever, and hot-reload churn must not grow
    the EMA map without bound. The *_total counters stay (process-
    lifetime cumulative by Prometheus convention)."""
    model = str(model)
    with _ema_lock:
        keys = [k for k in _ema if k[0] == model]
        for k in keys:
            _ema.pop(k, None)
    for m, kind, replica in keys:
        try:
            _MFU.remove(model=m, kind=kind, replica=replica)
            _BW_UTIL.remove(model=m, kind=kind, replica=replica)
        except Exception:
            _LOG.debug("mfu gauge detach failed", exc_info=True)


def in_dispatch_context():
    """True on a batcher worker thread inside dispatch_context — the
    serving path, where a block-until-ready observation moves cost
    instead of adding any (jit.EvalStep gates its sync on this)."""
    return getattr(_ctx, "value", None) is not None


def observe_dispatch(kind, stats, dur_s, model=None, replica=None,
                     devices=1):
    """Record one measured dispatch of a program with known ``stats``
    (the aot.CACHE entry's program_stats dict). ``dur_s`` is the
    block-until-ready span the caller measured; ``devices`` is how many
    chips executed the program (a tensor-parallel group passes its mesh
    size — the program's cost-analysis FLOPs are spread over all of
    them, so dividing by ONE chip's peak would overstate MFU by the
    group size). An ambient dispatch_context (the batcher worker's
    serving model name) WINS over the caller's ``model`` — the caller
    passes its model digest as the fallback label for dispatches outside
    serving. Never raises into the hot path; a dropped observation is
    debug-logged (R005 discipline)."""
    if not stats or dur_s <= 0.0:
        return
    try:
        ctx = getattr(_ctx, "value", None)
        if ctx is not None:
            model = ctx[0]
            if replica is None:
                replica = ctx[1]
        model = str(model if model is not None else "-")
        replica = int(replica or 0)
        devices = max(1, int(devices))
        flops_p, bw_p, _src = peaks()
        flops = float(stats.get("flops") or 0.0)
        nbytes = float(stats.get("bytes_accessed") or 0.0)
        mfu = flops / dur_s / (flops_p * devices)
        bw = nbytes / dur_s / (bw_p * devices)
        key = (model, str(kind), replica)
        with _ema_lock:
            cur = _ema.get(key)
            if cur is None:
                cur = _ema[key] = [mfu, bw]
            else:
                cur[0] += _EMA_ALPHA * (mfu - cur[0])
                cur[1] += _EMA_ALPHA * (bw - cur[1])
            mfu_s, bw_s = cur
        _MFU.set(mfu_s, model=model, kind=kind, replica=replica)
        _BW_UTIL.set(bw_s, model=model, kind=kind, replica=replica)
        _FLOPS_TOTAL.inc(flops, model=model, kind=kind)
        _BYTES_TOTAL.inc(nbytes, model=model, kind=kind)
        _DISPATCH_SECONDS.inc(dur_s, model=model, kind=kind)
        _CHIP_SECONDS.inc(dur_s * devices, model=model, kind=kind)
    except Exception:
        _LOG.debug("devstats dispatch observation dropped", exc_info=True)


# ------------------------------------------------------------- HBM sampler
_MEMORY_BYTES = gauge(
    "mxtpu_device_memory_bytes",
    "Live device memory sampled by the devstats daemon from PJRT "
    "device.memory_stats() (stats: bytes_in_use, peak_bytes_in_use, "
    "bytes_limit). Backends reporting no memory stats (CPU) degrade to "
    "host-RSS report-only samples under device='host' (stats: rss_bytes, "
    "peak_rss_bytes). >90% of bytes_limit files a flightrec "
    "hbm_pressure event once per episode.", ("device", "stat"))

_MEM_STATS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
#: pressure episode hysteresis: fire at >90% of bytes_limit, re-arm <85%
_PRESSURE_HIGH = 0.90
_PRESSURE_LOW = 0.85

_mem_lock = threading.Lock()
_mem_source = None       # injectable: fn() -> {device: {stat: bytes}}
_last_snapshot = {}
_published_series = set()          # (device, stat) pairs set on the gauge
_pressured = set()                 # devices currently in a pressure episode
#: gauge publishing happens ONLY between start() and stop() (guarded by
#: _mem_lock): a passive device_memory()/profiler read after stop() must
#: not resurrect mxtpu_device_memory_bytes series nobody will ever
#: refresh or detach again
_session_active = False
_sampler_lock = threading.Lock()   # sampler lifecycle
_sampler_thread = None
_sampler_stop = None
_HB_CHANNEL = "devstats"


def set_memory_source(fn):
    """Override where memory samples come from: ``fn() -> {device_name:
    {stat_name: bytes}}`` (tests; backends with out-of-band memory
    telemetry). None restores the PJRT default."""
    global _mem_source
    with _mem_lock:
        _mem_source = fn


def _host_rss():
    """Report-only host fallback so the memory series never silently
    vanishes on backends whose PJRT client reports nothing (CPU)."""
    import sys
    out = {}
    try:
        import resource
        peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        # ru_maxrss unit is platform-defined: kilobytes on Linux/BSD,
        # BYTES on macOS — scaling unconditionally would report 1024x
        out["peak_rss_bytes"] = int(peak) * (
            1 if sys.platform == "darwin" else 1024)
    except Exception:
        pass
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        out["rss_bytes"] = pages * os.sysconf("SC_PAGE_SIZE")
    except Exception:
        if "peak_rss_bytes" in out:
            out["rss_bytes"] = out["peak_rss_bytes"]
    return {"host": out} if out else {}


def _collect():
    with _mem_lock:
        src = _mem_source
    if src is not None:
        try:
            snap = src() or {}
            return {str(d): {str(k): int(v) for k, v in s.items()}
                    for d, s in snap.items()}
        except Exception:
            _LOG.debug("injected memory source failed", exc_info=True)
            return {}
    out = {}
    try:
        import jax
        for d in jax.local_devices():
            try:
                s = d.memory_stats() or {}
            except Exception:
                s = {}
            entry = {k: int(s[k]) for k in _MEM_STATS if k in s}
            if entry:
                out[str(d)] = entry
    except Exception:
        _LOG.debug("device memory sample failed", exc_info=True)
    if not out:
        out = _host_rss()
    return out


def sample_now():
    """One sampler tick, callable without the daemon: poll the memory
    source live, run the pressure check, and return the
    {device: {stat: bytes}} snapshot. The mxtpu_device_memory_bytes
    gauges are published only while a sampler session is active (between
    start() and stop()) — a passive read outside it must not leave
    frozen series on the exposition."""
    global _last_snapshot
    snap = _collect()
    with _mem_lock:
        publish = _session_active
    for dev, stats in snap.items():
        if publish:
            for stat, val in stats.items():
                try:
                    with _mem_lock:
                        # re-check under the lock: a concurrent stop()
                        # must not race a publish past its detach sweep
                        if _session_active:
                            _MEMORY_BYTES.set(val, device=dev, stat=stat)
                            _published_series.add((dev, stat))
                except Exception:
                    _LOG.debug("memory gauge update dropped",
                               exc_info=True)
        limit = stats.get("bytes_limit")
        used = stats.get("bytes_in_use")
        if limit and used is not None:
            frac = used / float(limit)
            with _mem_lock:
                in_episode = dev in _pressured
                if frac > _PRESSURE_HIGH and not in_episode:
                    _pressured.add(dev)
                    fire = True
                else:
                    fire = False
                    if frac < _PRESSURE_LOW and in_episode:
                        _pressured.discard(dev)
            if fire:
                flightrec.record("hbm_pressure", device=dev,
                                 frac=round(frac, 4), bytes_in_use=used,
                                 bytes_limit=limit)
                _LOG.warning("device %s HBM pressure: %.1f%% of limit "
                             "(%d / %d bytes)", dev, 100 * frac, used,
                             limit)
    with _mem_lock:
        _last_snapshot = snap
    return snap


def device_memory():
    """The newest sampler snapshot (stable keys: bytes_in_use /
    peak_bytes_in_use / bytes_limit per device; rss fallback keys under
    'host'). Samples on demand when the daemon is not running, but keeps
    serving the last-known snapshot if a live sample fails — this is the
    delegate behind profiler.device_memory()."""
    if not running():
        try:
            return sample_now()
        except Exception:
            _LOG.debug("on-demand memory sample failed", exc_info=True)
    with _mem_lock:
        return {d: dict(s) for d, s in _last_snapshot.items()}


def _poll(stop, poll_s):
    while not stop.wait(poll_s):
        watchdog.heartbeat(_HB_CHANNEL)
        try:
            sample_now()
        except Exception:
            # the sampler must outlive whatever it samples; the skipped
            # tick stays debug-visible (R005)
            _LOG.debug("devstats sampler tick failed", exc_info=True)


def start(poll_s=None):
    """Start (or restart with new settings) the HBM sampler daemon.
    Heartbeat-registered on the 'devstats' watchdog channel; autostarted
    at package import when MXTPU_DEVSTATS=1. Returns the thread."""
    from .. import config
    global _sampler_thread, _sampler_stop
    if poll_s is None:
        poll_s = config.get_env("MXTPU_DEVSTATS_POLL_S")
    poll_s = max(0.01, float(poll_s))
    global _session_active
    with _sampler_lock:
        _stop_locked()
        watchdog.register(_HB_CHANNEL, quiet_s=max(60.0, poll_s * 10))
        with _mem_lock:
            _session_active = True
        # first sample SYNCHRONOUSLY, before the daemon exists: a
        # device_memory() call right after start() must see a live
        # snapshot, not an empty one that only fills after the first
        # poll tick
        try:
            sample_now()
        except Exception:
            _LOG.debug("initial devstats sample failed", exc_info=True)
        stop_ev = threading.Event()
        t = threading.Thread(target=_poll, args=(stop_ev, poll_s),
                             daemon=True, name="mxtpu-devstats")
        _sampler_stop, _sampler_thread = stop_ev, t
        t.start()
    return t


def _stop_locked():
    """Signal + join the sampler and DETACH its state: the heartbeat
    channel is unregistered (silence from a stopped sampler is not a
    stall) and every memory series it published is removed (a stopped
    sampler must not export frozen bytes forever). Caller holds
    _sampler_lock."""
    global _sampler_thread, _sampler_stop, _session_active
    stop_ev, t = _sampler_stop, _sampler_thread
    _sampler_stop = _sampler_thread = None
    if stop_ev is not None:
        stop_ev.set()
        if t is not None:
            t.join(timeout=5.0)
        watchdog.unregister(_HB_CHANNEL)
        # end the session BEFORE the detach sweep: any sample racing the
        # stop re-checks _session_active under _mem_lock and cannot
        # publish after (and so escape) the sweep
        with _mem_lock:
            _session_active = False
            series = list(_published_series)
            _published_series.clear()
        for dev, stat in series:
            try:
                _MEMORY_BYTES.remove(device=dev, stat=stat)
            except Exception:
                _LOG.debug("memory gauge detach failed", exc_info=True)


def stop():
    with _sampler_lock:
        _stop_locked()


def running():
    t = _sampler_thread
    return t is not None and t.is_alive()


# ----------------------------------------------------------- profile capture
class ProfileCaptureBusy(RuntimeError):
    """A jax.profiler capture is already in flight (HTTP 409)."""


_capture_lock = threading.Lock()
_capture_seq = itertools.count(1)


def _capture_base(out_dir=None):
    from .. import config
    base = out_dir or config.get_env("MXTPU_PROFILE_DIR")
    if not base:
        base = os.path.join(tempfile.gettempdir(), "mxtpu_profile")
    return base


def _prune_mtime(path):
    """Missing-file-tolerant sort key: a capture subdir can be deleted
    (concurrent prune in another process, operator rm) between
    os.listdir and the sort's getmtime — a vanished dir sorts oldest and
    its rmtree below is already an ignore_errors no-op."""
    try:
        return os.path.getmtime(path)
    except OSError:
        return 0.0


def _prune(base, keep):
    """Bound the capture dir: keep the ``keep`` newest capture subdirs."""
    try:
        subdirs = [os.path.join(base, d) for d in os.listdir(base)
                   if d.startswith("capture-")]
        subdirs.sort(key=_prune_mtime)
        for victim in subdirs[:max(0, len(subdirs) - keep)]:
            shutil.rmtree(victim, ignore_errors=True)
    except Exception:
        _LOG.debug("profile dir prune failed", exc_info=True)


@contextlib.contextmanager
def _trace_session(path):
    """One profiler capture into ``path``, python tracer OFF by default.

    The python tracer instruments every interpreter call while tracing
    — measured ~30% on a timer-bound serving request — and that tax
    lands squarely on p99 whenever a capture overlaps traffic (the
    continuous profstats daemon's whole operating mode). The op-level
    attribution layer only reads the XLA TraceMe events (host_tracer),
    which survive with the python tracer off, so off is the default;
    MXTPU_PROFILE_PYTHON_TRACER=1 re-enables python frames for
    interactive debugging."""
    from .. import config
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = (
        1 if config.get_env("MXTPU_PROFILE_PYTHON_TRACER") else 0)
    jax.profiler.start_trace(path, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def capture_profile(seconds=2.0, out_dir=None):
    """On-demand ``jax.profiler`` capture (GET /debug/profile?seconds=N):
    trace into a fresh subdir of MXTPU_PROFILE_DIR for ``seconds``
    (clamped to MXTPU_PROFILE_MAX_S), then prune the dir down to
    MXTPU_PROFILE_KEEP captures. Single-flight: a concurrent call raises
    ProfileCaptureBusy instead of corrupting the in-flight trace (the
    HTTP route maps it to 409)."""
    from .. import config
    if _capture_lock.acquire(blocking=False):
        try:
            max_s = float(config.get_env("MXTPU_PROFILE_MAX_S"))
            seconds = min(max(0.05, float(seconds)), max(0.05, max_s))
            base = _capture_base(out_dir)
            path = os.path.join(base, "capture-%d-%d"
                                % (os.getpid(), next(_capture_seq)))
            os.makedirs(path, exist_ok=True)
            with _trace_session(path):
                _time.sleep(seconds)
            _prune(base, int(config.get_env("MXTPU_PROFILE_KEEP")))
            # capture_id = the subdir basename: stable across _prune (a
            # remembered profstats summary under this id outlives the
            # dir), unique per process+sequence
            return {"dir": path, "seconds": seconds,
                    "capture_id": os.path.basename(path)}
        finally:
            _capture_lock.release()
    raise ProfileCaptureBusy(
        "a profiler capture is already in progress (single-flight: "
        "retry after it finishes)")


def capture_in_progress():
    """True while capture_profile holds the single-flight lock."""
    if _capture_lock.acquire(blocking=False):
        try:
            return False
        finally:
            _capture_lock.release()
    return True


def dispatch_totals():
    """Process-cumulative dispatch facts summed over every (model, kind)
    series — the before/after snapshot pair profstats subtracts to join
    a capture window against device truth: {"flops", "bytes",
    "dispatch_s", "chip_s", "by_model": {model: dispatch_s}}."""
    out = {"flops": 0.0, "bytes": 0.0, "dispatch_s": 0.0, "chip_s": 0.0,
           "by_model": {}}
    for metric, key in ((_FLOPS_TOTAL, "flops"), (_BYTES_TOTAL, "bytes"),
                        (_DISPATCH_SECONDS, "dispatch_s"),
                        (_CHIP_SECONDS, "chip_s")):
        for labels, v in metric.series():
            out[key] += v
            if key == "dispatch_s":
                m = labels.get("model", "-")
                out["by_model"][m] = out["by_model"].get(m, 0.0) + v
    return out
