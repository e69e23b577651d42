"""Unified framework telemetry (the observability surface of ROADMAP's
"serve heavy traffic as fast as the hardware allows" north star).

One process-wide registry of named counters / gauges / fixed-bucket
histograms with labels, exported in Prometheus text format — shared by the
serving stack (serving/metrics.py), the compiled training step (jit.py),
kvstore push/pull, and the data-IO pipeline (io/io.py). Request-scoped
trace IDs ride from the HTTP front-end through the batcher into the
profiler's chrome-trace events (trace.py).

Two consumption paths:

- **Scrape**: the serving server exposes ``GET /metrics`` (Prometheus
  text; the old JSON snapshot moved to ``GET /metrics.json``).
- **Headless flush**: training jobs with no HTTP server run
  ``telemetry.start_periodic_flush()`` (or set
  ``MXTPU_TELEMETRY_FLUSH_S > 0`` to autostart at import) and the
  registry is written atomically to ``MXTPU_TELEMETRY_FILE`` every
  interval — node-exporter textfile-collector compatible.

Metric naming scheme (docs/OBSERVABILITY.md): ``mxtpu_<subsystem>_<what>
[_total|_seconds|_bytes]``, labels only for BOUNDED dimensions (model
name, store type, iterator class) — never request IDs.
"""
from __future__ import annotations

import logging
import os
import threading

from .registry import (Counter, Gauge, Histogram, MetricsRegistry, REGISTRY,
                       DEFAULT_BUCKETS, OVERFLOW_LABEL, counter, gauge,
                       histogram, export_text, reset)
from .trace import (new_request_id, current_request_id,
                    set_current_request_id, request_scope,
                    REQUEST_ID_HEADER)
from . import devstats
from . import faultlab
from . import flightrec
from . import history
from . import numwatch
from . import profstats
from . import setup_phases
from . import slo
from . import spans
from . import watchdog
from .spans import (Span, SpanContext, span, record_span, current_span,
                    current_context)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "REGISTRY",
    "DEFAULT_BUCKETS", "OVERFLOW_LABEL",
    "counter", "gauge", "histogram", "export_text", "reset",
    "new_request_id", "current_request_id", "set_current_request_id",
    "request_scope", "REQUEST_ID_HEADER",
    "start_periodic_flush", "stop_periodic_flush", "flush_to_file",
    "devstats", "faultlab", "flightrec", "history", "numwatch",
    "profstats", "setup_phases", "slo", "spans", "watchdog",
    "Span", "SpanContext", "span", "record_span", "current_span",
    "current_context",
]

_flush_lock = threading.Lock()
_flush_stop = None        # threading.Event of the running flusher, or None
_flush_thread = None


def flush_to_file(path=None):
    """Write the full exposition atomically (tmp + rename) so a concurrent
    reader (textfile collector, tail) never sees a torn file. The tmp name
    carries pid AND thread id: the periodic flusher and a one-shot
    flush_to_file() call in the same process must never interleave writes
    into one tmp file."""
    from .. import config
    if path is None:
        path = config.get_env("MXTPU_TELEMETRY_FILE")
    tmp = "%s.%d.%d.tmp" % (path, os.getpid(), threading.get_ident())
    with open(tmp, "w") as f:
        f.write(export_text())
    os.replace(tmp, path)
    return path


def start_periodic_flush(path=None, interval_s=None):
    """Flush the registry to ``path`` every ``interval_s`` seconds from a
    daemon thread (defaults: MXTPU_TELEMETRY_FILE / MXTPU_TELEMETRY_FLUSH_S).
    Idempotent: a second call restarts with the new settings. Returns the
    resolved path."""
    from .. import config
    global _flush_stop, _flush_thread
    if interval_s is None:
        interval_s = config.get_env("MXTPU_TELEMETRY_FLUSH_S")
    interval_s = max(0.05, float(interval_s))
    if path is None:
        path = config.get_env("MXTPU_TELEMETRY_FILE")

    def run(stop):
        while not stop.wait(interval_s):
            try:
                flush_to_file(path)
            except Exception:
                # a full disk / unwritable path must not kill the job the
                # telemetry exists to observe — but the skip must not be
                # silent either (R005): debug-log it so a flusher that
                # never lands a file is diagnosable
                logging.getLogger(__name__).debug(
                    "telemetry flush to %r failed", path, exc_info=True)
        try:                      # final flush so short jobs leave a file
            flush_to_file(path)
        except Exception:
            logging.getLogger(__name__).debug(
                "final telemetry flush to %r failed", path, exc_info=True)

    # stop-old + register-new is ONE critical section: concurrent starts
    # must never orphan a running flusher (its Event would be lost and the
    # thread unstoppable for process lifetime)
    with _flush_lock:
        _stop_locked()
        stop = threading.Event()
        t = threading.Thread(target=run, args=(stop,), daemon=True,
                             name="mxtpu-telemetry")
        _flush_stop, _flush_thread = stop, t
        t.start()
    return path


def _stop_locked():
    """Signal + join the current flusher; caller holds _flush_lock (the
    flusher thread itself never takes the lock, so joining under it is
    deadlock-free)."""
    global _flush_stop, _flush_thread
    stop, t = _flush_stop, _flush_thread
    _flush_stop = _flush_thread = None
    if stop is not None:
        stop.set()
        if t is not None:
            t.join(timeout=5.0)


def stop_periodic_flush():
    """Stop the flusher; the thread writes one final snapshot on exit so
    short jobs always leave a file behind."""
    with _flush_lock:
        _stop_locked()


def _maybe_autostart():
    """Package-import hook: MXTPU_TELEMETRY_FLUSH_S > 0 starts the flusher
    (headless training jobs get metrics with zero code changes), the
    flight recorder chains its crash-dump excepthooks (gated per-crash by
    MXTPU_FLIGHTREC_DUMP_ON_CRASH), MXTPU_WATCHDOG=1 starts the stall
    watchdog monitor, and the set-up listener joins ``jax.monitoring``
    (always: it runs only when JAX traces, lowers or compiles)."""
    from .. import config
    try:
        setup_phases.install()
    except Exception:
        pass
    try:
        if config.get_env("MXTPU_TELEMETRY_FLUSH_S") > 0:
            start_periodic_flush()
    except Exception:
        pass
    try:
        flightrec.install_crash_dump()
    except Exception:
        pass
    try:
        if config.get_env("MXTPU_WATCHDOG"):
            watchdog.start()
    except Exception:
        pass
    try:
        if config.get_env("MXTPU_DEVSTATS"):
            devstats.start()
    except Exception:
        pass
    try:
        if config.get_env("MXTPU_PROFSTATS"):
            profstats.start()
    except Exception:
        pass
    try:
        if config.get_env("MXTPU_HISTORY"):
            history.start()
    except Exception:
        pass
