"""Set-up seen from inside: JAX's own compile-pipeline events, booked to the
program's spans and to registry counters.

JAX times its pipeline itself and tells any ``jax.monitoring`` listener, on
the thread that did the work: a jaxpr trace, jaxpr -> MLIR (every Mosaic
lowering inside it), the backend compile, and whether the persistent cache
answered that compile. ``install()`` (package import; idempotent) registers
ONE listener for them. Each pipeline event becomes

- a child span of the innermost program span open on that thread, on the
  spans' own clock (an event nested in another one lies inside that one's
  span and gets none of its own: a step's trace holds thousands): ``train:trace`` / ``train:lower`` /
  ``train:backend_compile`` / ``train:cache_read`` under ``train:build``,
  ``eval:*`` under ``eval:build``, ``aot:*`` under ``aot:load``, ``init:*``
  under ``gluon:initialize`` / ``gluon:cast`` / ``train:init_states``;
- ``mxtpu_compile_phase_seconds_total{phase, owner}`` and
  ``mxtpu_compile_phase_events_total{phase, owner}``, ``owner`` the name of
  that span (code-authored constants: a bounded label) or ``other`` when no
  program span is open (uploads, eager ops, a caller's own ``jax.jit``).

A compile inside which the persistent cache hit is phase ``cache_read``,
otherwise ``backend_compile``; ``mxtpu_compile_cache_total{result, owner}``
counts the hits and the entries written after a miss.

Events nest: an inner ``jax.jit`` traced inside an outer one, an eager op's
whole pipeline inside a trace. Seconds are SELF time (an event's duration
less the events nested directly in it), so the phases partition the wall
time the thread spent in the pipeline and a nested trace adds its event but
not its seconds twice. A nested event costs one dictionary update; the
outermost one books what it held into the registry. A warm step emits no
event: nothing here runs in the steady state.
"""
from __future__ import annotations

import threading
import time

from jax import monitoring

from . import registry, spans

__all__ = ["install", "record_import", "PHASES", "OTHER"]

PHASES = ("trace", "lower", "backend_compile", "cache_read")
#: owner of an event that no program span was open around
OTHER = "other"

_PHASE_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
_CACHE_RESULT_OF = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
#: the initialisation spans: their pipeline children are named ``init:*``
_INIT_OWNERS = frozenset(
    ("gluon:initialize", "gluon:cast", "train:init_states"))

_SECONDS = registry.counter(
    "mxtpu_compile_phase_seconds_total",
    "Host seconds in JAX's compile pipeline by phase (self time: nested "
    "events subtracted) and by the innermost program span open around it.",
    ("phase", "owner"))
_EVENTS = registry.counter(
    "mxtpu_compile_phase_events_total",
    "Compile-pipeline events by phase and owning program span (one a "
    "traced function, a lowered module, a compiled or cache-read program).",
    ("phase", "owner"))
_CACHE = registry.counter(
    "mxtpu_compile_cache_total",
    "Persistent compilation cache: executables read (hit) and entries "
    "written after a compile (miss), by owning program span.",
    ("result", "owner"))
_IMPORT = registry.gauge(
    "mxtpu_import_seconds",
    "Seconds of this package's import: the first device touch (backend) "
    "and everything else (modules).", ("part",))


class _PerThread(threading.local):
    def __init__(self):
        #: per open pipeline event, the seconds of the events that ended
        #: directly inside it
        self.frames = []
        #: (phase, owner) -> [self seconds, events], held until the
        #: outermost open event ends
        self.booked = {}
        #: the persistent cache answered inside the compile that is open
        self.hit = False


_local = _PerThread()
_installed = False


def _owner():
    parent = spans.current_span()
    return parent, (parent.name if parent is not None else OTHER)


def _on_enter(event, _start_time, **_kw):
    # JAX records the event's start as a scalar when it opens
    if event in _PHASE_OF:
        _local.frames.append(0.0)


def _on_exit(event, start, end, **kw):
    phase = _PHASE_OF.get(event)
    if phase is None:
        return
    try:
        frames, booked = _local.frames, _local.booked
        nested = frames.pop() if frames else 0.0
        dur = max(end - start, 0.0)
        hit = False
        if phase == "backend_compile":
            hit, _local.hit = _local.hit, False
            if hit:
                phase = "cache_read"
        parent, owner = _owner()
        # a step's trace holds thousands of nested events: each is one
        # dictionary update, and the outermost one books them all
        seen = booked.get((phase, owner))
        if seen is None:
            booked[phase, owner] = [max(dur - nested, 0.0), 1]
        else:
            seen[0] += max(dur - nested, 0.0)
            seen[1] += 1
        if frames:
            frames[-1] += dur
            return
        for (phase_, owner_), (secs, count) in booked.items():
            _SECONDS.inc(secs, phase=phase_, owner=owner_)
            _EVENTS.inc(count, phase=phase_, owner=owner_)
        booked.clear()
        if parent is not None:
            args = {"fun_name": kw.get("fun_name")}
            if phase in ("backend_compile", "cache_read"):
                args["cache_hit"] = hit
            # JAX stamps its events with time.time() and the spans' clock
            # is anchored elsewhere: place the event by how long ago it
            # started, on JAX's own clock
            ago_s = time.time() - start  # mxtpulint: disable=R006
            prefix = "init" if owner in _INIT_OWNERS \
                else owner.partition(":")[0]
            spans.record_span(
                "%s:%s" % (prefix, phase), spans._now_us() - ago_s * 1e6,
                dur * 1e6, parent=parent, **args)
    except Exception:
        pass        # tracing must never take down the compile it observes


def _on_cache(event, **_kw):
    result = _CACHE_RESULT_OF.get(event)
    if result is None:
        return
    try:
        if result == "hit":
            _local.hit = True       # read by the enclosing compile's end
        _CACHE.inc(result=result, owner=_owner()[1])
    except Exception:
        pass


def install():
    """Register the listener with ``jax.monitoring``; a second call (or a
    second import path) registers nothing."""
    global _installed
    if _installed:
        return
    _installed = True
    monitoring.register_scalar_listener(_on_enter)
    monitoring.register_event_time_span_listener(_on_exit)
    monitoring.register_event_listener(_on_cache)


def record_import(total_s, backend_s):
    """Set once by the package's ``__init__``: its own import, split into
    the first device touch and the rest."""
    _IMPORT.set(backend_s, part="backend")
    _IMPORT.set(max(total_s - backend_s, 0.0), part="modules")
