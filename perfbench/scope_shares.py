"""Device time by the model's own blocks, and the train step's host spans:
what hlo_shapes.py is for tensor shapes, for the scope names the program
carries since PR 25 (trace/scopes.py has the classes and the rule for
fusions). The layer_metrics files of the by-scope metrics are one call into
this file each.

The driver hands the metrics its reduction (`context["trace"]`: every
instruction that ran in the window with its self time, mean over chips) but
not the capture's path, and may not be edited to add it: the capture read
here is the newest `*.xplane.pb` under `<checkout>/.perfbench_out/trace/`
(a run is one process, and the driver empties its cell's directory before
`start_trace`). From it come the programs' HLO (plane `/host:metadata`) and
the host plane's `train:*` spans beside the benchmark's `bench:*`. The
program's own `jit.compiled_train_programs()` cannot serve here: run.py
computes the metrics after the driver has returned, and a `TrainStep` that
is gone has released its cache entries (trace/scopes.py reads that text
too, and the tests hold the two routes to each other).

On a program without scope names (the parent of PR 25) every reader here
returns None and the result line leaves the metric out.
"""
import functools
import glob
import importlib.util
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_ROOT = os.path.join(os.path.dirname(HERE), ".perfbench_out", "trace")
HOST_SPAN_PREFIXES = ("bench:", "train:")
DISPATCH_SPAN = "train:dispatch"
#: the two backward kernels' names (`ops/attention.py`)
FLASH_BWD_KERNELS = ("flash_bwd_dkv", "flash_bwd_dq")


def _load_scopes():
    name = "perfbench_trace_scopes"      # run.load_module's name for it
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(HERE, "trace", "scopes.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


scopes = _load_scopes()
reduce = scopes.reduce


def newest_capture(root=None):
    """The newest .xplane.pb under the benchmark's trace directory, or
    None."""
    found = glob.glob(os.path.join(root or TRACE_ROOT, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


@functools.lru_cache(maxsize=2)
def _capture_programs(path):
    return scopes.programs_from_capture(scopes.read_capture_bytes(path))


def by_class(ops, programs):
    """{class: seconds} of the reduction's `ops` under the program that
    ran them, or None where no program carries scope names."""
    program = scopes.pick_program(programs, ops)
    if program is None or not scopes.has_scopes(program):
        return None
    return scopes.seconds_by_class(program, ops)


def share_of_busy(context, which):
    """Percent of device-busy time in ops of scope class `which`, or None
    where there is no trace, no capture or no scope name in the program.
    The classes are worked out once a run and kept in `context`."""
    trace = context["trace"]
    if trace is None or not trace["busy_s"]:
        return None
    if "scope_seconds" not in context:
        path = newest_capture()
        context["scope_seconds"] = by_class(
            trace["ops"], _capture_programs(path) if path else [])
    seconds = context["scope_seconds"]
    return None if seconds is None else \
        100.0 * seconds[which] / trace["busy_s"]


def flash_bwd_share(context):
    """Percent of device-busy time in the two backward kernels, found by
    the names the program gives them; None where no event has one."""
    trace = context["trace"]
    if trace is None or not trace["busy_s"]:
        return None
    seconds = sum(s for text, _, s in trace["ops"]
                  if any(k in reduce.parse(text)[0]
                         for k in FLASH_BWD_KERNELS))
    return 100.0 * seconds / trace["busy_s"] if seconds else None


# ------------------------------------------------------------ host spans
@functools.lru_cache(maxsize=2)
def host_view(path):
    """One pass over a capture -> (host spans [(name, start, end)] of the
    `bench:*` and `train:*` annotations, the window (start, end) or None,
    the idlest chip's busy intervals inside the window)."""
    data = reduce.load(path)
    spans, device = [], {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                          for ev in line.events
                          if ev.name.startswith(HOST_SPAN_PREFIXES)]
        elif plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == reduce.OPS_LINE:
                    device.setdefault(plane.name, []).extend(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events)
    window = next(((s, e) for n, s, e in spans
                   if n == reduce.WINDOW_SPAN), None)
    idlest = None
    if window:
        for ops in device.values():
            busy = reduce.merge((s, e) for _, s, e in
                                reduce.clip3(ops, *window))
            if idlest is None or reduce.length(busy) < \
                    reduce.length(idlest):
                idlest = busy
    return spans, window, idlest


def dispatch_ms(path):
    """Median milliseconds of the `train:dispatch` spans that start inside
    the traced window, or None where the capture has none."""
    spans, window, _ = host_view(path)
    if window is None:
        return None
    durations = [(e - s) / 1e6 for n, s, e in spans
                 if n == DISPATCH_SPAN and window[0] <= s < window[1]]
    return statistics.median(durations) if durations else None


def idle_gaps(path, top=10):
    """The longest idle gaps of the idlest chip inside the window, each
    labelled with the innermost `train:*` or `bench:*` span open on the
    host then (reduce._host_at's rule, over both prefixes) ->
    [[label, seconds]]."""
    spans, window, busy = host_view(path)
    if window is None or busy is None:
        return []
    inner = [x for x in spans if x[0] != reduce.WINDOW_SPAN]
    longest = sorted(reduce.gaps(busy, *window),
                     key=lambda g: g[0] - g[1])[:top]
    return [[reduce._host_at(inner, g0, g1), (g1 - g0) / 1e9]
            for g0, g1 in longest]
