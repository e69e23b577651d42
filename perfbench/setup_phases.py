"""What the program recorded of its own set-up, for the eight `setup_*`
per-layer metrics (`layer_metrics/setup_*.py`).

The run is one process: by the time `run.py` calls a reader, the package's
registry holds `mxtpu_compile_phase_seconds_total{phase, owner}` (JAX's
trace / lower / compile / cache-read events booked to the program span
open around them, `telemetry/setup_phases.py`), `mxtpu_compile_cache_total`,
`mxtpu_kernel_trace_seconds_total{kernel}` (`ops/kernel_trace.py`) and
`mxtpu_import_seconds{part}`, and the span ring holds the initialisation
and build spans. A program that lacks a counter family altogether (a
parent commit from before them) reads `None` and the result line leaves
the metric out; a family that is there and has no such series reads 0.0.
"""
import json

#: the spans inside which the program builds a step or a forward
BUILD_OWNERS = ("train:build", "eval:build", "aot:load")
#: the program's own initialisation (their small programs inside)
INIT_SPANS = ("gluon:initialize", "gluon:cast", "train:init_states")
OTHER = "other"


def _telemetry():
    from incubator_mxnet_tpu import telemetry
    return telemetry


def series(name):
    """[(labels, value)] of one metric family; None where the program has
    no such family."""
    metric = _telemetry().REGISTRY.get(name)
    return None if metric is None else metric.series()


def total(name, **where):
    """Sum over the family's series whose labels match `where` (a value,
    or a tuple of values); None where the program has no such family."""
    rows = series(name)
    if rows is None:
        return None

    def takes(labels):
        return all(labels.get(k) in (v if isinstance(v, tuple) else (v,))
                   for k, v in where.items())
    return float(sum(v for labels, v in rows if takes(labels)))


def phase_seconds(phase, owners=BUILD_OWNERS):
    return total("mxtpu_compile_phase_seconds_total", phase=phase,
                 owner=owners)


def span_records():
    """The finished spans, oldest first; None where the program has no
    set-up listener (its spans would lack the initialisation ones)."""
    telemetry = _telemetry()
    if getattr(telemetry, "setup_phases", None) is None:
        return None
    return telemetry.spans.snapshot()


def span_seconds(names, records=None):
    records = span_records() if records is None else records
    if records is None:
        return None
    return sum(r["dur_us"] for r in records if r["name"] in names) / 1e6


#: retroactive spans that cover a whole first call again (`jit.py`
#: `_record_compile_span`): they are the lump, not a part of it
LUMPS = ("train:compile", "eval:compile")


def _union_us(intervals):
    covered, edge = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > edge:
            covered += end - max(start, edge)
            edge = end
    return covered


def children(records, parent):
    """{name: seconds} of `parent`'s direct children (overlapping children
    of one name, a trace nested in a trace, count once), and the seconds of
    the parent inside none of them."""
    lo, hi = parent["start_us"], parent["start_us"] + parent["dur_us"]
    by_name = {}
    for r in records:
        if r["parent_id"] == parent["span_id"] and r["name"] not in LUMPS:
            by_name.setdefault(r["name"], []).append(
                (max(r["start_us"], lo),
                 min(r["start_us"] + r["dur_us"], hi)))
    covered = _union_us([i for spans in by_name.values() for i in spans])
    return ({name: _union_us(spans) / 1e6 for name, spans in by_name.items()},
            (parent["dur_us"] - covered) / 1e6)


def first_calls(records):
    """Per first call of a compiled program (a `train:step` that built,
    its `train:build`, an `eval:build`, an `aot:load`): its seconds, those
    outside its children, and its children's seconds by name — where a
    first call's time went, and what no span names yet."""
    built = {r["parent_id"] for r in records if r["name"] == "train:build"}
    out = []
    for r in records:
        if r["name"] in BUILD_OWNERS or r["span_id"] in built:
            kids, outside = children(records, r)
            out.append({"span": r["name"], "s": round(r["dur_us"] / 1e6, 4),
                        "outside_children_s": round(outside, 4),
                        "children": {n: round(v, 4)
                                     for n, v in kids.items()}})
    return out


def table():
    """The whole split, for the run's log: {owner: {phase: [seconds,
    events]}}, the persistent cache's hits and misses by owner, {kernel:
    [seconds, traces]}, the import, the initialisation spans and the first
    calls with what lies outside their children."""
    def by(name, outer, inner):
        out = {}
        for labels, v in series(name) or ():
            out.setdefault(labels[outer], {})[labels[inner]] = v
        return out

    seconds = by("mxtpu_compile_phase_seconds_total", "owner", "phase")
    events = by("mxtpu_compile_phase_events_total", "owner", "phase")
    kernel_s = dict((l["kernel"], v) for l, v in
                    series("mxtpu_kernel_trace_seconds_total") or ())
    kernel_n = dict((l["kernel"], v) for l, v in
                    series("mxtpu_kernel_traces_total") or ())
    records = span_records() or []
    return {
        "phases": {owner: {phase: [round(s, 4), int(events.get(owner, {})
                                                    .get(phase, 0))]
                           for phase, s in sorted(phases.items())}
                   for owner, phases in sorted(seconds.items())},
        "cache": by("mxtpu_compile_cache_total", "owner", "result"),
        "kernels": {k: [round(s, 4), int(kernel_n.get(k, 0))]
                    for k, s in sorted(kernel_s.items())},
        "import": dict((l["part"], round(v, 4)) for l, v in
                       series("mxtpu_import_seconds") or ()),
        "init_spans": {name: round(span_seconds((name,), records), 4)
                       for name in INIT_SPANS},
        "first_calls": first_calls(records),
        "first_eval_steps": [round(r["dur_us"] / 1e6, 4) for r in records
                             if r["name"] == "eval:step"
                             and (r.get("args") or {}).get("compile")],
        "span_records": len(records),
    }


def print_table():
    print("set-up as the program saw it: %s" % json.dumps(table()),
          flush=True)
