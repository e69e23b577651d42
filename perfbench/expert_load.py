"""The rows each expert got, step by step, as the program itself published
them (PR 50), for the four `held_*` / `expert_load_*` per-layer metrics
(`layer_metrics/held_window_fill.py`, `held_windows_per_pass.py`,
`expert_load_min_share.py`, `held_expert_us_per_live_row.py`).

A `MoELayer`'s dispatch hands the rows an expert out of the compiled step
(`group_sizes`, what the grouped matmuls and the held dispatch's windows
run on) and `jit.TrainStep` books them to their step once the host knows
them: one `train:counters` record a step in the span ring, on that step's
own `train:dispatch` clock, with the vector of each layer, whether it is a
held share, W (the rows of one window) and the even load T k / E. This file
flushes what is not resolved yet, takes the ring's last `context["steps"]`
records (the steps that finished in the window: the two in flight when it
opened and those dispatched inside it) and prints ONE line, `expert load
as the program saw it: {...}`, with the series a layer, so the drift over
the window is in every traced run's log.

A program without the channel (any parent of PR 50), or a model without a
`MoELayer`, reads `None` everywhere and the result line leaves the metrics
out.
"""
import collections
import json
import re
import statistics

RECORD = "train:counters"
#: a numbered instruction's name without its number
NUMBER = re.compile(r"\.\d+$")


def records(steps):
    """The last `steps` `train:counters` records, oldest first, everything
    a live TrainStep still holds resolved first; None where the program has
    no such channel or recorded nothing."""
    from incubator_mxnet_tpu import jit, telemetry
    flush = getattr(jit, "flush_step_counters", None)
    if flush is None or steps < 1:
        return None
    flush()
    mine = [r for r in telemetry.spans.snapshot() if r["name"] == RECORD]
    return mine[-steps:] or None


def loads(recs):
    """`train:counters` records -> {"layers": [{name, held, window_rows,
    even_rows}], "steps": [{"step": n, "rows": [live rows a layer],
    "windows": [ceil(live / W) a layer; 1 where every expert is held],
    "min": [the least loaded expert's rows a layer], "starved": [experts
    with no row a layer]}]}: the counters that carry an expert layer's
    facts (`even_rows`), in the program's order. None without one."""
    layers, steps = None, []
    for r in recs:
        mine = [c for c in r["args"]["counters"] if "even_rows" in c]
        if not mine:
            continue
        layers = [{"name": c["name"], "held": bool(c["held"]),
                   "window_rows": c["window_rows"],
                   "even_rows": c["even_rows"]} for c in mine]
        live = [sum(c["values"]) for c in mine]
        steps.append({
            "step": r["args"]["step"], "rows": live,
            "windows": [-(-n // c["window_rows"]) for n, c in zip(live, mine)],
            "min": [min(c["values"]) for c in mine],
            "starved": [sum(1 for v in c["values"] if v == 0) for c in mine]})
    return {"layers": layers, "steps": steps} if steps else None


def grouped_matmul_events(path):
    """{instruction name without its number: device events inside the
    traced window} of XLA's `ragged-dot-*` calls, chip 0: a held dispatch
    runs its grouped matmuls once a window a pass, so their count is the
    windows that ran, by the device's own account."""
    import moe_shares   # perfbench/moe_shares.py: run.py's directory is on sys.path
    reduce = moe_shares.reduce
    device, host = reduce.read_planes(path)
    window = [(s, e) for n, s, e in host if n == reduce.WINDOW_SPAN]
    if not device or not window:
        return None
    lo, hi = window[0]
    counts = collections.Counter()
    for text, start, _ in device[min(device)]["ops"]:
        name = reduce.parse(text)[0]
        if name.startswith(moe_shares.GROUPED_MATMUL) and lo <= start < hi:
            counts[NUMBER.sub("", name)] += 1
    return dict(counts)


def expert_load(context):
    """The run's `loads(...)`, worked out once, kept in `context` and
    printed as the run's ONE line (a traced run adds the device's count of
    grouped-matmul events in the window); None where the program published
    nothing."""
    if "expert_load" not in context:
        recs = records(context["steps"])
        found = loads(recs) if recs else None
        context["expert_load"] = found
        if found is not None:
            line = dict(found)
            if context.get("trace") is not None:
                import scope_shares  # perfbench/scope_shares.py
                path = scope_shares.newest_capture()
                try:
                    line["grouped_matmul_events"] = \
                        grouped_matmul_events(path) if path else None
                except Exception as e:   # a log's extra must not fail a run
                    line["grouped_matmul_events"] = "unread: %r" % (e,)
            print("expert load as the program saw it: %s" % json.dumps(line),
                  flush=True)
    return context["expert_load"]


def _held(found):
    return [i for i, layer in enumerate(found["layers"]) if layer["held"]]


def window_fill(context):
    """Percent of the rows of the windows that ran that a held expert
    owned: live rows over windows run x W, summed over the held layers, the
    median over the window's steps (a step in which no window ran has no
    fill). What the shapes expect is 50 where W is twice the even share."""
    found = expert_load(context)
    held = _held(found) if found else []
    fills = []
    for step in found["steps"] if held else ():
        room = sum(step["windows"][i] * found["layers"][i]["window_rows"]
                   for i in held)
        if room:
            fills.append(100.0 * sum(step["rows"][i] for i in held) / room)
    return statistics.median(fills) if fills else None


def windows_per_pass(context):
    """Windows a held layer's dispatch ran a pass, the mean over the held
    layers and the window's steps: 1.0 while every layer's live rows fit
    one window, more once a router sends its held experts over twice their
    even share, less where a layer's held experts got no row at all."""
    found = expert_load(context)
    held = _held(found) if found else []
    if not held:
        return None
    return statistics.fmean(step["windows"][i] for step in found["steps"]
                            for i in held)


def min_share(context):
    """Percent of the even load T k / E that the least loaded expert got,
    in the worst layer, the median over the window's steps: 100 is a
    balanced router, 0 an expert that starved."""
    found = expert_load(context)
    if found is None:
        return None
    return statistics.median(
        min(100.0 * low / layer["even_rows"]
            for low, layer in zip(step["min"], found["layers"]))
        for step in found["steps"])


def us_per_live_row(context):
    """Microseconds of device time under `moe_experts` (moe_shares.py: the
    scope inside a MoELayer and the `ragged-dot-*` calls by name, every
    pass) a live row of the held layers, over the traced window's steps:
    what a row the router sends this chip costs its grouped matmuls."""
    found = expert_load(context)
    held = _held(found) if found else []
    if not held:
        return None
    import moe_shares   # perfbench/moe_shares.py
    seconds = moe_shares.moe_seconds(context)
    live = sum(step["rows"][i] for step in found["steps"] for i in held)
    if seconds is None or not seconds["moe_experts"] or not live:
        return None
    return 1e6 * seconds["moe_experts"] / live
