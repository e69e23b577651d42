"""Device time inside the SambaY model's new mechanisms, by the names
`ops/selective_scan.py`, `ops/attention.py` and `models/phi4flash.py` give
them: ops under the `selective_scan` scope; the window kernels
(`flash_window_fwd`, `flash_window_bwd`, by the instruction's name, as
scope_shares.py finds the causal backward); ops under `gmu` and
`cross_attention` (the cross-decoder's two mixers). moe_shares.py's reading
of the capture for other names; the layer_metrics files of the five metrics
are one call into this file each. The costs the two rooflines divide by
are the builder's (`scan_bytes_per_token`, `window_keys`,
`diff_attention_flops_per_key`): what the algorithm requires, never
block-rounded or recomputed work.

An event's scope path is chosen by moe_shares.event_parts (trace/scopes.py's
rule for fusions); an op in a loop body or a recomputed layer keeps its
path's components.

On a program without these names (any parent of PR 34, any other model)
every reader here returns None and the result line leaves the metric out.
"""
import moe_shares  # perfbench/moe_shares.py: run.py's directory is on sys.path

scope_shares = moe_shares.scope_shares
scopes, reduce = moe_shares.scopes, moe_shares.reduce

SCAN_SCOPE = "selective_scan"
WINDOW_KERNELS = "flash_window_"
CROSS_SCOPES = ("gmu", "cross_attention")
KEYS = ("scan", "window_kernels", "cross_decoder")


def seconds_by_name(program, ops):
    """`ops` = the reduction's [[instruction text, class, seconds]] ->
    {key: seconds} over KEYS, or None where nothing ran under any of the
    names."""
    out = dict.fromkeys(KEYS, 0.0)
    for text, _, seconds in ops:
        if WINDOW_KERNELS in reduce.parse(text)[0]:
            out["window_kernels"] += seconds
            continue
        parts = moe_shares.event_parts(program, text) or ()
        if SCAN_SCOPE in parts:
            out["scan"] += seconds
        elif any(scope in parts for scope in CROSS_SCOPES):
            out["cross_decoder"] += seconds
    return out if any(out.values()) else None


def sambay_seconds(context):
    """The run's {key: seconds}, worked out once and kept in `context`;
    None without a trace, a capture, or any of the names in it."""
    trace = context["trace"]
    if trace is None or not trace["busy_s"]:
        return None
    if "sambay_seconds" not in context:
        path = scope_shares.newest_capture()
        program = scopes.pick_program(
            scope_shares._capture_programs(path) if path else [],
            trace["ops"])
        context["sambay_seconds"] = None if program is None \
            else seconds_by_name(program, trace["ops"])
    return context["sambay_seconds"]


def share_of_busy(context, key):
    """Percent of device-busy time booked to `key`."""
    seconds = sambay_seconds(context)
    if seconds is None:
        return None
    return 100.0 * seconds[key] / context["trace"]["busy_s"]


def _builder(context):
    import run as harness        # perfbench/run.py: its loader of builders
    return harness.load_module("builders", context["config"]["builder"])


def scan_roofline(context):
    """The least time the chip could take to move what the selective scans
    must move (the builder's `scan_bytes_per_token`: x, the low-rank input
    of the step sizes, B, C in and y out, forward and backward with its
    four gradients; neither the recomputation nor a state is required, and
    the float32 step sizes are formed inside the scope and are no input of
    it) over the time under `selective_scan`, percent. The scan is
    vector-unit and bandwidth work: about 2.2 M element-wise operations a
    token a layer against 52.4 kB."""
    seconds = sambay_seconds(context)
    if seconds is None or not seconds["scan"]:
        return None
    tokens = context["tokens_per_step"] * context["steps"] / context["chips"]
    least_s = tokens * _builder(context).scan_bytes_per_token(
        context["config"]) / context["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds["scan"]


def window_roofline(context):
    """The least time the chip could take for the windowed layers' scores
    (the LIVE BAND only: sum over queries of min(i + 1, window) keys, the
    builder's `window_keys`, times `diff_attention_flops_per_key`, forward
    + backward; the backward's recomputation of the scores, the keys a
    block holds beyond the band and the layers' recomputed forward are not
    counted) over the time in the window kernels, percent."""
    seconds = sambay_seconds(context)
    if seconds is None or not seconds["window_kernels"]:
        return None
    builder, cfg = _builder(context), context["config"]
    traffic = context["workload"]["traffic"]
    sequences = traffic["batch"] * context["steps"]
    needed = sequences * cfg["layer_pattern_run"].count("S") \
        * builder.diff_attention_flops_per_key(cfg) \
        * builder.window_keys(traffic["seq_len"], cfg["sliding_window"])
    least_s = needed / context["peaks"]["bf16_flops_per_s"]
    return 100.0 * least_s / seconds["window_kernels"]
