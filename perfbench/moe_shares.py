"""Device time inside the mixture-of-experts block, by the scopes
`parallel/moe.py` names: `router`, `moe_dispatch` (sort, gather),
`moe_experts` (the grouped matmuls and the SwiGLU between them),
`moe_combine` (un-sort, weighted sum), all under the MoELayer block's own
name. scope_shares.py's reading of the capture, one level finer; the
layer_metrics files of the three MoE metrics are one call into this file
each.

An event's scope path is its instruction's `op_name`, chosen by
trace/scopes.py's rule for fusions (a matmul-class fusion takes the
`convolution`/`dot` inside it). One kind of event has no path: on a TPU XLA
rewrites `jax.lax.ragged_dot` into Mosaic custom calls named
`ragged-dot-*` and gives them an `op_name` of that name alone. No other op
of these programs is a ragged dot, so they are booked to the block and to
`moe_experts` by the instruction's name.

On a program without these scopes (any parent of PR 27, any dense model)
every reader here returns None and the result line leaves the metric out.
"""
import scope_shares  # perfbench/scope_shares.py: run.py's directory is on sys.path

scopes, reduce = scope_shares.scopes, scope_shares.reduce

#: `Block._alias()` of parallel.MoELayer, as trace/scopes.py knows blocks
MOE_STEM = "moelayer"
PARTS = ("router", "moe_dispatch", "moe_experts", "moe_combine")
#: what XLA names the grouped matmul's custom calls (and their metadata op)
GROUPED_MATMUL = "ragged-dot"


def event_parts(program, text):
    """The scope components of one device event's chosen op_name
    (trace/scopes.py `event_class`'s choice, without its classes), or None
    where the program lacks the instruction."""
    name, opcode, _ = reduce.parse(text)
    instr = program.instrs.get(name)
    if instr is None:
        return None
    op_name = instr.op_name
    if opcode == "fusion":
        if reduce.classify(text) == "matmul":
            inner = program.matmul_inside(name)
            if inner is not None and inner.op_name:
                op_name = inner.op_name
        if not op_name:
            op_name = next((r.op_name for r in program.roots_of(name)
                            if r.op_name), "")
    return scopes.components(op_name)[0]


def seconds_by_part(program, ops):
    """`ops` = the reduction's [[instruction text, class, seconds]] ->
    {"block": seconds under a MoELayer, <part>: seconds under that scope
    inside one}, or None where nothing ran under a MoELayer."""
    out = dict.fromkeys(("block",) + PARTS, 0.0)
    for text, _, seconds in ops:
        if reduce.parse(text)[0].startswith(GROUPED_MATMUL):
            out["block"] += seconds
            out["moe_experts"] += seconds
            continue
        parts = event_parts(program, text) or ()
        if not any(MOE_STEM in p for p in parts):
            continue
        out["block"] += seconds
        for part in PARTS:
            if part in parts:
                out[part] += seconds
                break
    return out if out["block"] else None


def moe_seconds(context):
    """The run's {"block", <part>: seconds}, worked out once and kept in
    `context`; None without a trace, a capture, or a MoE block in it."""
    trace = context["trace"]
    if trace is None or not trace["busy_s"]:
        return None
    if "moe_seconds" not in context:
        path = scope_shares.newest_capture()
        program = scopes.pick_program(
            scope_shares._capture_programs(path) if path else [],
            trace["ops"])
        context["moe_seconds"] = None if program is None \
            else seconds_by_part(program, trace["ops"])
    return context["moe_seconds"]


def share_of_busy(context, parts):
    """Percent of device-busy time under the named parts of the block."""
    seconds = moe_seconds(context)
    if seconds is None:
        return None
    return 100.0 * sum(seconds[p] for p in parts) / context["trace"]["busy_s"]


def expert_matmul_roofline(context):
    """The least time the chip could take for the expert matmuls the
    algorithm requires (the builder's `expert_flops_per_token`: 6 x k x 3 x
    U x I per token per layer; a token's 8 experts, not the 64) over the
    time under `moe_experts`, percent. Compute-bound: an expert's 2048 rows
    a step do 2 x 2048 x 2048 x 1024 FLOP a matrix (43.6 us at peak) against
    4.2 MB of weights and 12.6 MB of rows (20.5 us at 819 GB/s)."""
    seconds = moe_seconds(context)
    if seconds is None or not seconds["moe_experts"]:
        return None
    import run as harness        # perfbench/run.py: its loader of builders
    builder = harness.load_module("builders", context["config"]["builder"])
    needed = builder.expert_flops_per_token(context["config"]) \
        * context["tokens_per_step"] * context["steps"] / context["chips"]
    least_s = needed / context["peaks"]["bf16_flops_per_s"]
    return 100.0 * least_s / seconds["moe_experts"]
