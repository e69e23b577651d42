"""Device time inside the Solar Open 2 model's blocks, by the names
`models/solar_open2.py`, `ops/delta_rule.py` and `parallel/moe.py` give
them: ops under a `KimiDeltaAttention` block, of those under `delta_rule`;
ops under a `GatedGroupedQueryAttention` block (the streamed kernels run
under it and carry its path); ops under a `SharedExpertMoE` block (router,
held dispatch, grouped matmuls, combine, shared expert). moe_shares.py's
reading of the capture for other stems; the layer_metrics files of the five
metrics are one call into this file each. The work the roofline divides by
is the builder's (`delta_rule_flops_per_token`, `delta_rule_bytes_per_token`
at its NOMINAL_CHUNK), never the program's own chunk.

An event's scope path is chosen by moe_shares.event_parts (trace/scopes.py's
rule for fusions). The per-layer recomputation puts `checkpoint` and
`rematted_computation` among a path's components and takes no block's name
away. XLA's `ragged-dot-*` custom calls carry no path: no other op of this
program is a ragged dot, so they are booked to the SharedExpertMoE block by
the instruction's name, as moe_shares.py does.

On a program without these names (any parent of PR 38, any other model)
every reader here returns None and the result line leaves the metric out.
"""
import moe_shares  # perfbench/moe_shares.py: run.py's directory is on sys.path

scope_shares = moe_shares.scope_shares
scopes, reduce = moe_shares.scopes, moe_shares.reduce

#: `Block._alias()` of the three blocks, as trace/scopes.py knows blocks
KDA_STEM = "kimideltaattention"
GQA_STEM = "gatedgroupedqueryattention"
MOE_STEM = "sharedexpertmoe"
RULE_SCOPE = "delta_rule"
KEYS = ("linear_attn_block", "delta_rule", "gated_attn_block",
        "shared_moe_block")


def seconds_by_block(program, ops):
    """`ops` = the reduction's [[instruction text, class, seconds]] ->
    {key: seconds} over KEYS, or None where nothing ran under any of the
    three blocks' own names (ragged dots alone are another model's
    MoELayer)."""
    out = dict.fromkeys(KEYS, 0.0)
    named = 0.0
    for text, _, seconds in ops:
        if reduce.parse(text)[0].startswith(moe_shares.GROUPED_MATMUL):
            out["shared_moe_block"] += seconds
            continue
        parts = moe_shares.event_parts(program, text) or ()
        for stem, key in ((KDA_STEM, "linear_attn_block"),
                          (GQA_STEM, "gated_attn_block"),
                          (MOE_STEM, "shared_moe_block")):
            if any(stem in p for p in parts):
                named += seconds
                out[key] += seconds
                if stem == KDA_STEM and RULE_SCOPE in parts:
                    out["delta_rule"] += seconds
                break
    return out if named else None


def delta_seconds(context):
    """The run's {key: seconds}, worked out once and kept in `context`;
    None without a trace, a capture, or any of the blocks in it."""
    trace = context["trace"]
    if trace is None or not trace["busy_s"]:
        return None
    if "delta_seconds" not in context:
        path = scope_shares.newest_capture()
        program = scopes.pick_program(
            scope_shares._capture_programs(path) if path else [],
            trace["ops"])
        context["delta_seconds"] = None if program is None \
            else seconds_by_block(program, trace["ops"])
    return context["delta_seconds"]


def share_of_busy(context, key):
    """Percent of device-busy time booked to `key`."""
    seconds = delta_seconds(context)
    if seconds is None:
        return None
    return 100.0 * seconds[key] / context["trace"]["busy_s"]


def rule_roofline(context):
    """The least time the chip could take for the delta rules the step
    runs (forward, the layers' recomputed forward and backward: the larger
    of the builder's `delta_rule_flops_per_token(config, passes=4)` over
    the peak FLOP/s and `delta_rule_bytes_per_token` over the peak bytes/s,
    both at the builder's nominal chunk of 64) over the time under
    `delta_rule`, percent. At 8 heads of 128 x 128 the bytes bound it:
    47.2 kB against 5.85 MFLOP a token a layer, 57.7 ns against 29.7 ns."""
    seconds = delta_seconds(context)
    if seconds is None or not seconds["delta_rule"]:
        return None
    import run as harness        # perfbench/run.py: its loader of builders
    builder = harness.load_module("builders", context["config"]["builder"])
    cfg, peaks = context["config"], context["peaks"]
    tokens = context["tokens_per_step"] * context["steps"] / context["chips"]
    least_s = tokens * max(
        builder.delta_rule_flops_per_token(cfg, passes=4)
        / peaks["bf16_flops_per_s"],
        builder.delta_rule_bytes_per_token(cfg) / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / seconds["delta_rule"]
