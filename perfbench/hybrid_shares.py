"""Device time inside the hybrid model's blocks, by the scopes
`models/nemotron_h.py`, `ops/ssd.py` and `parallel/moe.py` name: ops under
a `Mamba2Mixer` block, of those under `ssd_scan`; ops under a `LatentMoE`
block (router, latent maps, dispatch, experts, combine, shared expert), of
those under `moe_experts`. moe_shares.py's reading of the capture for
other stems; the layer_metrics files of the five metrics are one call into
this file each.

An event's scope path is chosen by moe_shares.event_parts (trace/scopes.py's
rule for fusions). The per-layer recomputation puts `checkpoint` and
`rematted_computation` among a path's components and takes no block's name
away. XLA's `ragged-dot-*` custom calls carry no path: no other op of this
program is a ragged dot, so they are booked to the LatentMoE block and to
`moe_experts` by the instruction's name, as moe_shares.py does.

On a program without these scopes (any parent of PR 31, any other model)
every reader here returns None and the result line leaves the metric out.
"""
import moe_shares  # perfbench/moe_shares.py: run.py's directory is on sys.path

scope_shares = moe_shares.scope_shares
scopes, reduce = moe_shares.scopes, moe_shares.reduce

#: `Block._alias()` of the two blocks, as trace/scopes.py knows blocks
SSM_STEM = "mamba2mixer"
LATENT_MOE_STEM = "latentmoe"
SCAN_SCOPE = "ssd_scan"
EXPERTS_SCOPE = "moe_experts"
KEYS = ("ssm_block", "ssm_scan", "latent_moe_block", "held_experts")


def seconds_by_block(program, ops):
    """`ops` = the reduction's [[instruction text, class, seconds]] ->
    {key: seconds} over KEYS, or None where nothing ran under either
    block's own name (ragged dots alone are another model's MoELayer)."""
    out = dict.fromkeys(KEYS, 0.0)
    named = 0.0
    for text, _, seconds in ops:
        if reduce.parse(text)[0].startswith(moe_shares.GROUPED_MATMUL):
            out["latent_moe_block"] += seconds
            out["held_experts"] += seconds
            continue
        parts = moe_shares.event_parts(program, text) or ()
        if any(SSM_STEM in p for p in parts):
            named += seconds
            out["ssm_block"] += seconds
            if SCAN_SCOPE in parts:
                out["ssm_scan"] += seconds
        elif any(LATENT_MOE_STEM in p for p in parts):
            named += seconds
            out["latent_moe_block"] += seconds
            if EXPERTS_SCOPE in parts:
                out["held_experts"] += seconds
    return out if named else None


def hybrid_seconds(context):
    """The run's {key: seconds}, worked out once and kept in `context`;
    None without a trace, a capture, or either block in it."""
    trace = context["trace"]
    if trace is None or not trace["busy_s"]:
        return None
    if "hybrid_seconds" not in context:
        path = scope_shares.newest_capture()
        program = scopes.pick_program(
            scope_shares._capture_programs(path) if path else [],
            trace["ops"])
        context["hybrid_seconds"] = None if program is None \
            else seconds_by_block(program, trace["ops"])
    return context["hybrid_seconds"]


def share_of_busy(context, key):
    """Percent of device-busy time booked to `key`."""
    seconds = hybrid_seconds(context)
    if seconds is None:
        return None
    return 100.0 * seconds[key] / context["trace"]["busy_s"]


def _builder(context):
    import run as harness        # perfbench/run.py: its loader of builders
    return harness.load_module("builders", context["config"]["builder"])


def _tokens(context):
    return context["tokens_per_step"] * context["steps"] / context["chips"]


def scan_roofline(context):
    """The least time the chip could take for the scans the algorithm
    requires (the larger of the builder's `ssd_flops_per_token` over the
    peak FLOP/s and `ssd_bytes_per_token` over the peak bytes/s) over the
    time under `ssd_scan`, percent. At 16 heads of 64 with 128 states and
    chunks of 128 the two bounds are close (2.46 MFLOP against 12.0 kB a
    token a layer: 12.5 ns against 14.6 ns); bytes bound it."""
    seconds = hybrid_seconds(context)
    if seconds is None or not seconds["ssm_scan"]:
        return None
    builder, cfg, peaks = _builder(context), context["config"], \
        context["peaks"]
    least_s = _tokens(context) * max(
        builder.ssd_flops_per_token(cfg) / peaks["bf16_flops_per_s"],
        builder.ssd_bytes_per_token(cfg) / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / seconds["ssm_scan"]


def held_expert_roofline(context):
    """The least time the chip could take for the held experts' matmuls
    over the LIVE rows (the builder's `held_expert_flops_per_token`: a
    token's expected 22 x 8 / 512 held experts, two matrices of 1024 x
    2688 each) over the time under `moe_experts`, percent. It reads low
    while the rows of the static bound that no expert owns are multiplied
    or copied: that is what it is there to show."""
    seconds = hybrid_seconds(context)
    if seconds is None or not seconds["held_experts"]:
        return None
    needed = _builder(context).held_expert_flops_per_token(
        context["config"]) * _tokens(context)
    least_s = needed / context["peaks"]["bf16_flops_per_s"]
    return 100.0 * least_s / seconds["held_experts"]
