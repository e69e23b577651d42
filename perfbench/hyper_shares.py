"""Device time inside the hyper-connections of the Xing 4.0 model, by the
names `models/xing4.py` gives them: ops under a `HyperConnection` block (the
wide norm, x^ P, the sigmoids and the Sinkhorn rounds, the two mixes;
forward, recomputed forward and backward), and of those the ops under the
scopes `hc_pre` (Hpre X) and `hc_post` (Hres X + Hpost^T F), the two mixes
that read and write the streams. latent_shares.py's reading of the capture
for another stem; the layer_metrics files of the two metrics are one call
into this file each. The bytes the roofline divides by are the builder's
(`hyper_connection_bytes_per_token`: what the mathematics must move with the
streams in bfloat16), never what the program moves.

On a program without these names (any parent of PR 53, any other model)
every reader here returns None and the result line leaves the metric out.
"""
import moe_shares  # perfbench/moe_shares.py: run.py's directory is on sys.path

scope_shares = moe_shares.scope_shares
scopes, reduce = moe_shares.scopes, moe_shares.reduce

#: `Block._alias()` of the block, as trace/scopes.py knows blocks
HC_STEM = "hyperconnection"
#: the scopes of the two mixes
MIX_SCOPES = ("hc_pre", "hc_post")
KEYS = ("hyper_conn", "hyper_mix")


def seconds_by_block(program, ops):
    """`ops` = the reduction's [[instruction text, class, seconds]] ->
    {key: seconds} over KEYS, or None where nothing ran under a
    `HyperConnection` block."""
    out = dict.fromkeys(KEYS, 0.0)
    for text, _, seconds in ops:
        parts = moe_shares.event_parts(program, text) or ()
        if any(HC_STEM in p for p in parts):
            out["hyper_conn"] += seconds
            if any(s in parts for s in MIX_SCOPES):
                out["hyper_mix"] += seconds
    return out if out["hyper_conn"] else None


def hyper_seconds(context):
    """The run's {key: seconds}, worked out once and kept in `context`;
    None without a trace, a capture, or the block in it."""
    trace = context["trace"]
    if trace is None or not trace["busy_s"]:
        return None
    if "hyper_seconds" not in context:
        path = scope_shares.newest_capture()
        program = scopes.pick_program(
            scope_shares._capture_programs(path) if path else [],
            trace["ops"])
        context["hyper_seconds"] = None if program is None \
            else seconds_by_block(program, trace["ops"])
    return context["hyper_seconds"]


def share_of_busy(context):
    """Percent of device-busy time under a `HyperConnection` block."""
    seconds = hyper_seconds(context)
    if seconds is None:
        return None
    return 100.0 * seconds["hyper_conn"] / context["trace"]["busy_s"]


def mix_roofline(context):
    """The least time the chip could take to move the streams through the
    two mixes of every hyper-connection the steps ran (the builder's
    `hyper_connection_bytes_per_token` over the peak bytes/s: the mixes do
    (2 n + 1) multiply-adds an element moved, far under the machine's
    balance) over the time of the ops under `hc_pre` and `hc_post`,
    percent. A program that read and wrote each stream once a pass would
    read near the 72 % an element-wise pass reaches on this chip (590 of
    819 GB/s); whatever reads X once a stream of X' reads under it."""
    seconds = hyper_seconds(context)
    if seconds is None or not seconds["hyper_mix"]:
        return None
    import run as harness        # perfbench/run.py: its loader of builders
    builder = harness.load_module("builders", context["config"]["builder"])
    if not hasattr(builder, "hyper_connection_bytes_per_token"):
        return None
    tokens = context["tokens_per_step"] * context["steps"] / context["chips"]
    least_s = tokens * builder.hyper_connection_bytes_per_token(
        context["config"]) / context["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds["hyper_mix"]
