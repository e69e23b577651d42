"""Device time inside the Ling 3.0 model's new blocks, by the names
`models/ling3.py`, `ops/attention.py` and `parallel/moe.py` give them: ops
under a `MultiHeadLatentAttention` block (the five maps, the latent's norm,
the QK-norm and rotation, the streamed kernels, the head gate), of those
the attention kernels' calls BY KERNEL NAME (`flash_fwd`, `flash_bwd_dkvq`:
a `custom-call` is also the delta rule's and the grouped matmuls'), and ops
under the scope `router` of a `SharedExpertMoE` block (scores, the group
choice under `router_groups`, top-k, the balancing rule). delta_shares.py's
reading of the capture for other stems; the layer_metrics files of the four
metrics are one call into this file each. The work the roofline divides by
is the builder's (`latent_attention_flops_per_token`,
`latent_attention_bytes_per_token`: the mathematics' 192 + 128 a causal
pair, padded lanes no work), never what the kernels compute.

On a program without these names (any parent of PR 48, any other model)
every reader here returns None and the result line leaves the metric out.
"""
import moe_shares  # perfbench/moe_shares.py: run.py's directory is on sys.path

scope_shares = moe_shares.scope_shares
scopes, reduce = moe_shares.scopes, moe_shares.reduce

#: `Block._alias()` of the two blocks, as trace/scopes.py knows blocks
MLA_STEM = "multiheadlatentattention"
MOE_STEM = "sharedexpertmoe"
ROUTER_SCOPE = "router"
#: the streamed kernels' names (`ops/attention.py`), as the instructions of
#: their calls carry them
FLASH_KERNELS = ("flash_fwd", "flash_bwd_dkvq")
KEYS = ("latent_attn_block", "latent_flash", "group_router")
#: forward-equivalents of the causal scores the model REQUIRES of a step:
#: the forward's two matmuls and the backward's four
PASSES = 3


def seconds_by_block(program, ops):
    """`ops` = the reduction's [[instruction text, class, seconds]] ->
    {key: seconds} over KEYS, or None where nothing ran under a
    `MultiHeadLatentAttention` block."""
    out = dict.fromkeys(KEYS, 0.0)
    for text, _, seconds in ops:
        parts = moe_shares.event_parts(program, text) or ()
        if any(MLA_STEM in p for p in parts):
            out["latent_attn_block"] += seconds
            if any(k in reduce.parse(text)[0] for k in FLASH_KERNELS):
                out["latent_flash"] += seconds
        elif ROUTER_SCOPE in parts and any(MOE_STEM in p for p in parts):
            out["group_router"] += seconds
    return out if out["latent_attn_block"] else None


def latent_seconds(context):
    """The run's {key: seconds}, worked out once and kept in `context`;
    None without a trace, a capture, or the block in it."""
    trace = context["trace"]
    if trace is None or not trace["busy_s"]:
        return None
    if "latent_seconds" not in context:
        path = scope_shares.newest_capture()
        program = scopes.pick_program(
            scope_shares._capture_programs(path) if path else [],
            trace["ops"])
        context["latent_seconds"] = None if program is None \
            else seconds_by_block(program, trace["ops"])
    return context["latent_seconds"]


def share_of_busy(context, key):
    """Percent of device-busy time booked to `key`; None where it is 0."""
    seconds = latent_seconds(context)
    if seconds is None or not seconds[key]:
        return None
    return 100.0 * seconds[key] / context["trace"]["busy_s"]


def flash_roofline(context):
    """The least time the chip could take for the latent attention the
    steps require (the larger of the builder's
    `latent_attention_flops_per_token(config, seq_len, 3)` over the peak
    FLOP/s and `latent_attention_bytes_per_token` over the peak bytes/s)
    over the time of the attention kernels' calls under the block,
    percent. At 8192 positions, 32 heads, 192 + 128 a pair the operations
    bound it: 252 MFLOP against 123 kB a token, 1.28 us against 0.15 us.
    The backward kernel computes the scores again (a seventh matmul of
    six) and the diagonal blocks' masked half, so a kernel at the MXU's
    peak would read 80 to 86 %, never over 100."""
    seconds = latent_seconds(context)
    if seconds is None or not seconds["latent_flash"]:
        return None
    import run as harness        # perfbench/run.py: its loader of builders
    builder = harness.load_module("builders", context["config"]["builder"])
    cfg, peaks = context["config"], context["peaks"]
    tokens = context["tokens_per_step"] * context["steps"] / context["chips"]
    least_s = tokens * max(
        builder.latent_attention_flops_per_token(
            cfg, context["workload"]["traffic"]["seq_len"], PASSES)
        / peaks["bf16_flops_per_s"],
        builder.latent_attention_bytes_per_token(cfg)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / seconds["latent_flash"]
