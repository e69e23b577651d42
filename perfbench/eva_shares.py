"""Device time inside EvaByte's attention block and its eight-head loss, by
the names `models/evabyte.py` and `ops/eva_attention.py` give them: ops
under an `EvaAttention` block (the four projections, the rotary embedding,
everything below), and of those the ops under the scopes `eva_pool` (the
pooling weights, kt, vt), `eva_local` (the exact part: the windows' causal
attention, forward and backward), `eva_remote` (the strips over the
summaries) and `eva_merge` (the two parts under one normaliser); and, under
the step's `loss`, the scope `multibyte_head` (the eight heads' one map and
their cross-entropies). sparse_shares.py's reading of the capture for
another stem; the layer_metrics files of the five metrics are one call into
this file each. The work the roofline divides by is the builder's
(`eva_attention_flops`, `eva_attention_bytes`: the SEEN pairs alone), never
what the program computes.

An event's scope path is chosen by moe_shares.event_parts (trace/scopes.py's
rule for fusions). The per-layer recomputation and the strips' own put
`checkpoint` and `rematted_computation` among a path's components and take
no name away. An op under two of the scopes (none today) is booked to the
innermost, the last in its path.

On a program without these names (any parent of PR 45, any other model)
every reader here returns None and the result line leaves the metric out.
"""
import moe_shares  # perfbench/moe_shares.py: run.py's directory is on sys.path

scope_shares = moe_shares.scope_shares
scopes = moe_shares.scopes

#: `Block._alias()` of the block, as trace/scopes.py knows blocks
BLOCK_STEM = "evaattention"
SCOPES = ("eva_pool", "eva_local", "eva_remote", "eva_merge")
HEAD_SCOPE = "multibyte_head"
KEYS = ("eva_attn_block", HEAD_SCOPE) + SCOPES
#: matmuls a seen (query, key) pair REQUIRES a layer, with nothing run
#: twice: q k^T and a v forward; dV, dA, dq and dk backward. The builder's
#: `model_flops_per_token` counts the same six.
MATMULS_A_PAIR = 6


def seconds_by_scope(program, ops):
    """`ops` = the reduction's [[instruction text, class, seconds]] ->
    {key: seconds} over KEYS, or None where nothing ran under the block or
    the head."""
    out = dict.fromkeys(KEYS, 0.0)
    for text, _, seconds in ops:
        parts = moe_shares.event_parts(program, text) or ()
        if HEAD_SCOPE in parts:
            out[HEAD_SCOPE] += seconds
        if not any(BLOCK_STEM in p for p in parts):
            continue
        out["eva_attn_block"] += seconds
        inner = [p for p in parts if p in SCOPES]
        if inner:
            out[inner[-1]] += seconds
    return out if out["eva_attn_block"] or out[HEAD_SCOPE] else None


def eva_seconds(context):
    """The run's {key: seconds}, worked out once and kept in `context`;
    None without a trace, a capture, or the names in it."""
    trace = context["trace"]
    if trace is None or not trace["busy_s"]:
        return None
    if "eva_seconds" not in context:
        path = scope_shares.newest_capture()
        program = scopes.pick_program(
            scope_shares._capture_programs(path) if path else [],
            trace["ops"])
        context["eva_seconds"] = None if program is None \
            else seconds_by_scope(program, trace["ops"])
    return context["eva_seconds"]


def share_of_busy(context, *keys):
    """Percent of device-busy time booked to `keys` together."""
    seconds = eva_seconds(context)
    if seconds is None:
        return None
    return 100.0 * sum(seconds[k] for k in keys) / context["trace"]["busy_s"]


def attention_roofline(context):
    """The least time the chip could take for the attention over the SEEN
    pairs that the steps run (the larger of the builder's
    `eva_attention_flops(config, seq_len, 6)` over the peak FLOP/s and
    `eva_attention_bytes` over the peak bytes/s, a layer a sequence) over
    the time under the four scopes, percent. At 16 384 positions, 32 heads
    of 128, 24.1 M pairs a head the operations bound it: 1.19 TFLOP against
    1.61 GB a layer, 6.0 ms against 2.0 ms. No program does fewer than the
    six matmuls over the seen pairs, so none reads over 100 %; the masked
    half of a diagonal block, scores made again in a backward (seven
    matmuls), the strips' second forward and the pooling all read low."""
    seconds = eva_seconds(context)
    if seconds is None:
        return None
    under = sum(seconds[k] for k in SCOPES)
    if not under:
        return None
    import run as harness        # perfbench/run.py: its loader of builders
    builder = harness.load_module("builders", context["config"]["builder"])
    cfg, peaks = context["config"], context["peaks"]
    seq_len = context["workload"]["traffic"]["seq_len"]
    sequences = context["tokens_per_step"] * context["steps"] \
        / context["chips"] / seq_len
    least_s = sequences * cfg["num_layers"] * max(
        builder.eva_attention_flops(cfg, seq_len, MATMULS_A_PAIR)
        / peaks["bf16_flops_per_s"],
        builder.eva_attention_bytes(cfg, seq_len)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / under
