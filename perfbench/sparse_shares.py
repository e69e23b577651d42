"""Device time inside the Keye-VL 2.0 decoder's attention block, by the names
`models/keye_vl2.py` and `ops/sparse_attention.py` give them: ops under a
`SparseGroupedQueryAttention` block (the projections, the two head norms,
the rotary embedding, everything below), and of those the ops under the
scopes `indexer` (the three maps, the LayerNorm, the index scores forward
and backward, the head-averaged probabilities, the KL), `topk_select` (the
threshold a row and the mask a strip) and `sparse_attention` (softmax
attention under the mask, forward and backward). moe_shares.py's reading of
the capture for another stem; the layer_metrics files of the five metrics
are one call into this file each. The work the roofline divides by is the
builder's (`sparse_attention_flops`, `sparse_attention_bytes`: the CHOSEN
pairs alone), never what the program computes.

An event's scope path is chosen by moe_shares.event_parts (trace/scopes.py's
rule for fusions). The per-layer recomputation and the scans over strips
put `checkpoint`, `rematted_computation`, `while` and `body` among a path's
components and take no name away. An op under two of the scopes (none
today) is booked to the innermost, the last in its path.

On a program without these names (any parent of PR 41, any other model)
every reader here returns None and the result line leaves the metric out.
"""
import moe_shares  # perfbench/moe_shares.py: run.py's directory is on sys.path

scope_shares = moe_shares.scope_shares
scopes, reduce = moe_shares.scopes, moe_shares.reduce

#: `Block._alias()` of the block, as trace/scopes.py knows blocks
BLOCK_STEM = "sparsegroupedqueryattention"
SCOPES = ("indexer", "topk_select", "sparse_attention")
KEYS = ("sparse_attn_block",) + SCOPES
#: matmuls a chosen (query, key) pair REQUIRES a layer, with nothing run
#: twice: q k^T and a v forward; dV, dA, dq and dk backward. The builder's
#: `model_flops_per_token` counts the same six. A backward that scores again
#: (seven) or a recomputed forward (eight) is the program's cost, not work.
MATMULS_A_PAIR = 6


def seconds_by_scope(program, ops):
    """`ops` = the reduction's [[instruction text, class, seconds]] ->
    {key: seconds} over KEYS, or None where nothing ran under the block."""
    out = dict.fromkeys(KEYS, 0.0)
    for text, _, seconds in ops:
        parts = moe_shares.event_parts(program, text) or ()
        if not any(BLOCK_STEM in p for p in parts):
            continue
        out["sparse_attn_block"] += seconds
        inner = [p for p in parts if p in SCOPES]
        if inner:
            out[inner[-1]] += seconds
    return out if out["sparse_attn_block"] else None


def sparse_seconds(context):
    """The run's {key: seconds}, worked out once and kept in `context`;
    None without a trace, a capture, or the block in it."""
    trace = context["trace"]
    if trace is None or not trace["busy_s"]:
        return None
    if "sparse_seconds" not in context:
        path = scope_shares.newest_capture()
        program = scopes.pick_program(
            scope_shares._capture_programs(path) if path else [],
            trace["ops"])
        context["sparse_seconds"] = None if program is None \
            else seconds_by_scope(program, trace["ops"])
    return context["sparse_seconds"]


def share_of_busy(context, key):
    """Percent of device-busy time booked to `key`."""
    seconds = sparse_seconds(context)
    if seconds is None:
        return None
    return 100.0 * seconds[key] / context["trace"]["busy_s"]


def attention_roofline(context):
    """The least time the chip could take for the attention over the CHOSEN
    keys that the steps run (the larger of the builder's
    `sparse_attention_flops(config, seq_len, 6)` over the peak FLOP/s and
    `sparse_attention_bytes` over the peak bytes/s, a layer a sequence) over
    the time under `sparse_attention`, percent. At 16 384 positions, 2048
    keys a query, 32 heads of 128 the operations bound it: 1.55 TFLOP
    against 0.91 GB a layer, 7.8 ms against 1.1 ms. No program does fewer
    than the six matmuls over the chosen pairs, so none reads over 100 %;
    one that scores every causal pair and masks covers 4.27 x the pairs at
    seven matmuls each (its backward scores again) and cannot read above
    20 %."""
    seconds = sparse_seconds(context)
    if seconds is None or not seconds["sparse_attention"]:
        return None
    import run as harness        # perfbench/run.py: its loader of builders
    builder = harness.load_module("builders", context["config"]["builder"])
    cfg, peaks = context["config"], context["peaks"]
    seq_len = context["workload"]["traffic"]["seq_len"]
    sequences = context["tokens_per_step"] * context["steps"] \
        / context["chips"] / seq_len
    least_s = sequences * cfg["num_layers"] * max(
        builder.sparse_attention_flops(cfg, seq_len, MATMULS_A_PAIR)
        / peaks["bf16_flops_per_s"],
        builder.sparse_attention_bytes(cfg, seq_len)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / seconds["sparse_attention"]
