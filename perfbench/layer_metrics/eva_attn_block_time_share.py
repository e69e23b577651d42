"""Share of device-busy time in ops under an `EvaAttention` block (the four
projections, the rotary embedding, the pooling, the exact part, the strips
over the summaries, the merge: the norm's output to W_o), forward, recomputed
forward and backward; the Adam update of its weights is `optimizer`'s, not
this."""
import eva_shares  # perfbench/eva_shares.py: run.py's directory is on sys.path


def compute(context):
    return eva_shares.share_of_busy(context, "eva_attn_block")
