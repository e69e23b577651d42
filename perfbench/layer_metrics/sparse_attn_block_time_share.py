"""Share of device-busy time in ops under a `SparseGroupedQueryAttention`
block (the four projections, the head norms, the rotary embedding, the
indexer, the selection, the attention over the chosen keys), forward,
recomputed forward and backward; the Adam update of its weights is
`optimizer`'s, not this."""
import sparse_shares  # perfbench/sparse_shares.py: run.py's directory is on sys.path


def compute(context):
    return sparse_shares.share_of_busy(context, "sparse_attn_block")
