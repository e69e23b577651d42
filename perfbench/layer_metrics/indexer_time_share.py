"""Share of device-busy time in ops under the `indexer` scope of a
`SparseGroupedQueryAttention` block: the three maps, the LayerNorm, the
indexer's rotary embedding, the index scores (every time they are made
again) and their backward, the head-averaged probabilities, the KL."""
import sparse_shares  # perfbench/sparse_shares.py: run.py's directory is on sys.path


def compute(context):
    return sparse_shares.share_of_busy(context, "indexer")
