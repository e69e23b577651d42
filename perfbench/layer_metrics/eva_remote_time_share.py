"""Share of device-busy time in ops under the `eva_pool`, `eva_remote` and
`eva_merge` scopes of an `EvaAttention` block: what the summaries cost
(pooling weights, kt and vt; a strip a window of queries against the
summaries it sees, every time it is made again; the merge under one
normaliser), forward and backward."""
import eva_shares  # perfbench/eva_shares.py: run.py's directory is on sys.path


def compute(context):
    return eva_shares.share_of_busy(context, "eva_pool", "eva_remote",
                                    "eva_merge")
