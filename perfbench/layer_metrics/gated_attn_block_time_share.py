"""Share of device-busy time in ops under a `GatedGroupedQueryAttention`
block: the five projections, the streamed attention kernels (they run under
the block and carry its path) and the sigmoid gate; forward, recomputed
forward and backward."""
import delta_shares  # perfbench/delta_shares.py: run.py's directory is on sys.path


def compute(context):
    return delta_shares.share_of_busy(context, "gated_attn_block")
