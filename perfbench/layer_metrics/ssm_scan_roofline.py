"""The chunked scan's share of its roofline: the larger of the operations
and the bytes the algorithm requires (the builder's `ssd_flops_per_token`,
`ssd_bytes_per_token`; the recomputation not counted) over the chip's
peaks, over the time under the `ssd_scan` scope."""
import hybrid_shares  # perfbench/hybrid_shares.py: run.py's directory is on sys.path


def compute(context):
    return hybrid_shares.scan_roofline(context)
