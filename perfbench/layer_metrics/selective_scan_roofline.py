"""The selective scan's share of its roofline: the bytes the algorithm
must move (the builder's `scan_bytes_per_token`; no recomputation, no
state) over the chip's peak bandwidth, over the time under the
`selective_scan` scope."""
import sambay_shares  # perfbench/sambay_shares.py: run.py's directory is on sys.path


def compute(context):
    return sambay_shares.scan_roofline(context)
