"""The window kernels' share of their roofline, compute-bound: the FLOPs of
the live band only (sum over queries of min(i + 1, window) keys, forward +
backward) over the chip's peak, over the time in `flash_window_fwd` and
`flash_window_bwd`."""
import sambay_shares  # perfbench/sambay_shares.py: run.py's directory is on sys.path


def compute(context):
    return sambay_shares.window_roofline(context)
