"""Share of device-busy time in the window kernels (`flash_window_fwd`,
`flash_window_bwd` of `ops/attention.py`), found by the names the program
gives them."""
import sambay_shares  # perfbench/sambay_shares.py: run.py's directory is on sys.path


def compute(context):
    return sambay_shares.share_of_busy(context, "window_kernels")
