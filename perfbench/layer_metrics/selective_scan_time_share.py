"""Share of device-busy time in ops under the `selective_scan` scope
(`ops/selective_scan.py`: the Mamba-1 scan, its recomputations and its
backward)."""
import sambay_shares  # perfbench/sambay_shares.py: run.py's directory is on sys.path


def compute(context):
    return sambay_shares.share_of_busy(context, "scan")
