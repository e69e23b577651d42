"""Share of device-busy time in ops under the `topk_select` scope
(`ops/sparse_attention.py`: a k-th-value threshold a row by bisection on
the float32 bits, ties by position, and the mask a strip made from it)."""
import sparse_shares  # perfbench/sparse_shares.py: run.py's directory is on sys.path


def compute(context):
    return sparse_shares.share_of_busy(context, "topk_select")
