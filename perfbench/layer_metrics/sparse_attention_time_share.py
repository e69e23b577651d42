"""Share of device-busy time in ops under the `sparse_attention` scope
(`ops/sparse_attention.py`: softmax attention over the chosen keys,
forward, recomputed forward and backward)."""
import sparse_shares  # perfbench/sparse_shares.py: run.py's directory is on sys.path


def compute(context):
    return sparse_shares.share_of_busy(context, "sparse_attention")
