"""Share of device-busy time in ops that read or write a (seq_len,
seq_len) score tensor: the composite attention's Q K^T, softmax, P V and
their gradients (hlo_shapes.py). Reads 0 where the Pallas kernels keep the
scores on chip."""
import hlo_shapes  # perfbench/hlo_shapes.py: run.py's directory is on sys.path


def compute(context):
    return hlo_shapes.share_of_busy(context, "scores")
