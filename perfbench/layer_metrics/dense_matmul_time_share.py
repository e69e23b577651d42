"""Share of device-busy time in the layers' dense matmuls: convolutions
and output fusions that touch neither the attention scores nor a
vocabulary-wide tensor, with the bias, activation or statistics XLA fused
into them (hlo_shapes.py)."""
import hlo_shapes  # perfbench/hlo_shapes.py: run.py's directory is on sys.path


def compute(context):
    return hlo_shapes.share_of_busy(context, "dense_matmul")
