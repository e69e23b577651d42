"""Share of device-busy time in ops with a vocabulary-wide operand or
output: the output head, the loss, the embedding look-up, and their
gradients and optimizer updates (hlo_shapes.py)."""
import hlo_shapes  # perfbench/hlo_shapes.py: run.py's directory is on sys.path


def compute(context):
    return hlo_shapes.share_of_busy(context, "vocab")
