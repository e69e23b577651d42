"""Share of device-busy time in ops under the `multibyte_head` scope of the
step's loss: the eight prediction heads' one (2560, 4096) map, its float32
logits a block of rows at a time, the eight cross-entropies a position and
their backward."""
import eva_shares  # perfbench/eva_shares.py: run.py's directory is on sys.path


def compute(context):
    return eva_shares.share_of_busy(context, "multibyte_head")
