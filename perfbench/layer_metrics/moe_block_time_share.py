"""Share of device-busy time in ops under a `parallel.MoELayer` block
(router, dispatch, grouped matmuls, combine), forward and backward; the
Adam update of its weights is `optimizer`'s, not this."""
import moe_shares  # perfbench/moe_shares.py: run.py's directory is on sys.path


def compute(context):
    return moe_shares.share_of_busy(context, ("block",))
