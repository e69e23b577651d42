"""Share of device-busy time in the MoE block's `router`, `moe_dispatch`
and `moe_combine` scopes: the softmax and top-k, the two sorts, the gather
of token rows, the un-sort and the weighted sum — what is not expert
arithmetic; forward and backward."""
import moe_shares  # perfbench/moe_shares.py: run.py's directory is on sys.path


def compute(context):
    return moe_shares.share_of_busy(
        context, ("router", "moe_dispatch", "moe_combine"))
