"""Share of device-busy time in ops under a `KimiDeltaAttention` block (the
two projections, the three short convolutions and L2 norms, the decay and
b, the delta rule, the gated norm), forward, recomputed forward and
backward; the Adam update of its weights is `optimizer`'s, not this."""
import delta_shares  # perfbench/delta_shares.py: run.py's directory is on sys.path


def compute(context):
    return delta_shares.share_of_busy(context, "linear_attn_block")
