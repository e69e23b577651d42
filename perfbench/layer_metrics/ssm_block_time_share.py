"""Share of device-busy time in ops under a `Mamba2Mixer` block (the two
projections, the causal convolution, the chunked scan, the gated group
norm), forward, recomputed forward and backward; the Adam update of its
weights is `optimizer`'s, not this."""
import hybrid_shares  # perfbench/hybrid_shares.py: run.py's directory is on sys.path


def compute(context):
    return hybrid_shares.share_of_busy(context, "ssm_block")
