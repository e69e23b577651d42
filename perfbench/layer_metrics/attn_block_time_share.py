"""Share of device-busy time in scope class `attn_block` (trace/scopes.py):
ops under a transformer layer and its MultiHeadAttention block: the
projections, the composite scores or the Pallas kernels, the output
projection; forward and backward."""
import scope_shares  # perfbench/scope_shares.py: run.py's directory is on sys.path


def compute(context):
    return scope_shares.share_of_busy(context, "attn_block")
