"""Share of device-busy time in scope class `mlp_block` (trace/scopes.py):
ops under a transformer layer's `ffn` scope: the two feed-forward Dense
blocks and the GELU between them; forward and backward."""
import scope_shares  # perfbench/scope_shares.py: run.py's directory is on sys.path


def compute(context):
    return scope_shares.share_of_busy(context, "mlp_block")
