"""Share of device-busy time in scope class `norm_residual` (trace/scopes.py):
ops under a transformer layer but neither its attention block nor `ffn`:
LayerNorm, residual adds, dropout."""
import scope_shares  # perfbench/scope_shares.py: run.py's directory is on sys.path


def compute(context):
    return scope_shares.share_of_busy(context, "norm_residual")
