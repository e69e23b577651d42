"""Share of device-busy time in scope class `update_fused_matmul` (trace/scopes.py):
matmul-class fusions whose root is under `optimizer`: a block's weight
gradient with its Adam update fused behind it; the time is both's, so it
has a line of its own."""
import scope_shares  # perfbench/scope_shares.py: run.py's directory is on sys.path


def compute(context):
    return scope_shares.share_of_busy(context, "update_fused_matmul")
