"""Share of device-busy time in scope class `optimizer` (trace/scopes.py):
ops under `optimizer` (TrainStep's update loop), but for the
weight-gradient matmuls XLA fused the update behind
(update_fused_matmul_time_share)."""
import scope_shares  # perfbench/scope_shares.py: run.py's directory is on sys.path


def compute(context):
    return scope_shares.share_of_busy(context, "optimizer")
