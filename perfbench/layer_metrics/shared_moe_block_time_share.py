"""Share of device-busy time in ops under a `SharedExpertMoE` block: router,
held dispatch, the grouped matmuls (`ragged-dot-*`, by the instruction's
name), combine and the shared expert; forward, recomputed forward and
backward."""
import delta_shares  # perfbench/delta_shares.py: run.py's directory is on sys.path


def compute(context):
    return delta_shares.share_of_busy(context, "shared_moe_block")
