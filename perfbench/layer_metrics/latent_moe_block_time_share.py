"""Share of device-busy time in ops under a `LatentMoE` block: router,
latent down- and up-projection, held dispatch, the grouped matmuls
(`ragged-dot-*`), combine and the shared expert; forward, recomputed
forward and backward."""
import hybrid_shares  # perfbench/hybrid_shares.py: run.py's directory is on sys.path


def compute(context):
    return hybrid_shares.share_of_busy(context, "latent_moe_block")
