"""Share of device-busy time in ops under a `MultiHeadLatentAttention`
block: W_q, W_kva, the latent's norm, W_kvb, the QK-norm and the rotation,
the streamed attention kernels (they run under the block and carry its
path), the head gate and W_o; forward, recomputed forward and backward."""
import latent_shares  # perfbench/latent_shares.py: run.py's directory is on sys.path


def compute(context):
    return latent_shares.share_of_busy(context, "latent_attn_block")
