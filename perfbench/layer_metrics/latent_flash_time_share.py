"""Share of device-busy time in the attention kernels' calls under a
`MultiHeadLatentAttention` block, found by the kernels' names (`flash_fwd`,
`flash_bwd_dkvq`) and not by `custom-call`, which this program's delta
rule and grouped matmuls are too."""
import latent_shares  # perfbench/latent_shares.py: run.py's directory is on sys.path


def compute(context):
    return latent_shares.share_of_busy(context, "latent_flash")
