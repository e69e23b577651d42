"""The latent attention's kernels' share of their roofline: the larger of
the operations and the bytes the causal scores require (q.k 192 wide, v
128, forward and backward, the builder's `latent_attention_flops_per_token`
and `latent_attention_bytes_per_token`; padded lanes and the backward's
recomputed scores are no work) over the chip's peaks, over the time of the
kernels' calls under the `MultiHeadLatentAttention` block."""
import latent_shares  # perfbench/latent_shares.py: run.py's directory is on sys.path


def compute(context):
    return latent_shares.flash_roofline(context)
