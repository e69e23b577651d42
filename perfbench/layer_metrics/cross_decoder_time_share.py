"""Share of device-busy time in the cross-decoder's two mixers: ops under
the scopes `gmu` (the gated memory unit) and `cross_attention` (attention
over the full-attention layer's K/V), the kernels of the latter included."""
import sambay_shares  # perfbench/sambay_shares.py: run.py's directory is on sys.path


def compute(context):
    return sambay_shares.share_of_busy(context, "cross_decoder")
