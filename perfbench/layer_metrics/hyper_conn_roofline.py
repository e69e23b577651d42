"""The hyper-connections' two mixes' share of their roofline: the bytes the
streams must move through Hpre X and Hres X + Hpost^T F (the builder's
`hyper_connection_bytes_per_token`, bfloat16 streams, forward and backward)
over the chip's peak bytes/s, over the time of the ops under the scopes
`hc_pre` and `hc_post`, whatever implements them."""
import hyper_shares  # perfbench/hyper_shares.py: run.py's directory is on sys.path


def compute(context):
    return hyper_shares.mix_roofline(context)
