"""Share of device-busy time in ops under a `HyperConnection` block: the
14 336-wide norm, x^ P, the sigmoids, the twenty Sinkhorn rounds and the two
mixes (Hpre X; Hres X + Hpost^T F) of every sublayer; forward, recomputed
forward and backward."""
import hyper_shares  # perfbench/hyper_shares.py: run.py's directory is on sys.path


def compute(context):
    return hyper_shares.share_of_busy(context)
