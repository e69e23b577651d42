"""Share of device-busy time in ops under the `ssd_scan` scope
(`ops/ssd.py`: the chunked state-space scan, its recomputation and its
backward) inside a `Mamba2Mixer` block."""
import hybrid_shares  # perfbench/hybrid_shares.py: run.py's directory is on sys.path


def compute(context):
    return hybrid_shares.share_of_busy(context, "ssm_scan")
