"""Share of device-busy time in ops under the `delta_rule` scope of a
`KimiDeltaAttention` block (`ops/delta_rule.py`: the decayed products, the
triangular solve, the scan over chunks), forward, recomputed forward and
backward."""
import delta_shares  # perfbench/delta_shares.py: run.py's directory is on sys.path


def compute(context):
    return delta_shares.share_of_busy(context, "delta_rule")
