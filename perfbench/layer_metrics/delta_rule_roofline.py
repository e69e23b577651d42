"""The gated delta rule's share of its roofline: the larger of the
operations and the bytes that forward, recomputed forward and backward
require at a nominal chunk of 64 (the builder's
`delta_rule_flops_per_token`, `delta_rule_bytes_per_token`) over the chip's
peaks, over the time under the `delta_rule` scope."""
import delta_shares  # perfbench/delta_shares.py: run.py's directory is on sys.path


def compute(context):
    return delta_shares.rule_roofline(context)
