"""The held experts' grouped matmuls' share of their compute roofline: the
FLOPs of the live rows the algorithm requires (the builder's
`held_expert_flops_per_token`) over the chip's peak, over the time under
the `moe_experts` scope (the `ragged-dot` custom calls and the relu^2
between them, all three passes)."""
import hybrid_shares  # perfbench/hybrid_shares.py: run.py's directory is on sys.path


def compute(context):
    return hybrid_shares.held_expert_roofline(context)
