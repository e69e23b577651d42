"""The grouped expert matmuls' share of their compute roofline: the expert
FLOPs the algorithm requires (the builder's `expert_flops_per_token`) over
the chip's peak, over the time under the `moe_experts` scope (the
`ragged-dot` custom calls and the SwiGLU between them, both directions)."""
import moe_shares  # perfbench/moe_shares.py: run.py's directory is on sys.path


def compute(context):
    return moe_shares.expert_matmul_roofline(context)
