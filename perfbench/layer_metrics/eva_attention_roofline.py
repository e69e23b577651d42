"""EVA attention as a share of its roofline: the larger of the operations
and the bytes that the SEEN pairs require (the builder's
`eva_attention_flops` at the 6 matmuls a pair that no program can do
without, 2 forward and 4 backward, as `model_flops_per_token` counts;
`eva_attention_bytes`) over the chip's peaks, over the time under the
`eva_pool`, `eva_local`, `eva_remote` and `eva_merge` scopes. A
masked-dense or recomputing form reads low by construction."""
import eva_shares  # perfbench/eva_shares.py: run.py's directory is on sys.path


def compute(context):
    return eva_shares.attention_roofline(context)
