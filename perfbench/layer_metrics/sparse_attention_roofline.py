"""The attention over the chosen keys as a share of its roofline: the
larger of the operations and the bytes that the CHOSEN pairs require (the
builder's `sparse_attention_flops` at the 6 matmuls a pair that no program
can do without, 2 forward and 4 backward, as `model_flops_per_token` counts;
`sparse_attention_bytes`) over the chip's peaks, over the time under the
`sparse_attention` scope. A masked-dense form reads low by construction."""
import sparse_shares  # perfbench/sparse_shares.py: run.py's directory is on sys.path


def compute(context):
    return sparse_shares.attention_roofline(context)
