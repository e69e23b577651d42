"""Share of device-busy time in ops under the `eva_local` scope of an
`EvaAttention` block: the exact part, the S / W aligned windows as a batch
of causal self-attentions with their log-sum-exp (the streamed kernels on a
TPU), forward, recomputed forward and backward."""
import eva_shares  # perfbench/eva_shares.py: run.py's directory is on sys.path


def compute(context):
    return eva_shares.share_of_busy(context, "eva_local")
