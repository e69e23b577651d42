"""Share of device-busy time in scope class `embed_head_loss` (trace/scopes.py):
ops under `loss`, or under the model's root scope but no transformer
layer: embeddings and their LayerNorm, the final norm, the head, the
(chunked) loss."""
import scope_shares  # perfbench/scope_shares.py: run.py's directory is on sys.path


def compute(context):
    return scope_shares.share_of_busy(context, "embed_head_loss")
