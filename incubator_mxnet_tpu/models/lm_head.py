"""Shared chunked LM loss-head base (ops/lm_ce.py wiring).

One forward for every vocab-projection head: subclasses provide
``_head_params() -> (weight (V, U), bias (V,) or None)`` — GPT's tied
embedding (models.gpt.ChunkedLMLoss), BERT's untied biased mlm_decoder
(models.bert.ChunkedMLMLoss). Lives in its own module so gpt.py and
bert.py (which import from each other's layer stacks) can both subclass
without a cycle."""
from __future__ import annotations

from ..ndarray import _apply

__all__ = ["ChunkedHeadLossBase"]


class ChunkedHeadLossBase:
    """Loss head fusing a (V, U) vocab projection with the CHUNKED
    softmax-CE (ops/lm_ce.py): the full (T, V) logits never materialize
    (what the chunking costs and saves on a v5e: PERF.md S6, PR 28). Pair
    with ``FeaturesView(model)`` so TrainStep feeds the trunk activations.
    A model whose layers have losses of their own (an indexer's KL, a
    router's balancing loss) hands ``forward`` (hidden, their sum a sample
    (B,)), and the sum is added to the head's loss."""

    def __init__(self, model, chunk=None):
        # chunk=None auto-routes (ops/lm_ce.py): dense below 128 MiB of
        # float32 logits; above, 512 to 1024 rows a chunk, chosen from
        # (T, V) alone so that a trip's four matmuls are compute-bound
        self._model = model
        self._chunk = chunk

    def _head_params(self):
        raise NotImplementedError

    def forward(self, hidden, labels):
        from ..ops.lm_ce import chunked_lm_cross_entropy
        w, b = self._head_params()
        own = None
        if isinstance(hidden, (tuple, list)):
            hidden, own = hidden

        def fn(h, w, y, b=None):
            losses = chunked_lm_cross_entropy(h, w, y, self._chunk,
                                              head_b=b)
            # gluon loss contract: per-sample mean over non-batch axes
            return losses.reshape(losses.shape[0], -1).mean(axis=1)

        if b is None:
            loss = _apply(fn, hidden, w, labels)
        else:
            loss = _apply(lambda h, w, b, y: fn(h, w, y, b), hidden, w, b,
                          labels)
        return loss if own is None else loss + own

    __call__ = forward
