"""Decoder-only (GPT-style) causal language model — the long-context model
family (SURVEY §5 long-context/SP; no GPT in the reference's zoo, this is
the TPU-era completion of its LM lineup alongside models/lstm_lm.py).

TPU-first choices:
- causal flash attention (ops/attention.py Pallas kernels) by default — the
  O(S) memory path that makes S >= 8k trainable on one chip;
- ring attention over an ``sp`` mesh axis for sequences beyond one chip
  (attention='ring');
- pre-norm blocks + weight-tied LM head (matmul-dominated, MXU-friendly);
- learned positions (static shapes; no data-dependent control flow).
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from .. import ndarray as nd
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ndarray import _apply
from .bert import MultiHeadAttention, mlp_tokens
from .lm_head import ChunkedHeadLossBase

__all__ = ["GPTModel", "TransformerDecoderLayer"]


class TransformerDecoderLayer(HybridBlock):
    """Pre-norm decoder block: x + attn(ln(x)); x + ffn(ln(x)).
    (B, S, U) in, (B, S, U) out. The attention half and both norms are
    (B, S, U); the MLP half (fc1, GELU, fc2) is (B x S, U) inside at
    B > 1 (`models.bert.mlp_tokens`, which says why), reshaped back
    before the residual add."""

    def __init__(self, units, hidden_size, num_heads, attention="flash",
                 tp_axis=None, sp_axis="sp", **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.ln1 = nn.LayerNorm(in_channels=units)
            self.attn = MultiHeadAttention(units, num_heads,
                                           attention=attention, causal=True,
                                           sp_axis=sp_axis, tp_axis=tp_axis)
            self.ln2 = nn.LayerNorm(in_channels=units)
            self.fc1 = nn.Dense(hidden_size, flatten=False, in_units=units,
                                activation=None)
            self.fc2 = nn.Dense(units, flatten=False, in_units=hidden_size)
        if tp_axis:
            self.fc1.weight.sharding = P(tp_axis, None)
            self.fc1.bias.sharding = P(tp_axis)
            self.fc2.weight.sharding = P(None, tp_axis)

    def forward(self, x):
        x = x + self.attn(self.ln1(x))
        h = self.ln2(x)
        with jax.named_scope("ffn"):
            h = mlp_tokens(h)
            h = self.fc2(nd.LeakyReLU(self.fc1(h), act_type="gelu"))
            h = h.reshape(x.shape)
        return x + h


class GPTModel(HybridBlock):
    """Decoder-only LM: tokens (B, S) int -> logits (B, S, vocab).

    The LM head is weight-tied to the token embedding (ref-era LM practice;
    one (V, U) matrix serves both gather and projection — XLA reuses it on
    the MXU without a transposed copy).
    """

    def __init__(self, vocab_size=32768, units=768, hidden_size=None,
                 num_layers=12, num_heads=12, max_length=2048,
                 attention="flash", tp_axis=None, sp_axis="sp", **kwargs):
        super().__init__(**kwargs)
        hidden_size = hidden_size or 4 * units
        self._max_length = max_length
        with self.name_scope():
            self.tok_embed = nn.Embedding(vocab_size, units)
            self.pos_embed = nn.Embedding(max_length, units)
            self.layers = nn.HybridSequential()
            for _ in range(num_layers):
                self.layers.add(TransformerDecoderLayer(
                    units, hidden_size, num_heads, attention=attention,
                    tp_axis=tp_axis, sp_axis=sp_axis))
            self.ln_f = nn.LayerNorm(in_channels=units)

    def features(self, token_ids):
        """Trunk output (B, S, U) — the pre-head activations (pair with
        ChunkedLMLoss to avoid materializing (B*S, V) logits)."""
        B, S = token_ids.shape
        if S > self._max_length:
            raise ValueError(
                "sequence length %d exceeds max_length %d (position table); "
                "construct GPTModel(max_length=...) large enough" %
                (S, self._max_length))
        pos = nd.arange(S, dtype="int32").reshape((1, S))
        h = self.tok_embed(token_ids) + self.pos_embed(pos)
        h = self.layers(h)
        return self.ln_f(h)

    def forward(self, token_ids):
        h = self.features(token_ids)
        # weight-tied head: logits = h @ E^T
        return _apply(lambda hd, e: hd @ e.T.astype(hd.dtype), h,
                      self.tok_embed.weight.data())


class ChunkedLMLoss(ChunkedHeadLossBase):
    """Loss head that fuses the (weight-tied) LM projection with a CHUNKED
    softmax-CE (ops/lm_ce.py): the full (T, V) logits never materialize —
    the vocab-CE HBM lever identified in docs/PERF_BERT.md. Use with the
    model's ``features`` output:

        gpt = GPTModel(...)
        loss_fn = ChunkedLMLoss(gpt)          # chunk=None auto-routes
        step = jit.TrainStep(FeaturesView(gpt), loss_fn, trainer)

    Gradients flow into the tied embedding through ``weight.data()`` the
    same way they do for any parameter the traced step reads."""

    def _head_params(self):
        return self._model.tok_embed.weight.data(), None


class FeaturesView(HybridBlock):
    """Expose a model's ``features`` as its forward (so TrainStep's
    net(*inputs) -> loss_fn(out, y) contract pairs the trunk with a fused
    loss head like ChunkedLMLoss). Shares the wrapped model's params;
    variadic so multi-input features (BERT's token_types/mask) pass
    through."""

    def __init__(self, model, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.model = model

    def forward(self, *args):
        return self.model.features(*args)
