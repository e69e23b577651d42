"""OLMoE (Muennighoff et al. 2024, arXiv:2409.02060; `model_type: olmoe`):
a decoder-only LM whose every layer is pre-norm attention with QK-norm and
RoPE, then a dropless mixture of small SwiGLU experts. No biases, RMSNorm
everywhere, an output head untied from the embedding.

    h   = x + Wo Attn(rope(rms_q(Wq n1(x))), rope(rms_k(Wk n1(x))), Wv n1(x))
    out = h + sum_{e in topk(p)} p_e Wdown_e(silu(Wgate_e n2(h)) * Wup_e n2(h))
          p = softmax(Wr n2(h)) in float32, used as it is (norm_topk_prob false)
    logits = Whead RMSNorm(out_L)

rms_q / rms_k normalise the WHOLE projection (all heads together) before it
is split into heads; rope is the rotate-half convention on each head. The
float32 reference of these equations is tests/olmoe_reference.py.

Built from the blocks every model shares: `nn.RMSNorm`, the attention of
`models/bert.py` (the streamed Pallas kernels at D = 128, `project` being
the one method that differs), `parallel.MoELayer`, and for training
`FeaturesView(model)` + `ChunkedUntiedLMLoss(model)` so the (S, V) logits
never exist at once.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ndarray import _apply
from ..parallel.moe import MoELayer
from .bert import MultiHeadAttention
from .lm_head import ChunkedHeadLossBase

__all__ = ["OLMoEModel", "OLMoETransformerDecoderLayer",
           "RotaryMultiHeadAttention", "ChunkedUntiedLMLoss", "rope"]


def rope(x, theta=10000.0):
    """Rotary position embedding, rotate-half convention, positions
    0..S-1. x (B, H, S, D) with D even: the pair (x[i], x[i + D/2]) turns
    by the angle pos * theta^(-2i/D). Float32 inside, x's type out."""
    s, d = x.shape[-2], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq   # (S, D/2)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


class RotaryMultiHeadAttention(MultiHeadAttention):
    """Causal multi-head attention without biases whose queries and keys
    are RMS-normalised over the whole projection (QK-norm, before the
    split into heads) and then rotated (RoPE)."""

    def __init__(self, units, num_heads, rope_theta=10000.0, epsilon=1e-5,
                 attention="flash", **kwargs):
        super().__init__(units, num_heads, attention=attention, causal=True,
                         use_bias=False, **kwargs)
        self._theta = rope_theta
        with self.name_scope():
            self.q_norm = nn.RMSNorm(in_channels=units, epsilon=epsilon)
            self.k_norm = nn.RMSNorm(in_channels=units, epsilon=epsilon)

    def project(self, x):
        q = self.split_heads(self.q_norm(self.query(x)))
        k = self.split_heads(self.k_norm(self.key(x)))
        with jax.named_scope("rope"):
            q, k = (_apply(lambda t: rope(t, self._theta), t)
                    for t in (q, k))
        return q, k, self.split_heads(self.value(x))


class OLMoETransformerDecoderLayer(HybridBlock):
    """x + attn(n1(x)), then h + moe(n2(h)); the MoE call sits under the
    layer's `ffn` scope, where a dense layer's MLP does."""

    def __init__(self, units, ffn_hidden, num_heads, num_experts, top_k,
                 norm_topk_prob=False, rope_theta=10000.0, epsilon=1e-5,
                 attention="flash", **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.ln1 = nn.RMSNorm(in_channels=units, epsilon=epsilon)
            self.attn = RotaryMultiHeadAttention(
                units, num_heads, rope_theta=rope_theta, epsilon=epsilon,
                attention=attention)
            self.ln2 = nn.RMSNorm(in_channels=units, epsilon=epsilon)
            self.moe = MoELayer(num_experts, units, ffn_hidden, top_k=top_k,
                                activation="silu", gated=True,
                                norm_topk_prob=norm_topk_prob)

    def forward(self, x):
        x = x + self.attn(self.ln1(x))
        h = self.ln2(x)
        with jax.named_scope("ffn"):
            h = self.moe(h)
        return x + h


class OLMoEModel(HybridBlock):
    """tokens (B, S) int -> logits (B, S, vocab). Defaults are
    OLMoE-1B-7B-0125's published config.json."""

    def __init__(self, vocab_size=50304, units=2048, ffn_hidden=1024,
                 num_layers=16, num_heads=16, num_experts=64, top_k=8,
                 norm_topk_prob=False, rope_theta=10000.0, epsilon=1e-5,
                 max_length=4096, attention="flash", **kwargs):
        super().__init__(**kwargs)
        self._max_length = max_length
        with self.name_scope():
            self.tok_embed = nn.Embedding(vocab_size, units)
            self.layers = nn.HybridSequential()
            for _ in range(num_layers):
                self.layers.add(OLMoETransformerDecoderLayer(
                    units, ffn_hidden, num_heads, num_experts, top_k,
                    norm_topk_prob=norm_topk_prob, rope_theta=rope_theta,
                    epsilon=epsilon, attention=attention))
            self.norm_f = nn.RMSNorm(in_channels=units, epsilon=epsilon)
            self.lm_head = nn.Dense(vocab_size, flatten=False,
                                    in_units=units, use_bias=False)

    def features(self, token_ids):
        """The final norm's output (B, S, U): pair with
        ChunkedUntiedLMLoss so the (B*S, V) logits never materialise."""
        if token_ids.shape[1] > self._max_length:
            raise ValueError(
                "sequence length %d exceeds max_length %d (the positions "
                "the configuration declares)"
                % (token_ids.shape[1], self._max_length))
        return self.norm_f(self.layers(self.tok_embed(token_ids)))

    def forward(self, token_ids):
        return self.lm_head(self.features(token_ids))


class ChunkedUntiedLMLoss(ChunkedHeadLossBase):
    """The chunked softmax-CE (ops/lm_ce.py) over a model's own untied,
    unbiased `lm_head`: use as ChunkedLMLoss is, with
    `jit.TrainStep(FeaturesView(model), ChunkedUntiedLMLoss(model), ...)`."""

    def _head_params(self):
        return self._model.lm_head.weight.data(), None
