"""EvaByte (`model_type: evabyte`, `attention_class: eva`; EvaByte/EvaByte,
6.5 B): a byte-level decoder whose attention is EVA (Zheng et al., "Efficient
Attention via Control Variates", ICLR 2023, arXiv:2302.04542) and whose head
predicts the next ``num_pred_heads`` bytes of every position. Every layer,
x (S, U) float32, W = window, c = chunk, s = d^-1/2:

    n    = rmsnorm(x; 1 + g1)                      the gain is 1 + g
    q_h  = rope((Wq n)_h)   k_h = rope((Wk n)_h)   v_h = (Wv n)_h
    a_h[j,u] = softmax over the c positions u of chunk j of s (phi_h . k_h[u])
    kt_h[j]  = sum_u a_h[j,u] k_h[u] + mu_h        vt_h[j] = sum_u a_h[j,u] v_h[u]
    o_h[t]   = ONE softmax over the exact keys u <= t of t's own aligned
               window and the summaries (kt, vt) of every chunk of every
               earlier window (ops/eva_attention.py)
    y    = x + Wo [o_h]                            the add in float32
    out  = y + Wdown (silu(Wgate m) * Wup m),      m = rmsnorm(y; 1 + g2)
    z[t, i, :] = (Whead rmsnorm(out_L; 1 + gf))[t, V i : V (i + 1)]   float32
    loss = mean over i < P and t < S - i of CE(z[t, i, :], byte[t + 1 + i])

No biases, no QK-norm, rotate-half over all d channels. **Types:** the
residual stream is float32 from the embedding to the final norm whatever
the blocks' type (`fp32_skip_add`): a norm reads it in float32 and hands
the block its own type (the gain's: bfloat16 after ``cast``), a block's
output is cast up before the add. phi and mu stay float32 under ``cast``,
the logits are float32 (`fp32_logits`). **Memory:** the MLP runs in row
blocks of `MLP_ROWS` tokens, each under its own `jax.checkpoint`, so the
(tokens, 11008) gate, up and product of a layer exist a block at a time in
the forward and in the backward; the rows are independent and no value
changes. The float32 reference of these equations is
perfbench/reference/evabyte.py.

NOT here: multibyte self-speculative decoding and EVA's two kinds of cache
state (the model trains; it cannot be served), the multimodal variant's
image patches.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import initializer
from ..gluon import nn, utils
from ..gluon.block import HybridBlock
from ..ndarray import _apply
from ..ops.eva_attention import eva_attention
from ..ops.lm_ce import multibyte_cross_entropy
from .bert import MultiHeadAttention
from .olmoe import rope

__all__ = ["EvaByteModel", "EvaByteLayer", "EvaAttention", "UnitOffsetRMSNorm",
           "RowBlockedSwiGLU", "MultiByteLoss"]

#: tokens a row block of the MLP holds
MLP_ROWS = 4096


class UnitOffsetRMSNorm(HybridBlock):
    """y = x / sqrt(mean(x^2) + epsilon) * (1 + g), g from zeros
    (`norm_add_unit_offset`). Statistics in float32; the result has the
    GAIN's type, so a float32 stream enters a bfloat16 block through it."""

    def __init__(self, in_channels, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(in_channels,),
                                         init="zeros")

    def forward(self, x):
        eps = self._epsilon

        def fn(x, g):
            xf = x.astype(jnp.float32)
            ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
            return (xf * jax.lax.rsqrt(ms + eps)
                    * (1.0 + g.astype(jnp.float32))).astype(g.dtype)

        return _apply(fn, x, self.gamma.data())


class EvaAttention(MultiHeadAttention):
    """EVA attention over ``num_heads`` heads of units / num_heads: rotated
    q and k, two learned vectors a head (``phi``: the pooling direction,
    ``mu``: the pooled key's offset; float32 under ``cast``). Scopes inside
    the block's own: `rope`, `eva_pool`, `eva_local`, `eva_remote`,
    `eva_merge`."""

    def __init__(self, units, num_heads, window, chunk, rope_theta=1e5,
                 **kwargs):
        super().__init__(units, num_heads, attention="eva", causal=True,
                         use_bias=False, **kwargs)
        self._window, self._chunk, self._theta = window, chunk, rope_theta
        head_dim = units // num_heads
        with self.name_scope():
            self.phi = self.params.get("phi", shape=(num_heads, head_dim),
                                       init=initializer.Normal(0.02))
            self.mu = self.params.get("mu", shape=(num_heads, head_dim),
                                      init=initializer.Normal(0.02))

    def cast(self, dtype):
        super().cast(dtype)
        for p in (self.phi, self.mu):
            p.cast("float32")

    def project(self, x):
        q, k = self.split_heads(self.query(x)), self.split_heads(self.key(x))
        with jax.named_scope("rope"):
            q, k = (_apply(lambda t: rope(t, self._theta), t)
                    for t in (q, k))
        return q, k, self.split_heads(self.value(x))

    def heads_output(self, x, mask=None):
        b, s, _ = x.shape
        q, k, v = self.project(x)
        o = _apply(lambda *a: eva_attention(*a, self._window, self._chunk),
                   q, k, v, self.phi.data(), self.mu.data())
        return o.transpose((0, 2, 1, 3)).reshape((b, s, -1))


class RowBlockedSwiGLU(HybridBlock):
    """Wdown (silu(Wgate m) * Wup m) without biases, over (tokens, U) in
    row blocks of ``rows`` (the whole where ``rows`` does not divide the
    tokens), each block recomputed in its own backward. Scope `ffn`."""

    def __init__(self, units, hidden, rows=MLP_ROWS, **kwargs):
        super().__init__(**kwargs)
        self._rows = rows
        with self.name_scope():
            self.gate = nn.Dense(hidden, flatten=False, in_units=units,
                                 use_bias=False)
            self.up = nn.Dense(hidden, flatten=False, in_units=units,
                               use_bias=False)
            self.down = nn.Dense(units, flatten=False, in_units=hidden,
                                 use_bias=False)

    def forward(self, m):
        rows = self._rows

        @jax.checkpoint
        def block(t, w_gate, w_up, w_down):
            hidden = jax.nn.silu(t @ w_gate.T) * (t @ w_up.T)
            return hidden @ w_down.T

        def fn(m, *weights):
            with jax.named_scope("ffn"):
                t = m.reshape(-1, m.shape[-1])
                n = t.shape[0] // rows if rows and t.shape[0] % rows == 0 \
                    else 1
                out = [block(part, *weights) for part in jnp.split(t, n)]
                return jnp.concatenate(out).reshape(
                    m.shape[:-1] + out[0].shape[-1:])

        return _apply(fn, m, self.gate.weight.data(), self.up.weight.data(),
                      self.down.weight.data())


class EvaByteLayer(HybridBlock):
    """x + attn(norm1(x)), then y + mlp(norm2(y)), x and both sums float32."""

    def __init__(self, units, hidden, attention, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.norm1 = UnitOffsetRMSNorm(units, epsilon)
            self.attn = EvaAttention(units, **attention)
            self.norm2 = UnitOffsetRMSNorm(units, epsilon)
            self.mlp = RowBlockedSwiGLU(units, hidden)

    def forward(self, x):
        x = x + self.attn(self.norm1(x)).astype("float32")
        return x + self.mlp(self.norm2(x)).astype("float32")


class EvaByteModel(HybridBlock):
    """bytes (B, S) int -> logits (B, S, num_pred_heads, vocab) float32:
    head i of position t scores byte t + 1 + i. ``attention`` holds the
    keyword arguments of `EvaAttention` after ``units`` (``num_heads``,
    ``window``, ``chunk``, ``rope_theta``). ``remat_layers``: each layer's
    forward is recomputed in the backward (`gluon.utils.recompute`); what
    is kept a layer is its float32 input."""

    def __init__(self, vocab_size, units, hidden_size, num_layers, attention,
                 num_pred_heads=8, epsilon=1e-5, remat_layers=False,
                 **kwargs):
        super().__init__(**kwargs)
        self._remat = remat_layers
        self.num_pred_heads, self.vocab_size = num_pred_heads, vocab_size
        with self.name_scope():
            self.tok_embed = nn.Embedding(vocab_size, units)
            self.layers = nn.HybridSequential()
            for _ in range(num_layers):
                self.layers.add(EvaByteLayer(units, hidden_size, attention,
                                             epsilon))
            self.norm_f = UnitOffsetRMSNorm(units, epsilon)
            self.lm_head = nn.Dense(num_pred_heads * vocab_size,
                                    flatten=False, in_units=units,
                                    use_bias=False)

    def stream(self, token_ids):
        """The residual stream behind the last layer (B, S, U), float32."""
        x = self.tok_embed(token_ids).astype("float32")
        for layer in self.layers:
            x = utils.recompute(layer, x) if self._remat else layer(x)
        return x

    def features(self, token_ids):
        """The final norm's output (B, S, U): pair with MultiByteLoss."""
        return self.norm_f(self.stream(token_ids))

    def forward(self, token_ids):
        heads, vocab = self.num_pred_heads, self.vocab_size
        return _apply(
            lambda h, w: jnp.einsum(
                "bsu,vu->bsv", h, w, preferred_element_type=jnp.float32)
            .reshape(h.shape[:2] + (heads, vocab)),
            self.features(token_ids), self.lm_head.weight.data())


class MultiByteLoss:
    """The eight-head loss beside `ChunkedHeadLossBase`: ONE
    (num_pred_heads x vocab, U) map, head i of position t against
    labels[t + i] (labels[t] is byte t + 1), the last i positions of head i
    without a target, every head and position of equal weight, float32
    logits a block of rows at a time (ops/lm_ce.py
    `multibyte_cross_entropy`). Pair with ``FeaturesView(model)``; returns
    the loss a sample (B,). Scope `multibyte_head`."""

    def __init__(self, model):
        self._model = model

    def forward(self, hidden, labels):
        heads = self._model.num_pred_heads

        def fn(h, w, y):
            with jax.named_scope("multibyte_head"):
                per_token, count = multibyte_cross_entropy(h, w, y, heads)
                return per_token.sum(-1) / count

        return _apply(fn, hidden, self._model.lm_head.weight.data(), labels)

    __call__ = forward
