"""Keye-VL 2.0's decoder (`model_type: KeyeVL2`; Keye-VL-2.0-30B-A3B): every
layer is grouped-query attention over the keys a lightning indexer picks
(DeepSeek-V3.2's sparse attention, arXiv:2512.02556, on grouped-query heads
in place of latent ones), then a mixture of SwiGLU experts, behind pre-norm
residuals. RMSNorm everywhere, no biases, an untied head, rotary positions
from three id streams (text: all three equal). x (S, U), positions p (3, S):

    n   = rmsnorm(x; g1)
    q_h = mrope(rms_d(Wq n)_h, p)   k_g = mrope(rms_d(Wk n)_g, p)
    v_g = (Wv n)_g                  query head h reads g = h // (H / G)
          rms_d: RMSNorm over the head's d channels, one gain for q, one
          for k;  mrope: rotate-half, frequency i < d / 2 is
          theta^(-2i/d) and turns by p[0], p[1] or p[2] as `mrope_section`
          (a, b, c) says: [0, a), [a, a + b), the rest
    nb  = stop_gradient(n)          the indexer learns from its own loss
    qI_j = ropeI((WqI nb)_j)        kI = ropeI(layernorm(WkI nb))
    w   = (Ww nb) J^-1/2 dI^-1/2    ropeI: the first half of the dI
                                    channels, rotate-half, by p[0]
    I[t,s] = sum_j w[t,j] relu(qI_j[t] . kI[s])            s <= t, float32
    S_t = the min(topk, t + 1) keys of largest I[t,s]; ties to the lower s
    y   = x + Wo [softmax attention of q_h over S_t]_h
    LI  = mean_t KL(stop_gradient(mean_h a_h[t,.]) || softmax_{S_t} I[t,.])
    m   = rmsnorm(y; g2);  r = softmax(Wr m) (float32);  T = top-k(r)
    out = y + sum over e in T and held of (r_e / sum_T r) W2_e(silu(W1_e m) * W3_e m)

and the training loss is the LM loss + the layers' LI (`features` hands the
sum out beside the hidden states, and `ChunkedUntiedLMLoss` adds it). The LM
loss has no gradient into WqI, WkI, Ww (the choice is not differentiable)
and LI none into anything else. The selection, the attention over it and
the KL are ops/sparse_attention.py. The vision tower is NOT here: `features`
takes the three position streams a multimodal input would give, and text
is the three alike.

A layer's experts can be ONE CHIP'S SHARE of an expert-parallel group
(`held=(first, count)`: `parallel.MoELayer` routes over all the experts and
computes its own experts' part of the sum). The float32 reference of these
equations is perfbench/reference/keye-vl-2.0-30b-a3b.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import ndarray as nd
from ..gluon import nn, utils
from ..gluon.block import HybridBlock
from ..ndarray import _apply
from ..ops.sparse_attention import (ATTENDED_NAME, TOPK_NAME,
                                     sparse_attention)
from ..parallel.moe import MoELayer
from .bert import MultiHeadAttention

__all__ = ["KeyeVL2Model", "KeyeVL2Layer", "SparseGroupedQueryAttention",
           "mrope", "rope_first_half"]

#: the indexer's LayerNorm
_LN_EPS = 1e-6
#: what a recomputed layer keeps of its forward: the selection (against the
#: same bits or not at all) and the attention's output and statistics
_KEPT = jax.checkpoint_policies.save_only_these_names(TOPK_NAME,
                                                      ATTENDED_NAME)


def _rotate(x, angle):
    """x (B, S, heads, D), angle (B, S, D / 2): the pair (x[i], x[i + D/2])
    turns by angle[i]. Float32 inside, x's type out."""
    cos, sin = jnp.cos(angle)[:, :, None], jnp.sin(angle)[:, :, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def mrope(x, positions, theta, sections):
    """Rotary embedding from three position streams: x (B, S, heads, D),
    positions (3, B, S) int; frequency i < D / 2 is theta^(-2i/D) and turns
    by the stream `sections` = (a, b, c), a + b + c = D / 2, gives it."""
    half = x.shape[-1] // 2
    if sum(sections) != half:
        raise ValueError("mrope_section %r does not cover %d frequencies"
                         % (tuple(sections), half))
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    pos = positions.astype(jnp.float32)
    angle = jnp.concatenate([
        pos[stream][..., None] * inv_freq[start:start + width]
        for stream, (start, width) in enumerate(zip(
            (0, sections[0], sections[0] + sections[1]), sections))], -1)
    return _rotate(x, angle)


def rope_first_half(x, positions, theta):
    """The indexer's rotary embedding: the first half of x's channels
    (B, S, heads, D) turns, rotate-half, by positions (B, S)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half // 2, dtype=jnp.float32)
                         / (half // 2))
    angle = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.concatenate([_rotate(x[..., :half], angle), x[..., half:]],
                           axis=-1)


class SparseGroupedQueryAttention(MultiHeadAttention):
    """Causal grouped-query attention over the ``topk`` keys a query's
    lightning indexer scores highest: per-head QK-norm, rotary positions
    from three streams, ``indexer_heads`` index heads of ``indexer_dim`` on
    one index key head. ``forward(x, positions)`` -> (y, LI (B,)): the
    block's output and the indexer's KL loss, which alone trains the
    indexer (its input is detached). Scopes inside the block's own: `rope`,
    `indexer`, `topk_select`, `sparse_attention`."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 indexer_heads, indexer_dim, topk, rope_theta=1e7,
                 mrope_section=(16, 24, 24), epsilon=1e-6, **kwargs):
        super().__init__(units, num_heads, attention="sparse", causal=True,
                         use_bias=False, num_kv_heads=num_kv_heads,
                         head_dim=head_dim, **kwargs)
        self._head_dim, self._topk = head_dim, topk
        self._theta, self._sections = rope_theta, tuple(mrope_section)
        self._index = (indexer_heads, indexer_dim)
        with self.name_scope():
            self.q_norm = nn.RMSNorm(in_channels=head_dim, epsilon=epsilon)
            self.k_norm = nn.RMSNorm(in_channels=head_dim, epsilon=epsilon)
            self.index_q = nn.Dense(indexer_heads * indexer_dim,
                                    flatten=False, in_units=units,
                                    use_bias=False)
            self.index_k = nn.Dense(indexer_dim, flatten=False,
                                    in_units=units, use_bias=False)
            self.index_k_norm = nn.LayerNorm(in_channels=indexer_dim,
                                             epsilon=_LN_EPS)
            self.index_w = nn.Dense(indexer_heads, flatten=False,
                                    in_units=units, use_bias=False)

    def project(self, x, positions):
        """-> q (B, S, H, D), k, v (B, S, G, D): normed and rotated."""
        b, s, _ = x.shape
        d = self._head_dim
        q = self.q_norm(self.query(x).reshape((b, s, self._num_heads, d)))
        k = self.k_norm(self.key(x).reshape((b, s, self._num_kv_heads, d)))
        with jax.named_scope("rope"):
            q, k = (_apply(lambda t, p: mrope(t, p, self._theta,
                                              self._sections), t, positions)
                    for t in (q, k))
        return q, k, self.value(x).reshape((b, s, self._num_kv_heads, d))

    def index(self, x, positions):
        """-> qI (B, S, J, dI), kI (B, S, dI), w (B, S, J) float32, of the
        DETACHED input."""
        b, s, _ = x.shape
        heads, dim = self._index
        nb = _apply(jax.lax.stop_gradient, x)

        def rotated(t, p):
            return rope_first_half(t, p[0], self._theta)

        qi = _apply(rotated, self.index_q(nb).reshape((b, s, heads, dim)),
                    positions)
        ki = _apply(rotated, self.index_k_norm(self.index_k(nb)).reshape(
            (b, s, 1, dim)), positions).reshape((b, s, dim))
        w = _apply(lambda t: t.astype(jnp.float32)
                   * (heads ** -0.5 * dim ** -0.5), self.index_w(nb))
        return qi, ki, w

    def heads_output(self, x, positions):
        """x (B, S, U) -> (the heads' outputs side by side (B, S, H * D),
        LI (B,))."""
        b, s, _ = x.shape
        q, k, v = self.project(x, positions)
        with jax.named_scope("indexer"):
            qi, ki, w = self.index(x, positions)
        o, li = _apply(lambda *a: sparse_attention(*a, self._topk),
                       q, k, v, qi, ki, w)
        return o.reshape((b, s, -1)), li

    def forward(self, x, positions):
        o, li = self.heads_output(x, positions)
        return self.proj(o), li


class KeyeVL2Layer(HybridBlock):
    """x + attn(n1(x)), then h + moe(n2(h)) -> (out, the layer's own loss
    (B,)): the indexer's KL."""

    def __init__(self, units, attention, moe, epsilon=1e-6, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.norm1 = nn.RMSNorm(in_channels=units, epsilon=epsilon)
            self.attn = SparseGroupedQueryAttention(
                units, epsilon=epsilon, **attention)
            self.norm2 = nn.RMSNorm(in_channels=units, epsilon=epsilon)
            self.moe = MoELayer(hidden_size=units, activation="silu",
                                gated=True, router="softmax",
                                z_loss_coef=0.0, **moe)

    def attend(self, x, positions):
        y, li = self.attn(self.norm1(x), positions)
        return x + y, li

    def forward(self, x, positions):
        x, li = self.attend(x, positions)
        h = self.norm2(x)
        with jax.named_scope("ffn"):
            h = self.moe(h)
        return x + h, li


class KeyeVL2Model(HybridBlock):
    """tokens (B, S) int -> logits (B, S, vocab). ``attention`` and ``moe``
    are the keyword arguments of `SparseGroupedQueryAttention` after
    ``units`` and of `parallel.MoELayer` (``num_experts``, ``ffn_hidden``,
    ``top_k``, ``norm_topk_prob``, ``held``). ``remat_layers``: each layer's
    forward is recomputed in the backward (`gluon.utils.recompute`) but for
    the selection (the indexer's operands and the thresholds: the choice
    means the same keys only against the same bits) and the attention's
    output and softmax statistics, which are kept."""

    def __init__(self, vocab_size, units, num_layers, attention, moe,
                 epsilon=1e-6, remat_layers=False, **kwargs):
        super().__init__(**kwargs)
        self._remat = remat_layers
        with self.name_scope():
            self.tok_embed = nn.Embedding(vocab_size, units)
            self.layers = nn.HybridSequential()
            for _ in range(num_layers):
                self.layers.add(KeyeVL2Layer(
                    units, attention, moe, epsilon=epsilon))
            self.norm_f = nn.RMSNorm(in_channels=units, epsilon=epsilon)
            self.lm_head = nn.Dense(vocab_size, flatten=False,
                                    in_units=units, use_bias=False)

    @staticmethod
    def text_positions(token_ids):
        """0 .. S - 1 on all three streams: (3, B, S)."""
        b, s = token_ids.shape
        return nd.NDArray(jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32), (3, b, s)))

    def features(self, token_ids, positions=None):
        """-> (the final norm's output (B, S, U), the layers' own losses
        summed (B,) float32): pair with ChunkedUntiedLMLoss, which adds the
        second to the LM loss. ``positions`` (3, B, S) int, text's where
        not given."""
        if positions is None:
            positions = self.text_positions(token_ids)
        x = self.tok_embed(token_ids)
        total = None
        for layer in self.layers:
            x, li = utils.recompute(layer, x, positions, policy=_KEPT) \
                if self._remat else layer(x, positions)
            total = li if total is None else total + li
        return self.norm_f(x), total

    def forward(self, token_ids, positions=None):
        return self.lm_head(self.features(token_ids, positions)[0])
