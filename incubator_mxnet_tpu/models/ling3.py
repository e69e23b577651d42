"""Ling 3.0 (`model_type: bailing_hybrid`; Ling-3.0-flash): a decoder whose
every layer is a mixer and a feed-forward block behind pre-norm residuals,

    x <- x + Mixer(RMSNorm(x));  x <- x + FFN(RMSNorm(x))

RMSNorm with a gain, a final RMSNorm, an untied head, no biases. The mixer
is read from a pattern string (the source's `layer_group_size`: the last
layer of every group is `M`):

    K   Kimi Delta Attention as models/solar_open2.py builds it, here with
        FULL-RANK decay and gate maps, the bounded decay
        g = lower * sigmoid(exp(A_log) (h W_f + dt_bias)) in (lower, 0) and
        b = sigmoid(h w_b) in (0, 1): `KimiDeltaAttention(rank="full",
        decay=("bounded", lower), neg_eigval=False)`.
    M   multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 section
        2.1, without the low-rank query). Per head, d_n = nope, d_r = rope:
        [q_n (d_n); q_r (d_r)] = h W_q
        [c (latent); k_r (d_r)] = h W_kva;     c^ = RMSNorm(c)
        [k_n (d_n); v (d_v)] = c^ W_kvb
        q_n, k_n <- RMSNorm_{d_n}(.) with a gain each (QK-norm on the
            non-rotary parts); q_r, k_r <- rotary(theta, interleaved pairs)
        k = [k_n; k_r]: ONE rotary key a position, the same for every head
        o = softmax_causal(q k^T / sqrt(d_n + d_r)) v      (q, k d_n + d_r
            wide, v d_v: the streamed kernels' own value width)
        out = W_o concat_h(o_h * sigmoid(h w_h))           a gate a HEAD
    FFN the first ``dense_layers`` layers: a dense SwiGLU; every other one
        `SharedExpertMoE` (sigmoid scores, a selection bias, the choice
        under a group limit, a shared expert), told which experts it holds.

The float32 reference of these equations is
perfbench/reference/ling-3.0-flash.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import telemetry
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ndarray import _apply
from ..ops.attention import attention_route, flash_attention
from .phi4flash import SwiGLU
from .solar_open2 import (KimiDeltaAttention, MixerStackLM, SharedExpertMoE,
                          SolarOpen2Layer)

__all__ = ["Ling3Model", "MultiHeadLatentAttention"]

#: the pattern's letters: Kimi Delta Attention, multi-head latent attention
MIXERS = "KM"

_ROUTES = telemetry.counter(
    "mxtpu_latent_attention_total",
    "MultiHeadLatentAttention calls traced, by the path their attention "
    "took (ops.attention.attention_route: streamed Pallas kernels at q.k "
    "wider than v, or the XLA composite).", ("route",))


def rope_interleaved(x, theta):
    """Rotary position embedding on INTERLEAVED pairs, positions 0..S-1.
    x (..., S, D), D even: the pair (x[2i], x[2i + 1]) turns by the angle
    pos * theta^(-2i / D). Float32 inside and out."""
    s, d = x.shape[-2], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq   # (S, D/2)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     -1).reshape(x.shape)


class MultiHeadLatentAttention(HybridBlock):
    """The `M` mixer: ``num_heads`` heads whose keys and values come up
    from one ``latent``-wide vector a position and share one rotary key.
    q and k are ``nope_dim + rope_dim`` wide, v ``v_dim``; the attention
    is `ops.attention.flash_attention` (causal, scale 1 / sqrt(q's width)),
    which takes the streamed kernels wherever they run and the composite
    elsewhere: `mxtpu_latent_attention_total{route}` says which.

    Scopes inside the block's own: `mla_down` (W_kva, the latent's norm),
    `mla_up` (W_q, W_kvb), `mla_rope` (the QK-norm of the non-rotary
    parts, the rotation, k put together), `mla_gate`; the kernels'
    `flash_fwd` / `flash_bwd_dkvq` run under the block's path. The three
    norms' gains stay float32 under ``cast``."""

    def __init__(self, units, num_heads, latent, nope_dim, rope_dim, v_dim,
                 rope_theta=10000.0, epsilon=1e-6, **kwargs):
        super().__init__(**kwargs)
        self._h, self._latent = num_heads, latent
        self._dn, self._dr, self._dv = nope_dim, rope_dim, v_dim
        self._theta, self._eps = rope_theta, epsilon

        def dense(out, into):
            return nn.Dense(out, flatten=False, in_units=into,
                            use_bias=False)

        with self.name_scope():
            self.query = dense(num_heads * (nope_dim + rope_dim), units)
            self.kv_down = dense(latent + rope_dim, units)
            self.kv_norm = nn.RMSNorm(in_channels=latent, epsilon=epsilon)
            self.kv_up = dense(num_heads * (nope_dim + v_dim), latent)
            self.q_gain = self.params.get("q_gain", shape=(nope_dim,),
                                          init="ones")
            self.k_gain = self.params.get("k_gain", shape=(nope_dim,),
                                          init="ones")
            self.gate = dense(num_heads, units)
            self.proj = dense(units, num_heads * v_dim)

    def cast(self, dtype):
        super().cast(dtype)
        for p in (self.q_gain, self.k_gain, self.kv_norm.gamma):
            p.cast("float32")

    @functools.partial(jax.checkpoint, static_argnums=0)
    def _heads(self, q, kv, k_rope, q_gain, k_gain):
        """q (b, s, h (d_n + d_r)), kv (b, s, h (d_n + d_v)), k_rope
        (b, s, d_r) -> q, k (b, h, s, d_n + d_r), v (b, h, s, d_v) in the
        inputs' type. (checkpoint: the gradient keeps the three
        projections, not the float32 halves.)"""
        b, s, _ = q.shape
        h, dn = self._h, self._dn

        def heads(t):
            return t.reshape(b, s, h, -1).transpose(0, 2, 1, 3)

        def normed(t, gain):
            t = t.astype(jnp.float32)
            return t * jax.lax.rsqrt(jnp.mean(t * t, -1, keepdims=True)
                                     + self._eps) * gain.astype(jnp.float32)

        with jax.named_scope("mla_rope"):
            q, kv = heads(q), heads(kv)
            q_rope = rope_interleaved(q[..., dn:], self._theta)
            k_rope = rope_interleaved(k_rope[:, None], self._theta)
            k_rope = jnp.broadcast_to(k_rope, q_rope.shape)
            return (jnp.concatenate([normed(q[..., :dn], q_gain), q_rope],
                                    -1).astype(q.dtype),
                    jnp.concatenate([normed(kv[..., :dn], k_gain), k_rope],
                                    -1).astype(q.dtype),
                    kv[..., dn:])

    def _attend(self, q, kv, k_rope, q_gain, k_gain):
        q, k, v = self._heads(q, kv, k_rope, q_gain, k_gain)
        route = attention_route(q.shape, k.shape, v.shape)
        _ROUTES.inc(route=route)
        o = flash_attention(q, k, v, True)
        return o.transpose(0, 2, 1, 3)                    # (b, s, h, d_v)

    def forward(self, x):
        with jax.named_scope("mla_down"):
            down = self.kv_down(x)
            latent = self.kv_norm(down[..., :self._latent])
        with jax.named_scope("mla_up"):
            q, kv = self.query(x), self.kv_up(latent)
        o = _apply(self._attend, q, kv, down[..., self._latent:],
                   self.q_gain.data(), self.k_gain.data())

        @jax.checkpoint
        def gated(o, z):
            with jax.named_scope("mla_gate"):
                o = o.astype(jnp.float32) * jax.nn.sigmoid(
                    z.astype(jnp.float32))[..., None]
                return o.reshape(o.shape[:2] + (-1,)).astype(z.dtype)

        return self.proj(_apply(gated, o, self.gate(x)))


class Ling3Model(MixerStackLM):
    """tokens (B, S) int -> logits (B, S, vocab). ``pattern`` names the
    layers' mixers (`K`, `M`); the first ``dense_layers`` layers' FFN is a
    SwiGLU of ``dense_hidden``, the others' `SharedExpertMoE(units,
    **moe)`; ``delta`` and ``latent`` are the keyword arguments of
    `KimiDeltaAttention` and `MultiHeadLatentAttention` after ``units``.
    A layer is `SolarOpen2Layer`, whose ``experts`` may here be the dense
    SwiGLU. ``remat_layers`` and ``moe["bias_rate"]``: as
    `SolarOpen2Model`'s (`MixerStackLM` walks the stack)."""

    def __init__(self, vocab_size, units, pattern, delta, latent, moe,
                 dense_hidden, dense_layers=1, epsilon=1e-6,
                 remat_layers=False, **kwargs):
        super().__init__(**kwargs)
        if set(pattern) - set(MIXERS) or not pattern:
            raise ValueError("pattern %r: a mixer is one of %s"
                             % (pattern, sorted(MIXERS)))
        self.pattern = pattern
        self._remat = remat_layers
        build = {"K": lambda: KimiDeltaAttention(units, epsilon=epsilon,
                                                 **delta),
                 "M": lambda: MultiHeadLatentAttention(
                     units, epsilon=epsilon, **latent)}
        with self.name_scope():
            self.tok_embed = nn.Embedding(vocab_size, units)
            self.layers = nn.HybridSequential()
            for i, letter in enumerate(pattern):
                self.layers.add(SolarOpen2Layer(
                    units, build[letter],
                    (lambda: SwiGLU(units, dense_hidden)) if i < dense_layers
                    else (lambda: SharedExpertMoE(units, **moe)),
                    epsilon=epsilon))
            self.norm_f = nn.RMSNorm(in_channels=units, epsilon=epsilon)
            self.lm_head = nn.Dense(vocab_size, flatten=False,
                                    in_units=units, use_bias=False)
