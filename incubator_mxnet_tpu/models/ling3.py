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
        2.1, without the low-rank query: `MultiHeadLatentAttention` at its
        defaults; its other arguments give the DeepSeek-V3 form, which
        models/xing4.py builds). Per head, d_n = nope, d_r = rope:
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
import math

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

__all__ = ["Ling3Model", "MultiHeadLatentAttention", "yarn_inv_freq",
           "yarn_mscale"]

#: the pattern's letters: Kimi Delta Attention, multi-head latent attention
MIXERS = "KM"

_ROUTES = telemetry.counter(
    "mxtpu_latent_attention_total",
    "MultiHeadLatentAttention calls traced, by the path their attention "
    "took (ops.attention.attention_route: streamed Pallas kernels at q.k "
    "wider than v, or the XLA composite).", ("route",))


def rope_interleaved(x, theta, inv_freq=None):
    """Rotary position embedding on INTERLEAVED pairs, positions 0..S-1.
    x (..., S, D), D even: the pair (x[2i], x[2i + 1]) turns by the angle
    pos * theta^(-2i / D), or by pos * ``inv_freq``[i] where a table of the
    D / 2 frequencies is given (`yarn_inv_freq`'s; ``theta`` is then not
    read). Float32 inside and out."""
    s, d = x.shape[-2], x.shape[-1]
    if inv_freq is None:
        inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    else:
        inv_freq = jnp.asarray(inv_freq, jnp.float32)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq   # (S, D/2)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     -1).reshape(x.shape)


def yarn_correction_range(dim, theta, original, beta_fast, beta_slow):
    """(low, high): the pairs below ``low`` turn more than ``beta_fast``
    times over the ``original`` positions and keep their frequency, those
    from ``high`` on turn less than ``beta_slow`` times and are
    interpolated whole (arXiv:2309.00071 section 3.2, as DeepSeek-V3's
    `yarn_find_correction_range` rounds them)."""
    def pair(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    return (max(math.floor(pair(beta_fast)), 0),
            min(math.ceil(pair(beta_slow)), dim - 1))


def yarn_inv_freq(dim, theta, factor, original, beta_fast=32, beta_slow=1):
    """The YaRN table of ``dim`` / 2 rotary frequencies (a tuple of
    floats): f_i = theta^(-2i / dim) for i < low, f_i / ``factor`` for
    i >= high, and the linear blend f_i (1 - r_i) + (f_i / factor) r_i,
    r_i = (i - low) / (high - low), between them."""
    low, high = yarn_correction_range(dim, theta, original, beta_fast,
                                      beta_slow)
    table = []
    for i in range(dim // 2):
        f = theta ** (-2.0 * i / dim)
        r = min(max((i - low) / max(high - low, 0.001), 0.0), 1.0)
        table.append(f * (1.0 - r) + f / factor * r)
    return tuple(table)


def yarn_mscale(factor, mscale=1.0):
    """m = 0.1 mscale ln(factor) + 1 (1 at factor <= 1): DeepSeek-V3
    multiplies the softmax scale by m^2 at `mscale_all_dim`, and cos and
    sin by m(mscale) / m(mscale_all_dim)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


class MultiHeadLatentAttention(HybridBlock):
    """Multi-head latent attention: ``num_heads`` heads whose keys and
    values come up from one ``latent``-wide vector a position and share
    one rotary key. q and k are ``nope_dim + rope_dim`` wide, v ``v_dim``;
    the attention is `ops.attention.flash_attention` (causal), which takes
    the streamed kernels wherever they run and the composite elsewhere:
    `mxtpu_latent_attention_total{route}` says which.

    The defaults are Ling 3.0's `M` mixer (the module's docstring): a
    full-rank query, a QK-norm on the non-rotary parts, a gate a head,
    rotary frequencies theta^(-2i / d), scores scaled by 1 / sqrt(q's
    width). The DeepSeek-V3 form (models/xing4.py) is ``q_latent=768``
    (q = RMSNorm(h W_qa) W_qb: a low-rank query with a norm on its latent),
    ``qk_norm=False``, ``head_gate=False``, ``inv_freq=`` a table of the
    rotary frequencies (`yarn_inv_freq`) and ``scale=`` the softmax scale
    (1 / sqrt(q's width) times `yarn_mscale`'s square).

    Scopes inside the block's own: `mla_q_down` (W_qa and its norm, with a
    low-rank query only), `mla_down` (W_kva, the latent's norm), `mla_up`
    (W_q or W_qb, W_kvb), `mla_rope` (the QK-norm of the non-rotary parts,
    the rotation, k put together), `mla_gate`; the kernels' `flash_fwd` /
    `flash_bwd_dkvq` run under the block's path. The norms' gains stay
    float32 under ``cast``."""

    def __init__(self, units, num_heads, latent, nope_dim, rope_dim, v_dim,
                 rope_theta=10000.0, epsilon=1e-6, q_latent=None,
                 qk_norm=True, head_gate=True, inv_freq=None, scale=None,
                 **kwargs):
        super().__init__(**kwargs)
        self._h, self._latent = num_heads, latent
        self._dn, self._dr, self._dv = nope_dim, rope_dim, v_dim
        self._theta, self._eps = rope_theta, epsilon
        self._inv_freq, self._scale = inv_freq, scale
        if inv_freq is not None and len(inv_freq) != rope_dim // 2:
            raise ValueError("inv_freq: %d frequencies for %d rotary pairs"
                             % (len(inv_freq), rope_dim // 2))

        def dense(out, into):
            return nn.Dense(out, flatten=False, in_units=into,
                            use_bias=False)

        with self.name_scope():
            if q_latent is not None:
                self.q_down = dense(q_latent, units)
                self.q_norm = nn.RMSNorm(in_channels=q_latent,
                                         epsilon=epsilon)
            self.query = dense(num_heads * (nope_dim + rope_dim),
                               units if q_latent is None else q_latent)
            self.kv_down = dense(latent + rope_dim, units)
            self.kv_norm = nn.RMSNorm(in_channels=latent, epsilon=epsilon)
            self.kv_up = dense(num_heads * (nope_dim + v_dim), latent)
            if qk_norm:
                self.q_gain = self.params.get("q_gain", shape=(nope_dim,),
                                              init="ones")
                self.k_gain = self.params.get("k_gain", shape=(nope_dim,),
                                              init="ones")
            if head_gate:
                self.gate = dense(num_heads, units)
            self.proj = dense(units, num_heads * v_dim)

    def cast(self, dtype):
        super().cast(dtype)
        for name in ("q_gain", "k_gain", "kv_norm", "q_norm"):
            gain = getattr(self, name, None)
            if gain is not None:
                getattr(gain, "gamma", gain).cast("float32")

    @functools.partial(jax.checkpoint, static_argnums=0)
    def _heads(self, q, kv, k_rope, *gains):
        """q (b, s, h (d_n + d_r)), kv (b, s, h (d_n + d_v)), k_rope
        (b, s, d_r), the QK-norm's two gains where there is one -> q, k
        (b, h, s, d_n + d_r), v (b, h, s, d_v) in the inputs' type.
        (checkpoint: the gradient keeps the three projections, not the
        float32 halves.)"""
        b, s, _ = q.shape
        h, dn = self._h, self._dn

        def heads(t):
            return t.reshape(b, s, h, -1).transpose(0, 2, 1, 3)

        def nope(t, gain):
            """The non-rotary part, under the QK-norm where there is one."""
            t = t[..., :dn]
            if gain is None:
                return t
            t = t.astype(jnp.float32)
            return t * jax.lax.rsqrt(jnp.mean(t * t, -1, keepdims=True)
                                     + self._eps) * gain.astype(jnp.float32)

        def turned(t):
            return rope_interleaved(t, self._theta, self._inv_freq)

        with jax.named_scope("mla_rope"):
            q, kv = heads(q), heads(kv)
            q_rope = turned(q[..., dn:])
            k_rope = turned(k_rope[:, None])
            k_rope = jnp.broadcast_to(k_rope, q_rope.shape)
            q_gain, k_gain = gains or (None, None)
            return (jnp.concatenate([nope(q, q_gain), q_rope],
                                    -1).astype(q.dtype),
                    jnp.concatenate([nope(kv, k_gain), k_rope],
                                    -1).astype(q.dtype),
                    kv[..., dn:])

    def _attend(self, q, kv, k_rope, *gains):
        q, k, v = self._heads(q, kv, k_rope, *gains)
        route = attention_route(q.shape, k.shape, v.shape)
        _ROUTES.inc(route=route)
        o = flash_attention(q, k, v, True, self._scale)
        return o.transpose(0, 2, 1, 3)                    # (b, s, h, d_v)

    def forward(self, x):
        low_rank = hasattr(self, "q_down")
        if low_rank:
            with jax.named_scope("mla_q_down"):
                q_latent = self.q_norm(self.q_down(x))
        with jax.named_scope("mla_down"):
            down = self.kv_down(x)
            latent = self.kv_norm(down[..., :self._latent])
        with jax.named_scope("mla_up"):
            q = self.query(q_latent if low_rank else x)
            kv = self.kv_up(latent)
        gains = (self.q_gain.data(), self.k_gain.data()) \
            if hasattr(self, "q_gain") else ()
        o = _apply(self._attend, q, kv, down[..., self._latent:], *gains)
        if not hasattr(self, "gate"):
            return self.proj(o.reshape(o.shape[:2] + (-1,)))

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
