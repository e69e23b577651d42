"""Nemotron-H (`model_type: nemotron_h`; NVIDIA-Nemotron-3-Super-120B-A12B):
a decoder-only hybrid whose every layer is ONE mixer behind a pre-norm
residual, `x <- x + mixer(RMSNorm(x))`, the mixer read from a pattern
string: `M` a Mamba-2 state-space mixer, `*` grouped-query attention
without rotary embedding, `E` a latent mixture of relu^2 experts beside a
shared expert. No biases but the convolution's, an untied head.

    M   z, xBC, dt = split(W_in u);  xBC = silu(conv1d_causal,k(xBC) + b)
        x, B, C = split(xBC);  D_t = softplus(dt + dt_bias);  A = -exp(A_log)
        h_t = exp(D_t A) h_{t-1} + D_t x_t B_t^T;  y_t = h_t C_t + D x_t
        out = W_out (RMSNorm_group(y * silu(z)) * g)      (ops/ssd.py)
    *   softmax(q k^T / sqrt(d) + causal) v, H query heads on H_kv
        key-value heads, no position embedding of any kind
    E   s = sigmoid(W_r u) (float32);  chosen = top-k(s + b)
        w_e = s_e / (sum_chosen s + 1e-20) * scale
        out = W_up sum_{e chosen} w_e W2_e relu2(W1_e W_down u)
              + W2_s relu2(W1_s u)

A block can be ONE CHIP'S SHARE of a tensor- and expert-parallel layout:
the mixers are told into how many shards their heads (and Mamba-2's B/C
groups, which exist for this: the gated norm is per group, so the division
is exact) are divided and build one shard; the expert layer is told which
experts it holds (`parallel.MoELayer(held=...)`) and keeps the router, the
latent maps and the shared expert whole. A share's output is its part of
the layer's sum; nothing stands in for the other ranks. The float32
reference of these equations is tests/nemotron_h_reference.py.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import initializer
from .. import ndarray as nd
from ..gluon import nn, utils
from ..gluon.block import HybridBlock
from ..ndarray import _apply
from ..ops.ssd import ssd_chunked
from ..parallel.moe import MoELayer, relu2
from .bert import MultiHeadAttention

__all__ = ["NemotronHModel", "NemotronHLayer", "Mamba2Mixer",
           "GroupedQueryAttention", "LatentMoE"]

#: the pattern's letters: Mamba-2, attention, latent mixture of experts
MIXERS = "M*E"


class _LogUniform(initializer.Initializer):
    """A_log: log of a uniform draw from [low, high] (Mamba-2's A_init_range)."""

    def __init__(self, low, high):
        super().__init__()
        self._low, self._high = low, high

    def _init_weight(self, name, arr):
        arr._data = jnp.log(nd.random.uniform(
            self._low, self._high, arr.shape)._data).astype(arr.dtype)


class _InverseSoftplusOfLogUniform(initializer.Initializer):
    """dt_bias: dt log-uniform in [dt_min, dt_max], floored at dt_floor;
    the bias is softplus^-1(dt) = dt + log(-expm1(-dt)), so that
    softplus(dt_bias) is that dt where the projection adds nothing."""

    def __init__(self, dt_min, dt_max, dt_floor):
        super().__init__()
        self._range = (math.log(dt_min), math.log(dt_max))
        self._floor = dt_floor

    def _init_weight(self, name, arr):
        dt = jnp.maximum(jnp.exp(nd.random.uniform(
            *self._range, arr.shape)._data), self._floor)
        arr._data = (dt + jnp.log(-jnp.expm1(-dt))).astype(arr.dtype)


class Mamba2Mixer(HybridBlock):
    """The Mamba-2 mixer, or one of ``shards`` equal parts of it.

    ``num_heads`` heads of ``head_dim`` and ``n_groups`` B/C groups of
    ``state`` are the WHOLE mixer's; this block holds num_heads / shards
    heads and n_groups / shards groups (whole groups only, so the gated
    norm, which is over each group's channels, divides exactly). A shard's
    output is its part of the row-parallel out-projection's sum.

    Scopes inside the block's own: `ssd_conv`, `ssd_scan`, `ssd_gate_norm`.
    ``A_log``, ``dt_bias`` and ``D`` stay float32 under ``cast``: the scan
    takes them so, and a bfloat16 A_log could not follow an update.
    """

    def __init__(self, units, num_heads, head_dim, n_groups, state,
                 conv_kernel=4, chunk=128, shards=1, epsilon=1e-5,
                 dt_min=0.001, dt_max=0.1, dt_floor=1e-4, **kwargs):
        super().__init__(**kwargs)
        if num_heads % shards or n_groups % shards:
            raise ValueError(
                "%d heads and %d groups do not divide into %d shards of "
                "whole groups" % (num_heads, n_groups, shards))
        self.heads, self.groups = num_heads // shards, n_groups // shards
        self.head_dim, self.state = head_dim, state
        self.inner = self.heads * head_dim
        self._chunk, self._eps, self._k = chunk, epsilon, conv_kernel
        conv = self.inner + 2 * self.groups * state
        with self.name_scope():
            # rows: z (inner), x (inner), B (groups*state), C (same), dt (heads)
            self.in_proj = nn.Dense(self.inner + conv + self.heads,
                                    flatten=False, in_units=units,
                                    use_bias=False)
            self.conv_weight = self.params.get(
                "conv_weight", shape=(conv, conv_kernel),
                init=initializer.Uniform(1.0 / math.sqrt(conv_kernel)))
            self.conv_bias = self.params.get("conv_bias", shape=(conv,),
                                             init="zeros")
            self.A_log = self.params.get("A_log", shape=(self.heads,),
                                         init=_LogUniform(1.0, 16.0))
            self.dt_bias = self.params.get(
                "dt_bias", shape=(self.heads,),
                init=_InverseSoftplusOfLogUniform(dt_min, dt_max, dt_floor))
            self.D = self.params.get("D", shape=(self.heads,), init="ones")
            self.norm_gamma = self.params.get(
                "norm_gamma", shape=(self.inner,), init="ones")
            self.out_proj = nn.Dense(units, flatten=False,
                                     in_units=self.inner, use_bias=False)

    def cast(self, dtype):
        super().cast(dtype)
        for p in (self.A_log, self.dt_bias, self.D):
            p.cast("float32")

    def _mix(self, zxbcdt, conv_w, conv_b, a_log, dt_bias, d_skip, gamma):
        b, s, _ = zxbcdt.shape
        inner, gn = self.inner, self.groups * self.state
        z = zxbcdt[..., :inner]
        xbc = zxbcdt[..., inner:2 * inner + 2 * gn]
        dt = zxbcdt[..., 2 * inner + 2 * gn:]
        with jax.named_scope("ssd_conv"):
            # depthwise, causal: position t sees t-k+1 .. t
            padded = jnp.pad(xbc, [(0, 0), (self._k - 1, 0), (0, 0)])
            taps = conv_w.astype(jnp.float32)
            acc = conv_b.astype(jnp.float32)
            for j in range(self._k):
                acc = acc + padded[:, j:j + s].astype(jnp.float32) * taps[:, j]
            xbc = jax.nn.silu(acc).astype(zxbcdt.dtype)
        x = xbc[..., :inner].reshape(b, s, self.heads, self.head_dim)
        bm = xbc[..., inner:inner + gn].reshape(b, s, self.groups, self.state)
        cm = xbc[..., inner + gn:].reshape(b, s, self.groups, self.state)
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + dt_bias.astype(jnp.float32))
        y = ssd_chunked(x, dt, -jnp.exp(a_log.astype(jnp.float32)), bm, cm,
                        d_skip, self._chunk)
        with jax.named_scope("ssd_gate_norm"):
            # the gate BEFORE the norm; the norm over each group's channels
            y = y.reshape(b, s, inner).astype(jnp.float32) \
                * jax.nn.silu(z.astype(jnp.float32))
            yg = y.reshape(b, s, self.groups, inner // self.groups)
            yg = yg * jax.lax.rsqrt(
                jnp.mean(yg * yg, -1, keepdims=True) + self._eps)
            return (yg.reshape(b, s, inner) * gamma.astype(jnp.float32)) \
                .astype(zxbcdt.dtype)

    def forward(self, u):
        y = _apply(self._mix, self.in_proj(u), *(p.data() for p in (
            self.conv_weight, self.conv_bias, self.A_log, self.dt_bias,
            self.D, self.norm_gamma)))
        return self.out_proj(y)


class GroupedQueryAttention(MultiHeadAttention):
    """Causal attention without biases and without any position embedding,
    ``num_heads`` query heads of ``head_dim`` on ``num_kv_heads`` key-value
    heads, whatever the input's width. k and v are repeated to the query
    heads before the kernels, which therefore see one shape."""

    def __init__(self, in_units, num_heads, num_kv_heads, head_dim,
                 attention="flash", **kwargs):
        super().__init__(in_units, num_heads, attention=attention,
                         causal=True, use_bias=False,
                         num_kv_heads=num_kv_heads, head_dim=head_dim,
                         **kwargs)


class LatentMoE(HybridBlock):
    """Experts in a latent space beside a shared expert on the full width:
    `W_up moe(W_down u; routed on u) + W2_s relu2(W1_s u)`. The routed
    part is a `parallel.MoELayer` (sigmoid scores, a bias that chooses,
    renormalised weights times ``scale``, relu^2 experts that are not
    gated) told which experts it holds; the router, the two latent maps
    and the shared expert are whole on every rank.

    Scopes: the MoELayer's own four under its block, `shared_expert`."""

    def __init__(self, units, latent, num_experts, ffn_hidden, top_k,
                 shared_hidden, scale=1.0, norm_topk_prob=True, held=None,
                 **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.latent_down = nn.Dense(latent, flatten=False,
                                        in_units=units, use_bias=False)
            self.moe = MoELayer(num_experts, latent, ffn_hidden, top_k=top_k,
                                activation="relu2", gated=False,
                                norm_topk_prob=norm_topk_prob,
                                router="sigmoid_bias", scale=scale,
                                held=held, router_units=units)
            self.latent_up = nn.Dense(units, flatten=False, in_units=latent,
                                      use_bias=False)
            self.shared_up = nn.Dense(shared_hidden, flatten=False,
                                      in_units=units, use_bias=False)
            self.shared_down = nn.Dense(units, flatten=False,
                                        in_units=shared_hidden,
                                        use_bias=False)

    def shared(self, u):
        """The shared expert alone: the part of the layer that is
        continuous in ``u`` (the routed part is a top-k choice)."""
        with jax.named_scope("shared_expert"):
            return self.shared_down(_apply(relu2, self.shared_up(u)))

    def forward(self, u):
        routed = self.latent_up(self.moe(self.latent_down(u), u))
        return routed + self.shared(u)


class NemotronHLayer(HybridBlock):
    """x + mixer(RMSNorm(x)); ``mixer`` builds the block (called inside
    this layer's name scope)."""

    def __init__(self, units, mixer, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.norm = nn.RMSNorm(in_channels=units, epsilon=epsilon)
            self.mixer = mixer()

    def forward(self, x):
        return x + self.mixer(self.norm(x))


class NemotronHModel(HybridBlock):
    """tokens (B, S) int -> logits (B, S, vocab). ``pattern`` names the
    layers (`M`, `*`, `E`); ``mamba``, ``attention`` and ``moe`` are the
    keyword arguments of `Mamba2Mixer`, `GroupedQueryAttention` and
    `LatentMoE` after ``units``. ``remat_layers``: each layer's forward is
    recomputed in the backward (`gluon.utils.recompute`), so a step keeps
    one (B, S, U) input a layer and one layer's working set."""

    def __init__(self, vocab_size, units, pattern, mamba, attention, moe,
                 epsilon=1e-5, remat_layers=False, **kwargs):
        super().__init__(**kwargs)
        unknown = set(pattern) - set(MIXERS)
        if unknown or not pattern:
            raise ValueError("pattern %r: a layer is one of %s"
                             % (pattern, sorted(MIXERS)))
        self.pattern = pattern
        self._remat = remat_layers
        build = {"M": lambda: Mamba2Mixer(units, epsilon=epsilon, **mamba),
                 "*": lambda: GroupedQueryAttention(units, **attention),
                 "E": lambda: LatentMoE(units, **moe)}
        with self.name_scope():
            self.tok_embed = nn.Embedding(vocab_size, units)
            self.layers = nn.HybridSequential()
            for letter in pattern:
                self.layers.add(NemotronHLayer(units, build[letter],
                                               epsilon=epsilon))
            self.norm_f = nn.RMSNorm(in_channels=units, epsilon=epsilon)
            self.lm_head = nn.Dense(vocab_size, flatten=False,
                                    in_units=units, use_bias=False)

    def features(self, token_ids):
        """The final norm's output (B, S, U): pair with
        ChunkedUntiedLMLoss so the (B*S, V) logits never materialise."""
        x = self.tok_embed(token_ids)
        for layer in self.layers:
            x = utils.recompute(layer, x) if self._remat else layer(x)
        return self.norm_f(x)

    def forward(self, token_ids):
        return self.lm_head(self.features(token_ids))
