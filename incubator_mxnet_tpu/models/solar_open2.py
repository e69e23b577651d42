"""Solar Open 2 (`model_type: solar_open2`; Solar-Open2-250B): a decoder
whose every layer is a mixer and a mixture of SwiGLU experts beside a
shared expert, behind pre-norm residuals,

    x <- x + Mixer(RMSNorm(x));  x <- x + Experts(RMSNorm(x))

RMSNorm with a gain, a final RMSNorm, an untied head without bias and NO
position embedding of any kind (`use_rope: false`; the `K` layers carry
position). The mixer is read from a pattern string (the source's
`gqa_layers` are the `G`s):

    K   Kimi Delta Attention (arXiv:2510.26692): the gated delta rule with
        a decay per channel. Per head, d = head_dim:
        q~, k~, v = silu(conv1d_causal,4(h W_q | h W_k | h W_v))  (no bias)
        q = q~ / |q~|_2 d^-1/2;   k = k~ / |k~|_2
        g_t = -exp(A_log) softplus((h W_f-) W_f+ + dt_bias)   (float32, d)
        b_t = 2 sigmoid(h w_b)    (the 2: `kda_allow_neg_eigval`, the
                                   eigenvalues of I - b k k^T reach -1)
        S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T
        o_t = S_t^T q_t                          (ops/delta_rule.py)
        out = W_o [RMSNorm_d(o_t) * gamma * sigmoid((h W_g-) W_g+)]
        W_f-, W_g- are units -> rank maps and W_f+, W_g+ rank -> heads x d
        (`kda_use_full_proj: false`: low-rank pairs, rank = head_dim)
    G   gated grouped-query attention without position embedding:
        softmax(q k^T / sqrt(d) + causal) v, H query heads on H_kv
        key-value heads; out = W_o [o * sigmoid(h W_gate)], W_gate
        units -> H x d (a gate per channel). No QK-norm, no bias.
    experts   s = sigmoid(W_r u) (float32);  chosen = top-k(s + b)
        w_e = s_e / (sum_chosen s + 1e-20) * scale
        out = sum_{e chosen} w_e W2_e (silu(W1_e u) * W3_e u)
              + W_down (silu(W_gate u) * W_up u)          (shared expert)

A block can be ONE CHIP'S SHARE of a tensor- and expert-parallel layout,
as models/nemotron_h.py's: a mixer is told into how many shards its heads
are divided and builds one (the two units -> rank maps are held whole, the
rank -> heads x d maps by this shard's columns; a key-value head that
fewer shards than there are would share is held once); the expert block is
told which experts it holds (`parallel.MoELayer(held=...)`) and keeps the
router and the shared expert whole. A share's output is its part of the
layer's sum; nothing stands in for the other ranks. The float32 reference
of these equations is perfbench/reference/solar-open2-250b.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .. import initializer
from ..gluon import nn, utils
from ..gluon.block import HybridBlock
from ..ndarray import _apply
from ..ops.attention import ATTENDED_NAME
from ..ops.delta_rule import RULED_NAME, gated_delta_rule_lanes
from ..parallel.moe import MoELayer
from .nemotron_h import (GroupedQueryAttention, _InverseSoftplusOfLogUniform,
                         _LogUniform)
from .phi4flash import SwiGLU

__all__ = ["SolarOpen2Model", "SolarOpen2Layer", "MixerStackLM",
           "KimiDeltaAttention", "GatedGroupedQueryAttention",
           "SharedExpertMoE"]

#: the pattern's letters: Kimi Delta Attention, gated grouped-query attention
MIXERS = "KG"
#: what a recomputed layer keeps of its forward: what the delta rule's and
#: the `G` layer's attention kernels wrote for their backward
_KEPT = jax.checkpoint_policies.save_only_these_names(RULED_NAME,
                                                      ATTENDED_NAME)
#: the decay's step sizes at initialisation, as Mamba's: log-uniform in
#: [min, max], floored
_DT_INIT = (0.001, 0.1, 1e-4)
#: what is added under the root of the L2 norms of q and k
_L2_EPS = 1e-6


class KimiDeltaAttention(HybridBlock):
    """The gated delta-rule mixer, or one of ``shards`` equal parts of it:
    ``num_heads`` heads of ``head_dim`` (keys, queries and values alike)
    are the WHOLE mixer's; this block holds num_heads / shards of them. A
    shard's output is its part of the row-parallel out-projection's sum.

    ``in_proj`` is the column-parallel maps of h side by side: q, k, v
    (this shard's heads), the two units -> ``rank`` maps (whole) and this
    shard's heads' b. Scopes inside the block's own: `kda_conv` (the
    three short convolutions and the L2 norms), `kda_decay` (g and b),
    `delta_rule` (the op's), `kda_gate_norm`. ``A_log``, ``dt_bias`` and
    the norm's gain stay float32 under ``cast``.

    ``rank``: the decay's and the gate's maps are low-rank pairs units ->
    rank -> heads x d (None: rank = head_dim), or with ``"full"`` ONE map
    units -> heads x d each, two more blocks of ``in_proj``'s rows and no
    ``decay_up`` / ``gate_up`` (Ling 3.0's `no_kda_lora`). ``decay``:
    ``"softplus"`` is g = -exp(A_log) softplus(f + dt_bias), unbounded below;
    ``("bounded", lower)`` is g = lower * sigmoid(exp(A_log) (f + dt_bias)),
    in (lower, 0): a step's log-decay never under ``lower`` (the published
    kernels' "safe gate", `kda_lower_bound`). ``neg_eigval`` False keeps b
    in (0, 1). The defaults are Solar Open 2's block, its traced program
    text for text (tests/test_ling3.py pins it). More than one head group
    has run on the chip: 32 heads are 4 groups of 8 on the kernels' grid
    (PERF.md section 6, PR 48 has the cost a group).

    One form of the operands: q, k, v, g, o and their gradients are (b,
    s, h d) from ``in_proj``'s output to ``out_proj``'s input, a head a
    block of the last dimension, which is what the rule's kernels read
    (`ops.delta_rule.gated_delta_rule_lanes`; the op alone chooses its
    schedule, and splits the heads off itself where it runs the XLA
    form)."""

    def __init__(self, units, num_heads, head_dim, conv_kernel=4, rank=None,
                 chunk=64, shards=1, neg_eigval=True, epsilon=1e-5,
                 decay="softplus", **kwargs):
        super().__init__(**kwargs)
        if num_heads % shards:
            raise ValueError("%d heads do not divide into %d shards"
                             % (num_heads, shards))
        if decay != "softplus" and not (
                isinstance(decay, tuple) and len(decay) == 2
                and decay[0] == "bounded" and decay[1] < 0):
            raise ValueError("decay=%r: 'softplus' or ('bounded', a lower "
                             "bound under 0)" % (decay,))
        self.heads, self.head_dim = num_heads // shards, head_dim
        self.inner = self.heads * head_dim
        self.rank = None if rank == "full" else rank or head_dim
        self._chunk, self._eps, self._k = chunk, epsilon, conv_kernel
        self._beta_max = 2.0 if neg_eigval else 1.0
        self._lower = None if decay == "softplus" else float(decay[1])
        # what the decay and the gate read of in_proj's output: rank wide,
        # or a channel each
        self._map_in = self.rank or self.inner
        with self.name_scope():
            # rows: q, k, v (inner each), decay, gate (rank each going
            # down, or inner each: the full-rank maps themselves), b (heads)
            self.in_proj = nn.Dense(
                3 * self.inner + 2 * self._map_in + self.heads, flatten=False,
                in_units=units, use_bias=False)
            self.conv_weight = self.params.get(
                "conv_weight", shape=(3 * self.inner, conv_kernel),
                init=initializer.Uniform(1.0 / math.sqrt(conv_kernel)))
            if self.rank:
                self.decay_up = self.params.get(
                    "decay_up", shape=(self.inner, self.rank), init="xavier")
                self.gate_up = self.params.get(
                    "gate_up", shape=(self.inner, self.rank), init="xavier")
            self.A_log = self.params.get("A_log", shape=(self.heads,),
                                         init=_LogUniform(1.0, 16.0))
            self.dt_bias = self.params.get(
                "dt_bias", shape=(self.inner,),
                init=_InverseSoftplusOfLogUniform(*_DT_INIT))
            self.norm_gamma = self.params.get(
                "norm_gamma", shape=(head_dim,), init="ones")
            self.out_proj = nn.Dense(units, flatten=False,
                                     in_units=self.inner, use_bias=False)

    def cast(self, dtype):
        super().cast(dtype)
        for p in (self.A_log, self.dt_bias, self.norm_gamma):
            p.cast("float32")

    def _head_sums(self, t):
        """The sum over each head's d channels of ``t`` (b, s, h d),
        float32, in ``t``'s shape. It is taken on the view (b, s / 8, h, 8,
        d) of (b, s, h d): the SAME bytes under the (8, 128) tiling (eight
        positions of one head's 128 lanes are a tile of both), so the view
        and the way back are bitcasts and the sum a reduction over lanes. A
        view (b, s, h, d) is tiled by (h, d): each move between it and (b,
        s, h d) is a pass over the array, 40 ms of the Ling cell's 620 ms
        step up to PR 50. (Multiplying inside the view, the sums left (..,
        8, 1), is the same block alone and 2.7 % of the Ling step slower:
        PERF.md section 6, PR 51.)"""
        b, s, _ = t.shape
        pad = -s % 8
        if pad:
            t = jnp.pad(t, [(0, 0), (0, pad), (0, 0)])
        tiles = t.reshape(b, -1, 8, self.heads, self.head_dim) \
            .transpose(0, 1, 3, 2, 4)
        sums = jnp.broadcast_to(jnp.sum(tiles, -1, keepdims=True),
                                tiles.shape)
        return sums.transpose(0, 1, 3, 2, 4).reshape(t.shape)[:, :s]

    @functools.partial(jax.checkpoint, static_argnums=0)
    def _conv(self, qkv, conv_w):
        """(b, s, 3 inner) -> q, k (b, s, inner) float32, normed a head; v
        in the input's type. (checkpoint: the gradient keeps qkv, not the
        float32 sums.)"""
        with jax.named_scope("kda_conv"):
            s = qkv.shape[1]
            padded = jnp.pad(qkv, [(0, 0), (self._k - 1, 0), (0, 0)])
            taps = conv_w.astype(jnp.float32)
            acc = 0.0
            for j in range(self._k):        # position t sees t-k+1 .. t
                acc = acc + padded[:, j:j + s].astype(jnp.float32) * taps[:, j]
            q, k, v = jnp.split(jax.nn.silu(acc), 3, -1)

            def unit(t):
                return t * jax.lax.rsqrt(self._head_sums(t * t) + _L2_EPS)

            return unit(q) * self.head_dim ** -0.5, unit(k), \
                v.astype(qkv.dtype)

    def _mix(self, proj, conv_w, a_log, dt_bias, gamma, decay_up=None,
             gate_up=None):
        inner, r, h, d = self.inner, self._map_in, self.heads, self.head_dim
        qkv, low_f, low_g, b_in = jnp.split(
            proj, [3 * inner, 3 * inner + r, 3 * inner + 2 * r], -1)
        q, k, v = self._conv(qkv, conv_w)
        with jax.named_scope("kda_decay"):
            pre = low_f.astype(jnp.float32) if decay_up is None \
                else jnp.einsum("bsr,cr->bsc", low_f, decay_up,
                                preferred_element_type=jnp.float32)
            pre = pre + dt_bias.astype(jnp.float32)
            # exp(A_log) a head, on its d channels
            rate = jnp.repeat(jnp.exp(a_log.astype(jnp.float32)), d)
            g = -rate * jax.nn.softplus(pre) if self._lower is None \
                else self._lower * jax.nn.sigmoid(rate * pre)
            beta = self._beta_max * jax.nn.sigmoid(b_in.astype(jnp.float32))
        o = gated_delta_rule_lanes(q, k, v, g, beta, h, self._chunk)

        @jax.checkpoint      # the gradient keeps o and the low-rank input
        def gate_norm(o, low, gate_up, gamma):
            with jax.named_scope("kda_gate_norm"):
                o = o.astype(jnp.float32)
                o = o * jax.lax.rsqrt(self._head_sums(o * o) / d
                                      + self._eps) \
                    * jnp.tile(gamma.astype(jnp.float32), h)
                gate = jax.nn.sigmoid(
                    low.astype(jnp.float32) if gate_up is None
                    else jnp.einsum("bsr,cr->bsc", low, gate_up,
                                    preferred_element_type=jnp.float32))
                return (o * gate).astype(proj.dtype)

        return gate_norm(o, low_g, gate_up, gamma)

    def forward(self, u):
        own = (self.conv_weight, self.A_log, self.dt_bias, self.norm_gamma) \
            + ((self.decay_up, self.gate_up) if self.rank else ())
        y = _apply(self._mix, self.in_proj(u), *(p.data() for p in own))
        return self.out_proj(y)


class GatedGroupedQueryAttention(GroupedQueryAttention):
    """`GroupedQueryAttention` (causal, no biases, no position embedding)
    with a sigmoid gate on the heads' outputs, a channel at a time, before
    the out-projection: W_o [o * sigmoid(h W_gate)]. Scope `gqa_gate`."""

    def __init__(self, in_units, num_heads, num_kv_heads, head_dim,
                 attention="flash", **kwargs):
        super().__init__(in_units, num_heads, num_kv_heads, head_dim,
                         attention=attention, **kwargs)
        with self.name_scope():
            self.gate = nn.Dense(num_heads * head_dim, flatten=False,
                                 in_units=in_units, use_bias=False)

    def forward(self, x, mask=None):
        def gated(o, z):
            with jax.named_scope("gqa_gate"):
                return (o.astype(jnp.float32) * jax.nn.sigmoid(
                    z.astype(jnp.float32))).astype(o.dtype)

        return self.proj(_apply(jax.checkpoint(gated),
                                self.heads_output(x, mask), self.gate(x)))


class SharedExpertMoE(HybridBlock):
    """Routed SwiGLU experts beside a shared one of the same form:
    `moe(u) + shared(u)`. The routed part is a `parallel.MoELayer` (sigmoid
    scores, a bias that chooses, renormalised weights times ``scale``) told
    which experts it holds; the router and the shared expert are whole on
    every rank. ``bias_rate``: the MoELayer's balancing rule; the block
    then returns (y, the moved selection bias). ``n_group``, ``topk_group``:
    the MoELayer's group-limited choice. Scopes: the MoELayer's own
    four under its block, the shared expert's `ffn` under its own."""

    def __init__(self, units, num_experts, ffn_hidden, top_k, shared_hidden,
                 scale=1.0, norm_topk_prob=True, held=None, bias_rate=None,
                 n_group=None, topk_group=None, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.moe = MoELayer(num_experts, units, ffn_hidden, top_k=top_k,
                                activation="silu", gated=True,
                                norm_topk_prob=norm_topk_prob,
                                router="sigmoid_bias", scale=scale, held=held,
                                bias_rate=bias_rate, n_group=n_group,
                                topk_group=topk_group)
            self.shared = SwiGLU(units, shared_hidden)

    def forward(self, u):
        routed = self.moe(u)
        if isinstance(routed, tuple):
            return routed[0] + self.shared(u), routed[1]
        return routed + self.shared(u)


class SolarOpen2Layer(HybridBlock):
    """x + mixer(RMSNorm(x)), then x + experts(RMSNorm(x)); ``mixer`` and
    ``experts`` build the blocks (inside this layer's name scope). Experts
    that hand out a moved selection bias: (x, that bias)."""

    def __init__(self, units, mixer, experts, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.norm1 = nn.RMSNorm(in_channels=units, epsilon=epsilon)
            self.mixer = mixer()
            self.norm2 = nn.RMSNorm(in_channels=units, epsilon=epsilon)
            self.experts = experts()

    def forward(self, x):
        x = x + self.mixer(self.norm1(x))
        y = self.experts(self.norm2(x))
        if isinstance(y, tuple):
            return x + y[0], y[1]
        return x + y


class MixerStackLM(HybridBlock):
    """What the decoders of this family share: ``tok_embed``, ``layers``
    (each a `SolarOpen2Layer`), ``norm_f``, ``lm_head`` and ``_remat``, set
    by the subclass, walked here. ``_remat``: each layer's forward is
    recomputed in the backward (`gluon.utils.recompute`) but for what its
    Pallas kernels wrote (`_KEPT`); a layer's moved selection bias is
    booked here, outside the recomputed layers. A subclass whose layers
    carry something else than (B, S, U) overrides `stream_in` /
    `stream_out` (models/xing4.py's four streams)."""

    def stream_in(self, x):
        """The embeddings (B, S, U) -> what the first layer takes."""
        return x

    def stream_out(self, x):
        """What the last layer hands out -> (B, S, U) for the final norm."""
        return x

    def features(self, token_ids):
        """The final norm's output (B, S, U): pair with
        ChunkedUntiedLMLoss so the (B*S, V) logits never materialise."""
        x = self.stream_in(self.tok_embed(token_ids))
        for layer in self.layers:
            x = utils.recompute(layer, x, policy=_KEPT) if self._remat \
                else layer(x)
            if isinstance(x, tuple):
                x, moved = x
                layer.experts.moe.move_bias(moved)
        return self.norm_f(self.stream_out(x))

    def forward(self, token_ids):
        return self.lm_head(self.features(token_ids))


class SolarOpen2Model(MixerStackLM):
    """tokens (B, S) int -> logits (B, S, vocab). ``pattern`` names the
    layers' mixers (`K`, `G`); ``delta``, ``attention`` and ``moe`` are the
    keyword arguments of `KimiDeltaAttention`, `GatedGroupedQueryAttention`
    and `SharedExpertMoE` after ``units``. ``remat_layers``: each layer's
    forward is recomputed in the backward (`gluon.utils.recompute`) but for
    what its Pallas kernels wrote (`_KEPT`), so each forward kernel runs
    once a step. With
    ``moe["bias_rate"]`` every training step moves every router's selection
    bias by the balancing rule, here, outside the recomputed layers."""

    def __init__(self, vocab_size, units, pattern, delta, attention, moe,
                 epsilon=1e-5, remat_layers=False, **kwargs):
        super().__init__(**kwargs)
        if set(pattern) - set(MIXERS) or not pattern:
            raise ValueError("pattern %r: a mixer is one of %s"
                             % (pattern, sorted(MIXERS)))
        self.pattern = pattern
        self._remat = remat_layers
        build = {"K": lambda: KimiDeltaAttention(units, epsilon=epsilon,
                                                 **delta),
                 "G": lambda: GatedGroupedQueryAttention(units, **attention)}
        with self.name_scope():
            self.tok_embed = nn.Embedding(vocab_size, units)
            self.layers = nn.HybridSequential()
            for letter in pattern:
                self.layers.add(SolarOpen2Layer(
                    units, build[letter],
                    lambda: SharedExpertMoE(units, **moe), epsilon=epsilon))
            self.norm_f = nn.RMSNorm(in_channels=units, epsilon=epsilon)
            self.lm_head = nn.Dense(vocab_size, flatten=False,
                                    in_units=units, use_bias=False)
