"""Xing 4.0 (`model_type: xing4_0`; Xing4.0-29B-A4B): a decoder whose
residual path is ``streams`` (`hc_mult`) wide. A position carries X in
R^{n x C}, held here as (B, S, n C), stream i the channels i C .. (i + 1) C.
After the embedding X = [e; e; ...; e]; before the final RMSNorm the streams
are summed. Every layer is two sublayers, a mixer and a feed-forward block,
each F(u) = f(RMSNorm_C(u)) wrapped by its own `HyperConnection`
(manifold-constrained hyper-connections, arXiv:2512.24880 on top of
arXiv:2409.19606). A position at a time, float32:

    x^ = vec(X) rsqrt(mean(vec(X)^2) + eps)          over all n C, no gain
    H~pre  = a_pre  (x^ P_pre)  + b_pre              (n)
    H~post = a_post (x^ P_post) + b_post             (n)
    H~res  = a_res  mat(x^ P_res) + b_res            (n x n)
    Hpre = sigmoid(H~pre);  Hpost = 2 sigmoid(H~post)
    M_0 = exp(clip(H~res, lo, hi))
    M_t = cols(rows(M_{t-1})),  rows(M) = M / (M 1 + eps),
          cols(M) = M / (1^T M + eps),  t = 1..rounds;   Hres = M_rounds
    u  = Hpre X                                      the sublayer's input (C)
    X' = Hres X + Hpost^T F(u)

    mixer   multi-head latent attention in the DeepSeek-V3 form
            (`models.ling3.MultiHeadLatentAttention` with a low-rank query,
            no QK-norm, no head gate, a YaRN frequency table, the softmax
            scale times the square of YaRN's m)
    FFN     the first ``dense_layers`` layers: a dense SwiGLU; every other
            one `SharedExpertMoE` (sigmoid scores, a selection bias, a
            shared expert), told which experts it holds.

The float32 reference of these equations is
perfbench/reference/xing4.0-29b-a4b.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as onp

from .. import telemetry
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ndarray import _apply
from .ling3 import MultiHeadLatentAttention
from .phi4flash import SwiGLU
from .solar_open2 import MixerStackLM, SharedExpertMoE

__all__ = ["Xing4Model", "Xing4Layer", "HyperConnection"]

_BLOCKS = telemetry.counter(
    "mxtpu_hyper_connection_total",
    "HyperConnection blocks traced (the maps and the mix that reads the "
    "streams), by how many streams they mix.", ("streams",))


def sinkhorn(m, rounds, eps):
    """m (n, n, ...) positive -> ``rounds`` times the rows (axis 1 summed)
    and then the columns (axis 0 summed) divided by their sums + eps. The
    loop is traced once and unrolled by the lowering."""
    def one(m, _):
        m = m / (m.sum(1, keepdims=True) + eps)
        return m / (m.sum(0, keepdims=True) + eps), None

    return jax.lax.scan(one, m, None, length=rounds, unroll=rounds)[0]


class HyperConnection(HybridBlock):
    """The residual path around ONE sublayer of a ``streams``-wide stream
    X (B, S, n C): the block's call reads the stream, `write` puts the
    sublayer's output back,

        u, h_post, h_res = hc(X);   X' = hc.write(X, F(u), h_post, h_res)

    with the three maps a position at a time as the module's docstring has
    them. The maps are float32 and live as (n, B S), (n, n, B S): the
    positions on the lanes, so the ``rounds`` Sinkhorn rounds are dense
    element-wise work; x^ P is one float32 matmul at full precision on the
    stream as it is (the norm's factor, a scalar a position, goes on
    after); the two mixes accumulate in float32 and hand out the stream's
    type. ``weight`` (n + n + n^2, n C) holds P_pre, P_post, P_res by rows,
    ``bias`` their b, ``scale`` the three a; all stay float32 under
    ``cast``. Scopes inside the block's own: `hc_maps` (the wide norm,
    x^ P, the sigmoids), `hc_sinkhorn`, `hc_pre` (Hpre X), `hc_post`
    (Hres X + Hpost^T F)."""

    def __init__(self, units, streams, rounds=20, epsilon=1e-6,
                 clamp=(-30.0, 30.0), **kwargs):
        super().__init__(**kwargs)
        self._c, self._n, self._rounds = units, streams, rounds
        self._eps, self._clamp = epsilon, clamp
        self._streams_label = str(streams)      # the counter's label
        maps = 2 * streams + streams * streams
        with self.name_scope():
            self.weight = self.params.get(
                "weight", shape=(maps, streams * units))
            self.bias = self.params.get("bias", shape=(maps,), init="zeros")
            self.scale = self.params.get("scale", shape=(3,), init="ones")

    def cast(self, dtype):
        super().cast(dtype)
        for p in (self.weight, self.bias, self.scale):
            p.cast("float32")

    def _streams(self, x):
        return [x[..., i * self._c:(i + 1) * self._c].astype(jnp.float32)
                for i in range(self._n)]

    def maps(self, x, weight, bias, scale):
        """x (B, S, n C) -> Hpre (n, T), Hpost (n, T), Hres (n, n, T),
        T = B S, float32."""
        n = self._n
        flat = x.reshape(-1, x.shape[-1])
        with jax.named_scope("hc_maps"):
            x32 = flat.astype(jnp.float32)
            norm = jax.lax.rsqrt(jnp.mean(x32 * x32, -1) + self._eps)  # (T,)
            raw = jnp.einsum("kc,tc->kt", weight.astype(jnp.float32), x32,
                             precision=jax.lax.Precision.HIGHEST)
            a = jnp.repeat(scale.astype(jnp.float32),
                           onp.array([n, n, n * n]))
            h = a[:, None] * (raw * norm) + bias.astype(jnp.float32)[:, None]
            h_pre = jax.nn.sigmoid(h[:n])
            h_post = 2.0 * jax.nn.sigmoid(h[n:2 * n])
        with jax.named_scope("hc_sinkhorn"):
            h_res = sinkhorn(jnp.exp(jnp.clip(
                h[2 * n:], *self._clamp)).reshape(n, n, -1),
                self._rounds, self._eps)
        return h_pre, h_post, h_res

    def _read(self, x, weight, bias, scale):
        h_pre, h_post, h_res = self.maps(x, weight, bias, scale)
        with jax.named_scope("hc_pre"):
            lead = x.shape[:-1]
            u = sum(h_pre[i].reshape(lead + (1,)) * x_i
                    for i, x_i in enumerate(self._streams(x)))
        return u.astype(x.dtype), h_post, h_res

    def _write(self, x, y, h_post, h_res):
        with jax.named_scope("hc_post"):
            lead = x.shape[:-1]
            xs, y = self._streams(x), y.astype(jnp.float32)
            return jnp.concatenate([
                (sum(h_res[i, j].reshape(lead + (1,)) * x_j
                     for j, x_j in enumerate(xs))
                 + h_post[i].reshape(lead + (1,)) * y).astype(x.dtype)
                for i in range(self._n)], -1)

    def forward(self, x):
        """X (B, S, n C) -> (u (B, S, C), Hpost (n, B S), Hres (n, n, B S))."""
        _BLOCKS.inc(streams=self._streams_label)
        return _apply(self._read, x, self.weight.data(), self.bias.data(),
                      self.scale.data())

    def write(self, x, y, h_post, h_res):
        """X' = Hres X + Hpost^T y, under the block's own scope as its
        call's ops are."""
        with jax.named_scope(self.name):
            return _apply(self._write, x, y, h_post, h_res)


class Xing4Layer(HybridBlock):
    """Two hyper-connected sublayers on the wide stream X (B, S, n C):
    the mixer of `norm1` of what `hc_mixer` reads, written back; then the
    same with ``experts`` behind `norm2` and `hc_ffn`. ``mixer`` and
    ``experts`` build the blocks (inside this layer's name scope), as
    `SolarOpen2Layer`'s do. Experts that hand out a moved selection bias:
    (X, that bias)."""

    def __init__(self, units, streams, mixer, experts, hyper, epsilon=1e-6,
                 **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.hc_mixer = HyperConnection(units, streams, **hyper)
            self.norm1 = nn.RMSNorm(in_channels=units, epsilon=epsilon)
            self.mixer = mixer()
            self.hc_ffn = HyperConnection(units, streams, **hyper)
            self.norm2 = nn.RMSNorm(in_channels=units, epsilon=epsilon)
            self.experts = experts()

    def sublayers(self, experts=None):
        """[(its hyper-connection, its norm, its block)] in order;
        ``experts`` stands in for the second one's block."""
        return [(self.hc_mixer, self.norm1, self.mixer),
                (self.hc_ffn, self.norm2,
                 self.experts if experts is None else experts)]

    def forward(self, x):
        moved = None
        for hc, norm, block in self.sublayers():
            u, h_post, h_res = hc(x)
            y = block(norm(u))
            if isinstance(y, tuple):
                y, moved = y
            x = hc.write(x, y, h_post, h_res)
        return x if moved is None else (x, moved)


class Xing4Model(MixerStackLM):
    """tokens (B, S) int -> logits (B, S, vocab). ``streams`` streams a
    position, expanded after the embedding and summed before the final
    norm; ``layers`` layers of `Xing4Layer`, every mixer
    `MultiHeadLatentAttention(units, **latent)`, the first
    ``dense_layers`` layers' FFN a SwiGLU of ``dense_hidden``, the others'
    `SharedExpertMoE(units, **moe)`; ``hyper`` are the keyword arguments of
    `HyperConnection` after ``units`` and ``streams``. ``remat_layers`` and
    ``moe["bias_rate"]``: as `SolarOpen2Model`'s (`MixerStackLM` walks the
    stack)."""

    def __init__(self, vocab_size, units, layers, streams, latent, moe,
                 dense_hidden, hyper=None, dense_layers=1, epsilon=1e-6,
                 remat_layers=False, **kwargs):
        super().__init__(**kwargs)
        self._remat, self._n = remat_layers, streams
        with self.name_scope():
            self.tok_embed = nn.Embedding(vocab_size, units)
            self.layers = nn.HybridSequential()
            for i in range(layers):
                self.layers.add(Xing4Layer(
                    units, streams,
                    lambda: MultiHeadLatentAttention(units, epsilon=epsilon,
                                                     **latent),
                    (lambda: SwiGLU(units, dense_hidden)) if i < dense_layers
                    else (lambda: SharedExpertMoE(units, **moe)),
                    hyper or {}, epsilon=epsilon))
            self.norm_f = nn.RMSNorm(in_channels=units, epsilon=epsilon)
            self.lm_head = nn.Dense(vocab_size, flatten=False,
                                    in_units=units, use_bias=False)

    def stream_in(self, x):
        """The embedding repeated: X_0 = [e; e; ...; e]."""
        return _apply(lambda e: jnp.tile(e, self._n), x)

    def stream_out(self, x):
        """The streams summed (float32 inside)."""
        def summed(x):
            c = x.shape[-1] // self._n
            return sum(x[..., i * c:(i + 1) * c].astype(jnp.float32)
                       for i in range(self._n)).astype(x.dtype)

        return _apply(summed, x)
