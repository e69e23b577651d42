"""Phi-4-mini-flash (`model_type: phi4flash`; the SambaY
decoder-hybrid-decoder of arXiv:2507.06607): a decoder whose every layer is
a mixer and a SwiGLU MLP behind pre-norm residuals,

    x <- x + Mixer(LN1(x));  x <- x + MLP(LN2(x))

LayerNorm with gain and bias, a final LayerNorm, logits by the tied
embedding, and NO position embedding of any kind (the Mamba layers carry
position). The mixer is read from a pattern string:

    M   Mamba-1 (arXiv:2312.00752): [x', z] = W_in h;
        x'' = silu(conv1d_causal,k(x') + b);  [d, B, C] = W_x x''
        D_t = softplus(W_dt d + b_dt);  A = -exp(A_log)   (float32)
        h_t = exp(D_t A) h_{t-1} + (D_t x''_t) B_t^T;  y_t = h_t C_t + D x''_t
        out = W_out (y * silu(z))                (ops/selective_scan.py:
        on a TPU at channels a multiple of 1024 a Pallas kernel pair that
        keeps the state in VMEM, forward and backward; else XLA ops)
        The LAST M before F also hands on m = y, before the gate.
    S   differential attention (arXiv:2410.05258) under a sliding window
    F   the same, full causal; it also hands on its k and v
        [q, k, v] = W_qkv h + b; heads taken in pairs (q_1, q_2), (k_1, k_2),
        (v_1, v_2), key-value pairs repeated to the query pairs;
        a_i = softmax(q_i k_i^T / sqrt(d) + mask)
        o = (a_1 - l a_2) [v_1; v_2]
        l = exp(l_q1 . l_k1) - exp(l_q2 . l_k2) + l_init(depth)
        out = W_o concat(RMSNorm_2d(o) (1 - l_init)) + b
    G   gated memory unit: W_2 (m * silu(W_1 h)), m the memory of M
    C   cross-decoder attention: q = W_q h + b alone; k, v are F's; the
        same differential form, full causal

So a layer may hand later layers more than the residual stream: the stack
passes the memory to every G and F's k, v to every C (their gradients sum
over the readers by autodiff), and `gluon.utils.recompute` carries the
tuples. The float32 reference of these equations is
perfbench/reference/phi-4-mini-flash-reasoning.py.

Differential attention runs on the streamed kernels as TWO calls a layer
(scope `diff_attention`; under a window the kernels are flash_window_fwd /
flash_window_bwd): each softmax map a_i = softmax(q_i k_i^T) is computed
once and meets [v_1; v_2], v's own layout read 2 d wide, through the
kernels' value width D_v (ops/attention.py): q, k 64 wide, v and the
output 128. Where the equal-width shape belongs to the short family
(d 32 / 64 at 256 <= S <= 768), whose lane layout knows one width, the
block makes the four calls of one shape it made up to PR 36, a_1 v_1,
a_1 v_2, a_2 v_1, a_2 v_2; everywhere else, the composite included, two.

Why two calls and not four (v5e, the SambaY stage's (1, 20, 16384, 64)
bfloat16 causal, a call alone; PERF.md section 6, PR 37): a head of 64
half-fills the MXU's 128 lanes and every (.., S, 64) row is padded to 128
in HBM, so a call against a value 128 wide takes what a call against one
64 wide takes, and replaces two: forward 14.2 ms and forward + backward
37.7 ms, where the two calls of one width take 28.3 and 74.6 (under the
window of 512: 4.0 and 8.9 for 7.7 and 17.3), the output columns equal to
the bit; in the stage's step 13.1 ms a forward call and 21.7 a backward,
what ONE of the four took. Why not one call over 4 x the
head pairs (PR 34): the backward's float32 dQ, dK, dV of all four exist
at once, 2 GB at 16k tokens in 64-wide rows padded to 128 lanes, and the
step does not load beside 9.8 GB of state; two calls a layer hold what
one of the four held.
"""
from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp

from .. import initializer
from ..gluon import nn, utils
from ..gluon.block import HybridBlock
from ..ndarray import _apply
from ..ops.attention import ATTENDED_NAME
from ..ops.selective_scan import SCANNED_NAME, selective_scan
from .nemotron_h import _InverseSoftplusOfLogUniform

__all__ = ["Phi4FlashModel", "SambaYLayer", "Mamba1Mixer",
           "DifferentialAttention", "GatedMemoryUnit", "SwiGLU",
           "sambay_pattern"]

#: Mamba-1's initial step sizes: log-uniform in [min, max], floored
_DT_INIT = (0.001, 0.1, 1e-4)
#: the pattern's letters: Mamba-1, window attention, full attention (hands
#: on k, v), gated memory unit, cross-decoder attention
MIXERS = "MSFGC"
#: what a recomputed layer keeps of its forward: what the attention and scan
#: kernels wrote for their backward (o and lse a call, y and the chunks'
#: states a scan; under 1 GB of the stage's step at 16k tokens)
_KEPT = jax.checkpoint_policies.save_only_these_names(ATTENDED_NAME,
                                                      SCANNED_NAME)


def sambay_pattern(num_layers, mb_per_layer=2):
    """The source's rule for a whole model: every ``mb_per_layer``-th layer
    is attention, the others Mamba; the first half is the self-decoder
    (window attention), layer n/2 the Mamba whose scan output is the
    memory, n/2 + 1 the one full attention, and from n/2 + 2 on the
    cross-decoder: gated memory units in Mamba's places, cross attention in
    attention's."""
    half = num_layers // 2
    out = []
    for i in range(num_layers):
        attention = i % mb_per_layer == mb_per_layer - 1
        if i >= half + 2:
            out.append("C" if attention else "G")
        elif attention:
            out.append("F" if i >= half else "S")
        else:
            out.append("M")
    return "".join(out)


def lambda_init(depth):
    """Differential attention's l_init at layer ``depth``."""
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


class _LogArange(initializer.Initializer):
    """A_log: log(1 .. N) along the states of every channel (Mamba-1's
    S4D-real initialisation)."""

    def _init_weight(self, name, arr):
        arr._data = jnp.broadcast_to(jnp.log(jnp.arange(
            1, arr.shape[1] + 1, dtype=jnp.float32)), arr.shape) \
            .astype(arr.dtype)


class SwiGLU(HybridBlock):
    """The dense gated MLP: [g, u] = W_gu h; out = W_down (u * silu(g)),
    no biases. Scope `ffn`."""

    def __init__(self, units, hidden, **kwargs):
        super().__init__(**kwargs)
        self._hidden = hidden
        with self.name_scope():
            self.gate_up = nn.Dense(2 * hidden, flatten=False,
                                    in_units=units, use_bias=False)
            self.down = nn.Dense(units, flatten=False, in_units=hidden,
                                 use_bias=False)

    def forward(self, h):
        with jax.named_scope("ffn"):
            # (checkpoint: the gradient keeps [g, u], not silu(g) beside it)
            return self.down(_apply(jax.checkpoint(
                lambda t: t[..., self._hidden:]
                * jax.nn.silu(t[..., :self._hidden])), self.gate_up(h)))


class Mamba1Mixer(HybridBlock):
    """The Mamba-1 mixer. ``hand_on``: forward returns (out, y), y the
    scan's output before the gate (the cross-decoder's memory).

    Scopes inside the block's own: `mamba_conv`, `selective_scan`,
    `mamba_gate`. ``A_log``, ``D`` and ``dt_bias`` stay float32 under
    ``cast``: the scan takes them so."""

    def __init__(self, units, inner, state=16, conv_kernel=4, hand_on=False,
                 **kwargs):
        super().__init__(**kwargs)
        self.inner, self.state = inner, state
        self.dt_rank = -(-units // 16)        # the family's rule
        self._k, self._hand_on = conv_kernel, hand_on
        with self.name_scope():
            self.in_proj = nn.Dense(2 * inner, flatten=False, in_units=units,
                                    use_bias=False)
            self.conv_weight = self.params.get(
                "conv_weight", shape=(inner, conv_kernel),
                init=initializer.Uniform(1.0 / math.sqrt(conv_kernel)))
            self.conv_bias = self.params.get("conv_bias", shape=(inner,),
                                             init="zeros")
            self.x_proj = nn.Dense(self.dt_rank + 2 * state, flatten=False,
                                   in_units=inner, use_bias=False)
            self.dt_weight = self.params.get(
                "dt_weight", shape=(inner, self.dt_rank))
            self.dt_bias = self.params.get(
                "dt_bias", shape=(inner,),
                init=_InverseSoftplusOfLogUniform(*_DT_INIT))
            self.A_log = self.params.get("A_log", shape=(inner, state),
                                         init=_LogArange())
            self.D = self.params.get("D", shape=(inner,), init="ones")
            self.out_proj = nn.Dense(units, flatten=False, in_units=inner,
                                     use_bias=False)

    def cast(self, dtype):
        super().cast(dtype)
        for p in (self.A_log, self.D, self.dt_bias):
            p.cast("float32")

    @functools.partial(jax.checkpoint, static_argnums=0)
    def _conv(self, x, conv_w, conv_b):
        # (checkpoint: the gradient keeps x, not the float32 sum)
        with jax.named_scope("mamba_conv"):
            # depthwise, causal: position t sees t-k+1 .. t
            s = x.shape[1]
            padded = jnp.pad(x, [(0, 0), (self._k - 1, 0), (0, 0)])
            taps = conv_w.astype(jnp.float32)
            acc = conv_b.astype(jnp.float32)
            for j in range(self._k):
                acc = acc + padded[:, j:j + s].astype(jnp.float32) * taps[:, j]
            return jax.nn.silu(acc).astype(x.dtype)

    def _scan(self, x, dbc, dt_w, a_log, dt_bias, d_skip):
        r, n = self.dt_rank, self.state
        # the step sizes are formed inside the scan where they are used (a
        # chunk of a channel block in VMEM in the kernels, a channel block
        # in the XLA form), in float32 from the matmul on: no bfloat16
        # rounding between the projection and the softplus, no (S, inner)
        # float32 tensor
        return selective_scan(
            x, dbc[..., :r], -jnp.exp(a_log.astype(jnp.float32)),
            dbc[..., r:r + n], dbc[..., r + n:], d_skip, (dt_w, dt_bias))

    def forward(self, u):
        inner = self.inner
        xz = self.in_proj(u)
        x = _apply(lambda t, w, b: self._conv(t[..., :inner], w, b), xz,
                   self.conv_weight.data(), self.conv_bias.data())
        dbc = self.x_proj(x)
        y = _apply(self._scan, x, dbc, self.dt_weight.data(),
                   self.A_log.data(),
                   self.dt_bias.data(), self.D.data())

        @jax.checkpoint      # the gradient keeps y and z, no float32 copy
        def gate(y, xz):
            with jax.named_scope("mamba_gate"):
                return (y.astype(jnp.float32) * jax.nn.silu(
                    xz[..., inner:].astype(jnp.float32))).astype(y.dtype)

        out = self.out_proj(_apply(gate, y, xz))
        return (out, y) if self._hand_on else out


class DifferentialAttention(HybridBlock):
    """Differential attention over ``num_heads`` query heads of
    ``head_dim`` on ``num_kv_heads`` key-value heads, the heads taken in
    pairs. ``window``: key j is seen from i iff 0 <= i - j < window (None:
    full causal). ``hand_on``: forward returns (out, k, v), the key and
    value projections (B, S, kv heads x d) before the repetition.
    ``cross``: the block has the query projection alone and forward takes
    (x, k, v) of the layer that handed them on.

    Two attention calls a layer: each map of a pair against [v_1; v_2],
    a value 2 x ``head_dim`` wide on keys of ``head_dim``
    (`flash_attention`'s D_v; four calls of one width where that shape is
    the short family's: the module's docstring). Scope `diff_attention`
    (the two maps, their combination and the per-head norm), under
    `cross_attention` where the block is the cross-decoder's; the four l
    vectors and the norm's gain stay float32 under ``cast``."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim, depth,
                 window=None, hand_on=False, cross=False, epsilon=1e-5,
                 **kwargs):
        super().__init__(**kwargs)
        if num_heads % 2 or num_kv_heads % 2 \
                or num_heads % num_kv_heads:
            raise ValueError("%d query heads on %d key-value heads do not "
                             "pair" % (num_heads, num_kv_heads))
        self._h, self._hkv, self._d = num_heads, num_kv_heads, head_dim
        self._window, self._hand_on, self._cross = window, hand_on, cross
        self._eps, self._l_init = epsilon, lambda_init(depth)
        q_units, kv_units = num_heads * head_dim, num_kv_heads * head_dim
        with self.name_scope():
            self.qkv = nn.Dense(q_units + (0 if cross else 2 * kv_units),
                                flatten=False, in_units=units)
            self.proj = nn.Dense(units, flatten=False, in_units=q_units)
            self.lambdas = self.params.get(
                "lambdas", shape=(4, head_dim),
                init=initializer.Normal(0.1))      # rows: q1, k1, q2, k2
            self.subln_gamma = self.params.get(
                "subln_gamma", shape=(2 * head_dim,), init="ones")

    def cast(self, dtype):
        super().cast(dtype)
        for p in (self.lambdas, self.subln_gamma):
            p.cast("float32")

    def _attend(self, q, k, v, lambdas, gamma):
        """q (B, S, H d); k, v (B, S, Hkv d) -> (B, S, H d)."""
        from ..ops.attention import attention_route, flash_attention, \
            flash_attention_on_mesh
        from ..parallel.mesh import step_mesh
        b, s, _ = q.shape
        d = self._d

        def halves(t, n):     # (B, S, n d) -> two (B, n/2, S, d)
            t = t.reshape(b, s, n // 2, 2, d).transpose(3, 0, 2, 1, 4)
            return t[0], t[1]

        with jax.named_scope("diff_attention"):
            q1, q2 = halves(q, self._h)
            k1, k2 = halves(k, self._hkv)
            # [v_1; v_2] of a pair is v's own layout read 2 d wide
            vv = v.reshape(b, s, self._hkv // 2, 2 * d).transpose(0, 2, 1, 3)
            rep = self._h // self._hkv
            if rep > 1:
                k1, k2, vv = (jnp.repeat(t, rep, 1) for t in (k1, k2, vv))
            step = step_mesh()

            def attend(q, k, v):
                if step is not None:
                    out = flash_attention_on_mesh(
                        q, k, v, step[0], batch_axis=step[1], causal=True,
                        window=self._window)
                else:
                    out = flash_attention(q, k, v, True, window=self._window)
                return out.astype(jnp.float32)

            if attention_route(q1.shape, window=self._window) == "short":
                # the short family's lane layout knows one width: the four
                # calls a pair, each map against v_1 and against v_2
                def a(q, k):
                    return jnp.concatenate([attend(q, k, vv[..., :d]),
                                            attend(q, k, vv[..., d:])], -1)
            else:
                a = functools.partial(attend, v=vv)   # a value 2 d wide
            lam = jnp.exp(jnp.sum(lambdas[0] * lambdas[1])) \
                - jnp.exp(jnp.sum(lambdas[2] * lambdas[3])) + self._l_init
            o = a(q1, k1) - lam * a(q2, k2)           # (B, pairs, S, 2d)
            o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                                  + self._eps) * gamma * (1.0 - self._l_init)
            return o.transpose(0, 2, 1, 3).reshape(b, s, self._h * d) \
                .astype(q.dtype)

    def forward(self, x, k=None, v=None):
        scope = jax.named_scope("cross_attention") if self._cross \
            else contextlib.nullcontext()
        with scope:
            q = self.qkv(x)
            if not self._cross:
                qu, kvu = self._h * self._d, self._hkv * self._d
                q, k, v = (_apply(lambda t, a=a, z=z: t[..., a:z], q)
                           for a, z in ((0, qu), (qu, qu + kvu),
                                        (qu + kvu, qu + 2 * kvu)))
            out = self.proj(_apply(self._attend, q, k, v,
                                   self.lambdas.data(),
                                   self.subln_gamma.data()))
        return (out, k, v) if self._hand_on else out


class GatedMemoryUnit(HybridBlock):
    """W_2 (m * silu(W_1 h)): the cross-decoder's cheap mixer, gating the
    memory m (the last self-decoder Mamba's scan output) element-wise. No
    biases. Scope `gmu`."""

    def __init__(self, units, inner, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.in_proj = nn.Dense(inner, flatten=False, in_units=units,
                                    use_bias=False)
            self.out_proj = nn.Dense(units, flatten=False, in_units=inner,
                                     use_bias=False)

    def forward(self, h, memory):
        with jax.named_scope("gmu"):
            gated = _apply(
                lambda g, m: (m.astype(jnp.float32) * jax.nn.silu(
                    g.astype(jnp.float32))).astype(g.dtype),
                self.in_proj(h), memory)
            return self.out_proj(gated)


class SambaYLayer(HybridBlock):
    """x + mixer(LN1(x)), then x + MLP(LN2(x)). ``mixer`` builds the block
    (inside this layer's name scope). forward takes the stream and
    whatever the mixer reads beside it (G: the memory; C: k, v) and returns
    the stream with whatever the mixer hands on behind it."""

    def __init__(self, units, hidden, mixer, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.ln1 = nn.LayerNorm(in_channels=units, epsilon=epsilon)
            self.mixer = mixer()
            self.ln2 = nn.LayerNorm(in_channels=units, epsilon=epsilon)
            self.mlp = SwiGLU(units, hidden)

    def forward(self, x, *read):
        mixed = self.mixer(self.ln1(x), *read)
        handed = ()
        if isinstance(mixed, tuple):
            mixed, handed = mixed[0], mixed[1:]
        x = x + mixed
        x = x + self.mlp(self.ln2(x))
        return (x,) + tuple(handed) if handed else x


class Phi4FlashModel(HybridBlock):
    """tokens (B, S) int -> logits (B, S, vocab) by the tied embedding.
    ``pattern`` names the layers (`M`, `S`, `F`, `G`, `C`): the last `M`
    before the `F` hands on its scan output, every `G` behind reads it,
    every `C` reads `F`'s k and v. ``mamba`` and ``attention`` are the
    keyword arguments of `Mamba1Mixer` and `DifferentialAttention` after
    ``units``. ``remat_layers``: each layer's forward is recomputed in the
    backward (`gluon.utils.recompute`) but for what its Pallas kernels
    wrote (`_KEPT`), so each forward kernel runs once a step."""

    def __init__(self, vocab_size, units, hidden_size, pattern, mamba,
                 attention, window, epsilon=1e-5, remat_layers=False,
                 **kwargs):
        super().__init__(**kwargs)
        full = pattern.find("F")
        readers = [i for i, c in enumerate(pattern) if c in "GC"]
        if set(pattern) - set(MIXERS) or not pattern \
                or pattern.count("F") > 1 or (readers and not (
                    "M" in pattern[:max(full, 0)] and readers[0] > full)):
            raise ValueError(
                "pattern %r: a layer is one of %s, and G and C stand "
                "behind the one F, which stands behind an M"
                % (pattern, sorted(MIXERS)))
        self.pattern = pattern
        self._remat = remat_layers
        self._memory_layer = pattern.rfind("M", 0, full) if full >= 0 else -1

        def mixer(i, letter):
            if letter == "M":
                return lambda: Mamba1Mixer(
                    units, hand_on=i == self._memory_layer, **mamba)
            if letter == "G":
                return lambda: GatedMemoryUnit(units, mamba["inner"])
            return lambda: DifferentialAttention(
                units, depth=i, epsilon=epsilon,
                window=window if letter == "S" else None,
                hand_on=letter == "F", cross=letter == "C", **attention)

        with self.name_scope():
            self.tok_embed = nn.Embedding(vocab_size, units)
            self.layers = nn.HybridSequential()
            for i, letter in enumerate(pattern):
                self.layers.add(SambaYLayer(units, hidden_size,
                                            mixer(i, letter),
                                            epsilon=epsilon))
            self.ln_f = nn.LayerNorm(in_channels=units, epsilon=epsilon)

    def features(self, token_ids):
        """The final LayerNorm's output (B, S, U): pair with ChunkedLMLoss
        so the (B*S, V) logits never materialise."""
        x = self.tok_embed(token_ids)
        read = {"G": (), "C": ()}
        for i, (layer, letter) in enumerate(zip(self.layers, self.pattern)):
            args = (x,) + read.get(letter, ())
            out = utils.recompute(layer, *args, policy=_KEPT) \
                if self._remat else layer(*args)
            if i == self._memory_layer:
                x, memory = out
                read["G"] = (memory,)
            elif letter == "F":
                x, k, v = out
                read["C"] = (k, v)
            else:
                x = out
        return self.ln_f(x)

    def forward(self, token_ids):
        h = self.features(token_ids)
        return _apply(lambda hd, e: hd @ e.T.astype(hd.dtype), h,
                      self.tok_embed.weight.data())
