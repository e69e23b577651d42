"""EVA attention (Zheng, Yuan, Wang, Kong, "Efficient Attention via Control
Variates", ICLR 2023, arXiv:2302.04542) in the deterministic form EvaByte
ships (`attention_class: eva`): softmax attention as a sum over a PARTITION
of the causal keys. The keys of a query's own aligned window are kept
exact; every chunk of every EARLIER window is replaced by one summary, a
pooled key that stands for the chunk's mass and a weighted mean of its
values; and ONE softmax normalises both sets. q, k, v (B, H, S, D), window
W, chunk c, s = D^-1/2, phi and mu (H, D) learned, a head:

    a[j,u]  = softmax over the c positions u of chunk j of s (phi . k[u])
    kt[j]   = sum_u a[j,u] k[u] + mu        vt[j] = sum_u a[j,u] v[u]
    L(t)    = { u : u // W == t // W, u <= t }
    R(t)    = { j : j < (W / c) (t // W) }
    Z[t]    = sum_{L(t)} exp(s q[t].k[u]) + sum_{R(t)} exp(s q[t].kt[j])
    o[t]    = (sum_{L(t)} exp(s q[t].k[u]) v[u]
               + sum_{R(t)} exp(s q[t].kt[j]) vt[j]) / Z[t]

At S <= W there is no summary and the op is causal attention; at c = 1 and
mu = 0 a summary is its key and value, and the op is causal attention at
every S (the partition adds up: tests/test_evabyte.py).

**How it is computed.** Four scopes, which perfbench/eva_shares.py reads:
  `eva_pool`    the pooling weights (float32), kt and vt of the chunks of
                every window but the last (nothing reads the last's);
  `eva_local`   the exact part: the S / W windows as a batch of causal
                self-attentions of W, through `attention_with_lse` (on a TPU
                the streamed kernels, `flash_attention_lse`; else the dense
                form), which hands out the log-sum-exp beside the output;
  `eva_remote`  window w >= 1: its W queries against the first (W / c) w
                summaries, one strip a window in XLA ops (at 16 384 x 32
                heads the widest strip holds 0.23 GB of float32 scores, all
                of (S, S / c) at once 2.1 GB), each strip recomputed in its
                backward from q, kt, vt and the local part's two results
                (`jax.checkpoint`: no strip's scores are kept);
  `eva_merge`   the two parts under one normaliser: Z = Z_L + Z_R by the
                two log-sum-exps, o = (Z_L o_L + Z_R o_R) / Z, float32.
Operands keep their type (bfloat16 in a trained model), every matmul
accumulates in float32, the pooling weights and every softmax statistic are
float32 (`mixedp_attn` as the configuration's `assumed` reads it).
Gradients are autodiff's through all four: phi and mu are trained through
kt and vt, k and v as exact keys and through the pooling, and the local
part's log-sum-exp carries a cotangent of its own into the kernels'
backward (`flash_attention_lse`'s delta shift).

The paths taken are counted once a traced call,
`mxtpu_eva_attention_total{local=streamed|dense, remote=strips|none}`, and
`mxtpu_eva_pairs{kind=local|remote}` holds the (query, key) pairs a head of
the last traced call sees. S must be a multiple of c, and of W where it is
longer than W; anything else is refused, not served by another form. One
chip: under a mesh train step the kernels would need a `shard_map` this op
does not make.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import telemetry
from .attention import attention_with_lse, flash_attention_supported

__all__ = ["eva_attention", "eva_pool", "seen_pairs"]

_F32 = jnp.float32

_CALLS = telemetry.counter(
    "mxtpu_eva_attention_total",
    "EVA attentions traced, by the path of the exact part (streamed: the "
    "Pallas kernels with their log-sum-exp; dense: XLA ops, off the TPU or "
    "a window the kernels refuse) and of the summaries (strips: one XLA "
    "strip a window; none: the sequence is one window).",
    ("local", "remote"))
_PAIRS = telemetry.gauge(
    "mxtpu_eva_pairs",
    "(query, key) pairs a head of the last traced EVA attention sees: "
    "local (exact keys of the query's own window) and remote (summaries "
    "of earlier windows' chunks).", ("kind",))


def seen_pairs(seq_len, window, chunk):
    """-> (exact pairs, summary pairs) one head of one sequence sees."""
    if seq_len <= window:
        return seq_len * (seq_len + 1) // 2, 0
    n = seq_len // window
    return n * (window * (window + 1) // 2), \
        window * (window // chunk) * (n * (n - 1) // 2)


def eva_pool(k, v, phi, mu, chunk, scale):
    """k, v (B, H, T, D), T a multiple of ``chunk`` -> kt, vt
    (B, H, T / chunk, D) in k's and v's types. The weights are a float32
    softmax inside each chunk; mu is added to the pooled key alone."""
    b, h, t, d = k.shape
    kc = k.reshape(b, h, t // chunk, chunk, d).astype(_F32)
    vc = v.reshape(b, h, t // chunk, chunk, v.shape[-1]).astype(_F32)
    phi, mu = phi.astype(_F32), mu.astype(_F32)
    a = jax.nn.softmax(
        scale * (kc * phi[None, :, None, None, :]).sum(-1), -1)
    kt = (a[..., None] * kc).sum(-2) + mu[None, :, None, :]
    vt = (a[..., None] * vc).sum(-2)
    return kt.astype(k.dtype), vt.astype(v.dtype)


def _strip(q_w, kt_w, vt_w, o_l, lse_l, scale):
    """One window's queries q_w (B, H, W, D) against the summaries it sees
    (B, H, n, D), merged with the window's exact part o_l (B, H, W, D_v),
    lse_l (B, H, W) -> o (B, H, W, D_v) in o_l's type."""
    with jax.named_scope("eva_remote"):
        s = jnp.einsum("bhqd,bhjd->bhqj", q_w, kt_w,
                       preferred_element_type=_F32) * scale
        m = jax.lax.stop_gradient(s.max(-1, keepdims=True))
        p = jnp.exp(s - m)
        l = p.sum(-1, keepdims=True)
        o_r = jnp.einsum("bhqj,bhjd->bhqd", p.astype(vt_w.dtype), vt_w,
                         preferred_element_type=_F32) / l
        lse_r = (m + jnp.log(l))[..., 0]
    with jax.named_scope("eva_merge"):
        lse = jnp.logaddexp(lse_l, lse_r)
        o = jnp.exp(lse_l - lse)[..., None] * o_l.astype(_F32) \
            + jnp.exp(lse_r - lse)[..., None] * o_r
        return o.astype(o_l.dtype)


def eva_attention(q, k, v, phi, mu, window, chunk, scale=None):
    """q, k, v (B, H, S, D), phi, mu (H, D) -> (B, H, S, D): the equations
    of the module's docstring."""
    b, h, s, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if window % chunk or s % chunk or (s > window and s % window):
        raise ValueError(
            "eva_attention: %d positions in windows of %d and chunks of %d: "
            "the windows are aligned and whole, and a window is whole chunks"
            % (s, window, chunk))
    w = min(window, s)
    n = s // w
    local, remote = seen_pairs(s, window, chunk)
    _PAIRS.set(local, kind="local")
    _PAIRS.set(remote, kind="remote")
    windows = (b, h * n, w, d)
    _CALLS.inc(local="streamed" if flash_attention_supported(windows)
               else "dense", remote="strips" if n > 1 else "none")

    with jax.named_scope("eva_local"):
        # (B, H, n W, D) -> (B, H n, W, D): the windows are contiguous rows
        o_l, lse_l = attention_with_lse(
            q.reshape(windows), k.reshape(windows),
            v.reshape(windows[:3] + v.shape[-1:]), causal=True, scale=scale)
        o_l = o_l.reshape(b, h, n, w, -1)
        lse_l = lse_l.reshape(b, h, n, w)
    if n == 1:
        return o_l.reshape(b, h, s, -1)

    per_window = w // chunk
    with jax.named_scope("eva_pool"):
        # (checkpoint: the gradient keeps k and v, not their float32 copies)
        kt, vt = jax.checkpoint(eva_pool, static_argnums=(4, 5))(
            k[:, :, :s - w], v[:, :, :s - w], phi, mu, chunk, scale)
    q_w = q.reshape(b, h, n, w, d)
    strip = jax.checkpoint(_strip, static_argnums=(5,))
    out = [o_l[:, :, 0]]
    for i in range(1, n):
        seen = per_window * i
        out.append(strip(q_w[:, :, i], kt[:, :, :seen], vt[:, :, :seen],
                         o_l[:, :, i], lse_l[:, :, i], scale))
    return jnp.stack(out, 2).reshape(b, h, s, -1)
