"""Plain float32 reference of EvaByte (Hugging Face `model_type: evabyte`,
`attention_class: eva`; EvaByte/EvaByte, 6.5 B), as models/evabyte.py states
it. jax.numpy only, matmuls at "highest" precision, no kernels. Every
layer, x (S, U) float32, W = window_size, c = chunk_size, s = d^-1/2:

    n    = rmsnorm(x; 1 + g1)                    (norm_add_unit_offset)
    q_h  = rope((Wq n)_h)   k_h = rope((Wk n)_h)   v_h = (Wv n)_h
           rotate-half over all d channels, theta, positions 0 .. S-1
    a_h[j,u] = softmax over the c positions u of chunk j of s (phi_h . k_h[u])
    kt_h[j]  = sum_u a_h[j,u] k_h[u] + mu_h      vt_h[j] = sum_u a_h[j,u] v_h[u]
    L(t) = { u : u // W == t // W, u <= t }      the query's own window
    R(t) = { j : j < (W / c) (t // W) }          chunks of EARLIER windows
    o_h[t] = softmax over L(t) + R(t) TOGETHER of
             [s q_h[t].k_h[u] ; s q_h[t].kt_h[j]]  against  [v_h[u] ; vt_h[j]]
    y    = x + Wo [o_h]
    out  = y + Wdown (silu(Wgate m) * Wup m),    m = rmsnorm(y; 1 + g2)
    z[t, i, :] = (Whead rmsnorm(out_L; 1 + gf))[t, V i : V (i + 1)]   i < P
    loss = mean over i < P and t < S - i of CE(z[t, i, :], byte[t + 1 + i])

The system splits the softmax into an exact part (kernels, a window as a
batch entry) and window strips over the summaries and joins them by their
log-sum-exps; here a query block of Q_BLOCK meets the concatenation
[all S keys ; all S / c summaries] under the two masks and ONE softmax
runs over the row: the two share no structure. `labels` are the next
bytes (labels[t] = byte[t + 1]); head i's target at t is labels[t + i].

Blocking that changes no arithmetic: queries in blocks of Q_BLOCK, the head
in blocks of positions, each block and each layer recomputed in the
gradient. Departures from the published model: random weights (the
caller's); what the configuration's `assumed` lists.

forward(params, config, tokens, labels, tail) ->
    (the final norm's output of the last `tail` positions (B, tail, U),
     per-sequence loss (B,) over all P heads)
features(params, config, tokens) -> the final norm's output (B, S, U)
logits(params, config, tokens) -> z (B, S, P, V)
update_checked(params) / checked_grads(params, config, tokens, labels)
eva_attention(q, k, v, phi, mu, window, chunk): the op alone, q, k, v
    (B, H, S, D), for the tests and perfbench/probe_eva.py, which also
    replaces `pool`, `masks`, `attend`, `stream` and `loss_heads` by the
    broken programs a limit has to tell
"""
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 256        # queries per block of the attention
HEAD_BLOCK = 2048    # positions per block of the heads' projection


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _dense(p, x):
    """Every matmul of a weight goes through here (a probe rounds its
    operands to see whether the limits tell a lower precision)."""
    return x @ p["w"].T + p["b"]


def _mm(w, x):
    return _dense({"w": w, "b": 0.0}, x)


def _rms(g, x, eps):
    """RMSNorm whose gain is 1 + g."""
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * (1.0 + g)


def _block(n, want):
    return want if n % want == 0 else n


def rope(x, theta):
    """x (b, h, s, d): rotate-half, positions 0 .. s-1."""
    s, d = x.shape[-2], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ---------------------------------------------------------------- the op
def pool(k, v, phi, mu, chunk, scale):
    """k, v (b, h, s, d), phi, mu (h, d) -> kt, vt (b, h, s / chunk, d)."""
    b, h, s, d = k.shape
    kc = k.reshape(b, h, s // chunk, chunk, d)
    vc = v.reshape(b, h, s // chunk, chunk, d)
    a = jax.nn.softmax(
        scale * (kc * phi[None, :, None, None, :]).sum(-1), -1)
    return (a[..., None] * kc).sum(-2) + mu[None, :, None, :], \
        (a[..., None] * vc).sum(-2)


def masks(t, s, window, chunk):
    """Queries at positions t (qb,) -> (L (qb, s), R (qb, s / chunk))."""
    u, j = jnp.arange(s), jnp.arange(s // chunk)
    same = (u[None, :] // window) == (t[:, None] // window)
    return same & (u[None, :] <= t[:, None]), \
        j[None, :] < (window // chunk) * (t[:, None] // window)


def attend(sc_l, sc_r, seen_l, seen_r, v, vt):
    """Scores (b, h, qb, s) and (b, h, qb, s / c) under their masks ->
    o (b, h, qb, d): ONE softmax over both sets."""
    sc = jnp.concatenate([jnp.where(seen_l, sc_l, -jnp.inf),
                          jnp.where(seen_r, sc_r, -jnp.inf)], -1)
    a = jax.nn.softmax(sc, -1)
    return jnp.einsum("bhqk,bhkd->bhqd", a, jnp.concatenate([v, vt], 2))


def eva_attention(q, k, v, phi, mu, window, chunk):
    b, h, s, d = q.shape
    scale = 1.0 / math.sqrt(d)
    kt, vt = pool(k, v, phi, mu, chunk, scale)
    qb = _block(s, Q_BLOCK)

    @jax.checkpoint      # the gradient keeps no block's scores
    def one(args):
        q_blk, start = args                           # (b, h, qb, d)
        seen_l, seen_r = masks(start + jnp.arange(qb), s, window, chunk)
        return attend(jnp.einsum("bhqd,bhkd->bhqk", q_blk, k) * scale,
                      jnp.einsum("bhqd,bhkd->bhqk", q_blk, kt) * scale,
                      seen_l, seen_r, v, vt)

    o = jax.lax.map(one, (
        jnp.moveaxis(q.reshape(b, h, s // qb, qb, d), 2, 0),
        jnp.arange(0, s, qb)))
    return jnp.moveaxis(o, 0, 2).reshape(b, h, s, d)


def attention(p, n, config):
    """The attention block on its normed input n (b, s, U) -> Wo [o_h]."""
    b, s, _ = n.shape
    h = config["num_attention_heads"]
    d = p["q"].shape[0] // h
    theta = float(config["rope_theta"])

    def heads(t):
        return t.reshape(b, s, h, d).transpose(0, 2, 1, 3)

    q, k = rope(heads(_mm(p["q"], n)), theta), rope(heads(_mm(p["k"], n)),
                                                   theta)
    o = eva_attention(q, k, heads(_mm(p["v"], n)), p["phi"], p["mu"],
                      config["window_size"], config["chunk_size"])
    return _mm(p["o"], o.transpose(0, 2, 1, 3).reshape(b, s, h * d))


# ------------------------------------------------------------------ model
def stream(x):
    """The residual stream after an add: float32 as it is (fp32_skip_add)."""
    return x


def _layer(p, x, config):
    eps = config["rms_norm_eps"]
    x = stream(x + attention(p, _rms(p["norm1"], x, eps), config))
    m = _rms(p["norm2"], x, eps)
    return stream(x + _mm(p["down"], jax.nn.silu(_mm(p["gate"], m))
                          * _mm(p["up"], m)))


def _trunk(p, config, tokens):
    x = p["tok_embed"][tokens]
    for layer in p["layers"]:
        x = jax.checkpoint(lambda p, x: _layer(p, x, config))(layer, x)
    return _rms(p["norm_f"], x, config["rms_norm_eps"])


def features(params, config, tokens):
    with jax.default_matmul_precision("highest"):
        return _trunk(_f32(params), config, tokens)


def logits(params, config, tokens):
    with jax.default_matmul_precision("highest"):
        p = _f32(params)
        z = _mm(p["head"], _trunk(p, config, tokens))
        return z.reshape(z.shape[:2] + (config["num_pred_heads"], -1))


def loss_heads(config):
    """The prediction heads the loss runs over: all of them."""
    return tuple(range(config["num_pred_heads"]))


def _loss(p, config, tokens, labels):
    b, s = tokens.shape
    heads = config["num_pred_heads"]
    feats = _trunk(p, config, tokens)
    hb = _block(s, HEAD_BLOCK)
    counted = loss_heads(config)
    # head i at t predicts labels[t + i]; past the end there is no target
    at = jnp.arange(s)[:, None] + jnp.arange(heads)[None, :]     # (s, P)
    targets = jnp.asarray(labels)[:, jnp.minimum(at, s - 1)]     # (b, s, P)
    weight = (at < s) & jnp.isin(jnp.arange(heads), jnp.array(counted))

    @jax.checkpoint
    def nll(args):
        f, y = args                              # (b, hb, U), (b, hb, P)
        z = _mm(p["head"], f).reshape(b, hb, heads, -1)
        logp = jax.nn.log_softmax(z, -1)
        return -jnp.take_along_axis(logp, y[..., None], -1)[..., 0]

    per = jax.lax.map(nll, (
        feats.reshape(b, s // hb, hb, -1).transpose(1, 0, 2, 3),
        targets.reshape(b, s // hb, hb, heads).transpose(1, 0, 2, 3)))
    per = per.transpose(1, 0, 2, 3).reshape(b, s, heads)
    return (per * weight).sum((1, 2)) / weight.sum()


def forward(params, config, tokens, labels, tail):
    with jax.default_matmul_precision("highest"):
        p = _f32(params)
        trunk = _trunk(p, config, tokens)
        return trunk[:, tokens.shape[1] - tail:], \
            _loss(p, config, tokens, labels)


#: what is checked, all of the LAST layer: the two learned vectors a head
#: (moved only through the summaries), q, k, v, o (the merged softmax's
#: backward), the MLP's three matrices (the row blocks' summed gradients),
#: and the heads' map in two pieces: the rows of prediction 0 and of the
#: LAST prediction, which move only while that head is in the loss. (The
#: norms' gains start at 0, where Adam's first step moves them whatever
#: their gradient's size: they say nothing the matrices do not.)
OWN = ("phi", "mu", "q", "k", "v", "o", "gate", "up", "down")


def _head_rows(head, heads):
    rows = head.shape[0] // heads
    return {"head_pred0": head[:rows],
            "head_pred%d" % (heads - 1): head[-rows:]}


def _heads_of(params):
    return params["head"].shape[0] // params["tok_embed"].shape[0]


def update_checked(params):
    out = {n: params["layers"][-1][n] for n in OWN}
    out.update(_head_rows(params["head"], _heads_of(params)))
    return out


def checked_grads(params, config, tokens, labels):
    last, head = params["layers"][-1], params["head"]
    heads = config["num_pred_heads"]
    rows = head.shape[0] // heads

    def loss_of(picked):
        layers = list(params["layers"][:-1]) \
            + [dict(last, **{n: picked[n] for n in OWN})]
        whole = jnp.concatenate([
            picked["head_pred0"], head[rows:-rows].astype(jnp.float32),
            picked["head_pred%d" % (heads - 1)]])
        with jax.default_matmul_precision("highest"):
            return _loss(_f32(dict(params, layers=layers, head=whole)),
                         config, tokens, labels).sum()

    return jax.grad(loss_of)(_f32(update_checked(params)))
