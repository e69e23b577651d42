"""Plain float32 reference of the GPT-2 architecture Cerebras-GPT uses
(arXiv:2304.03208: pre-LN, GELU, learned positions, biases, tied head), as
models/gpt.py states it. jax.numpy only, matmuls at "highest" precision.
Causal attention is computed in blocks of queries against the whole
context and the head in blocks of positions, so that 16k tokens fit; that
blocking changes no arithmetic.

forward(params, config, tokens, labels, tail) ->
    (final-LayerNorm output of the last `tail` positions (B, tail, U),
     per-sequence mean next-token cross-entropy over every position (B,))
update_checked(params) -> the parameters whose first update the driver
    compares with this file's gradient, {name: array}
checked_grads(params, config, tokens, labels) -> the gradient of the summed
    loss with respect to them, {name: array}
"""
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
Q_BLOCK = 256        # queries per attention block
HEAD_BLOCK = 1024    # positions per block of the vocabulary projection


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _dense(p, x):
    return x @ p["w"].T + p["b"]


def _ln(p, x):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["g"] + p["b"]


def _block(n, want):
    return want if n % want == 0 else n


def _causal_attention(p, x, heads):
    b, s, u = x.shape
    d = u // heads

    def split(t):
        return t.reshape(b, s, heads, d).transpose(0, 2, 1, 3)

    q, k, v = (split(_dense(p[n], x)) for n in ("q", "k", "v"))
    qb = _block(s, Q_BLOCK)
    key_pos = jnp.arange(s)

    @jax.checkpoint      # the gradient keeps no block's scores
    def one(args):
        q_blk, start = args                              # (b, h, qb, d)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q_blk, k) / math.sqrt(d)
        q_pos = start + jnp.arange(qb)
        scores = jnp.where(q_pos[:, None] >= key_pos[None, :], scores,
                           -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)

    blocks = q.reshape(b, heads, s // qb, qb, d).transpose(2, 0, 1, 3, 4)
    out = jax.lax.map(one, (blocks, jnp.arange(0, s, qb)))
    out = out.transpose(1, 2, 0, 3, 4).reshape(b, heads, s, d)
    return _dense(p["o"], out.transpose(0, 2, 1, 3).reshape(b, s, u))


def forward(params, config, tokens, labels, tail):
    with jax.default_matmul_precision("highest"):
        p = _f32(params)
        b, s = tokens.shape
        x = p["tok_embed"][tokens] + p["pos_embed"][:s]
        for layer in p["layers"]:
            x = x + _causal_attention(layer, _ln(layer["ln1"], x),
                                      config["n_head"])
            h = jax.nn.gelu(_dense(layer["fc1"], _ln(layer["ln2"], x)),
                            approximate=False)
            x = x + _dense(layer["fc2"], h)
        feats = _ln(p["ln_f"], x)

        hb = _block(s, HEAD_BLOCK)

        @jax.checkpoint
        def nll(args):
            f, y = args                                   # (b, hb, u), (b, hb)
            logp = jax.nn.log_softmax(f @ p["tok_embed"].T, -1)
            return -jnp.take_along_axis(logp, y[..., None], -1)[..., 0]

        per_pos = jax.lax.map(nll, (
            feats.reshape(b, s // hb, hb, -1).transpose(1, 0, 2, 3),
            labels.reshape(b, s // hb, hb).transpose(1, 0, 2)))
        return feats[:, s - tail:], per_pos.transpose(1, 0, 2).reshape(
            b, s).mean(-1)


def update_checked(params):
    """The last block's query, key and value weights: all that the dQ and
    dK/dV kernels produce for that block flows into them, and the backward
    pass need go no deeper than one block."""
    last = params["layers"][-1]
    return {n: last[n]["w"] for n in ("q", "k", "v")}


def checked_grads(params, config, tokens, labels):
    def loss_of(picked):
        last = dict(params["layers"][-1])
        for n, w in picked.items():
            last[n] = {"w": w, "b": last[n]["b"]}
        p = dict(params, layers=list(params["layers"][:-1]) + [last])
        return forward(p, config, tokens, labels, 1)[1].sum()

    return jax.grad(loss_of)(_f32(update_checked(params)))
