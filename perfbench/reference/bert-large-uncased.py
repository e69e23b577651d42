"""Plain float32 reference of the BERT encoder with its MLM head, as
models/bert.py states it (Devlin et al. 2018, post-LN; the departures from
the paper are the configuration file's `assumed`). jax.numpy only, matmuls
at "highest" precision, no kernel, no program code.

forward(params, config, tokens, labels, tail) ->
    (MLM logits of the last `tail` positions (B, tail, V),
     per-sequence mean cross-entropy over every position (B,))
"""
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5      # nn.LayerNorm's default, which the model file uses


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _dense(p, x):
    return x @ p["w"].T + p["b"]


def _ln(p, x):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["g"] + p["b"]


def _attention(p, x, heads):
    b, s, u = x.shape
    d = u // heads

    def split(t):
        return t.reshape(b, s, heads, d).transpose(0, 2, 1, 3)

    q, k, v = (split(_dense(p[n], x)) for n in ("q", "k", "v"))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(d)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)
    return _dense(p["o"], out.transpose(0, 2, 1, 3).reshape(b, s, u))


def forward(params, config, tokens, labels, tail):
    with jax.default_matmul_precision("highest"):
        p = _f32(params)
        s = tokens.shape[1]
        x = _ln(p["embed_ln"], p["word_embed"][tokens]) + p["position"][:s]
        for layer in p["layers"]:
            x = _ln(layer["ln1"], x + _attention(
                layer, x, config["num_attention_heads"]))
            h = _dense(layer["ffn2"], jax.nn.gelu(
                _dense(layer["ffn1"], x), approximate=False))
            x = _ln(layer["ln2"], x + h)
        h = _ln(p["mlm_ln"], jax.nn.relu(_dense(p["mlm_dense"], x)))
        logits = _dense(p["mlm_decoder"], h)
        logp = jax.nn.log_softmax(logits, -1)
        nll = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
        return logits[:, s - tail:], nll.mean(-1)
