"""Plain float32 reference of the OLMoE architecture (Muennighoff et al.
2024, arXiv:2409.02060; Hugging Face `model_type: olmoe`), as
models/olmoe.py states it. jax.numpy only, matmuls at "highest" precision.

    h   = x + Wo Attn(rope(rms_q(Wq n1(x))), rope(rms_k(Wk n1(x))), Wv n1(x))
    out = h + sum_{e in top-k(p)} p_e Wdown_e(silu(Wgate_e n2(h)) * Wup_e n2(h))
    logits = Whead RMSNorm(out_L)

n1, n2, rms_q, rms_k are RMSNorms over the whole model width (rms_q and
rms_k BEFORE the split into heads); rope is the rotate-half convention on
each head, positions 0..S-1; attention is causal softmax(q k^T / sqrt(d)) v;
p = softmax(Wr n2(h)) over all experts, its k largest entries used as they
are, or renormalised to sum to one where the configuration's
`norm_topk_prob` is true. No biases anywhere.

The experts are a loop over ALL of them against a dense (T, E) matrix of
weights that is zero where an expert was not chosen: no sort, no grouped
matmul, no kernel. Causal attention is computed in blocks of queries against
the whole context, the loop over experts in blocks of tokens and the head in
blocks of positions, so that 4 x 4k tokens and their gradient fit; that
blocking changes no arithmetic. Departures from the published
model: none in the equations; the weights are random (the caller's).

forward(params, config, tokens, labels, tail) ->
    (final-RMSNorm output of the last `tail` positions (B, tail, U),
     per-sequence mean next-token cross-entropy over every position (B,))
update_checked(params) -> the parameters whose first update the driver
    compares with this file's gradient, {name: array}
checked_grads(params, config, tokens, labels) -> the gradient of the summed
    loss with respect to them, {name: array}
routing(params, config, tokens) -> the last layer's (T, k) expert choices

This file exists twice, byte for byte: tests/olmoe_reference.py, which the
tier-1 tests import, and perfbench/reference/olmoe-1b-7b-0125.py, where the
benchmark finds a configuration's reference by name. A test holds the two
to the same text and the same outputs.
"""
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 256        # queries per attention block
HEAD_BLOCK = 1024    # positions per block of the vocabulary projection
TOKEN_BLOCK = 1024   # tokens per block of the loop over experts


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _dense(p, x):
    """Every matmul goes through here (perfbench/probe_limits.py rounds its
    operands to see whether the limits tell a lower precision)."""
    return x @ p["w"].T + p["b"]


def _mm(w, x):
    return _dense({"w": w, "b": 0.0}, x)


def _rms(g, x, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x (b, h, s, d): the pair (x[i], x[i + d/2]) turns by
    pos * theta^(-2i/d)."""
    s, d = x.shape[-2:]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _block(n, want):
    return want if n % want == 0 else n


def _causal_attention(p, x, config):
    b, s, u = x.shape
    heads, eps = config["num_attention_heads"], config["rms_norm_eps"]
    d = u // heads

    def split(t):
        return t.reshape(b, s, heads, d).transpose(0, 2, 1, 3)

    q = _rope(split(_rms(p["q_norm"], _mm(p["q"], x), eps)),
              config["rope_theta"])
    k = _rope(split(_rms(p["k_norm"], _mm(p["k"], x), eps)),
              config["rope_theta"])
    v = split(_mm(p["v"], x))
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
    return _mm(p["o"], out.transpose(0, 2, 1, 3).reshape(b, s, u))


def _route(p, t, config):
    """t (T, U) -> (probabilities of the chosen experts (T, k), their
    indices (T, k))."""
    probs = jax.nn.softmax(_mm(p["router"], t), -1)               # (T, E)
    vals, idx = jax.lax.top_k(probs, config["num_experts_per_tok"])
    if config["norm_topk_prob"]:
        vals = vals / vals.sum(-1, keepdims=True)
    return vals, idx


def _moe(p, x, config):
    t = x.reshape(-1, x.shape[-1])
    vals, idx = _route(p, t, config)
    # (T, E): p_e where expert e was chosen for the token, else 0
    weight = jnp.zeros((t.shape[0], config["num_experts"]), jnp.float32) \
        .at[jnp.arange(t.shape[0])[:, None], idx].set(vals)
    # gate/up are stored (E, U, I) and down (E, I, U): x @ w, so w.T is "w"
    experts = tuple(p[n].transpose(0, 2, 1) for n in ("gate", "up", "down"))

    @jax.checkpoint      # the gradient keeps one block's loop at a time
    def block(args):
        t_blk, w_blk = args                               # (tb, U), (tb, E)

        def one(out, expert):
            gate, up, down, w_e = expert
            y = _mm(down, jax.nn.silu(_mm(gate, t_blk)) * _mm(up, t_blk))
            return out + w_e[:, None] * y, None

        return jax.lax.scan(one, jnp.zeros_like(t_blk),
                            experts + (w_blk.T,))[0]

    tb = _block(t.shape[0], TOKEN_BLOCK)
    out = jax.lax.map(block, (t.reshape(-1, tb, t.shape[-1]),
                              weight.reshape(-1, tb, weight.shape[-1])))
    return out.reshape(x.shape), idx


def _trunk(p, config, tokens):
    """-> (the final norm's output, the last layer's expert choices)."""
    x = p["tok_embed"][tokens]
    eps = config["rms_norm_eps"]
    for layer in p["layers"]:
        x = x + _causal_attention(layer, _rms(layer["n1"], x, eps), config)
        y, chosen = _moe(layer, _rms(layer["n2"], x, eps), config)
        x = x + y
    return _rms(p["norm_f"], x, eps), chosen


def forward(params, config, tokens, labels, tail):
    with jax.default_matmul_precision("highest"):
        p = _f32(params)
        b, s = tokens.shape
        feats, _ = _trunk(p, config, tokens)
        hb = _block(s, HEAD_BLOCK)

        @jax.checkpoint
        def nll(args):
            f, y = args                                   # (b, hb, u), (b, hb)
            logp = jax.nn.log_softmax(_mm(p["head"], f), -1)
            return -jnp.take_along_axis(logp, y[..., None], -1)[..., 0]

        per_pos = jax.lax.map(nll, (
            feats.reshape(b, s // hb, hb, -1).transpose(1, 0, 2, 3),
            labels.reshape(b, s // hb, hb).transpose(1, 0, 2)))
        return feats[:, s - tail:], per_pos.transpose(1, 0, 2).reshape(
            b, s).mean(-1)


def routing(params, config, tokens):
    """The last layer's expert choices for every token, (B*S, k): what the
    system's router must reproduce but for near-ties."""
    with jax.default_matmul_precision("highest"):
        return _trunk(_f32(params), config, tokens)[1]


#: of the last block's stacked expert weights, the experts whose update is
#: checked: every 9th, 0 and 63 among OLMoE's 64 — both ends of the sorted
#: rows and six group boundaries between. All 64 would cost the benchmark
#: 23 s a run in host arithmetic over 403 M weights for what eight show.
CHECKED_EXPERTS = slice(None, None, 9)


def update_checked(params):
    """The last block's router and, of its three stacked expert weights,
    the CHECKED_EXPERTS: all that the dispatch, the grouped matmuls and the
    combine produce flows into them, and the backward pass need go no
    deeper than that block's feed-forward half."""
    last = params["layers"][-1]
    return dict({n: last[n][CHECKED_EXPERTS] for n in ("gate", "up", "down")},
                router=last["router"])


def checked_grads(params, config, tokens, labels):
    last = params["layers"][-1]

    def loss_of(picked):
        layer = dict(last, router=picked["router"], **{
            n: jnp.asarray(last[n], jnp.float32).at[CHECKED_EXPERTS].set(picked[n])
            for n in ("gate", "up", "down")})
        p = dict(params, layers=list(params["layers"][:-1]) + [layer])
        return forward(p, config, tokens, labels, 1)[1].sum()

    return jax.grad(loss_of)(_f32(update_checked(params)))
