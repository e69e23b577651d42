"""Plain float32 reference of the Keye-VL-2.0-30B-A3B decoder (Hugging Face
`model_type: KeyeVL2`, Kwai-Keye/Keye-VL-2.0-30B-A3B; the language model
alone, no vision tower), as models/keye_vl2.py states it. jax.numpy only,
matmuls at "highest" precision, no kernels. Every layer, x (S, U),
positions p (3, S):

    n   = rmsnorm(x; g1)
    q_h = mrope(rms_d(Wq n)_h, p)    k_g = mrope(rms_d(Wk n)_g, p)
    v_g = (Wv n)_g                   head h reads g = h // (H / G)
          rms_d: RMSNorm over a head's d channels, one gain for q, one for k
          mrope: rotate-half over d / 2 frequencies theta^(-2i/d); the
          frequencies [0, a) turn by p[0], [a, a + b) by p[1], the rest by
          p[2], (a, b, c) = rope_scaling.mrope_section
    nb  = stop_gradient(n)
    qI_j = ropeI((WqI nb)_j)   kI = ropeI(layernorm(WkI nb))
    w   = (Ww nb) J^-1/2 dI^-1/2
          ropeI: rotate-half of the FIRST HALF of the dI channels by p[0]
    I[t,s] = sum_j w[t,j] relu(qI_j[t] . kI[s])       s <= t
    S_t  = the min(topk, t + 1) keys s <= t of largest I[t,s] (lax.top_k a
           row: ties to the lower s)
    a_h[t,s] = softmax over S_t of q_h[t] . k_g[s] / sqrt(d)
    y   = x + Wo [sum over S_t of a_h[t,s] v_g[s]]_h
    LI  = mean_t KL(stop_gradient(mean_h a_h[t,.]) || softmax over S_t of I[t,.])
    m   = rmsnorm(y; g2);  r = softmax(Wr m);  T = top-k(r);  c_e = r_e / sum_T r
    out = y + sum over e in T and held of c_e W2_e (silu(W1_e m) * W3_e m)
    loss = CE(Whead rmsnorm(out_L), labels) + sum over layers of LI

The selection here is `lax.top_k` (a sort) over a row of scores, where the
system finds a k-th-value threshold by bisection on the bits: two
algorithms, one set. From top_k's values and indices the row's mask is
rebuilt (the k-th value, and the last index chosen at that value), and the
attention is a masked softmax over every key, where the system streams key
blocks with an online softmax. The experts are a loop over the HELD ones
against a dense (T, held) matrix of weights. The parameters may be one
chip's share (`w1`/`w2`/`w3` of the experts `first_held_expert` .. + their
count; a slice of the vocabulary): every size is read from the arrays, the
router is as wide as it is, and the normalisation is over all k chosen.

What `forward` hands out to be compared is the TRUNK WITHOUT THE EXPERTS
(x <- x + attention(rmsnorm(x)) alone, every layer): top-8 of 128 softmax
scores flips on a bfloat16 rounding of the router's input and a token then
gains or loses a whole expert's output (PERF.md section 6, PR 31), while a
key that enters or leaves S_t at the 2048th place carries about 1 / 2048 of
a row's weight. The routed path is held by the loss, which is the whole
model's, and by the gradients below.

Blocking that changes no arithmetic: queries in blocks of Q_BLOCK, the head
in blocks of positions, each block and each layer recomputed in the
gradient. Departures from the published model: random weights (the
caller's); what the configuration's `assumed` lists.

forward(params, config, tokens, labels, tail, positions=None) ->
    (final-RMSNorm output of the last `tail` positions (B, tail, U) of the
     trunk without the experts, per-sequence loss (B,) of the whole model:
     mean next-token cross-entropy + the layers' LI)
features(params, config, tokens, positions=None, routed=True)
    -> (the final RMSNorm's output (B, S, U), the layers' LI summed (B,))
loss_terms(params, config, tokens, labels, positions=None) -> (lm (B,), li (B,))
update_checked(params) / checked_grads(params, config, tokens, labels)
sparse_attention(q, k, v, qi, ki, w, topk) -> (o, kl (B,)), chosen(...) the
    mask (B, S, S), attention(p, x, positions, config), experts(p, x, config,
    first): the op and the blocks alone, for the tests and the probes
"""
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 128        # queries per block of the sparse attention
HEAD_BLOCK = 1024    # positions per block of the vocabulary projection
LN_EPS = 1e-6        # the indexer's LayerNorm


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _dense(p, x):
    """Every matmul of a weight goes through here (a probe rounds its
    operands to see whether the limits tell a lower precision)."""
    return x @ p["w"].T + p["b"]


def _mm(w, x):
    return _dense({"w": w, "b": 0.0}, x)


def _rms(g, x, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _block(n, want):
    return want if n % want == 0 else n


def default_positions(b, s):
    return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (3, b, s))


# ------------------------------------------------------------------ rotary
def _rotate(x, angle):
    """x (b, s, h, d), angle (b, s, d / 2): rotate-half."""
    cos, sin = jnp.cos(angle)[:, :, None], jnp.sin(angle)[:, :, None]
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mrope(x, positions, theta, sections):
    """x (b, s, h, d), positions (3, b, s)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    stream = jnp.repeat(jnp.arange(3), jnp.array(sections),
                        total_repeat_length=half)
    pos = positions.astype(jnp.float32)[stream]            # (half, b, s)
    return _rotate(x, jnp.moveaxis(pos, 0, -1) * inv_freq)


def rope_indexer(x, positions, theta):
    """The first half of x's channels turns by positions[0]."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half // 2, dtype=jnp.float32)
                         / (half // 2))
    angle = positions[0].astype(jnp.float32)[..., None] * inv_freq
    return jnp.concatenate([_rotate(x[..., :half], angle), x[..., half:]], -1)


# ------------------------------------------------------ the sparse attention
def _index_block(qi_blk, ki, w_blk, start):
    """(b, qb, j, di), (b, s, di), (b, qb, j) -> I (b, qb, s), -inf right
    of the diagonal, -0.0 as +0.0."""
    r = jnp.einsum("bqjd,bsd->bqjs", qi_blk, ki)
    scores = jnp.einsum("bqj,bqjs->bqs", w_blk, jax.nn.relu(r))
    scores = jnp.where(scores == 0, 0.0, scores)
    t = start + jnp.arange(qi_blk.shape[1])
    return jnp.where(jnp.arange(ki.shape[1])[None, :] <= t[:, None], scores,
                     -jnp.inf)


def _chosen_block(scores, topk):
    """I (b, qb, s) -> the mask of S_t (b, qb, s), from lax.top_k's own
    values and indices: everything above the k-th value, and at it the
    indices up to the last one top_k took (it takes the lowest first)."""
    s = scores.shape[-1]
    vals, idx = jax.lax.top_k(scores, min(topk, s))
    kth = vals[..., -1:]
    last = jnp.max(jnp.where(vals == kth, idx, -1), -1, keepdims=True)
    at = jnp.arange(s)
    return (scores > -jnp.inf) & (
        (scores > kth) | ((scores == kth) & (at <= last)))


def _blocks_of(x, qb):
    """(b, s, ..) -> (s / qb, b, qb, ..)."""
    b, s = x.shape[:2]
    return jnp.moveaxis(x.reshape((b, s // qb, qb) + x.shape[2:]), 1, 0)


def _unblocked(x):
    x = jnp.moveaxis(x, 0, 1)
    return x.reshape((x.shape[0], -1) + x.shape[3:])


def chosen(qi, ki, w, topk):
    """The mask of S_t, whole: (B, S, S) bool. Small S only."""
    return _chosen_block(_index_block(qi, ki, w, 0), topk)


def sparse_attention(q, k, v, qi, ki, w, topk):
    """q (b, s, h, d), k, v (b, s, g, d), qi (b, s, j, di), ki (b, s, di),
    w (b, s, j) -> (o (b, s, h, d), kl (b,): the mean over t of KL_t)."""
    b, s, h, d = q.shape
    g = k.shape[2]
    qb = _block(s, Q_BLOCK)

    @jax.checkpoint      # the gradient keeps no block's scores
    def one(args):
        q_blk, qi_blk, w_blk, start = args
        scores = _index_block(qi_blk, ki, w_blk, start)
        mask = jax.lax.stop_gradient(_chosen_block(scores, topk))
        att = jnp.einsum("bqgrd,bsgd->bgrqs",
                         q_blk.reshape(b, qb, g, h // g, d), k) / math.sqrt(d)
        a = jax.nn.softmax(jnp.where(mask[:, None, None], att, -jnp.inf), -1)
        o = jnp.einsum("bgrqs,bsgd->bqgrd", a, v).reshape(b, qb, h, d)
        target = jax.lax.stop_gradient(a.mean((1, 2)))         # (b, qb, s)
        log_pi = jax.nn.log_softmax(
            jnp.where(mask, scores, -jnp.inf), -1)
        kl = jnp.where(target > 0, target * (
            jnp.log(jnp.where(target > 0, target, 1.0))
            - jnp.where(mask, log_pi, 0.0)), 0.0).sum((-1, -2))
        return o, kl

    o, kl = jax.lax.map(one, (_blocks_of(q, qb), _blocks_of(qi, qb),
                              _blocks_of(w, qb), jnp.arange(0, s, qb)))
    return _unblocked(o), kl.sum(0) / s


def indexer(p, nb, positions, config):
    """-> qi (b, s, j, di), ki (b, s, di), w (b, s, j)."""
    b, s, _ = nb.shape
    sa = config["sa_config"]
    j, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    theta = config["rope_theta"]
    qi = rope_indexer(_mm(p["iq"], nb).reshape(b, s, j, di), positions, theta)
    ki = _mm(p["ik"], nb)
    mean = ki.mean(-1, keepdims=True)
    ki = (ki - mean) / jnp.sqrt(((ki - mean) ** 2).mean(-1, keepdims=True)
                                + LN_EPS) * p["ik_ln_g"] + p["ik_ln_b"]
    ki = rope_indexer(ki[:, :, None], positions, theta)[:, :, 0]
    return qi, ki, _mm(p["iw"], nb) * (j ** -0.5) * (di ** -0.5)


def attention(p, n, positions, config):
    """The attention block on its normed input n (b, s, U) ->
    (Wo [o_h] (b, s, U), LI (b,))."""
    b, s, _ = n.shape
    d, eps = config["head_dim"], config["rms_norm_eps"]
    h, g = p["q"].shape[0] // d, p["k"].shape[0] // d
    theta = config["rope_theta"]
    sections = config["rope_scaling"]["mrope_section"]
    q = mrope(_rms(p["q_norm"], _mm(p["q"], n).reshape(b, s, h, d), eps),
              positions, theta, sections)
    k = mrope(_rms(p["k_norm"], _mm(p["k"], n).reshape(b, s, g, d), eps),
              positions, theta, sections)
    v = _mm(p["v"], n).reshape(b, s, g, d)
    qi, ki, w = indexer(p, jax.lax.stop_gradient(n), positions, config)
    o, kl = sparse_attention(q, k, v, qi, ki, w, config["sa_config"]["topk"])
    return _mm(p["o"], o.reshape(b, s, h * d)), kl


# ------------------------------------------------------------------ experts
def route(p, t, config):
    """t (T, U) -> (weights of the chosen experts (T, k), their indices
    (T, k)) over ALL the router's experts."""
    r = jax.nn.softmax(_mm(p["router"], t), -1)
    vals, idx = jax.lax.top_k(r, config["num_experts_per_tok"])
    if config["norm_topk_prob"]:
        vals = vals / vals.sum(-1, keepdims=True)
    return vals, idx


def experts(p, x, config, first=0):
    """The experts `first` .. first + count - 1 (count = w1's) of the
    routed sum."""
    t = x.reshape(-1, x.shape[-1])
    vals, idx = route(p, t, config)
    # (T, E): c_e where expert e was chosen for the token, else 0
    weight = jnp.zeros((t.shape[0], p["router"].shape[0]), jnp.float32) \
        .at[jnp.arange(t.shape[0])[:, None], idx].set(vals)
    weight = jax.lax.dynamic_slice_in_dim(weight, first, p["w1"].shape[0], 1)

    def one(out, expert):
        w1, w2, w3, c_e = expert      # stored (U, I), (I, U), (U, I): x @ w
        hidden = jax.nn.silu(_mm(w1.T, t)) * _mm(w3.T, t)
        return out + c_e[:, None] * _mm(w2.T, hidden), None

    summed, _ = jax.lax.scan(one, jnp.zeros_like(t),
                             (p["w1"], p["w2"], p["w3"], weight.T))
    return summed.reshape(x.shape)


# ------------------------------------------------------------------ model
def _layer(p, x, positions, config, routed):
    eps = config["rms_norm_eps"]
    y, li = attention(p, _rms(p["norm1"], x, eps), positions, config)
    x = x + y
    if routed:
        x = x + experts(p, _rms(p["norm2"], x, eps), config,
                        config["first_held_expert"])
    return x, li


def _trunk(p, config, tokens, positions, routed):
    x = p["tok_embed"][tokens]
    if positions is None:
        positions = default_positions(*tokens.shape)
    total = jnp.zeros((tokens.shape[0],), jnp.float32)
    for layer in p["layers"]:
        x, li = jax.checkpoint(
            lambda p, x: _layer(p, x, positions, config, routed))(layer, x)
        total = total + li
    return _rms(p["norm_f"], x, config["rms_norm_eps"]), total


def features(params, config, tokens, positions=None, routed=True):
    with jax.default_matmul_precision("highest"):
        return _trunk(_f32(params), config, tokens, positions, routed)


def _loss_terms(p, config, tokens, labels, positions):
    b, s = tokens.shape
    feats, li = _trunk(p, config, tokens, positions, True)
    hb = _block(s, HEAD_BLOCK)

    @jax.checkpoint
    def nll(args):
        f, y = args                                   # (b, hb, u), (b, hb)
        logp = jax.nn.log_softmax(_mm(p["head"], f), -1)
        return -jnp.take_along_axis(logp, y[..., None], -1)[..., 0]

    per_pos = jax.lax.map(nll, (
        feats.reshape(b, s // hb, hb, -1).transpose(1, 0, 2, 3),
        labels.reshape(b, s // hb, hb).transpose(1, 0, 2)))
    return per_pos.transpose(1, 0, 2).reshape(b, s).mean(-1), li


def loss_terms(params, config, tokens, labels, positions=None):
    with jax.default_matmul_precision("highest"):
        return _loss_terms(_f32(params), config, tokens, labels, positions)


def forward(params, config, tokens, labels, tail, positions=None):
    with jax.default_matmul_precision("highest"):
        p = _f32(params)
        lm, li = _loss_terms(p, config, tokens, labels, positions)
        jax.debug.print("reference loss terms: LM {} + indexer KL {}", lm, li)
        trunk, _ = _trunk(p, config, tokens, positions, False)
        return trunk[:, tokens.shape[1] - tail:], lm + li


#: what is checked, all of the LAST layer: the three indexer maps (moved by
#: LI alone), q, k, v, o (the sparse attention's backward), the router, and
#: every held expert's three matrices under a name of its own
#: (`moe_w1_e3`): an expert that the dispatch dropped does not move, and the
#: driver reads a parameter of which nothing moved as 0. (The norms' gains
#: start at 1, where a bfloat16 weight is too coarse for Adam's first step
#: to move: they would read 0 and are left out.)
OWN = ("iq", "ik", "iw", "q", "k", "v", "o", "router")
STACKED = ("w1", "w2", "w3")


def _picked(layer):
    out = {n: layer[n] for n in OWN}
    out.update({"moe_%s_e%d" % (n, i): layer[n][i] for n in STACKED
                for i in range(layer[n].shape[0])})
    return out


def update_checked(params):
    return _picked(params["layers"][-1])


def checked_grads(params, config, tokens, labels):
    last = params["layers"][-1]
    held = last["w1"].shape[0]

    def loss_of(picked):
        layer = dict(last, **{n: picked[n] for n in OWN})
        layer.update({n: jnp.stack([picked["moe_%s_e%d" % (n, i)]
                                    for i in range(held)])
                      for n in STACKED})
        layers = list(params["layers"][:-1]) + [layer]
        with jax.default_matmul_precision("highest"):
            lm, li = _loss_terms(_f32(dict(params, layers=layers)), config,
                                 tokens, labels, None)
        return (lm + li).sum()

    return jax.grad(loss_of)(_f32(_picked(last)))
