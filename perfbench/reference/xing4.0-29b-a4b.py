"""Plain float32 reference of the Xing 4.0 architecture (Hugging Face
`model_type: xing4_0`, XingChen-AGI/Xing4.0-29B-A4B), as models/xing4.py
states it. jax.numpy only, matmuls at "highest" precision, nothing of the
package. A position carries n = hc_mult streams X (n, C); after the
embedding X = [e; ...; e]; before the final RMSNorm the streams are summed.
Every layer is two sublayers F(u) = f(RMSNorm_C(u)), the mixer and the
feed-forward block, each inside its own hyper-connection (manifold-
constrained hyper-connections, arXiv:2512.24880 on arXiv:2409.19606),
written here FOR ONE POSITION and mapped over the positions:

    x^ = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)      all n C, no gain
    H~pre = a_pre (P_pre x^) + b_pre;  H~post = a_post (P_post x^) + b_post
    H~res = a_res mat(P_res x^) + b_res              (n x n)
    Hpre = sigmoid(H~pre);  Hpost = 2 sigmoid(H~post)
    M_0 = exp(clip(H~res, mhc_h_res_clamp_min, mhc_h_res_clamp_max))
    M_t = cols(rows(M_{t-1})), rows(M) = M / (M 1 + hc_eps),
          cols(M) = M / (1^T M + hc_eps), t = 1..hc_sinkhorn_iters
    u = Hpre X;   X' = Hres X + Hpost^T F(u)

    mixer  c_q = RMSNorm(W_qa h);  [q_n; q_r] = W_qb c_q a head
           [c; k_r] = W_kva h;  c^ = RMSNorm(c);  [k_n; v] = W_kvb c^ a head
           q_r, k_r <- rotary(the YaRN table, interleaved pairs), k_r ONE a
           position;  k = [k_n; k_r] BY CONCATENATION, a head at a time
           o = softmax_causal(q k^T 192^-1/2 m^2) v;  out = W_o concat(o)
           YaRN: f_i = theta^(-2i/d); low, high = the pairs that turn
           beta_fast and beta_slow times over the original positions
           (floor, ceil); r_i = clip((i - low) / (high - low), 0, 1);
           inv_freq_i = f_i (1 - r_i) + f_i / factor r_i;
           m = 0.1 mscale_all_dim ln(factor) + 1; cos and sin times
           m(mscale) / m(mscale_all_dim) = 1
    FFN    layer < first_k_dense_replace: W_down (silu(W_gate u) * W_up u)
           else  s = sigmoid(W_r u);  chosen = top-k of s + b
             w_e = routed_scaling_factor s_e / (sum_chosen s + 1e-20)
             out = sum_{e chosen, held} w_e W2_e (silu(W1_e u) * W3_e u)
                   + W_down (silu(W_gate u) * W_up u)      (shared expert)
    logits = W_head RMSNorm(sum_i X_L[i])

Attention is dense and causal in blocks of queries with k built by explicit
concatenation, where the system hands the streamed kernels operands 192 and
128 wide. The experts are a loop over the HELD ones against a dense
(T, held) matrix of weights: the parameters may be one chip's share (`w1` /
`w2` / `w3` of the experts `first_held_expert` .. + their count), the router
is as wide as it is and the normalisation is over all k chosen, held or not.

What `forward` hands out to be compared (`compared`), side by side on the
channel axis, each part scaled so that a position's squares sum to C:
  (i)   the CONTINUOUS TRUNK (the routed sum left out of every layer, for the
        reason perfbench/reference/solar-open2-250b.py states: a top-k
        choice is discontinuous in its input): the final norm's output;
  (ii)  the FIRST layer ALONE ON THE EMBEDDINGS, both sublayers with their
        hyper-connections (the leading layer's FFN is the dense SwiGLU, or
        the shared expert alone: continuous): X' of X_0, all n streams. On
        X_0 itself the streams are equal and ANY doubly stochastic Hres
        leaves them so; the mixer sublayer's Hpost parts them, and the
        second sublayer's Hres then mixes streams that differ;
  (iii) the three maps (Hpre, Hpost, Hres: n + n + n^2 numbers) of the LAST
        layer's mixer hyper-connection on the stream (ii) hands it.
The routed path is held by the loss, which is the whole model's, and by the
gradients below.

Blocking that changes no arithmetic: attention in blocks of queries, the
head in blocks of positions, each layer recomputed in the gradient.
Departures from the published model: the weights are random (the caller's);
the multi-token-prediction layer is left out (the configuration's
`reduced`); every reading the published config does not settle is the
configuration file's `assumed`.

forward(params, config, tokens, labels, tail) -> (`compared` at the last
    `tail` positions (B, tail, C + n C + n + n + n^2), per-sequence mean
    next-token cross-entropy (B,) of the whole model)
features(params, config, tokens, routed=True) -> the final RMSNorm's output
update_checked(params) -> the parameters whose first update the driver
    compares with this file's gradient, {name: array}
checked_grads(params, config, tokens, labels) -> the gradient of the summed
    loss with respect to them, {name: array}
hyper_maps, hyper_sublayer, mla, route, experts -> one position's maps and
    one block on (B, S, .), for the tests of the blocks and of the shares
"""
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 256        # queries per attention block
HEAD_BLOCK = 1024    # positions per block of the vocabulary projection


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _dense(p, x):
    """Every matmul of a bfloat16 weight goes through here
    (perfbench/probe_limits.py rounds its operands to see whether the
    limits tell a lower precision). The hyper-connections' x^ P does not:
    the configuration states it float32."""
    return x @ p["w"].T + p["b"]


def _mm(w, x):
    return _dense({"w": w, "b": 0.0}, x)


def _rms(g, x, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _block(n, want):
    return want if n % want == 0 else n


def _swiglu(gate_up, down, t):
    gate, up = jnp.split(_mm(gate_up, t), 2, -1)
    return _mm(down, jax.nn.silu(gate) * up)


# ------------------------------------------------------- hyper-connections
def sinkhorn_rounds(config):
    return config["hc_sinkhorn_iters"]


def post_gate(h):
    return 2.0 * jax.nn.sigmoid(h)


def mix_res(h_res, x):
    """Hres X for one position: (n, n) (n, C)."""
    return h_res @ x


def hyper_maps(p, x, config):
    """ONE position's stream x (n, C) -> Hpre (n,), Hpost (n,), Hres (n, n).
    p: `w` (n + n + n^2, n C) = P_pre, P_post, P_res by rows, `b` their
    biases, `a` the three scales."""
    n, eps = x.shape[0], config["hc_eps"]
    vec = x.reshape(-1)
    normed = vec / jnp.sqrt((vec * vec).mean() + eps)
    raw = p["w"] @ normed
    a_pre, a_post, a_res = p["a"]
    h_pre = jax.nn.sigmoid(a_pre * raw[:n] + p["b"][:n])
    h_post = post_gate(a_post * raw[n:2 * n] + p["b"][n:2 * n])
    m = jnp.exp(jnp.clip((a_res * raw[2 * n:] + p["b"][2 * n:]).reshape(n, n),
                         config["mhc_h_res_clamp_min"],
                         config["mhc_h_res_clamp_max"]))
    for _ in range(sinkhorn_rounds(config)):
        m = m / (m.sum(1, keepdims=True) + eps)           # rows
        m = m / (m.sum(0, keepdims=True) + eps)           # columns
    return h_pre, h_post, m


def hyper_sublayer(p, x, f, config):
    """x (B, S, n, C), f: (B, S, C) -> (B, S, C), the sublayer with its
    pre-norm inside -> X' (B, S, n, C)."""
    maps = jax.vmap(jax.vmap(lambda one: hyper_maps(p, one, config)))
    h_pre, h_post, h_res = maps(x)
    y = f(jnp.einsum("bsn,bsnc->bsc", h_pre, x))
    return jax.vmap(jax.vmap(mix_res))(h_res, x) \
        + h_post[..., None] * y[..., None, :]


# ------------------------------------------------------------------ mixer
def inv_freq(config):
    """The YaRN table of qk_rope_head_dim / 2 frequencies."""
    d, theta = config["qk_rope_head_dim"], float(config["rope_theta"])
    ys = config["rope_scaling"]

    def pair(turns):
        return d * math.log(ys["original_max_position_embeddings"]
                            / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair(ys["beta_fast"])), 0)
    high = min(math.ceil(pair(ys["beta_slow"])), d - 1)
    i = jnp.arange(d // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * i / d)
    r = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return f * (1.0 - r) + f / ys["factor"] * r


def rotary(x, freq):
    """x (..., s, d): the interleaved pair (x[2i], x[2i + 1]) of position t
    turns by t freq[i]."""
    s = x.shape[-2]
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                        odd * jnp.cos(angle) + even * jnp.sin(angle)], -1)
    return turned.reshape(x.shape)


def rotary_key(k_rope, heads, config):
    """k_rope (b, s, d_r) -> the heads' rotary keys (b, h, s, d_r): ONE key
    a position, the same for every head."""
    return jnp.broadcast_to(rotary(k_rope, inv_freq(config))[:, None],
                            (k_rope.shape[0], heads) + k_rope.shape[1:])


def score_scale(config):
    ys = config["rope_scaling"]
    m = 0.1 * ys["mscale_all_dim"] * math.log(ys["factor"]) + 1.0
    return m * m / math.sqrt(config["qk_nope_head_dim"]
                             + config["qk_rope_head_dim"])


def query_latent(p, x, config):
    return _rms(p["q_norm"], _mm(p["q_down"], x), config["rms_norm_eps"])


def mla(p, x, config):
    """Causal multi-head latent attention with a low-rank query, a head at
    a time."""
    b, s, _ = x.shape
    dn, dr = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, rank = config["v_head_dim"], config["kv_lora_rank"]
    heads = p["q_up"].shape[0] // (dn + dr)

    def split(t):
        return t.reshape(b, s, heads, -1).transpose(0, 2, 1, 3)

    q = split(_mm(p["q_up"], query_latent(p, x, config)))   # (b, h, s, 192)
    down = _mm(p["kv_down"], x)
    latent = _rms(p["kv_norm"], down[..., :rank], config["rms_norm_eps"])
    kv = split(_mm(p["kv_up"], latent))                     # (b, h, s, 256)
    q = jnp.concatenate([q[..., :dn], rotary(q[..., dn:], inv_freq(config))],
                        -1)
    k = jnp.concatenate([kv[..., :dn],
                         rotary_key(down[..., rank:], heads, config)], -1)
    v = kv[..., dn:]
    qb = _block(s, Q_BLOCK)
    key_pos = jnp.arange(s)
    scale = score_scale(config)

    @jax.checkpoint      # the gradient keeps no block's scores
    def one(args):
        q_blk, start = args                              # (b, h, qb, 192)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q_blk, k) * scale
        q_pos = start + jnp.arange(qb)
        scores = jnp.where(q_pos[:, None] >= key_pos[None, :], scores,
                           -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)

    blocks = q.reshape(b, heads, s // qb, qb, dn + dr).transpose(2, 0, 1, 3, 4)
    out = jax.lax.map(one, (blocks, jnp.arange(0, s, qb)))
    # (blocks, b, h, qb, d_v) -> (b, s, h d_v)
    return _mm(p["o"], out.transpose(1, 0, 3, 2, 4).reshape(b, s, heads * dv))


# ------------------------------------------------------------------ experts
def route(p, t, config):
    """t (T, U) -> (weights of the chosen experts (T, k), their indices
    (T, k)) over ALL the router's experts. The bias chooses and does not
    weigh; n_group 1: no group step."""
    s = jax.nn.sigmoid(_mm(p["router"], t))                       # (T, E)
    _, idx = jax.lax.top_k(s + p["router_bias"],
                           config["num_experts_per_tok"])
    vals = jnp.take_along_axis(s, idx, -1)
    if config["norm_topk_prob"]:
        vals = vals / (vals.sum(-1, keepdims=True) + 1e-20)
    return vals * config["routed_scaling_factor"], idx


def experts(p, x, config, first=0, routed=True):
    """The experts `first` .. first + count - 1 (count = w1's) of the
    routed sum, plus the shared expert; not `routed`: the shared expert
    alone."""
    t = x.reshape(-1, x.shape[-1])
    shared = _swiglu(p["shared_gate_up"], p["shared_down"], t)
    if not routed:
        return shared.reshape(x.shape)
    vals, idx = route(p, t, config)
    # (T, E): w_e where expert e was chosen for the token, else 0
    weight = jnp.zeros((t.shape[0], p["router"].shape[0]), jnp.float32) \
        .at[jnp.arange(t.shape[0])[:, None], idx].set(vals)
    weight = jax.lax.dynamic_slice_in_dim(weight, first, p["w1"].shape[0], 1)

    def one(out, expert):
        w1, w2, w3, w_e = expert      # stored (U, I), (I, U), (U, I): x @ w
        hidden = jax.nn.silu(_mm(w1.T, t)) * _mm(w3.T, t)
        return out + w_e[:, None] * _mm(w2.T, hidden), None

    summed, _ = jax.lax.scan(one, jnp.zeros_like(t),
                             (p["w1"], p["w2"], p["w3"], weight.T))
    return (summed + shared).reshape(x.shape)


# ------------------------------------------------------------------ model
def _hc(p, which):
    return {k: p["%s_%s" % (which, k)] for k in "wba"}


def mixer_sublayer(p, x, config):
    eps = config["rms_norm_eps"]
    return hyper_sublayer(
        _hc(p, "hc_mixer"), x,
        lambda u: mla(p, _rms(p["norm1"], u, eps), config), config)


def _layer(p, x, config, routed):
    eps = config["rms_norm_eps"]
    x = mixer_sublayer(p, x, config)

    def ffn(u):
        u = _rms(p["norm2"], u, eps)
        if "dense_gate_up" in p:                   # a leading dense layer
            return _swiglu(p["dense_gate_up"], p["dense_down"], u)
        return experts(p, u, config, config["first_held_expert"], routed)

    return hyper_sublayer(_hc(p, "hc_ffn"), x, ffn, config)


def stream_in(p, config, tokens):
    e = p["tok_embed"][tokens]
    return jnp.broadcast_to(e[..., None, :], e.shape[:-1]
                            + (config["hc_mult"], e.shape[-1]))


def _trunk(p, config, tokens, routed):
    x = stream_in(p, config, tokens)
    for layer in p["layers"]:
        # the gradient keeps a layer's input and nothing of its inside
        x = jax.checkpoint(
            lambda p, x: _layer(p, x, config, routed))(layer, x)
    return _rms(p["norm_f"], x.sum(-2), config["rms_norm_eps"])


def features(params, config, tokens, routed=True):
    with jax.default_matmul_precision("highest"):
        return _trunk(_f32(params), config, tokens, routed)


def alone(p, config, tokens):
    """(X' (B, S, n, C) of the first layer, both sublayers, on X_0, the
    last layer's mixer hyper-connection's maps on that X' (B, S, n + n +
    n^2): Hpre | Hpost | Hres by rows)."""
    x = _layer(p["layers"][0], stream_in(p, config, tokens), config, False)
    hc = _hc(p["layers"][-1], "hc_mixer")
    h_pre, h_post, h_res = jax.vmap(jax.vmap(
        lambda one: hyper_maps(hc, one, config)))(x)
    return x, jnp.concatenate(
        [h_pre, h_post, h_res.reshape(h_res.shape[:2] + (-1,))], -1)


def compared(p, config, tokens):
    """(B, S, C + n C + n + n + n^2): the continuous trunk | the first
    layer's X' | the last mixer hyper-connection's maps, each part scaled
    so that a position's squares sum to C."""
    trunk = _trunk(p, config, tokens, False)
    c = trunk.shape[-1]

    def part(t):
        t = t.reshape(t.shape[:2] + (-1,))
        return t * jnp.sqrt(c / (t * t).sum(-1, keepdims=True))

    return jnp.concatenate(
        [trunk] + [part(t) for t in alone(p, config, tokens)], -1)


def forward(params, config, tokens, labels, tail):
    with jax.default_matmul_precision("highest"):
        p = _f32(params)
        b, s = tokens.shape
        feats = _trunk(p, config, tokens, True)
        hb = _block(s, HEAD_BLOCK)

        @jax.checkpoint
        def nll(args):
            f, y = args                                   # (b, hb, u), (b, hb)
            logp = jax.nn.log_softmax(_mm(p["head"], f), -1)
            return -jnp.take_along_axis(logp, y[..., None], -1)[..., 0]

        per_pos = jax.lax.map(nll, (
            feats.reshape(b, s // hb, hb, -1).transpose(1, 0, 2, 3),
            labels.reshape(b, s // hb, hb).transpose(1, 0, 2)))
        return compared(p, config, tokens)[:, s - tail:], \
            per_pos.transpose(1, 0, 2).reshape(b, s).mean(-1)


#: what is checked, all of the LAST layer. Its MIXER's hyper-connection by
#: the rows of its maps (P_pre, P_post, P_res and their b under a name
#: each) and its three a as one vector (a parameter of ONE entry agrees to
#: 0 or 1 and nothing between). The mixer's and not the FFN's: the very
#: last sublayer's X' is summed over the streams at once, the columns of a
#: doubly stochastic Hres sum to 1, and the loss does not depend on that
#: Hres at all. Of the latent attention W_qa, W_qb, W_kva, W_kvb, both
#: latent norms' gains and W_o; the router, the shared expert, and every
#: held expert's three matrices under a name of its own (`moe_w1_e3`: an
#: expert that the dispatch dropped does not move, and the driver reads a
#: parameter of which nothing moved as 0).
HC_ROWS = ("pre", "post", "res")
MLA_OWN = ("q_down", "q_up", "kv_down", "kv_up", "q_norm", "kv_norm", "o")
MOE_OWN = ("router", "shared_gate_up", "shared_down")
STACKED = ("w1", "w2", "w3")


def _hc_rows(layer):
    """Where P_pre and P_post end among the maps' rows."""
    n = int(round(math.sqrt(layer["hc_mixer_b"].shape[0] + 1))) - 1
    return [n, 2 * n]


def _picked(layer):
    out = {"hc_a": layer["hc_mixer_a"]}
    for kind, name in (("P", "w"), ("b", "b")):
        out.update(zip(("hc_%s_%s" % (kind, r) for r in HC_ROWS), jnp.split(
            layer["hc_mixer_" + name], _hc_rows(layer), 0)))
    out.update({"mla_" + n: layer[n] for n in MLA_OWN})
    out.update({"moe_" + n: layer[n] for n in MOE_OWN})
    out.update({"moe_%s_e%d" % (n, i): layer[n][i] for n in STACKED
                for i in range(layer[n].shape[0])})
    return out


def update_checked(params):
    return _picked(params["layers"][-1])


def checked_grads(params, config, tokens, labels):
    last = params["layers"][-1]
    held = last["w1"].shape[0]

    def loss_of(picked):
        layer = dict(last)
        layer["hc_mixer_a"] = picked["hc_a"]
        for kind, name in (("P", "w"), ("b", "b")):
            layer["hc_mixer_" + name] = jnp.concatenate(
                [picked["hc_%s_%s" % (kind, r)] for r in HC_ROWS], 0)
        layer.update({n: picked["mla_" + n] for n in MLA_OWN})
        layer.update({n: picked["moe_" + n] for n in MOE_OWN})
        layer.update({n: jnp.stack([picked["moe_%s_e%d" % (n, i)]
                                    for i in range(held)]) for n in STACKED})
        return forward(dict(params, layers=params["layers"][:-1] + [layer]),
                       config, tokens, labels, 1)[1].sum()

    return jax.grad(loss_of)(_f32(_picked(last)))
