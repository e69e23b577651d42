"""Plain float32 reference of the Ling 3.0 architecture (Hugging Face
`model_type: bailing_hybrid`, inclusionAI/Ling-3.0-flash), as
models/ling3.py states it. jax.numpy only, matmuls at "highest" precision,
nothing of the package. Every layer is

    x <- x + Mixer(RMSNorm(x));  x <- x + FFN(RMSNorm(x))

and the configuration's `layer_pattern_run` names the mixers:

    K  q~, k~, v = silu(conv1d_causal,4(h W_q | h W_k | h W_v));  per head
       q = q~ / |q~|_2 d^-1/2;  k = k~ / |k~|_2
       g_t = kda_lower_bound sigmoid(exp(A_log) (h W_f + dt_bias))  in (-5, 0)
       b_t = sigmoid(h w_b)                                          in (0, 1)
       S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T; S_0 = 0
       o_t = S_t^T q_t
       out = W_o [RMSNorm_d(o_t) gamma * sigmoid(h W_g)]     W_f, W_g FULL rank
    M  [q_n; q_r] = h W_q a head;  [c; k_r] = h W_kva;  c^ = RMSNorm(c)
       [k_n; v] = c^ W_kvb a head;  q_n, k_n <- RMSNorm_128(.) with a gain
       q_r, k_r <- rotary(rope_theta, interleaved pairs), k_r ONE a position
       k = [k_n; k_r] BY CONCATENATION, a head at a time
       o = softmax_causal(q k^T / sqrt(192)) v;  out = W_o [o_h sigmoid(h w_h)]
    FFN  layer < first_k_dense_replace: W_down (silu(W_gate u) * W_up u)
       else  s = sigmoid(W_r u);  c = s + b
         group score = the sum of the 2 largest c of each of n_group groups
         of consecutive experts; the topk_group best groups are kept
         chosen = top-k of c over the kept groups (`top_k` and masks)
         w_e = routed_scaling_factor s_e / (sum_chosen s + 1e-20)
         out = sum_{e chosen, held} w_e W2_e (silu(W1_e u) * W3_e u)
               + W_down (silu(W_gate u) * W_up u)          (shared expert)
    logits = W_head RMSNorm(x_L)

The delta rule is run A POSITION AT A TIME (`lax.scan` over t carrying S,
the three lines above verbatim), where the system runs the chunked WY form
in two Pallas kernels. M is dense causal attention in blocks of queries
with k built by explicit concatenation, where the system hands the
streamed kernels operands 192 and 128 wide. The experts are a loop over
the HELD ones against a dense (T, held) matrix of weights. The parameters
may be one chip's share (`w1`/`w2`/`w3` of the experts `first_held_expert`
.. + their count): every size is read from the arrays, the router is as
wide as it is, and the normalisation is over all k chosen, held or not.

What `forward` hands out to be compared (`compared`) is the CONTINUOUS
TRUNK (the routed sum left out of every layer), for the reason
perfbench/reference/solar-open2-250b.py states: a top-8 choice of 512
scores is discontinuous in its input; and beside it, on the channel axis,
the last M mixer's own output and the last K mixer's, each ALONE ON THE
EMBEDDINGS (the mixer of its layer's first norm of tok_embed[tokens], an
input that both sides have to the bit), every position scaled to unit RMS
(the final norm's output has it by its gain of 1). In the stream a mixer's
error lies under what the layers before it left there, and the M layer
adds a thousandth of the stream: alone, a wrong scale of the scores or a
key left unrotated is the mixer's whole error.
The routed path is held by the loss, which is the whole model's, and by
the gradients below.

Blocking that changes no arithmetic: attention in blocks of queries, the
head in blocks of positions, the recurrence in checkpointed segments, each
layer recomputed in the gradient. Departures from the published model: the
weights are random (the caller's); the L2 norms of q and k add 1e-6 under
the root, as the program's do; the multi-token-prediction layer is left
out (its published loss weight is 0).

forward(params, config, tokens, labels, tail) ->
    (`compared` at the last `tail` positions (B, tail, 3 U): the continuous
     trunk's final-RMSNorm output | the M mixer alone | the K mixer alone,
     per-sequence mean next-token cross-entropy (B,) of the whole model)
features(params, config, tokens, routed=True) -> the final RMSNorm's
    output (B, S, U), of the whole model or of the continuous trunk
update_checked(params) -> the parameters whose first update the driver
    compares with this file's gradient, {name: array}
checked_grads(params, config, tokens, labels) -> the gradient of the summed
    loss with respect to them, {name: array}
delta_rule, kda, mla, route, experts -> the recurrence and one block on
    (B, S, U), for the tests of the blocks and of the shares
"""
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 256        # queries per attention block
HEAD_BLOCK = 1024    # positions per block of the vocabulary projection
SCAN_SEGMENT = 128   # positions per checkpointed segment of the recurrence
L2_EPS = 1e-6        # under the root of the L2 norms of q and k


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


def _block(n, want):
    return want if n % want == 0 else n


def _swiglu(gate_up, down, t):
    gate, up = jnp.split(_mm(gate_up, t), 2, -1)
    return _mm(down, jax.nn.silu(gate) * up)


# ------------------------------------------------------------------ K
def rule_step(state, at):
    """One position of the recurrence: S_{t-1} (b, h, d_k, d_v) and the
    position's q, k, g (b, h, d_k), v (b, h, d_v), b (b, h) -> S_t, o_t."""
    q_t, k_t, v_t, g_t, b_t = at
    state = jnp.exp(g_t)[..., None] * state               # Diag(a_t) S_{t-1}
    u = b_t[..., None] * (v_t - (state * k_t[..., None]).sum(-2))
    state = state + k_t[..., None] * u[..., None, :]
    return state, (state * q_t[..., None]).sum(-2)


def delta_rule(q, k, v, g, beta):
    """q, k, g (b, s, h, d_k), v (b, s, h, d_v), beta (b, s, h) ->
    o (b, s, h, d_v), one position at a time."""
    b, s, h, dk = q.shape

    @jax.checkpoint      # the gradient keeps one state a segment
    def segment(state, seg):
        return jax.lax.scan(rule_step, state, seg)

    seg = _block(s, SCAN_SEGMENT)
    by_time = tuple(t.swapaxes(0, 1).reshape((s // seg, seg) + t.shape[:1]
                                             + t.shape[2:])
                    for t in (q, k, v, g, beta))
    _, o = jax.lax.scan(segment,
                        jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
                        by_time)
    return o.reshape((s,) + o.shape[2:]).swapaxes(0, 1)


def decay(p, f, config):
    """The log-decay a channel from the full-rank map's output f
    (b, s, h, d): bounded below by kda_lower_bound."""
    return config["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(p["A_log"])[:, None] * f)


def kda(p, x, config):
    """One Kimi Delta Attention mixer: the heads are A_log's."""
    b, s, _ = x.shape
    heads, d = p["A_log"].shape[0], config["head_dim"]
    inner = heads * d
    proj = _mm(p["in_proj"], x)
    qkv, f, z, bl = jnp.split(proj, [3 * inner, 4 * inner, 5 * inner], -1)
    taps = p["conv_w"].shape[1]
    padded = jnp.pad(qkv, [(0, 0), (taps - 1, 0), (0, 0)])
    qkv = jax.nn.silu(sum(padded[:, j:j + s] * p["conv_w"][:, j]
                          for j in range(taps)))
    q, k, v = (t.reshape(b, s, heads, d) for t in jnp.split(qkv, 3, -1))
    q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + L2_EPS) / math.sqrt(d)
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + L2_EPS)
    g = decay(p, (f + p["dt_bias"]).reshape(b, s, heads, d), config)
    o = delta_rule(q, k, v, g, jax.nn.sigmoid(bl))
    o = _rms(p["gate_norm"], o, config["rms_norm_eps"]).reshape(b, s, inner)
    return _mm(p["out_proj"], o * jax.nn.sigmoid(z))


# ------------------------------------------------------------------ M
def rotary(x, theta):
    """x (..., s, d): the interleaved pair (x[2i], x[2i + 1]) of position t
    turns by t theta^(-2i / d)."""
    s, d = x.shape[-2:]
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] \
        * theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                        odd * jnp.cos(angle) + even * jnp.sin(angle)], -1)
    return turned.reshape(x.shape)


def rotary_key(p, k_rope, heads, config):
    """k_rope (b, s, d_r) -> the heads' rotary keys (b, h, s, d_r): ONE key
    a position, the same for every head."""
    return jnp.broadcast_to(rotary(k_rope, config["rope_theta"])[:, None],
                            (k_rope.shape[0], heads) + k_rope.shape[1:])


def score_scale(config):
    return 1.0 / math.sqrt(config["qk_head_dim"])


def mla(p, x, config):
    """Causal multi-head latent attention, a head at a time."""
    b, s, _ = x.shape
    dn, dr = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, rank = config["v_head_dim"], config["kv_lora_rank"]
    heads = p["q"].shape[0] // (dn + dr)
    eps = config["rms_norm_eps"]

    def split(t):
        return t.reshape(b, s, heads, -1).transpose(0, 2, 1, 3)

    q = split(_mm(p["q"], x))                               # (b, h, s, 192)
    down = _mm(p["kv_down"], x)
    latent = _rms(p["kv_norm"], down[..., :rank], eps)
    kv = split(_mm(p["kv_up"], latent))                     # (b, h, s, 256)
    q = jnp.concatenate([_rms(p["q_gain"], q[..., :dn], eps),
                         rotary(q[..., dn:], config["rope_theta"])], -1)
    k = jnp.concatenate([_rms(p["k_gain"], kv[..., :dn], eps),
                         rotary_key(p, down[..., rank:], heads, config)], -1)
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
    out = out.transpose(1, 2, 0, 3, 4).reshape(b, heads, s, dv)
    gate = jax.nn.sigmoid(_mm(p["gate"], x))                # (b, s, h)
    out = out.transpose(0, 2, 1, 3) * gate[..., None]
    return _mm(p["o"], out.reshape(b, s, heads * dv))


# ------------------------------------------------------------------ experts
def kept_groups(c, config):
    """c (T, E) -> (T, E) bool: the experts of each token's topk_group best
    groups, a group's score the sum of its 2 largest c."""
    n_group = config["n_group"]
    by_group = c.reshape(c.shape[0], n_group, -1)
    score = jax.lax.top_k(by_group, 2)[0].sum(-1)                 # (T, G)
    _, best = jax.lax.top_k(score, config["topk_group"])
    kept = jnp.zeros(score.shape, bool).at[
        jnp.arange(c.shape[0])[:, None], best].set(True)
    return jnp.repeat(kept, by_group.shape[-1], -1)


def route(p, t, config):
    """t (T, U) -> (weights of the chosen experts (T, k), their indices
    (T, k)) over ALL the router's experts. The bias chooses and does not
    weigh; the choice is under the group limit."""
    s = jax.nn.sigmoid(_mm(p["router"], t))                       # (T, E)
    c = s + p["router_bias"]
    _, idx = jax.lax.top_k(jnp.where(kept_groups(c, config), c, -jnp.inf),
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
_MIXERS = {"K": kda, "M": mla}


def _layer(p, x, letter, config, routed):
    eps = config["rms_norm_eps"]
    x = x + _MIXERS[letter](p, _rms(p["norm1"], x, eps), config)
    u = _rms(p["norm2"], x, eps)
    if "dense_gate_up" in p:                       # a leading dense layer
        return x + _swiglu(p["dense_gate_up"], p["dense_down"], u)
    return x + experts(p, u, config, config["first_held_expert"], routed)


def _trunk(p, config, tokens, routed):
    x = p["tok_embed"][tokens]
    for layer, letter in zip(p["layers"], config["layer_pattern_run"]):
        # the gradient keeps a layer's input and nothing of its inside
        x = jax.checkpoint(
            lambda p, x, letter=letter: _layer(p, x, letter, config, routed))(
                layer, x)
    return _rms(p["norm_f"], x, config["rms_norm_eps"])


def features(params, config, tokens, routed=True):
    with jax.default_matmul_precision("highest"):
        return _trunk(_f32(params), config, tokens, routed)


def alone(p, config, tokens):
    """[the last M mixer's output, the last K mixer's] (B, S, U) each, the
    mixer of its own layer's first norm of the EMBEDDINGS."""
    x = p["tok_embed"][tokens]
    kl, ml, _ = _places(p["layers"])
    return [_MIXERS[letter](p["layers"][i], _rms(
        p["layers"][i]["norm1"], x, config["rms_norm_eps"]), config)
        for letter, i in (("M", ml), ("K", kl))]


def compared(p, config, tokens):
    """(B, S, 3 U): the continuous trunk | the two mixers alone, a position
    at unit RMS."""
    def unit(t):
        return t / jnp.sqrt((t * t).mean(-1, keepdims=True))

    return jnp.concatenate(
        [_trunk(p, config, tokens, False)]
        + [unit(t) for t in alone(p, config, tokens)], -1)


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


#: what is checked. Of the last K layer, the delta rule's backward: A_log,
#: dt_bias, and the in-projection BY ITS ROWS (q, k, v, the decay's map W_f,
#: the gate's W_g, b) under a name each, so that the 32 rows of b are not
#: lost among twenty thousand. Of the M layer W_q, W_kva, W_kvb, the
#: latent's norm gain and the head gate. Of the last layer the router, the
#: shared expert, and every held expert's three matrices under a name of
#: its own (`moe_w1_e3`): an expert that the dispatch dropped does not
#: move, and the driver reads a parameter of which nothing moved as 0.
KDA_OWN = ("A_log", "dt_bias")
KDA_ROWS = ("q", "k", "v", "decay", "gate", "beta")
MLA_OWN = ("q", "kv_down", "kv_up", "kv_norm", "gate")
MOE_OWN = ("router", "shared_gate_up", "shared_down")
STACKED = ("w1", "w2", "w3")


def _in_proj_rows(layer):
    """Where KDA_ROWS end among the in-projection's rows."""
    inner = layer["out_proj"].shape[1]
    return [inner, 2 * inner, 3 * inner, 4 * inner, 5 * inner]


def _picked(layers, kl, ml, el):
    out = {"kda_" + n: layers[kl][n] for n in KDA_OWN}
    out.update(zip(("kda_" + n for n in KDA_ROWS), jnp.split(
        layers[kl]["in_proj"], _in_proj_rows(layers[kl]), 0)))
    out.update({"mla_" + n: layers[ml][n] for n in MLA_OWN})
    out.update({"moe_" + n: layers[el][n] for n in MOE_OWN})
    out.update({"moe_%s_e%d" % (n, i): layers[el][n][i] for n in STACKED
                for i in range(layers[el][n].shape[0])})
    return out


def _places(layers):
    """(the last K layer, the last M layer, the last layer)."""
    return (max(i for i, l in enumerate(layers) if "A_log" in l),
            max(i for i, l in enumerate(layers) if "kv_down" in l),
            len(layers) - 1)


def update_checked(params):
    return _picked(params["layers"], *_places(params["layers"]))


def checked_grads(params, config, tokens, labels):
    kl, ml, el = _places(params["layers"])
    held = params["layers"][el]["w1"].shape[0]

    def loss_of(picked):
        layers = [dict(l) for l in params["layers"]]
        layers[kl].update({n: picked["kda_" + n] for n in KDA_OWN})
        layers[kl]["in_proj"] = jnp.concatenate(
            [picked["kda_" + n] for n in KDA_ROWS], 0)
        layers[ml].update({n: picked["mla_" + n] for n in MLA_OWN})
        layers[el].update({n: picked["moe_" + n] for n in MOE_OWN})
        layers[el].update({n: jnp.stack([picked["moe_%s_e%d" % (n, i)]
                                         for i in range(held)])
                           for n in STACKED})
        return forward(dict(params, layers=layers), config, tokens, labels,
                       1)[1].sum()

    return jax.grad(loss_of)(_f32(_picked(params["layers"], kl, ml, el)))
