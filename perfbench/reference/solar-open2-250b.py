"""Plain float32 reference of the Solar Open 2 architecture (Hugging Face
`model_type: solar_open2`, upstage/Solar-Open2-250B), as
models/solar_open2.py states it. jax.numpy only, matmuls at "highest"
precision. Every layer is

    x <- x + Mixer(RMSNorm(x));  x <- x + Experts(RMSNorm(x))

and the configuration's `layer_pattern_run` names the mixers:

    K  q~, k~, v = silu(conv1d_causal,4(h W_q | h W_k | h W_v));  per head
       q = q~ / |q~|_2 d^-1/2;  k = k~ / |k~|_2
       g_t = -exp(A_log) softplus((h W_f-) W_f+ + dt_bias);  a_t = exp(g_t)
       b_t = 2 sigmoid(h w_b)              (2 where kda_allow_neg_eigval)
       S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T;  S_0 = 0
       o_t = S_t^T q_t
       out = W_o [RMSNorm_d(o_t) gamma * sigmoid((h W_g-) W_g+)]
    G  softmax(q k^T / sqrt(d) + causal) v, grouped-query, NO rotary or
       other position embedding;  out = W_o [o * sigmoid(h W_gate)]
    experts  s = sigmoid(W_r u);  chosen = top-k(s + b)
       w_e = s_e / (sum_chosen s + 1e-20) * routed_scaling_factor
       out = sum_{e chosen, held} w_e W2_e (silu(W1_e u) * W3_e u)
             + W_down (silu(W_gate u) * W_up u)
    logits = W_head RMSNorm(x_L)

The delta rule is run A POSITION AT A TIME (`lax.scan` over t carrying S,
the three lines above verbatim), where the system runs the chunked WY form
with a triangular solve a chunk: two algorithms, one function. The experts
are a loop over the HELD ones against a dense (T, held) matrix of weights,
zero where an expert was not chosen: no sort, no grouped matmul. The
parameters may be one chip's share (fewer heads in a mixer, `w1`/`w2`/`w3`
of the experts `first_held_expert` .. + their count): every size is read
from the arrays, the router is as wide as it is, and the normalisation is
over all k chosen, held or not. What the absent experts and heads would
add is left out, as in the program.

What `forward` hands out to be compared is continuous in its inputs and
the whole model's features are not: top-8 of 320 sigmoid scores whose
neighbours at the 8th place lie closer than a bfloat16 rounding of the
router's input flips a last choice for many tokens, and where that choice
is a held expert the token gains or loses a whole expert output (PERF.md
section 6, PR 31 measured what that hides). So the compared features come
from a second pass over the same layers that leaves the routed sum out;
the routed path is held by the loss, which is the whole model's, and by the
gradients below (the router and every held expert under a name of its own).

Blocking that changes no arithmetic: attention in blocks of queries, the
head in blocks of positions, the recurrence in checkpointed segments (the
gradient keeps one state a segment, not one a position), each layer
recomputed in the gradient (which then fits one chip). Departures from
the published model: the weights are random (the caller's); the L2 norms
of q and k add 1e-6 under the root, as the program's do.

forward(params, config, tokens, labels, tail) ->
    (final-RMSNorm output of the last `tail` positions (B, tail, U) OF THE
     CONTINUOUS TRUNK: the same layers with the routed experts' sum left
     out of every layer (the shared expert stays),
     per-sequence mean next-token cross-entropy over every position (B,),
     of the whole model)
features(params, config, tokens, routed=True) -> the final RMSNorm's
    output (B, S, U), of the whole model or of the continuous trunk
update_checked(params) -> the parameters whose first update the driver
    compares with this file's gradient, {name: array}
checked_grads(params, config, tokens, labels) -> the gradient of the summed
    loss with respect to them, {name: array}
delta_rule(q, k, v, g, beta), kda(p, x, config), attention(p, x, config),
    experts(p, x, config, first) -> the recurrence and one block on
    (B, S, U), for the tests of the op and of the shares
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


# ------------------------------------------------------------------ K
def delta_rule(q, k, v, g, beta):
    """q, k, g (b, s, h, d_k), v (b, s, h, d_v), beta (b, s, h) ->
    o (b, s, h, d_v), one position at a time."""
    b, s, h, dk = q.shape

    def step(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = jnp.exp(g_t)[..., None] * state           # Diag(a_t) S_{t-1}
        u = b_t[..., None] * (v_t - (state * k_t[..., None]).sum(-2))
        state = state + k_t[..., None] * u[..., None, :]
        return state, (state * q_t[..., None]).sum(-2)

    @jax.checkpoint      # the gradient keeps one state a segment
    def segment(state, seg):
        return jax.lax.scan(step, state, seg)

    seg = _block(s, SCAN_SEGMENT)
    by_time = tuple(t.swapaxes(0, 1).reshape((s // seg, seg) + t.shape[:1]
                                             + t.shape[2:])
                    for t in (q, k, v, g, beta))
    _, o = jax.lax.scan(segment,
                        jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
                        by_time)
    return o.reshape((s,) + o.shape[2:]).swapaxes(0, 1)


def kda(p, x, config):
    """One Kimi Delta Attention mixer, or the shard of one that `p` holds:
    the heads are A_log's, the rank the low-rank maps'."""
    b, s, _ = x.shape
    lin = config["linear_attn_config"]
    heads, d = p["A_log"].shape[0], lin["head_dim"]
    inner, r = heads * d, p["decay_up"].shape[1]
    proj = _mm(p["in_proj"], x)
    qkv, low_f, low_g, bl = jnp.split(
        proj, [3 * inner, 3 * inner + r, 3 * inner + 2 * r], -1)
    taps = p["conv_w"].shape[1]
    padded = jnp.pad(qkv, [(0, 0), (taps - 1, 0), (0, 0)])
    qkv = jax.nn.silu(sum(padded[:, j:j + s] * p["conv_w"][:, j]
                          for j in range(taps)))
    q, k, v = (t.reshape(b, s, heads, d) for t in jnp.split(qkv, 3, -1))
    q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + L2_EPS) / math.sqrt(d)
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + L2_EPS)
    step = jax.nn.softplus(_mm(p["decay_up"], low_f) + p["dt_bias"])
    g = -jnp.exp(p["A_log"])[:, None] * step.reshape(b, s, heads, d)
    beta = (2.0 if config["kda_allow_neg_eigval"] else 1.0) \
        * jax.nn.sigmoid(bl)
    o = delta_rule(q, k, v, g, beta)
    o = _rms(p["gate_norm"], o, config["rms_norm_eps"]).reshape(b, s, inner)
    return _mm(p["out_proj"], o * jax.nn.sigmoid(_mm(p["gate_up"], low_g)))


# ------------------------------------------------------------------ G
def attention(p, x, config):
    """Causal grouped-query attention over the heads `p` holds, gated."""
    b, s, _ = x.shape
    d = config["head_dim"]
    heads, kv = p["q"].shape[0] // d, p["k"].shape[0] // d

    def split(t, n):
        return t.reshape(b, s, n, d).transpose(0, 2, 1, 3)

    q = split(_mm(p["q"], x), heads)
    k, v = (jnp.repeat(split(_mm(p[n], x), kv), heads // kv, 1)
            for n in ("k", "v"))
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
    out = out.transpose(0, 2, 1, 3).reshape(b, s, heads * d)
    return _mm(p["o"], _gated(out, _mm(p["gate"], x)))


def _gated(out, z):
    """The heads' outputs under their gate, a channel at a time."""
    return out * jax.nn.sigmoid(z)


# ------------------------------------------------------------------ experts
def route(p, t, config):
    """t (T, U) -> (weights of the chosen experts (T, k), their indices
    (T, k)) over ALL the router's experts. The bias chooses and does not
    weigh."""
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
    gate, up = jnp.split(_mm(p["shared_gate_up"], t), 2, -1)
    shared = _mm(p["shared_down"], jax.nn.silu(gate) * up)
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
_MIXERS = {"K": kda, "G": attention}


def _layer(p, x, letter, config, routed):
    eps = config["rms_norm_eps"]
    x = x + _MIXERS[letter](p, _rms(p["norm1"], x, eps), config)
    return x + experts(p, _rms(p["norm2"], x, eps), config,
                       config["first_held_expert"], routed)


def _trunk(p, config, tokens, routed):
    x = p["tok_embed"][tokens]
    for layer, letter in zip(p["layers"], config["layer_pattern_run"]):
        # the gradient keeps a layer's input, not what its eight experts
        # and its mixer kept (3 GB a layer in float32 at 8192 positions)
        x = jax.checkpoint(
            lambda p, x, letter=letter: _layer(p, x, letter, config, routed))(
                layer, x)
    return _rms(p["norm_f"], x, config["rms_norm_eps"])


def features(params, config, tokens, routed=True):
    with jax.default_matmul_precision("highest"):
        return _trunk(_f32(params), config, tokens, routed)


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
        continuous = _trunk(p, config, tokens, False)
        return continuous[:, s - tail:], per_pos.transpose(1, 0, 2).reshape(
            b, s).mean(-1)


#: what is checked. Of the last K layer, the delta rule's backward: A_log,
#: dt_bias, both rank -> heads x d maps, and the in-projection BY ITS ROWS
#: (q, k, v, the two units -> rank maps, b) under a name each, so that the
#: eight rows of b are not lost among three thousand. Of the G layer its
#: gate. Of the last layer the router, the shared expert, and every held
#: expert's three matrices under a name of its own (`moe_w1_e3`): an
#: expert that the dispatch dropped does not move, and the driver reads a
#: parameter of which nothing moved as 0.
KDA_OWN = ("A_log", "dt_bias", "decay_up", "gate_up")
KDA_ROWS = ("q", "k", "v", "decay_down", "gate_down", "beta")
MOE_OWN = ("router", "shared_gate_up", "shared_down")
STACKED = ("w1", "w2", "w3")


def _in_proj_rows(layer):
    """Where KDA_ROWS end among the in-projection's rows."""
    inner = layer["out_proj"].shape[1]
    r = layer["decay_up"].shape[1]
    return [inner, 2 * inner, 3 * inner, 3 * inner + r, 3 * inner + 2 * r]


def _picked(layers, kl, gl, el):
    out = {"kda_" + n: layers[kl][n] for n in KDA_OWN}
    out.update(zip(("kda_" + n for n in KDA_ROWS), jnp.split(
        layers[kl]["in_proj"], _in_proj_rows(layers[kl]), 0)))
    out["gqa_gate"] = layers[gl]["gate"]
    out.update({"moe_" + n: layers[el][n] for n in MOE_OWN})
    out.update({"moe_%s_e%d" % (n, i): layers[el][n][i] for n in STACKED
                for i in range(layers[el][n].shape[0])})
    return out


def _places(layers):
    """(the last K layer, the last G layer, the last layer)."""
    return (max(i for i, l in enumerate(layers) if "A_log" in l),
            max(i for i, l in enumerate(layers) if "gate" in l),
            len(layers) - 1)


def update_checked(params):
    return _picked(params["layers"], *_places(params["layers"]))


def checked_grads(params, config, tokens, labels):
    kl, gl, el = _places(params["layers"])
    held = params["layers"][el]["w1"].shape[0]

    def loss_of(picked):
        layers = [dict(l) for l in params["layers"]]
        layers[kl].update({n: picked["kda_" + n] for n in KDA_OWN})
        layers[kl]["in_proj"] = jnp.concatenate(
            [picked["kda_" + n] for n in KDA_ROWS], 0)
        layers[gl]["gate"] = picked["gqa_gate"]
        layers[el].update({n: picked["moe_" + n] for n in MOE_OWN})
        layers[el].update({n: jnp.stack([picked["moe_%s_e%d" % (n, i)]
                                         for i in range(held)])
                           for n in STACKED})
        return forward(dict(params, layers=layers), config, tokens, labels,
                       1)[1].sum()

    return jax.grad(loss_of)(_f32(_picked(params["layers"], kl, gl, el)))
