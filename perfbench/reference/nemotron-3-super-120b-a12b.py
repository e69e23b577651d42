"""Plain float32 reference of the Nemotron-H architecture (Hugging Face
`model_type: nemotron_h`, NVIDIA-Nemotron-3-Super-120B-A12B), as
models/nemotron_h.py states it. jax.numpy only, matmuls at "highest"
precision. Every layer is x <- x + mixer(RMSNorm(x)); the configuration's
`layer_pattern_run` names the mixers:

    M  z, xBC, dt = split(W_in u);  xBC = silu(conv1d_causal,k(xBC) + b)
       x, B, C = split(xBC);  D_t = softplus(dt + dt_bias);  A = -exp(A_log)
       h_t = exp(D_t A) h_{t-1} + D_t x_t B_t^T;   y_t = h_t C_t + D x_t
       out = W_out (RMSNorm_group(y * silu(z)) * g)
    *  softmax(q k^T / sqrt(d) + causal) v, grouped-query, NO rotary or
       other position embedding (the nemotron_h attention applies none)
    E  s = sigmoid(W_r u);  chosen = top-k(s + b);
       w_e = s_e / (sum_chosen s + 1e-20) * routed_scaling_factor
       out = W_up sum_{e chosen, held} w_e W2_e relu2(W1_e W_down u)
             + W2_s relu2(W1_s u)
    logits = W_head RMSNorm(x_L)

The recurrence is run A STEP AT A TIME (`lax.scan` over positions), where
the system runs the chunked dual form: two algorithms, one function. The
experts are a loop over the HELD ones against a dense (T, held) matrix of
weights, zero where an expert was not chosen: no sort, no grouped matmul.
The parameters may be one chip's share (fewer heads and groups in a
Mamba-2 mixer, fewer heads in attention, `w1`/`w2` of the experts
`first_held_expert` .. + their count): every size is read from the arrays,
the router is as wide as it is, and the normalisation is over all k
chosen, held or not. What the absent experts and heads would add is left
out, as in the program.

What `forward` hands out to be compared is continuous in its inputs and
the whole model's features are not: top-k of 512 scores whose neighbours
at the k-th place lie closer than a bfloat16 rounding of the router's
input flips a last choice for most tokens, and where that choice is a held
expert the token gains or loses a whole expert output. That is no error of
precision, and it hid every error of precision below it (on the chip the
cell read 1.3-3.3 % by seed with it and 1.08 % without: PERF.md section
6, PR 31). So the
compared features come from a second pass over the same layers that leaves
the routed sum out; the routed path is held by the loss, which is the
whole model's, and by the gradients below (the router, both latent maps
and every held expert under a name of its own).

Blocking that changes no arithmetic: attention in blocks of queries, the
head in blocks of positions, the scan in checkpointed segments (the
gradient keeps one state a segment, not one a position). Departures from
the published model: no multi-token-prediction appendix (the configuration
cuts it as depth); the weights are random (the caller's).

forward(params, config, tokens, labels, tail) ->
    (final-RMSNorm output of the last `tail` positions (B, tail, U) OF THE
     CONTINUOUS TRUNK: the same layers with the routed experts' sum left
     out of every expert layer (the shared expert stays) — see below,
     per-sequence mean next-token cross-entropy over every position (B,),
     of the whole model)
features(params, config, tokens, routed=True) -> the final RMSNorm's
    output (B, S, U), of the whole model or of the continuous trunk
update_checked(params) -> the parameters whose first update the driver
    compares with this file's gradient, {name: array}
checked_grads(params, config, tokens, labels) -> the gradient of the summed
    loss with respect to them, {name: array}
mamba(p, x, config), attention(p, x, config), latent_moe(p, x, config,
    first) -> one mixer on (B, S, U), for the tests of the shares

This file exists twice, byte for byte: tests/nemotron_h_reference.py, which
the tier-1 tests import, and
perfbench/reference/nemotron-3-super-120b-a12b.py, where the benchmark
finds a configuration's reference by name. A test holds the two to the
same text and the same outputs.
"""
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 256        # queries per attention block
HEAD_BLOCK = 1024    # positions per block of the vocabulary projection
SCAN_SEGMENT = 128   # positions per checkpointed segment of the scan


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


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _block(n, want):
    return want if n % want == 0 else n


# ------------------------------------------------------------------ M
def _scan(x, dt, a, bm, cm):
    """x (b, s, h, p), dt (b, s, h), a (h,), bm, cm (b, s, h, n) ->
    y (b, s, h, p) with y_t = h_t C_t, one position at a time."""
    b, s, h, p = x.shape
    n = bm.shape[-1]

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, (state * c_t[:, :, None, :]).sum(-1)

    @jax.checkpoint      # the gradient keeps one state a segment
    def segment(state, seg):
        return jax.lax.scan(step, state, seg)

    seg = _block(s, SCAN_SEGMENT)
    by_time = tuple(t.swapaxes(0, 1).reshape((s // seg, seg) + t.shape[:1]
                                             + t.shape[2:])
                    for t in (x, dt, bm, cm))
    _, y = jax.lax.scan(segment, jnp.zeros((b, h, p, n), jnp.float32),
                        by_time)
    return y.reshape((s,) + y.shape[2:]).swapaxes(0, 1)


def mamba(p, x, config):
    """One Mamba-2 mixer, or the shard of one that `p` holds: the heads
    are A_log's, the groups what is left of the convolution's channels."""
    b, s, _ = x.shape
    eps, n = config["layer_norm_epsilon"], config["ssm_state_size"]
    heads, hd = p["A_log"].shape[0], config["mamba_head_dim"]
    inner = heads * hd
    groups = (p["conv_w"].shape[0] - inner) // (2 * n)
    proj = _mm(p["in_proj"], x)
    z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * groups * n], -1)
    k = p["conv_w"].shape[1]
    padded = jnp.pad(xbc, [(0, 0), (k - 1, 0), (0, 0)])
    xbc = jax.nn.silu(sum(padded[:, j:j + s] * p["conv_w"][:, j]
                          for j in range(k)) + p["conv_b"])
    xs, bm, cm = jnp.split(xbc, [inner, inner + groups * n], -1)
    xs = xs.reshape(b, s, heads, hd)
    # B and C are a group's: every head of the group reads the same
    bm, cm = (jnp.repeat(t.reshape(b, s, groups, n), heads // groups, 2)
              for t in (bm, cm))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = _scan(xs, dt, -jnp.exp(p["A_log"]), bm, cm) \
        + p["D"][:, None] * xs
    y = y.reshape(b, s, inner) * jax.nn.silu(z)       # the gate, then the norm
    yg = y.reshape(b, s, groups, inner // groups)
    yg = yg / jnp.sqrt((yg * yg).mean(-1, keepdims=True) + eps)
    return _mm(p["out_proj"], yg.reshape(b, s, inner) * p["gate_norm"])


# ------------------------------------------------------------------ *
def attention(p, x, config):
    """Causal grouped-query attention over the heads `p` holds."""
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
    return _mm(p["o"], out.transpose(0, 2, 1, 3).reshape(b, s, heads * d))


# ------------------------------------------------------------------ E
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


def latent_moe(p, x, config, first=0, routed=True):
    """The latent mixture with the experts `first` .. first + count - 1
    held (count = w1's), plus the shared expert on the full width; not
    `routed`: the shared expert alone."""
    t = x.reshape(-1, x.shape[-1])
    shared = _mm(p["shared_down"], _relu2(_mm(p["shared_up"], t)))
    if not routed:
        return shared.reshape(x.shape)
    vals, idx = route(p, t, config)
    # (T, E): w_e where expert e was chosen for the token, else 0
    weight = jnp.zeros((t.shape[0], p["router"].shape[0]), jnp.float32) \
        .at[jnp.arange(t.shape[0])[:, None], idx].set(vals)
    count = p["w1"].shape[0]
    weight = jax.lax.dynamic_slice_in_dim(weight, first, count, 1)
    latent = _mm(p["latent_down"], t)

    def one(out, expert):
        w1, w2, w_e = expert          # stored (L, I) and (I, L): x @ w
        return out + w_e[:, None] * _mm(w2.T, _relu2(_mm(w1.T, latent))), \
            None

    summed, _ = jax.lax.scan(one, jnp.zeros_like(latent),
                             (p["w1"], p["w2"], weight.T))
    return (_mm(p["latent_up"], summed) + shared).reshape(x.shape)


# ------------------------------------------------------------------ model
_MIXERS = {"M": mamba, "*": attention}


def _layer(p, x, letter, config, routed):
    u = _rms(p["norm"], x, config["layer_norm_epsilon"])
    if letter == "E":
        return x + latent_moe(p, u, config, config["first_held_expert"],
                              routed)
    return x + _MIXERS[letter](p, u, config)


def _trunk(p, config, tokens, routed):
    x = p["tok_embed"][tokens]
    for layer, letter in zip(p["layers"], config["layer_pattern_run"]):
        x = _layer(layer, x, letter, config, routed)
    return _rms(p["norm_f"], x, config["layer_norm_epsilon"])


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


#: what is checked of the last Mamba-2 layer and of the last expert layer:
#: the chunked scan's backward flows into the first three; the held
#: dispatch, the grouped matmuls, the combine and the sigmoid weights into
#: the rest. Every held expert is checked, each under a name of its own
#: (`moe_w1_e3`): an expert that the dispatch dropped does not move, and
#: the driver reads a parameter of which nothing moved as 0.
CHECKED = {"M": ("A_log", "dt_bias", "in_proj"),
           "E": ("router", "latent_down", "latent_up")}
STACKED = ("w1", "w2")


def _picked(layers, m, e):
    out = {"mamba_" + n: layers[m][n] for n in CHECKED["M"]}
    out.update({"moe_" + n: layers[e][n] for n in CHECKED["E"]})
    out.update({"moe_%s_e%d" % (n, i): layers[e][n][i] for n in STACKED
                for i in range(layers[e][n].shape[0])})
    return out


def update_checked(params):
    """CHECKED of the last Mamba-2 layer and the last expert layer, found
    by the parameters a layer has. The backward pass goes no deeper than
    the earlier of the two."""
    layers = params["layers"]
    return _picked(layers,
                   max(i for i, l in enumerate(layers) if "A_log" in l),
                   max(i for i, l in enumerate(layers) if "router" in l))


def checked_grads(params, config, tokens, labels):
    pattern = config["layer_pattern_run"]
    m, e = pattern.rindex("M"), pattern.rindex("E")
    held = params["layers"][e]["w1"].shape[0]

    def loss_of(picked):
        layers = list(params["layers"])
        layers[m] = dict(layers[m], **{n: picked["mamba_" + n]
                                       for n in CHECKED["M"]})
        layers[e] = dict(
            layers[e], **{n: picked["moe_" + n] for n in CHECKED["E"]},
            **{n: jnp.stack([picked["moe_%s_e%d" % (n, i)]
                             for i in range(held)]) for n in STACKED})
        return forward(dict(params, layers=layers), config, tokens, labels,
                       1)[1].sum()

    return jax.grad(loss_of)(_f32(_picked(params["layers"], m, e)))
