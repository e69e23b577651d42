"""Plain float32 reference of Phi-4-mini-flash (Hugging Face `model_type:
phi4flash`; the SambaY decoder-hybrid-decoder, arXiv:2507.06607), as
models/phi4flash.py states it. jax.numpy only, matmuls at "highest"
precision. Every layer is

    x <- x + Mixer(LN1(x));  x <- x + W_down (u * silu(g)), [g, u] = W_gu LN2(x)

LayerNorm with gain and bias; logits by the tied embedding; no position
embedding. The configuration's `layer_pattern_run` names the mixers:

    M  [x', z] = W_in h;  x'' = silu(conv1d_causal,k(x') + b)
       [d, B, C] = W_x x'';  D_t = softplus(W_dt d + b_dt);  A = -exp(A_log)
       h_t = exp(D_t A) h_{t-1} + (D_t x''_t) B_t^T;  y_t = h_t C_t + D x''_t
       out = W_out (y * silu(z)); the last M before F hands on m = y
    S  differential attention, key j seen from i iff 0 <= i - j < window
    F  the same, full causal; hands on its k, v
       [q, k, v] = W_qkv h + b, heads in pairs, key-value pairs repeated;
       a_i = softmax(q_i k_i^T / sqrt(d) + mask), i = 1, 2
       o = (a_1 - l a_2) [v_1; v_2];  l = exp(l_q1 . l_k1) - exp(l_q2 . l_k2)
       + l_init,  l_init = 0.8 - 0.6 exp(-0.3 depth)
       out = W_o concat(RMSNorm_2d(o) g (1 - l_init)) + b
    G  W_2 (m * silu(W_1 h))
    C  q = W_q h + b; k, v are F's; the same differential form, full causal

The recurrence is run A STEP AT A TIME (`lax.scan` over positions) where
the system steps all chunks together and carries states between them; the
two softmax maps are computed ONCE each and subtracted before they meet
[v_1; v_2], where the system runs four attentions through its kernels:
two algorithms, one function.

Blocking that changes no arithmetic: attention in blocks of queries, the
head in blocks of positions, the scan in checkpointed segments, each layer
under `jax.checkpoint` (the gradient keeps one stream a layer). Departures
from the published model: the cut the configuration states (six of 32
layers, a slice of the vocabulary); the weights are random (the caller's).

forward(params, config, tokens, labels, tail) ->
    (final-LayerNorm output of the last `tail` positions (B, tail, U),
     per-sequence mean next-token cross-entropy over every position (B,))
features(params, config, tokens) -> the final LayerNorm's output (B, S, U)
The parameters: {"tok_embed", "layers": [{letter: {...}}, ...], "ln_f"}, a
layer's own under its letter (builders/phi4flash_lm.py reference_params).
update_checked(params) -> the parameters whose first update the driver
    compares with this file's gradient, {name: array}
checked_grads(params, config, tokens, labels) -> the gradient of the summed
    loss with respect to them, {name: array}
mamba(p, x), diff_attention(p, x, config, depth, window, kv=None),
    gmu(p, x, memory) -> one mixer on (B, S, U), for the tests
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


def _mm(w, x, b=0.0):
    return _dense({"w": w, "b": b}, x)


def _ln(p, x, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["g"] + p["b"]


def _block(n, want):
    return want if n % want == 0 else n


# ------------------------------------------------------------------ M
def _scan(x, dt, a, bm, cm):
    """x, dt (b, s, c); a (c, n); bm, cm (b, s, n) -> y (b, s, c) with
    y_t = h_t C_t, one position at a time."""
    b, s, c = x.shape

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        state = jnp.exp(dt_t[..., None] * a) * state \
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return state, (state * c_t[:, None, :]).sum(-1)

    @jax.checkpoint      # the gradient keeps one state a segment
    def segment(state, seg):
        return jax.lax.scan(step, state, seg)

    seg = _block(s, SCAN_SEGMENT)
    by_time = tuple(t.swapaxes(0, 1).reshape((s // seg, seg) + t.shape[:1]
                                             + t.shape[2:])
                    for t in (x, dt, bm, cm))
    _, y = jax.lax.scan(segment, jnp.zeros((b, c, a.shape[1]), jnp.float32),
                        by_time)
    return y.reshape((s,) + y.shape[2:]).swapaxes(0, 1)


def mamba(p, x):
    """One Mamba-1 mixer -> (out, y): y the scan's output before the gate."""
    s = x.shape[1]
    inner, n = p["A_log"].shape
    rank = p["dt_w"].shape[1]
    xs, z = jnp.split(_mm(p["in_proj"], x), 2, -1)
    k = p["conv_w"].shape[1]
    padded = jnp.pad(xs, [(0, 0), (k - 1, 0), (0, 0)])
    xs = jax.nn.silu(sum(padded[:, j:j + s] * p["conv_w"][:, j]
                         for j in range(k)) + p["conv_b"])
    d, bm, cm = jnp.split(_mm(p["x_proj"], xs), [rank, rank + n], -1)
    dt = jax.nn.softplus(_mm(p["dt_w"], d, p["dt_b"]))
    y = _scan(xs, dt, -jnp.exp(p["A_log"]), bm, cm) + p["D"] * xs
    return _mm(p["out_proj"], y * jax.nn.silu(z)), y


# ------------------------------------------------------------------ S F C
def lambda_init(depth):
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


def _differ(a, lam):
    """a (b, pairs, 2, q, k), the two softmax maps of every pair ->
    a_1 - lam a_2 (perfbench/probe_sambay.py drops the second map here to
    see which limit tells)."""
    return a[:, :, 0] - lam * a[:, :, 1]


def diff_attention(p, x, config, depth, window=None, kv=None):
    """Differential attention -> (out, (k, v)): k, v the projections
    (B, S, kv heads x d) before the repetition, `kv` given: the
    cross-decoder's form (p["qkv"] is the query projection alone)."""
    b, s, _ = x.shape
    heads, kvh = config["num_attention_heads"], config["num_key_value_heads"]
    d = config["hidden_size"] // heads
    proj = _mm(p["qkv"]["w"], x, p["qkv"]["b"])
    if kv is None:
        q, k, v = jnp.split(proj, [heads * d, (heads + kvh) * d], -1)
    else:
        q, (k, v) = proj, kv

    def pairs(t, n):      # (b, s, n d) -> (b, n/2, 2, s, d)
        return t.reshape(b, s, n // 2, 2, d).transpose(0, 2, 3, 1, 4)

    qp = pairs(q, heads)
    kp, vp = (jnp.repeat(pairs(t, kvh), heads // kvh, 1) for t in (k, v))
    # [v_1; v_2]: (b, pairs, s, 2d)
    vv = vp.transpose(0, 1, 3, 2, 4).reshape(b, heads // 2, s, 2 * d)
    lam = jnp.exp(p["lambdas"][0] @ p["lambdas"][1]) \
        - jnp.exp(p["lambdas"][2] @ p["lambdas"][3]) + lambda_init(depth)
    qb = _block(s, Q_BLOCK)
    key_pos = jnp.arange(s)

    @jax.checkpoint      # the gradient keeps no block's scores
    def one(args):
        q_blk, start = args                          # (b, pairs, 2, qb, d)
        scores = jnp.einsum("bpiqd,bpikd->bpiqk", q_blk, kp) / math.sqrt(d)
        dist = (start + jnp.arange(qb))[:, None] - key_pos[None, :]
        seen = dist >= 0 if window is None else (dist >= 0) & (dist < window)
        a = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("bpqk,bpkd->bpqd", _differ(a, lam), vv)

    blocks = qp.reshape(b, heads // 2, 2, s // qb, qb, d) \
        .transpose(3, 0, 1, 2, 4, 5)
    o = jax.lax.map(one, (blocks, jnp.arange(0, s, qb)))
    o = o.transpose(1, 2, 0, 3, 4).reshape(b, heads // 2, s, 2 * d)
    o = o / jnp.sqrt((o * o).mean(-1, keepdims=True)
                     + config["layer_norm_eps"]) * p["subln"] \
        * (1.0 - lambda_init(depth))
    out = _mm(p["o"]["w"], o.transpose(0, 2, 1, 3).reshape(b, s, heads * d),
              p["o"]["b"])
    return out, (k, v)


# ------------------------------------------------------------------ G
def gmu(p, x, memory):
    return _mm(p["out_proj"], memory * jax.nn.silu(_mm(p["in_proj"], x)))


# ------------------------------------------------------------------ model
def _mlp(p, x):
    g, u = jnp.split(_mm(p["gate_up"], x), 2, -1)
    return _mm(p["down"], u * jax.nn.silu(g))


def _pattern_of(layers):
    """A layer's parameters lie under its letter: [{"M": {...}}, ...]."""
    return "".join(next(iter(entry)) for entry in layers)


def _trunk(p, config, tokens):
    eps, pattern = config["layer_norm_eps"], _pattern_of(p["layers"])
    assert pattern == config["layer_pattern_run"], pattern
    memory_layer = pattern.rfind("M", 0, max(pattern.find("F"), 0))
    x = p["tok_embed"][tokens]
    memory = kv = None
    for i, letter in enumerate(pattern):
        layer = p["layers"][i][letter]

        @jax.checkpoint
        def run(layer, x, memory, kv, i=i, letter=letter):
            h = _ln(layer["ln1"], x, eps)
            y = new_kv = None
            if letter == "M":
                mixed, y = mamba(layer, h)
            elif letter == "G":
                mixed = gmu(layer, h, memory)
            else:
                mixed, new_kv = diff_attention(
                    layer, h, config, i,
                    config["sliding_window"] if letter == "S" else None,
                    kv if letter == "C" else None)
            x = x + mixed
            return x + _mlp(layer, _ln(layer["ln2"], x, eps)), y, new_kv

        x, y, new_kv = run(layer, x, memory, kv)
        if i == memory_layer:
            memory = y
        if letter == "F":
            kv = new_kv
    return _ln(p["ln_f"], x, eps)


def features(params, config, tokens):
    with jax.default_matmul_precision("highest"):
        return _trunk(_f32(params), config, tokens)


def forward(params, config, tokens, labels, tail):
    with jax.default_matmul_precision("highest"):
        p = _f32(params)
        b, s = tokens.shape
        feats = _trunk(p, config, tokens)
        hb = _block(s, HEAD_BLOCK)

        @jax.checkpoint
        def nll(args):
            f, y = args                                   # (b, hb, u), (b, hb)
            logp = jax.nn.log_softmax(_mm(p["tok_embed"], f), -1)
            return -jnp.take_along_axis(logp, y[..., None], -1)[..., 0]

        per_pos = jax.lax.map(nll, (
            feats.reshape(b, s // hb, hb, -1).transpose(1, 0, 2, 3),
            labels.reshape(b, s // hb, hb).transpose(1, 0, 2)))
        return feats[:, s - tail:], per_pos.transpose(1, 0, 2).reshape(
            b, s).mean(-1)


#: what is checked, by the layer's letter (the LAST layer of each kind):
#: the scan's backward flows into M's five (A_log, the step sizes' two, the
#: projection that makes B and C, the in-projection); the window kernels'
#: into S's projection and its l vectors, which move only while a_2 is
#: subtracted; F's projection holds dK and dV SUMMED over F itself and
#: every C behind it; G's gate reads the memory; C's query projection and
#: l vectors read F's k, v through the full-causal kernels.
CHECKED = {"M": ("A_log", "dt_b", "dt_w", "x_proj", "in_proj"),
           "S": ("qkv.w", "lambdas"), "F": ("qkv.w", "lambdas"),
           "G": ("in_proj",), "C": ("qkv.w", "lambdas")}


def _get(layer, name):
    for part in name.split("."):
        layer = layer[part]
    return layer


def _with(layer, name, value):
    head, _, rest = name.partition(".")
    return dict(layer, **{head: _with(layer[head], rest, value)
                          if rest else value})


def _places(pattern):
    """[(checked name, layer index, parameter path)]."""
    return [("%s%d_%s" % (letter, pattern.rindex(letter),
                          name.replace(".", "_")),
             pattern.rindex(letter), name)
            for letter, names in CHECKED.items() if letter in pattern
            for name in names]


def update_checked(params):
    layers = params["layers"]
    pattern = _pattern_of(layers)
    return {key: _get(layers[i][pattern[i]], name)
            for key, i, name in _places(pattern)}


def checked_grads(params, config, tokens, labels):
    pattern = config["layer_pattern_run"]
    places = _places(pattern)

    def loss_of(picked):
        layers = list(params["layers"])
        for key, i, name in places:
            layers[i] = {pattern[i]: _with(layers[i][pattern[i]], name,
                                           picked[key])}
        return forward(dict(params, layers=layers), config, tokens, labels,
                       1)[1].sum()

    return jax.grad(loss_of)(_f32(update_checked(params)))
