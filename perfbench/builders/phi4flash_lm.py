"""phi-4-mini-flash-reasoning -> models.Phi4FlashModel, through the public
package, as ONE STAGE of an 8-stage pipeline whose vocabulary layers are
divided over all 8 stages (the configuration's `cut`): `num_layers` whole
layers named by `layer_pattern_run`, the `vocab_size`-row slice of the tied
embedding. Every width is the source's; Mamba-1's sizes, which the source's
config leaves to the family's defaults, follow from `hidden_size` (the
configuration's `assumed`).

Xavier weights from the seed (A_log, dt_bias and D by Mamba-1's own rules,
the l vectors normal(0, 0.1)), bfloat16 but for A_log, dt_bias, D, the l
vectors and the per-head norm's gain; each layer recomputed in the backward
(`remat_layers`); trained as `FeaturesView(model)` + `ChunkedLMLoss(model)`
over the tied embedding so the (S, V) logits never exist at once.

The arithmetic below counts what the algorithm requires of THIS chip, from
the configuration's keys alone, in integers.
"""


def shapes(config):
    """The sizes of each kind of layer, from the keys."""
    u, heads = config["hidden_size"], config["num_attention_heads"]
    return {
        "units": u, "hidden": config["intermediate_size"],
        "heads": heads, "kv_heads": config["num_key_value_heads"],
        "head_dim": u // heads,
        "inner": config["mamba_expand"] * u, "state": config["mamba_d_state"],
        "conv": config["mamba_d_conv"], "dt_rank": -(-u // 16),
        "window": config["sliding_window"],
        "pattern": config["layer_pattern_run"],
    }


def matmul_params(config):
    """{letter: weights in one layer's matmuls, mixer + MLP}, `head` the
    tied embedding's as a head."""
    s = shapes(config)
    u, inner, d = s["units"], s["inner"], s["head_dim"]
    q, kv = s["heads"] * d, s["kv_heads"] * d
    mlp = 3 * u * s["hidden"]
    attention = u * (q + 2 * kv) + q * u
    return {
        "M": u * 2 * inner + inner * (s["dt_rank"] + 2 * s["state"])
        + s["dt_rank"] * inner + inner * u + mlp,
        "S": attention + mlp, "F": attention + mlp,
        "G": 2 * u * inner + mlp,
        "C": 2 * u * q + mlp,
        "head": config["vocab_size"] * u,
    }


def other_params(config):
    """{letter: a layer's parameters outside its matmuls}: the two
    LayerNorms; M: convolution, its bias, dt bias, A_log, D; attention:
    projection biases, the four l vectors, the per-head norm's gain."""
    s = shapes(config)
    u, inner, d = s["units"], s["inner"], s["head_dim"]
    q, kv = s["heads"] * d, s["kv_heads"] * d
    norms = 4 * u
    return {
        "M": norms + inner * (s["conv"] + 3 + s["state"]),
        "S": norms + q + 2 * kv + u + 6 * d,
        "F": norms + q + 2 * kv + u + 6 * d,
        "G": norms,
        "C": norms + q + u + 6 * d,
    }


def parameter_count(config, pattern=None, vocab_size=None):
    """Every parameter of a stack with this `pattern` (default: the one
    that is run) over `vocab_size` rows: 697.09 M as cut, 3.85 B whole."""
    m, o = matmul_params(config), other_params(config)
    pattern = pattern or config["layer_pattern_run"]
    vocab_size = vocab_size or config["vocab_size"]
    u = config["hidden_size"]
    return sum(m[c] + o[c] for c in pattern) + vocab_size * u + 2 * u


def window_keys(seq_len, window):
    """Keys the windowed queries of one sequence see, in all: sum over i of
    min(i + 1, window)."""
    full = max(seq_len - window, 0)
    edge = seq_len - full
    return full * window + edge * (edge + 1) // 2


def diff_attention_flops_per_key(config):
    """Forward + backward matmul operations of one (query, key) pair of a
    differential attention layer: every query head's q k^T (2 d) and each of
    the two maps of a pair against [v_1; v_2] (2 x 2d a head), backward
    twice the forward."""
    s = shapes(config)
    return 3 * s["heads"] * (2 * s["head_dim"] + 4 * s["head_dim"])


def attention_flops_per_token(config, seq_len):
    """The full-causal layers (`F`, `C`): a query sees seq_len / 2 keys on
    average. What grows with the context; the window's part does not."""
    causal = sum(config["layer_pattern_run"].count(c) for c in "FC")
    return causal * diff_attention_flops_per_key(config) * seq_len // 2


def window_flops_per_token(config):
    """The windowed layers (`S`) at their asymptote, `sliding_window` keys
    a query: the constant MFU is booked with (the exact band, short by the
    first window's triangle, is window_keys')."""
    return config["layer_pattern_run"].count("S") \
        * diff_attention_flops_per_key(config) * config["sliding_window"]


def model_flops_per_token(config, seq_len):
    """Forward + backward operations the algorithm requires of this chip
    per trained token: 6 x the matmul weights (the tied embedding as the
    head) + attention. The scan's element-wise work (3 passes x 9 x inner x
    state a token a layer, ~2.2 M) is vector-unit work and in no matmul
    count: it is booked by `selective_scan_roofline`, in bytes."""
    m = matmul_params(config)
    return 6 * (sum(m[c] for c in config["layer_pattern_run"]) + m["head"]) \
        + attention_flops_per_token(config, seq_len) \
        + window_flops_per_token(config)


def scan_bytes_per_token(config):
    """Bytes the selective scan must move for one token, all Mamba layers,
    as the op is entered (ops/selective_scan.py: the step sizes are formed
    inside it from their dt_rank-wide input, so the (S, inner) float32
    step sizes are no input of it and never lie in HBM): x, the low-rank
    step-size input, B and C (all bfloat16) in and y (bfloat16) out
    forward; the same in, dy in and the four gradients out backward.
    Neither the recomputation nor the states are required; the
    projection's weights and their gradients are per step, not per token."""
    s = shapes(config)
    x, low, bc = 2 * s["inner"], 2 * s["dt_rank"], 2 * 2 * s["state"]
    forward = x + low + bc + x
    backward = (x + low + bc) + x + (x + low + bc)
    return (forward + backward) * s["pattern"].count("M")


def build(config, seed, seq_len):
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import models
    s = shapes(config)
    mx.random.seed(seed)
    net = models.Phi4FlashModel(
        config["vocab_size"], s["units"], s["hidden"], s["pattern"],
        mamba=dict(inner=s["inner"], state=s["state"],
                   conv_kernel=s["conv"]),
        attention=dict(num_heads=s["heads"], num_kv_heads=s["kv_heads"],
                       head_dim=s["head_dim"]),
        window=s["window"], epsilon=config["layer_norm_eps"],
        remat_layers=True)
    net.initialize(mx.init.Xavier())
    # Xavier over (V, U) gives logits of std well under 1: the loss would be
    # ln V whatever the features are, and the check of it would be blind
    embed = net.tok_embed.weight
    embed.set_data(embed.data() * config["init_tok_embed_scale"])
    net.cast("bfloat16")
    view = models.FeaturesView(net)
    return {"model": net, "train_net": view,
            "loss": models.ChunkedLMLoss(net), "eval_net": view}


def reference_params(model):
    def w(param):
        return param.data()._data

    def dense(layer):
        return {"w": w(layer.weight), "b": w(layer.bias)}

    def ln(layer):
        return {"g": w(layer.gamma), "b": w(layer.beta)}

    def layer(l, letter):
        m = l.mixer
        if letter == "M":
            own = {"in_proj": w(m.in_proj.weight), "conv_w": w(m.conv_weight),
                   "conv_b": w(m.conv_bias), "x_proj": w(m.x_proj.weight),
                   "dt_w": w(m.dt_weight), "dt_b": w(m.dt_bias),
                   "A_log": w(m.A_log), "D": w(m.D),
                   "out_proj": w(m.out_proj.weight)}
        elif letter == "G":
            own = {"in_proj": w(m.in_proj.weight),
                   "out_proj": w(m.out_proj.weight)}
        else:
            own = {"qkv": dense(m.qkv), "o": dense(m.proj),
                   "lambdas": w(m.lambdas), "subln": w(m.subln_gamma)}
        return {letter: dict(own, ln1=ln(l.ln1), ln2=ln(l.ln2),
                             gate_up=w(l.mlp.gate_up.weight),
                             down=w(l.mlp.down.weight))}

    return {"tok_embed": w(model.tok_embed.weight),
            "layers": [layer(l, c)
                       for l, c in zip(model.layers, model.pattern)],
            "ln_f": ln(model.ln_f)}
