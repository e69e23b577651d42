"""evabyte -> models.EvaByteModel, through the public package, as ONE STAGE
of an 8-stage pipeline (the configuration's `cut`): `num_layers` whole
layers of the published 32, every head, the whole MLP, the whole
320-row vocabulary. Every width is the source's.

Xavier weights from the seed (W_down times `init_down_scale`, phi and mu
normal with `init_phi_std` / `init_mu_std`: the configuration's `assumed`
says why), the norms' g at 0, bfloat16 but for phi and mu; the residual
stream float32; each layer recomputed in the backward (`remat_layers`), the
MLP in row blocks; trained as `FeaturesView(model)` + `MultiByteLoss(model)`:
all eight prediction heads, (rows, 2560) float32 logits a block at a time.

The arithmetic below counts what the algorithm requires of THIS chip, from
the configuration's keys alone, in integers.
"""


def shapes(config):
    u, heads = config["hidden_size"], config["num_attention_heads"]
    return {
        "units": u, "hidden": config["intermediate_size"],
        "layers": config["num_layers"], "heads": heads,
        "head_dim": u // heads, "window": config["window_size"],
        "chunk": config["chunk_size"], "vocab": config["vocab_size"],
        "pred_heads": config["num_pred_heads"],
    }


def matmul_params(config):
    """{`attention`: q, k, v, o; `mlp`: gate, up, down; `head`: the eight
    heads' one map}. The embedding is a gather."""
    s = shapes(config)
    u = s["units"]
    return {"attention": 4 * u * u, "mlp": 3 * u * s["hidden"],
            "head": s["pred_heads"] * s["vocab"] * u}


def parameter_count(config):
    """Every parameter this chip holds (821 366 784 at the published
    widths, four layers): the matrices, two norm gains and phi and mu
    (heads x head_dim each) a layer, the embedding, the final gain."""
    s, m = shapes(config), matmul_params(config)
    layer = m["attention"] + m["mlp"] + 2 * s["units"] \
        + 2 * s["heads"] * s["head_dim"]
    return s["layers"] * layer + s["vocab"] * s["units"] + m["head"] \
        + s["units"]


def seen_pairs(config, seq_len):
    """-> (exact, summary) (query, key) pairs ONE head of one sequence
    sees: a query's own aligned window up to itself, and one summary a
    chunk of every earlier window. 16 785 408 and 7 340 032 at 16 384."""
    s = shapes(config)
    w, c = s["window"], s["chunk"]
    if seq_len <= w:
        return seq_len * (seq_len + 1) // 2, 0
    n = seq_len // w
    return n * (w * (w + 1) // 2), w * (w // c) * (n * (n - 1) // 2)


def eva_attention_flops(config, seq_len, matmuls):
    """Operations of `matmuls` (heads x head_dim)-wide matmuls a seen pair,
    one layer, one sequence: 2 forward (q k^T, a v), 4 backward (dV, dA,
    dq, dk). Scores made again in a backward or a recomputed forward, and
    pairs a block computes and masks, are a program's own cost."""
    s = shapes(config)
    return matmuls * 2 * s["heads"] * s["head_dim"] \
        * sum(seen_pairs(config, seq_len))


def eva_attention_bytes(config, seq_len):
    """Bytes one layer's attention must move, forward + backward with
    nothing run twice, bfloat16: q, k, v in and o out; q, k, v, o, dO in
    and dq, dk, dv out. The summaries are made from k and v and need not
    lie in memory at all."""
    s = shapes(config)
    return 12 * seq_len * s["heads"] * s["head_dim"] * 2


def attention_flops_per_token(config, seq_len):
    """What grows with the length, forward + backward, all layers: the
    summaries a query sees at their asymptote, seq_len / (2 chunk) (the
    exact count is short by window / (2 chunk): `seen_pairs`). The exact
    keys do NOT grow: a query sees (window + 1) / 2 of them on average
    however long the sequence is."""
    s = shapes(config)
    return s["layers"] * 6 * 2 * s["heads"] * s["head_dim"] * seq_len \
        // (2 * s["chunk"])


def model_flops_per_token(config, seq_len):
    """Forward + backward operations the algorithm requires of this chip
    per trained token: 6 x the matmul weights, 6 matmuls over the
    (window + 1) / 2 exact keys of a query, and the summaries. The pooling
    (2 x 3 x head_dim a key a head) is vector-unit work and in no matmul
    count."""
    s, m = shapes(config), matmul_params(config)
    return s["layers"] * (6 * (m["attention"] + m["mlp"])
                          + 6 * s["heads"] * s["head_dim"]
                          * (s["window"] + 1)) \
        + 6 * m["head"] + attention_flops_per_token(config, seq_len)


def build(config, seed, seq_len):
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import models
    s = shapes(config)
    mx.random.seed(seed)
    net = models.EvaByteModel(
        s["vocab"], s["units"], s["hidden"], s["layers"],
        attention=dict(num_heads=s["heads"], window=s["window"],
                       chunk=s["chunk"],
                       rope_theta=float(config["rope_theta"])),
        num_pred_heads=s["pred_heads"], epsilon=config["rms_norm_eps"],
        remat_layers=True)
    net.initialize(mx.init.Xavier())
    for layer in net.layers:
        # Xavier leaves the MLP's output ten times the attention block's
        # (0.36 an element against 0.03: an attention output is a mean over
        # a thousand values) and a fault in the attention a tenth of what
        # it is; the configuration's `assumed.init` has the arithmetic
        down = layer.mlp.down.weight
        down.set_data(down.data() * config["init_down_scale"])
        for p, std in ((layer.attn.phi, config["init_phi_std"]),
                       (layer.attn.mu, config["init_mu_std"])):
            p.set_data(mx.nd.random.normal(0, std, p.shape))
    net.cast("bfloat16")
    view = models.FeaturesView(net)
    return {"model": net, "train_net": view,
            "loss": models.MultiByteLoss(net), "eval_net": view}


def reference_params(model):
    def w(param):
        return param.data()._data

    def layer(l):
        a, m = l.attn, l.mlp
        return {"norm1": w(l.norm1.gamma), "norm2": w(l.norm2.gamma),
                "q": w(a.query.weight), "k": w(a.key.weight),
                "v": w(a.value.weight), "o": w(a.proj.weight),
                "phi": w(a.phi), "mu": w(a.mu),
                "gate": w(m.gate.weight), "up": w(m.up.weight),
                "down": w(m.down.weight)}

    return {"tok_embed": w(model.tok_embed.weight),
            "layers": [layer(l) for l in model.layers],
            "norm_f": w(model.norm_f.gamma),
            "head": w(model.lm_head.weight)}
