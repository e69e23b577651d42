"""olmoe-1b-7b-0125 -> models.OLMoEModel, through the public package.

Xavier weights from the seed (the stacked expert weights per expert:
`parallel.moe._StackedXavier`), bfloat16, causal `attention="flash"` (the
streamed Pallas kernels at D = 128), trained as `FeaturesView(model)` +
`ChunkedUntiedLMLoss(model)` so the (S, 50304) logits never exist at once.
The untied head is scaled after Xavier (the configuration's `assumed`).
The configuration's keys are the source's own (`hidden_size`,
`intermediate_size`, ...); the depth that is run is `num_layers`.
"""
import flops      # perfbench/flops.py: run.py's own directory is on sys.path


def expert_flops_per_token(config):
    """Forward + backward operations of the experts' three matmuls for one
    token, all layers: each of its k experts multiplies it by a gate, an up
    and a down matrix of hidden_size x intermediate_size. What the grouped
    matmul is there for (`moe_expert_matmul_roofline`)."""
    return 6 * config["num_layers"] * config["num_experts_per_tok"] * 3 \
        * config["hidden_size"] * config["intermediate_size"]


def attention_flops_per_token(config, seq_len):
    """Causal Q K^T and P V, all layers: the streamed Pallas kernels'."""
    return config["num_layers"] * flops.attention_train_flops_per_token(
        config["hidden_size"], seq_len, causal=True)


def model_flops_per_token(config, seq_len):
    """Forward + backward operations the algorithm requires per trained
    token: four U x U projections, the router, the k experts a token
    visits (not the 64 it could), and the untied V x U head."""
    u = config["hidden_size"]
    per_layer = 4 * u * u + config["num_experts"] * u
    return 6 * (config["num_layers"] * per_layer
                + config["vocab_size"] * u) \
        + expert_flops_per_token(config) \
        + attention_flops_per_token(config, seq_len)


def build(config, seed, seq_len):
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import models
    mx.random.seed(seed)
    net = models.OLMoEModel(
        vocab_size=config["vocab_size"], units=config["hidden_size"],
        ffn_hidden=config["intermediate_size"],
        num_layers=config["num_layers"],
        num_heads=config["num_attention_heads"],
        num_experts=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        rope_theta=float(config["rope_theta"]),
        epsilon=config["rms_norm_eps"],
        max_length=max(seq_len, config["max_position_embeddings"]),
        attention="flash")
    net.initialize(mx.init.Xavier())
    # Xavier over (V, U) gives logits of std 0.28: the loss would be ln V
    # whatever the features are, and the reference check would check nothing
    head = net.lm_head.weight
    head.set_data(head.data() * config["init_head_scale"])
    net.cast("bfloat16")
    view = models.FeaturesView(net)
    return {"model": net, "train_net": view,
            "loss": models.ChunkedUntiedLMLoss(net), "eval_net": view}


def reference_params(model):
    def w(param):
        return param.data()._data

    return {
        "tok_embed": w(model.tok_embed.weight),
        "layers": [{
            "n1": w(l.ln1.gamma), "n2": w(l.ln2.gamma),
            "q": w(l.attn.query.weight), "k": w(l.attn.key.weight),
            "v": w(l.attn.value.weight), "o": w(l.attn.proj.weight),
            "q_norm": w(l.attn.q_norm.gamma),
            "k_norm": w(l.attn.k_norm.gamma),
            "router": w(l.moe.gate_weight), "gate": w(l.moe.w1),
            "up": w(l.moe.w3), "down": w(l.moe.w2)}
            for l in model.layers],
        "norm_f": w(model.norm_f.gamma),
        "head": w(model.lm_head.weight),
    }
