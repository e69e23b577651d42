"""cerebras-gpt-1.3b -> models.GPTModel, through the public package.

Built as bench.py's bench_long_context builds it: Xavier weights from the
seed, bfloat16, causal `attention="flash"` (the three Pallas kernels at
D = 128), trained as `FeaturesView(gpt)` + `ChunkedLMLoss(gpt)` so the
(S, 50257) logits never exist at once. The position table is as long as
the cell's sequence, and the tied embedding is scaled after Xavier (both
are the configuration's `assumed`).
"""
import flops      # perfbench/flops.py: run.py's own directory is on sys.path


def model_flops_per_token(config, seq_len):
    """Forward + backward operations the algorithm requires per trained
    token: the blocks and the tied V x U head at every position."""
    u = config["n_embd"]
    return flops.transformer_train_flops_per_token(
        u, config["n_inner"], config["n_layer"], config["vocab_size"] * u,
        seq_len, causal=True)


def attention_flops_per_token(config, seq_len):
    """The part of that in causal Q K^T and P V, all layers: what the
    Pallas kernels are there for."""
    return config["n_layer"] * flops.attention_train_flops_per_token(
        config["n_embd"], seq_len, causal=True)


def build(config, seed, seq_len):
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import models
    mx.random.seed(seed)
    net = models.GPTModel(
        vocab_size=config["vocab_size"], units=config["n_embd"],
        hidden_size=config["n_inner"], num_layers=config["n_layer"],
        num_heads=config["n_head"],
        max_length=max(seq_len, config["n_positions"]), attention="flash")
    net.initialize(mx.init.Xavier())
    # Xavier over (V, U) gives logits of std 0.28: the loss would be ln V
    # whatever the features are, and the reference check would check nothing
    embed = net.tok_embed.weight
    embed.set_data(embed.data() * config["init_tok_embed_scale"])
    net.cast("bfloat16")
    view = models.FeaturesView(net)
    # train: view(tokens) -> ln_f output, ChunkedLMLoss applies the tied
    # head; check: the ln_f output itself
    return {"model": net, "train_net": view,
            "loss": models.ChunkedLMLoss(net), "eval_net": view}


def _dense(layer):
    return {"w": layer.weight.data()._data, "b": layer.bias.data()._data}


def _ln(layer):
    return {"g": layer.gamma.data()._data, "b": layer.beta.data()._data}


def reference_params(model):
    return {
        "tok_embed": model.tok_embed.weight.data()._data,
        "pos_embed": model.pos_embed.weight.data()._data,
        "layers": [{
            "ln1": _ln(l.ln1), "q": _dense(l.attn.query),
            "k": _dense(l.attn.key), "v": _dense(l.attn.value),
            "o": _dense(l.attn.proj), "ln2": _ln(l.ln2),
            "fc1": _dense(l.fc1), "fc2": _dense(l.fc2)}
            for l in model.layers],
        "ln_f": _ln(model.ln_f),
    }
