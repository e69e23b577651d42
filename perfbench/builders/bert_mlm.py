"""bert-large-uncased -> models.BERTModel, through the public package.

Built as bench.py's bench_transformer builds it: Xavier weights from the
seed, cast to bfloat16, `attention="flash"` (which routes itself to the XLA
composite at D = 64, S = 512 — ops.attention.flash_attention_supported),
`gluon.loss.SoftmaxCrossEntropyLoss` on the MLM logits of every position.
"""
import flops      # perfbench/flops.py: run.py's own directory is on sys.path


def model_flops_per_token(config, seq_len):
    """Forward + backward operations the algorithm requires per trained
    token: the encoder, `mlm_dense` (U x U) and the untied decoder (V x U)
    at every position."""
    u = config["hidden_size"]
    return flops.transformer_train_flops_per_token(
        u, config["intermediate_size"], config["num_hidden_layers"],
        u * u + config["vocab_size"] * u, seq_len, causal=False)


def attention_flops_per_token(config, seq_len):
    """The part of that in Q K^T and P V, all layers."""
    return config["num_hidden_layers"] * \
        flops.attention_train_flops_per_token(
            config["hidden_size"], seq_len, causal=False)


def build(config, seed, seq_len):
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, models
    if seq_len > config["max_position_embeddings"]:
        raise ValueError("sequence %d exceeds the configuration's %d positions"
                         % (seq_len, config["max_position_embeddings"]))
    mx.random.seed(seed)
    net = models.BERTModel(
        vocab_size=config["vocab_size"], units=config["hidden_size"],
        hidden_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        max_length=config["max_position_embeddings"],
        dropout=config["hidden_dropout_prob"], attention="flash")
    net.initialize(mx.init.Xavier())
    net.cast("bfloat16")
    # train: net(tokens) -> logits, loss(logits, labels); check: the logits
    return {"model": net, "train_net": net,
            "loss": gluon.loss.SoftmaxCrossEntropyLoss(), "eval_net": net}


def _dense(layer):
    return {"w": layer.weight.data()._data, "b": layer.bias.data()._data}


def _ln(layer):
    return {"g": layer.gamma.data()._data, "b": layer.beta.data()._data}


def reference_params(model):
    """The live parameters as the plain nested dict reference/ takes
    (device arrays in the served type; the reference casts)."""
    enc = model.encoder
    return {
        "word_embed": model.word_embed.weight.data()._data,
        "position": enc.position_weight.data()._data,
        "embed_ln": _ln(model.embed_ln),
        "layers": [{
            "q": _dense(l.attention_cell.query),
            "k": _dense(l.attention_cell.key),
            "v": _dense(l.attention_cell.value),
            "o": _dense(l.attention_cell.proj),
            "ln1": _ln(l.ln1), "ffn1": _dense(l.ffn1),
            "ffn2": _dense(l.ffn2), "ln2": _ln(l.ln2)} for l in enc.layers],
        "mlm_dense": _dense(model.mlm_dense), "mlm_ln": _ln(model.mlm_ln),
        "mlm_decoder": _dense(model.mlm_decoder),
    }
