"""keye-vl-2.0-30b-a3b -> models.KeyeVL2Model, through the public package,
as ONE CHIP'S SHARE of an EP 8 layout with data-parallel attention (the
configuration's `cut`): every layer holds `num_experts` of the
`reduced_from.num_experts` the router chooses among, attention and the
indexer whole (a rank attends its own sequences), the vocabulary is the
`vocab_size`-row slice. The widths are the source's keys, untouched.

Xavier weights from the seed (stacked expert weights per expert), the head
times `init_head_scale`, the embedding times sqrt(2 x num_hidden_layers x
hidden_size) (plain Xavier leaves every position's hidden state the same
vector to 97 % of its norm and a softmax router then sends every token to
the same eight experts), bfloat16; each layer recomputed in
the backward but for the selection and the attention's output
(`remat_layers`); trained as
`FeaturesView(model)` + `ChunkedUntiedLMLoss(model)`, which adds the layers'
own losses (the indexer's KL) that `features` hands out beside the hidden
states, so the (S, V) logits never exist at once. The forward that is
compared with the reference's is `continuous_trunk`: the same attention
blocks with the experts left out (why: the reference's docstring).

The arithmetic below counts what the algorithm requires of THIS chip, from
the configuration's keys alone, in integers.
"""


def shapes(config):
    sa = config["sa_config"]
    return {
        "units": config["hidden_size"], "layers": config["num_layers"],
        "q_heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "index_heads": sa["indexer_num_heads"],
        "index_dim": sa["indexer_head_dim"], "topk": sa["topk"],
        "experts_routed": config["reduced_from"]["num_experts"],
        "experts_held": config["num_experts"],
    }


def matmul_params(config):
    """{`attention`: q, k, v, o; `indexer`: its three maps; `router`;
    `expert`: one routed expert's three matrices; `head`}."""
    s = shapes(config)
    u, d = s["units"], s["head_dim"]
    return {
        "attention": 2 * u * s["q_heads"] * d + 2 * u * s["kv_heads"] * d,
        "indexer": u * (s["index_heads"] * s["index_dim"] + s["index_dim"]
                        + s["index_heads"]),
        "router": u * s["experts_routed"],
        "expert": 3 * u * config["moe_intermediate_size"],
        "head": config["vocab_size"] * u,
    }


def parameter_count(config):
    """Every parameter this chip holds (465 391 104 at the published
    widths): the matrices, two RMSNorm gains a layer, the two head norms'
    gains, the indexer's LayerNorm, the final norm."""
    s, m = shapes(config), matmul_params(config)
    layer = m["attention"] + m["indexer"] + m["router"] \
        + s["experts_held"] * m["expert"] + 2 * s["units"] \
        + 2 * s["head_dim"] + 2 * s["index_dim"]
    return s["layers"] * layer + 2 * m["head"] + s["units"]


def chosen_pairs(seq_len, topk):
    """(query, key) pairs one sequence attends over: query t chooses
    min(topk, t + 1) keys."""
    full = min(topk, seq_len)
    return full * (full + 1) // 2 + (seq_len - full) * topk


def sparse_attention_flops(config, seq_len, matmuls):
    """Operations of `matmuls` (H x d)-wide matmuls a chosen pair, one
    layer, one sequence: 2 forward (q k^T, a v), 4 backward (dV, dA, dq,
    dk). Those six are the work; scores computed again in a backward or a
    recomputed forward are a program's own cost and are not counted."""
    s = shapes(config)
    return matmuls * 2 * s["q_heads"] * s["head_dim"] \
        * chosen_pairs(seq_len, s["topk"])


def sparse_attention_bytes(config, seq_len):
    """Bytes one layer's attention over the chosen keys must move, forward
    + backward with nothing run twice, bfloat16: q in and o out, k and v
    in; q, o, dO, k, v in and dq, dk, dv out."""
    s = shapes(config)
    wide = seq_len * s["q_heads"] * s["head_dim"] * 2
    narrow = seq_len * s["kv_heads"] * s["head_dim"] * 2
    return 6 * wide + 6 * narrow


def held_expert_flops_per_token(config):
    """Forward + backward operations of the held experts for one token, all
    layers, at the EXPECTED number of visits: a token's k choices fall on
    this chip's experts held / routed of the time."""
    s = shapes(config)
    visits = config["num_experts_per_tok"] * s["experts_held"]
    return 6 * visits * matmul_params(config)["expert"] * s["layers"] \
        // s["experts_routed"]


#: the name `moe_expert_matmul_roofline` (perfbench/moe_shares.py) asks a
#: builder for: the experts this chip holds are all the experts it computes
expert_flops_per_token = held_expert_flops_per_token


def attention_flops_per_token(config, seq_len):
    """What grows with the length, forward + backward: the index scores,
    which every causal pair has (S / 2 a token, as every builder counts
    causal attention), all layers. The attention proper does NOT grow: a
    query attends to `topk` keys however long the sequence is."""
    s = shapes(config)
    return s["layers"] * 3 * 2 * s["index_heads"] * s["index_dim"] \
        * seq_len // 2


def model_flops_per_token(config, seq_len):
    """Forward + backward operations the algorithm requires of this chip
    per trained token: 6 x the matmul weights every token visits (4 x the
    indexer's: its input is detached, no gradient goes back through it),
    the held experts at their expected number, 6 matmuls over the `topk`
    keys a query chooses (its asymptote: the first `topk` queries of a
    sequence choose t + 1, 6 % fewer pairs at 16k, which
    `sparse_attention_flops` counts exactly for the roofline) and the index
    scores over the causal pairs."""
    s, m = shapes(config), matmul_params(config)
    return s["layers"] * (6 * (m["attention"] + m["router"])
                          + 4 * m["indexer"]
                          + 6 * 2 * s["q_heads"] * s["head_dim"] * s["topk"]) \
        + 6 * m["head"] + held_expert_flops_per_token(config) \
        + attention_flops_per_token(config, seq_len)


def router_readings(model):
    """tokens (1, S) -> (the mean over positions of every layer's router
    input (L, U) float32, the rows every expert of every layer's router is
    sent (L, E) int32), on text positions: what perfbench/probe_sparse.py
    reads a step (how much of a router's input is one vector, and whether a
    share's experts keep their rows)."""
    import jax.numpy as jnp
    from incubator_mxnet_tpu.gluon.block import HybridBlock
    from incubator_mxnet_tpu.ndarray import NDArray

    class RouterReadings(HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.model = model

        def forward(self, token_ids):
            positions = model.text_positions(token_ids)
            x = model.tok_embed(token_ids)
            means, loads = [], []
            for layer in model.layers:
                x, _ = layer.attend(x, positions)
                u = layer.norm2(x)
                moe = layer.moe
                rows = u._data.reshape(-1, u.shape[-1])
                idx = moe.route(rows, moe.gate_weight.data()._data)[3]
                means.append(rows.astype(jnp.float32).mean(0))
                loads.append(jnp.bincount(idx.reshape(-1),
                                          length=moe.num_experts))
                x = x + moe(u)
            return NDArray(jnp.stack(means)), NDArray(jnp.stack(loads))

    return RouterReadings()


def build(config, seed, seq_len):
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import models
    s = shapes(config)
    mx.random.seed(seed)
    net = models.KeyeVL2Model(
        config["vocab_size"], s["units"], s["layers"],
        attention=dict(
            num_heads=s["q_heads"], num_kv_heads=s["kv_heads"],
            head_dim=s["head_dim"], indexer_heads=s["index_heads"],
            indexer_dim=s["index_dim"], topk=s["topk"],
            rope_theta=float(config["rope_theta"]),
            mrope_section=config["rope_scaling"]["mrope_section"]),
        moe=dict(num_experts=s["experts_routed"],
                 ffn_hidden=config["moe_intermediate_size"],
                 top_k=config["num_experts_per_tok"],
                 norm_topk_prob=config["norm_topk_prob"],
                 held=(config["first_held_expert"], s["experts_held"])),
        epsilon=config["rms_norm_eps"], remat_layers=True)
    net.initialize(mx.init.Xavier())
    # Xavier over (V, U) gives logits of std 0.44: the loss would be near
    # ln V whatever the features are, and the check of it would be blind
    head = net.lm_head.weight
    head.set_data(head.data() * config["init_head_scale"])
    # Plain Xavier: the embedding is 0.0097 an element, an attention
    # block's output 0.15 and the same mean of values at every position
    # (the ids repeat), so from the second layer on a router's input is one
    # vector to 97 % of its norm and every token goes to the same eight
    # experts (40 to 58 of 128 had a row, most of a share's 16 none:
    # docs/PERF_KEYE_VL2.md section 3). A token's own row has to carry the
    # stream, as in a trained model. Gemma's embedding multiplier sqrt(U)
    # with GPT-2's (2 L)^-1/2 on the maps that write to the stream, L the
    # whole model's depth, does it; every block reads the stream through a
    # norm, so the same forward pass is the embedding alone times
    # sqrt(2 L U), and written so no weight is so small that Adam's fixed
    # step of 1e-4 is a twentieth of it.
    embed = net.tok_embed.weight
    embed.set_data(embed.data() * (
        2 * config["num_hidden_layers"] * s["units"]) ** 0.5)
    net.cast("bfloat16")
    return {"model": net, "train_net": models.FeaturesView(net),
            "loss": models.ChunkedUntiedLMLoss(net),
            "eval_net": continuous_trunk(net)}


def continuous_trunk(model):
    """tokens -> the final norm's output of `model`'s own attention blocks
    with the experts left out of every layer, on text positions: what of
    the forward pass a bfloat16 rounding moves by a rounding (a key at the
    2048th place carries 1 / 2048 of a row's weight; a flipped expert a
    whole expert's output). The reference's `forward` hands out the
    same."""
    from incubator_mxnet_tpu.gluon.block import HybridBlock

    class ContinuousTrunk(HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.model = model

        def forward(self, token_ids):
            positions = model.text_positions(token_ids)
            x = model.tok_embed(token_ids)
            for layer in model.layers:
                x, _ = layer.attend(x, positions)
            return model.norm_f(x)

    return ContinuousTrunk()


def reference_params(model):
    def w(param):
        return param.data()._data

    def layer(l):
        a, e = l.attn, l.moe
        return {"norm1": w(l.norm1.gamma), "norm2": w(l.norm2.gamma),
                "q": w(a.query.weight), "k": w(a.key.weight),
                "v": w(a.value.weight), "o": w(a.proj.weight),
                "q_norm": w(a.q_norm.gamma), "k_norm": w(a.k_norm.gamma),
                "iq": w(a.index_q.weight), "ik": w(a.index_k.weight),
                "iw": w(a.index_w.weight),
                "ik_ln_g": w(a.index_k_norm.gamma),
                "ik_ln_b": w(a.index_k_norm.beta),
                "router": w(e.gate_weight),
                "w1": w(e.w1), "w2": w(e.w2), "w3": w(e.w3)}

    return {"tok_embed": w(model.tok_embed.weight),
            "layers": [layer(l) for l in model.layers],
            "norm_f": w(model.norm_f.gamma),
            "head": w(model.lm_head.weight)}
