"""solar-open2-250b -> models.SolarOpen2Model, through the public package,
as ONE CHIP'S SHARE of a TP 8 x EP 40 layout (the configuration's `cut`):
every mixer holds 1 / `mixer_shards` of its heads, every layer
`n_routed_experts` of the `reduced_from.n_routed_experts` the router
chooses among beside the whole shared expert, the vocabulary is the
`vocab_size`-row slice. The widths are the source's keys, untouched.

Xavier weights from the seed (stacked expert weights per expert, A_log and
dt_bias by Mamba's rules), bfloat16 but for A_log, dt_bias, the delta
rule's norm gain and the router's selection bias, which starts at zero and
is moved until the experts' loads are even, as a deployment's is
(`balance_routers`: the rule of builders/nemotron_h_lm.py at this router's
slope; the configuration's `assumed`), and goes on moving by the same rule
in every train step (`router_bias_rate` -> `MoELayer(bias_rate=)`); each layer recomputed in the
backward (`remat_layers`); trained as `FeaturesView(model)` +
`ChunkedUntiedLMLoss(model)` so the (S, V) logits never exist at once.
The forward that is compared with the reference's is `continuous_trunk`:
the same blocks with the routed sum left out (why: the reference's
docstring).

The arithmetic below counts what the algorithm requires of THIS chip, from
the configuration's keys alone, in integers.
"""
import functools
from fractions import Fraction

#: the chunk the delta rule's required work is counted at, whatever chunk
#: (or none) the program runs: a later kernel is read against the same work
NOMINAL_CHUNK = 64


def shapes(config):
    """What this chip holds of each kind of layer, from the keys."""
    shards = config["mixer_shards"]
    lin = config["linear_attn_config"]
    kda_heads = (lin["num_kv_heads"] or lin["num_heads"]) // shards
    return {
        "units": config["hidden_size"],
        "kda_heads": kda_heads, "kda_dim": lin["head_dim"],
        "kda_inner": kda_heads * lin["head_dim"],
        # kda_use_full_proj false: low-rank pairs of rank head_dim
        "kda_rank": lin["head_dim"],
        "conv": lin["short_conv_kernel_size"],
        "q_heads": config["num_attention_heads"] // shards,
        "kv_heads": max(1, config["num_key_value_heads"] // shards),
        "experts_routed": config["reduced_from"]["n_routed_experts"],
        "experts_held": config["n_routed_experts"],
        "shared": config["n_shared_experts"]
        * config["moe_intermediate_size"],
        "pattern": config["layer_pattern_run"],
    }


def matmul_params(config):
    """{`K`, `G`: weights in one mixer's matmuls, `experts`: in one
    layer's router and shared expert (every token visits all of these),
    `expert`: one routed expert's, `head`: the untied head's}."""
    s = shapes(config)
    u, d = s["units"], config["head_dim"]
    inner, r = s["kda_inner"], s["kda_rank"]
    return {
        "K": u * (3 * inner + 2 * r + s["kda_heads"]) + 2 * r * inner
        + inner * u,
        "G": 3 * u * s["q_heads"] * d + 2 * u * s["kv_heads"] * d,
        "experts": u * s["experts_routed"] + 3 * u * s["shared"],
        "expert": 3 * u * config["moe_intermediate_size"],
        "head": config["vocab_size"] * u,
    }


def parameter_count(config):
    """Every parameter this chip holds (840.8 M at the published widths)."""
    s, m = shapes(config), matmul_params(config)
    u = s["units"]
    extra = {"K": 3 * s["kda_inner"] * s["conv"] + s["kda_inner"]
             + s["kda_heads"] + s["kda_dim"],        # conv, dt_bias, A, gain
             "G": 0}
    layer = 2 * u + m["experts"] + s["experts_routed"] \
        + s["experts_held"] * m["expert"]            # norms, selection bias
    return sum(m[c] + extra[c] + layer for c in s["pattern"]) \
        + 2 * m["head"] + u


def delta_rule_forward_flops(chunk, dk, dv):
    """Operations of ONE forward pass of the chunked gated delta rule for
    one token of one head: the two (C, C) maps of decayed products
    (2 C d_k each), the triangular inverse (2 C^2 / 3), W and U from it
    (2 C d_k, 2 C d_v), W S, Q S and the state's update (2 d_k d_v each),
    P U' (2 C d_v). At C = 64, d = 128: 182 954."""
    return 4 * chunk * dk + 2 * chunk * chunk // 3 + 2 * chunk * (dk + dv) \
        + 6 * dk * dv + 2 * chunk * dv


def delta_rule_flops_per_token(config, passes=3):
    """Operations of the delta rule for one token, all `K` layers and held
    heads, at NOMINAL_CHUNK: ``passes`` = 3 is forward + backward (twice
    the forward), what the model requires; 4 adds the recomputed forward
    that the layers' recomputation makes the op run."""
    s = shapes(config)
    return passes * s["pattern"].count("K") * s["kda_heads"] \
        * delta_rule_forward_flops(NOMINAL_CHUNK, s["kda_dim"], s["kda_dim"])


def delta_rule_bytes_per_token(config):
    """Bytes the delta rule must move for one token, all `K` layers and
    held heads, forward + recomputed forward + backward: q, k, v in
    bfloat16, g in float32 (d each) and b in, o out (12 d + 4 a head a
    forward); the same in, do in and the five gradients out (22 d + 8 a
    backward). What the chunked form keeps between its own ops, the states
    among it, is not required."""
    s = shapes(config)
    d = s["kda_dim"]
    return s["pattern"].count("K") * s["kda_heads"] \
        * (2 * (12 * d + 4) + 22 * d + 8)


def held_expert_flops_per_token(config):
    """Forward + backward operations of the held experts' three matmuls
    for one token, all layers, at the EXPECTED number of live rows: a
    token's k choices fall on this chip's experts held / routed of the
    time. Dead rows of the static bound are not required."""
    s = shapes(config)
    visited = Fraction(config["num_experts_per_tok"] * s["experts_held"],
                       s["experts_routed"])
    flops = 6 * visited * matmul_params(config)["expert"] * len(s["pattern"])
    assert flops.denominator == 1
    return int(flops)


def attention_flops_per_token(config, seq_len):
    """Causal Q K^T and P V of the held query heads, the `G` layers: the
    streamed Pallas kernels'. The only term that grows with the length."""
    s = shapes(config)
    return s["pattern"].count("G") * 6 * seq_len \
        * s["q_heads"] * config["head_dim"]


def model_flops_per_token(config, seq_len):
    """Forward + backward operations the algorithm requires of this chip
    per trained token: 6 x the matmul weights a token visits (a token's
    held experts at their expected number), the delta rule, the causal
    scores."""
    s, m = shapes(config), matmul_params(config)
    return 6 * (sum(m[c] + m["experts"] for c in s["pattern"]) + m["head"]) \
        + held_expert_flops_per_token(config) \
        + delta_rule_flops_per_token(config) \
        + attention_flops_per_token(config, seq_len)


#: rounds of the balancing rule at build, and how far one round moves a
#: bias per unit of ln(even load / load): a fifth of a Newton step at the
#: 8th of 320 sigmoid scores of Xavier logits (std 1.36: the threshold
#: lies 1.96 std out, at s = 0.935, where the scores' density is 0.70 and
#: d ln(load) / d bias = 0.70 / 0.025 = 28; builders/nemotron_h_lm.py's
#: rule, whose 22nd of 512 has 17.5 there)
BALANCE_ROUNDS, BALANCE_STEP = 60, 0.2 / 28.0
#: batches the rule sees. Zipf(1) ids repeat their first id 770 times in
#: 8192, and layer 0's router sees little but the id: on ONE batch the
#: bias fits that batch's ids, and on fresh ones the 8 held experts of
#: four layers draw 6 060 to 7 310 rows a step by the seed; on eight,
#: 6 370 to 6 530 of an even router's 6 554, and sixteen read the same
#: (docs/PERF_SOLAR_OPEN2.md section 6)
BALANCE_BATCHES = 8


def balance_routers(net, tokens):
    """The selection bias as a deployment's is: moved until every expert
    is chosen about equally often (the source's family raises the bias of
    an expert that is chosen too rarely and lowers it where too often;
    here the step is proportional to ln(even load / load), BALANCE_ROUNDS
    times, on the rows of all of `tokens`' batches (n, S) at once, layer
    by layer as the forward pass reaches them).
    -> [(fewest, most) assignments an expert of the router's has on those
    batches, a layer]."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu import jit, nd

    @functools.partial(jax.jit, static_argnums=3)
    def settle(seen, gw, bias, k):
        scores = jax.nn.sigmoid(jnp.einsum(
            "td,ed->te", seen, gw, preferred_element_type=jnp.float32))
        target = scores.shape[0] * k / scores.shape[1]

        def loads(bias):
            _, idx = jax.lax.top_k(scores + bias, k)
            return jnp.bincount(idx.reshape(-1), length=scores.shape[1])

        def one(_, bias):
            return bias + BALANCE_STEP * jnp.log(
                target / jnp.maximum(loads(bias), 1.0))

        bias = jax.lax.fori_loop(0, BALANCE_ROUNDS, one, bias)
        return bias, loads(bias)

    # each block through its compiled forward (`jit.EvalStep`), as
    # builders/nemotron_h_lm.py's: eagerly a fresh checkout compiles
    # primitives for minutes. A batch at a time, so that the programs are
    # those of one batch and nothing larger than the step's is resident
    spread = []
    embed = jit.EvalStep(net.tok_embed)
    xs = [embed(nd.array(batch[None])) for batch in tokens]
    for layer in net.layers:
        mixer, norm1, norm2, experts = (jit.EvalStep(b) for b in (
            layer.mixer, layer.norm1, layer.norm2, layer.experts))
        xs = [x + mixer(norm1(x)) for x in xs]
        us = [norm2(x) for x in xs]
        moe = layer.experts.moe
        bias, loads = settle(
            jnp.concatenate([u._data.reshape(-1, u.shape[-1]) for u in us]),
            moe.gate_weight.data()._data, moe.router_bias.data()._data,
            moe.top_k)
        moe.router_bias.set_data(nd.NDArray(bias))
        spread.append((int(loads.min()), int(loads.max())))
        # a block with the rule hands out (y, the bias it would move to)
        ys = [experts(u) for u in us]
        xs = [x + (y[0] if isinstance(y, (tuple, list)) else y)
              for x, y in zip(xs, ys)]
    return spread


def build(config, seed, seq_len):
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import models
    s = shapes(config)
    lin = config["linear_attn_config"]
    mx.random.seed(seed)
    net = models.SolarOpen2Model(
        config["vocab_size"], s["units"], s["pattern"],
        delta=dict(num_heads=lin["num_kv_heads"] or lin["num_heads"],
                   head_dim=lin["head_dim"], conv_kernel=s["conv"],
                   rank=s["kda_rank"], chunk=config["delta_rule_chunk"],
                   shards=config["mixer_shards"],
                   neg_eigval=config["kda_allow_neg_eigval"]),
        attention=dict(num_heads=s["q_heads"], num_kv_heads=s["kv_heads"],
                       head_dim=config["head_dim"], attention="flash"),
        moe=dict(num_experts=s["experts_routed"],
                 ffn_hidden=config["moe_intermediate_size"],
                 top_k=config["num_experts_per_tok"],
                 shared_hidden=s["shared"],
                 scale=float(config["routed_scaling_factor"]),
                 norm_topk_prob=config["norm_topk_prob"],
                 held=(config["first_held_expert"], s["experts_held"]),
                 bias_rate=config["router_bias_rate"]),
        epsilon=config["rms_norm_eps"], remat_layers=True)
    net.initialize(mx.init.Xavier())
    # Xavier over (V, U) gives logits of std 0.54: the loss would be near
    # ln V whatever the features are, and the check of it would be blind
    head = net.lm_head.weight
    head.set_data(head.data() * config["init_head_scale"])
    net.cast("bfloat16")
    # batches of the traffic's law (Zipf(1) ids), on a stream of their own
    # from the seed: none that is trained on or checked
    rng = np.random.default_rng([seed, 38])
    weights = 1.0 / np.arange(1, config["vocab_size"] + 1)
    spread = balance_routers(net, rng.choice(
        config["vocab_size"], (BALANCE_BATCHES, seq_len),
        p=weights / weights.sum()).astype(np.int32))
    print("routers balanced at build: fewest and most of %d assignments an "
          "expert has, a layer: %s" % (
              BALANCE_BATCHES * seq_len * config["num_experts_per_tok"],
              spread), flush=True)
    return {"model": net, "train_net": models.FeaturesView(net),
            "loss": models.ChunkedUntiedLMLoss(net),
            "eval_net": continuous_trunk(net)}


def continuous_trunk(model):
    """tokens -> the final norm's output of `model`'s own blocks with the
    routed experts' sum left out of every layer: every mixer, the shared
    experts and the norms, which is what of the forward pass is continuous
    in its inputs and so can be held to the size of bfloat16's rounding.
    The reference's `forward` hands out the same (its docstring says
    why)."""
    from incubator_mxnet_tpu.gluon.block import HybridBlock

    class ContinuousTrunk(HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.model = model

        def forward(self, token_ids):
            x = model.tok_embed(token_ids)
            for layer in model.layers:
                x = x + layer.mixer(layer.norm1(x))
                x = x + layer.experts.shared(layer.norm2(x))
            return model.norm_f(x)

    return ContinuousTrunk()


def reference_params(model):
    def w(param):
        return param.data()._data

    def layer(l):
        m, e = l.mixer, l.experts
        if hasattr(m, "A_log"):
            own = {"in_proj": w(m.in_proj.weight), "conv_w": w(m.conv_weight),
                   "decay_up": w(m.decay_up), "gate_up": w(m.gate_up),
                   "A_log": w(m.A_log), "dt_bias": w(m.dt_bias),
                   "gate_norm": w(m.norm_gamma),
                   "out_proj": w(m.out_proj.weight)}
        else:
            own = {"q": w(m.query.weight), "k": w(m.key.weight),
                   "v": w(m.value.weight), "gate": w(m.gate.weight),
                   "o": w(m.proj.weight)}
        return dict(own, norm1=w(l.norm1.gamma), norm2=w(l.norm2.gamma),
                    router=w(e.moe.gate_weight),
                    router_bias=w(e.moe.router_bias),
                    w1=w(e.moe.w1), w2=w(e.moe.w2), w3=w(e.moe.w3),
                    shared_gate_up=w(e.shared.gate_up.weight),
                    shared_down=w(e.shared.down.weight))

    return {"tok_embed": w(model.tok_embed.weight),
            "layers": [layer(l) for l in model.layers],
            "norm_f": w(model.norm_f.gamma),
            "head": w(model.lm_head.weight)}
