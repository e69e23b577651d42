"""ling-3.0-flash -> models.Ling3Model, through the public package, as ONE
EP 64 RANK'S SHARE of one pipeline stage (the configuration's `cut`): every
mixer whole, every expert layer `num_experts` of the
`reduced_from.num_experts` the router chooses among (under the published
group limit) beside the whole shared expert, the vocabulary the
`vocab_size`-row slice, one leading dense layer. The widths are the
source's keys, untouched.

Xavier weights from the seed (stacked expert weights per expert, A_log and
dt_bias by Mamba's rules), bfloat16 but for A_log, dt_bias, the delta
rule's norm gain, the latent attention's two head gains and the router's
selection bias, which starts at zero, is moved until the experts' loads
are even (`balance_routers`: builders/solar_open2_lm.py's rule at this
router's slope, the loads counted under the group limit) and goes on moving
by the same rule in every train step (`router_bias_rate`); each layer
recomputed in the backward but for what its kernels wrote; trained as
`FeaturesView(model)` + `ChunkedUntiedLMLoss(model)`. The forward that is
compared with the reference's is `continuous_trunk` (why: the reference's
docstring). On a TPU `build` refuses a program whose delta rule traced as
XLA ops or whose latent attention took the composite: the timed step is
the kernels' or nothing. The counters are read across `balance_routers`,
which runs every block's compiled forward on the step's own shapes (1 x
seq_len); `ops.delta_rule` and `ops.attention.attention_route` choose by
shape, type and platform alone, so what a block took there is what it
takes in the train step's forward, recomputation and backward
(tests/test_ling3.py holds the two counts to the same labels).

The arithmetic below counts what the algorithm requires of THIS chip, from
the configuration's keys alone, in integers.
"""
import functools
from fractions import Fraction

#: the chunk the delta rule's required work is counted at, whatever chunk
#: the program runs (builders/solar_open2_lm.py's)
NOMINAL_CHUNK = 64


def shapes(config):
    """What this chip holds of each kind of layer, from the keys."""
    # num_kv_heads_for_linear_attn 0: as many as the query heads
    kda_heads = config["num_kv_heads_for_linear_attn"] \
        or config["num_attention_heads"]
    return {
        "units": config["hidden_size"],
        "kda_heads": kda_heads, "kda_dim": config["head_dim"],
        "kda_inner": kda_heads * config["head_dim"],
        "conv": config["short_conv_kernel_size"],
        "heads": config["num_attention_heads"],
        "latent": config["kv_lora_rank"],
        "nope": config["qk_nope_head_dim"], "rope": config["qk_rope_head_dim"],
        "value": config["v_head_dim"],
        "experts_routed": config["reduced_from"]["num_experts"],
        "experts_held": config["num_experts"],
        "shared": config["num_shared_experts"]
        * config["moe_shared_expert_intermediate_size"],
        "dense": config["intermediate_size"],
        "dense_layers": config["first_k_dense_replace"],
        "pattern": config["layer_pattern_run"],
    }


def matmul_params(config):
    """{`K`, `M`: weights in one mixer's matmuls, `dense`: in a leading
    layer's SwiGLU, `experts`: in one expert layer's router and shared
    expert (every token visits all of these), `expert`: one routed
    expert's, `head`: the untied head's}."""
    s = shapes(config)
    u, inner, h = s["units"], s["kda_inner"], s["heads"]
    return {
        # q, k, v, the decay's and the gate's full-rank maps, b; out
        "K": u * (5 * inner + s["kda_heads"]) + inner * u,
        # W_q, W_kva, W_kvb, W_o, the head gate
        "M": u * h * (s["nope"] + s["rope"]) + u * (s["latent"] + s["rope"])
        + s["latent"] * h * (s["nope"] + s["value"]) + h * s["value"] * u
        + u * h,
        "dense": 3 * u * s["dense"],
        "experts": u * s["experts_routed"] + 3 * u * s["shared"],
        "expert": 3 * u * config["moe_intermediate_size"],
        "head": config["vocab_size"] * u,
    }


def _ffn_of(s, i):
    return "dense" if i < s["dense_layers"] else "experts"


def parameter_count(config):
    """Every parameter this chip holds (767.0 M at the published widths)."""
    s, m = shapes(config), matmul_params(config)
    u = s["units"]
    extra = {"K": 3 * s["kda_inner"] * s["conv"] + s["kda_inner"]
             + s["kda_heads"] + s["kda_dim"],        # conv, dt_bias, A, gain
             "M": s["latent"] + 2 * s["nope"]}       # three norms' gains
    ffn = {"dense": m["dense"],
           "experts": m["experts"] + s["experts_routed"]     # selection bias
           + s["experts_held"] * m["expert"]}
    return sum(m[c] + extra[c] + 2 * u + ffn[_ffn_of(s, i)]
               for i, c in enumerate(s["pattern"])) + 2 * m["head"] + u


def always_visited_params(config):
    """The matmul weights every token visits (480.5 M): the mixers' maps,
    the dense FFN, the routers and shared experts, the head."""
    s, m = shapes(config), matmul_params(config)
    return sum(m[c] + m[_ffn_of(s, i)]
               for i, c in enumerate(s["pattern"])) + m["head"]


def delta_rule_forward_flops(chunk, dk, dv):
    """builders/solar_open2_lm.py's count of one forward pass of the
    chunked gated delta rule for one token of one head."""
    return 4 * chunk * dk + 2 * chunk * chunk // 3 + 2 * chunk * (dk + dv) \
        + 6 * dk * dv + 2 * chunk * dv


def delta_rule_flops_per_token(config, passes=3):
    """Operations of the delta rule for one token, all `K` layers and
    heads, at NOMINAL_CHUNK: ``passes`` = 3 is forward + backward, what the
    model requires (and what the step runs: a recomputed layer keeps the
    forward kernel's outputs by name); 4 adds a recomputed forward."""
    s = shapes(config)
    return passes * s["pattern"].count("K") * s["kda_heads"] \
        * delta_rule_forward_flops(NOMINAL_CHUNK, s["kda_dim"], s["kda_dim"])


def delta_rule_bytes_per_token(config):
    """Bytes the delta rule must move for one token, all `K` layers and
    heads, as builders/solar_open2_lm.py counts them (two forwards and a
    backward: the accepted `delta_rule_roofline` divides by these)."""
    s = shapes(config)
    d = s["kda_dim"]
    return s["pattern"].count("K") * s["kda_heads"] \
        * (2 * (12 * d + 4) + 22 * d + 8)


def latent_attention_flops_per_token(config, seq_len, passes=3):
    """Causal Q K^T (nope + rope wide) and P V (value wide) of the `M`
    layers' heads for one token: the forward is 2 (192 + 128) S / 2 a head
    (83.9 MFLOP at 8192); ``passes`` = 3 adds the backward's four
    gradient matmuls, what the model requires; 3.5 its recomputation of
    the scores as well. Padded lanes are no work."""
    s = shapes(config)
    flops = Fraction(passes) * s["pattern"].count("M") * s["heads"] \
        * (s["nope"] + s["rope"] + s["value"]) * seq_len
    assert flops.denominator == 1
    return int(flops)


def latent_attention_bytes_per_token(config):
    """Bytes the `M` layers' attention must move for one token in
    bfloat16: q, k, v in and o out (the forward), the same four and dO in
    and dQ, dK, dV out (the backward), a float32 log-sum-exp a head each
    way."""
    s = shapes(config)
    qk, dv = s["nope"] + s["rope"], s["value"]
    forward = 2 * (2 * qk + 2 * dv) + 4
    backward = 2 * (2 * qk + 3 * dv) + 2 * (2 * qk + dv) + 4
    return s["pattern"].count("M") * s["heads"] * (forward + backward)


def held_expert_flops_per_token(config):
    """Forward + backward operations of the held experts' three matmuls
    for one token, all expert layers, at the EXPECTED number of live rows."""
    s = shapes(config)
    visited = Fraction(config["num_experts_per_tok"] * s["experts_held"],
                       s["experts_routed"])
    flops = 6 * visited * matmul_params(config)["expert"] \
        * (len(s["pattern"]) - s["dense_layers"])
    assert flops.denominator == 1
    return int(flops)


def attention_flops_per_token(config, seq_len):
    """What the driver and `flash_roofline`'s kin call the attention's
    required operations: the latent attention's, forward + backward. The
    only term that grows with the length."""
    return latent_attention_flops_per_token(config, seq_len)


def model_flops_per_token(config, seq_len):
    """Forward + backward operations the algorithm requires of this chip
    per trained token: 6 x the matmul weights a token visits (a token's
    held experts at their expected number), the delta rule, the causal
    scores."""
    return 6 * always_visited_params(config) \
        + held_expert_flops_per_token(config) \
        + delta_rule_flops_per_token(config) \
        + attention_flops_per_token(config, seq_len)


#: rounds of the balancing rule at build, and how far one round moves a
#: bias per unit of ln(even load / load): a fifth of a Newton step at the
#: 8th of 512 sigmoid scores of Xavier logits (std 1.29: the threshold lies
#: 2.15 std out, at s = 0.942, where the scores' density is 0.56 and
#: d ln(load) / d bias = 0.56 / 0.0156 = 36; builders/solar_open2_lm.py's
#: rule, whose 8th of 320 has 28 there)
BALANCE_ROUNDS, BALANCE_STEP = 60, 0.2 / 36.0
#: batches the rule sees (builders/solar_open2_lm.py: on one batch the
#: bias fits that batch's ids)
BALANCE_BATCHES = 8


def balance_routers(net, tokens):
    """The selection bias as a deployment's is: moved until every expert
    is chosen about equally often UNDER THE GROUP LIMIT (the loads are
    `MoELayer.choose`'s), BALANCE_ROUNDS damped rounds on the rows of all
    of `tokens`' batches (n, S) at once, layer by layer as the forward pass
    reaches them. -> [(fewest, most) assignments an expert of the router's
    has on those batches, an expert layer]."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu import jit, nd

    def settle(moe, seen, gw, bias):
        scores = jax.nn.sigmoid(jnp.einsum(
            "td,ed->te", seen, gw, preferred_element_type=jnp.float32))
        target = scores.shape[0] * moe.top_k / scores.shape[1]

        def loads(bias):
            return jnp.bincount(moe.choose(scores, bias).reshape(-1),
                                length=scores.shape[1])

        def one(_, bias):
            return bias + BALANCE_STEP * jnp.log(
                target / jnp.maximum(loads(bias), 1.0))

        bias = jax.lax.fori_loop(0, BALANCE_ROUNDS, one, bias)
        return bias, loads(bias)

    # each block through its compiled forward (`jit.EvalStep`), a batch at
    # a time: nothing larger than the step's own is resident
    spread = []
    embed = jit.EvalStep(net.tok_embed)
    xs = [embed(nd.array(batch[None])) for batch in tokens]
    for layer in net.layers:
        mixer, norm1, norm2, ffn = (jit.EvalStep(b) for b in (
            layer.mixer, layer.norm1, layer.norm2, layer.experts))
        xs = [x + mixer(norm1(x)) for x in xs]
        us = [norm2(x) for x in xs]
        moe = getattr(layer.experts, "moe", None)
        if moe is not None:
            bias, loads = jax.jit(functools.partial(settle, moe))(
                jnp.concatenate([u._data.reshape(-1, u.shape[-1])
                                 for u in us]),
                moe.gate_weight.data()._data, moe.router_bias.data()._data)
            moe.router_bias.set_data(nd.NDArray(bias))
            spread.append((int(loads.min()), int(loads.max())))
        # a block with the rule hands out (y, the bias it would move to)
        ys = [ffn(u) for u in us]
        xs = [x + (y[0] if isinstance(y, (tuple, list)) else y)
              for x, y in zip(xs, ys)]
    return spread


def _slow_routes():
    """What the program's counters say of the two slow forms so far."""
    from incubator_mxnet_tpu import telemetry
    return (telemetry.REGISTRY.get("mxtpu_delta_rule_total").value(path="xla"),
            telemetry.REGISTRY.get("mxtpu_latent_attention_total").value(
                route="composite"))


def build(config, seed, seq_len):
    import jax
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import models
    s = shapes(config)
    mx.random.seed(seed)
    net = models.Ling3Model(
        config["vocab_size"], s["units"], s["pattern"],
        delta=dict(num_heads=s["kda_heads"], head_dim=s["kda_dim"],
                   conv_kernel=s["conv"],
                   rank="full" if config["no_kda_lora"] else None,
                   chunk=config["delta_rule_chunk"],
                   decay=("bounded", float(config["kda_lower_bound"]))
                   if config["kda_safe_gate"] else "softplus",
                   neg_eigval=False),
        latent=dict(num_heads=s["heads"], latent=s["latent"],
                    nope_dim=s["nope"], rope_dim=s["rope"],
                    v_dim=s["value"],
                    rope_theta=float(config["rope_theta"])),
        moe=dict(num_experts=s["experts_routed"],
                 ffn_hidden=config["moe_intermediate_size"],
                 top_k=config["num_experts_per_tok"],
                 shared_hidden=s["shared"],
                 scale=float(config["routed_scaling_factor"]),
                 norm_topk_prob=config["norm_topk_prob"],
                 held=(config["first_held_expert"], s["experts_held"]),
                 bias_rate=config["router_bias_rate"],
                 n_group=config["n_group"], topk_group=config["topk_group"]),
        dense_hidden=s["dense"], dense_layers=s["dense_layers"],
        epsilon=config["rms_norm_eps"], remat_layers=True)
    net.initialize(mx.init.Xavier())
    # Xavier over (V, U) gives logits too flat for the loss to depend on
    # the features (builders/solar_open2_lm.py; the configuration's
    # `assumed` says why the factor is 2 here)
    head = net.lm_head.weight
    head.set_data(head.data() * config["init_head_scale"])
    net.cast("bfloat16")
    # batches of the traffic's law (Zipf(1) ids), on a stream of their own
    # from the seed: none that is trained on or checked
    rng = np.random.default_rng([seed, 48])
    weights = 1.0 / np.arange(1, config["vocab_size"] + 1)
    slow_before = _slow_routes()
    spread = balance_routers(net, rng.choice(
        config["vocab_size"], (BALANCE_BATCHES, seq_len),
        p=weights / weights.sum()).astype(np.int32))
    print("routers balanced at build: fewest and most of %d assignments an "
          "expert has, an expert layer: %s" % (
              BALANCE_BATCHES * seq_len * config["num_experts_per_tok"],
              spread), flush=True)
    xla_rules, composites = (now - before for now, before in zip(
        _slow_routes(), slow_before))
    if jax.devices()[0].platform == "tpu" and (xla_rules or composites):
        raise SystemExit(
            "ling3_lm: on a TPU %d delta rule(s) traced as XLA ops and %d "
            "latent attention(s) took the composite: the step that would "
            "be timed is not the kernels'" % (xla_rules, composites))
    return {"model": net, "train_net": models.FeaturesView(net),
            "loss": models.ChunkedUntiedLMLoss(net),
            "eval_net": continuous_trunk(net)}


def continuous_trunk(model):
    """tokens -> what the reference's `compared` hands out, (B, S, 3 U)
    float32, side by side on the channel axis:

    the final norm's output of `model`'s own blocks with the routed
    experts' sum left out of every layer: every mixer, the dense FFN, the
    shared experts and the norms (builders/solar_open2_lm.py says why);

    the last `M` mixer's own output and the last `K` mixer's, each ALONE ON
    THE EMBEDDINGS (the mixer of its layer's first norm of the embedded
    tokens, an input both sides have to the bit), every position scaled to
    unit RMS, which the final norm's output has by its gain of 1. In the
    stream a mixer's error lies under what the layers before it left there
    (2.9 % of the stream) and the M layer adds a thousandth of the stream;
    alone, its output is its own."""
    import jax.numpy as jnp
    from incubator_mxnet_tpu.gluon.block import HybridBlock
    from incubator_mxnet_tpu.ndarray import _apply

    last = {"K" if hasattr(layer.mixer, "A_log") else "M": layer
            for layer in model.layers}

    def side_by_side(trunk, *alone):
        def unit(t):
            t = t.astype(jnp.float32)
            return t / jnp.sqrt(jnp.mean(t * t, -1, keepdims=True))

        return jnp.concatenate([trunk.astype(jnp.float32)]
                               + [unit(t) for t in alone], -1)

    class ContinuousTrunk(HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.model = model

        def forward(self, token_ids):
            embedded = x = model.tok_embed(token_ids)
            for layer in model.layers:
                x = x + layer.mixer(layer.norm1(x))
                ffn = getattr(layer.experts, "shared", layer.experts)
                x = x + ffn(layer.norm2(x))
            return _apply(side_by_side, model.norm_f(x), *(
                last[c].mixer(last[c].norm1(embedded)) for c in "MK"))

    return ContinuousTrunk()


def reference_params(model):
    def w(param):
        return param.data()._data

    def layer(l):
        m, e = l.mixer, l.experts
        if hasattr(m, "A_log"):
            own = {"in_proj": w(m.in_proj.weight), "conv_w": w(m.conv_weight),
                   "A_log": w(m.A_log), "dt_bias": w(m.dt_bias),
                   "gate_norm": w(m.norm_gamma),
                   "out_proj": w(m.out_proj.weight)}
        else:
            own = {"q": w(m.query.weight), "kv_down": w(m.kv_down.weight),
                   "kv_norm": w(m.kv_norm.gamma), "kv_up": w(m.kv_up.weight),
                   "q_gain": w(m.q_gain), "k_gain": w(m.k_gain),
                   "gate": w(m.gate.weight), "o": w(m.proj.weight)}
        own.update(norm1=w(l.norm1.gamma), norm2=w(l.norm2.gamma))
        if not hasattr(e, "moe"):
            return dict(own, dense_gate_up=w(e.gate_up.weight),
                        dense_down=w(e.down.weight))
        return dict(own, router=w(e.moe.gate_weight),
                    router_bias=w(e.moe.router_bias),
                    w1=w(e.moe.w1), w2=w(e.moe.w2), w3=w(e.moe.w3),
                    shared_gate_up=w(e.shared.gate_up.weight),
                    shared_down=w(e.shared.down.weight))

    return {"tok_embed": w(model.tok_embed.weight),
            "layers": [layer(l) for l in model.layers],
            "norm_f": w(model.norm_f.gamma),
            "head": w(model.lm_head.weight)}
