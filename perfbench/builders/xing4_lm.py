"""xing4.0-29b-a4b -> models.Xing4Model, through the public package, as ONE
EP 8 RANK'S SHARE of one pipeline stage (the configuration's `cut`): every
mixer whole, every expert layer `n_routed_experts` of the
`reduced_from.n_routed_experts` the router chooses among beside the whole
shared expert, the vocabulary the `vocab_size`-row slice, one leading dense
layer, the residual path its published `hc_mult` streams wide. The widths
are the source's keys, untouched.

Xavier weights from the seed (stacked expert weights per expert), the
embedding and every block's last map scaled so that a token's own row
carries its streams (`init_stream`), the hyper-connections' maps by the
configuration's `hc_init`; bfloat16 but for
the hyper-connections' P, b and a, the two latent norms' gains and the
router's selection bias, which starts at zero, is moved until the experts'
loads are even (`balance_routers`: each bias to the quantile that gives its
expert the even load) and goes on moving by the Solar and Ling cells' rule
in every train step (`router_bias_rate`); each layer recomputed in the backward but for what
its kernels wrote; trained as `FeaturesView(model)` +
`ChunkedUntiedLMLoss(model)`. The forward that is compared with the
reference's is `continuous_trunk` (why: the reference's docstring). On a
TPU `build` refuses a program whose latent attention took the composite:
the timed step is the kernels' or nothing. The counter is read across
`balance_routers`, which runs every block's compiled forward on the step's
own shapes (1 x seq_len); `ops.attention.attention_route` chooses by shape,
type and platform alone, so what a block took there is what it takes in the
train step's forward and backward (tests/test_xing4.py holds the two counts
to the same labels).

The arithmetic below counts what the algorithm requires of THIS chip, from
the configuration's keys alone, in integers.
"""
import functools
from fractions import Fraction


def shapes(config):
    """What this chip holds of each kind of layer, from the keys."""
    return {
        "units": config["hidden_size"],
        "streams": config["hc_mult"],
        "layers": config["num_layers"],
        "heads": config["num_attention_heads"],
        "q_latent": config["q_lora_rank"],
        "latent": config["kv_lora_rank"],
        "nope": config["qk_nope_head_dim"], "rope": config["qk_rope_head_dim"],
        "value": config["v_head_dim"],
        "experts_routed": config["reduced_from"]["n_routed_experts"],
        "experts_held": config["n_routed_experts"],
        "shared": config["n_shared_experts"] * config["moe_intermediate_size"],
        "dense": config["intermediate_size"],
        "dense_layers": config["first_k_dense_replace"],
    }


def matmul_params(config):
    """{`mla`: weights in one mixer's five maps, `hyper`: in one
    hyper-connection's P, `dense`: in a leading layer's SwiGLU, `experts`:
    in one expert layer's router and shared expert (every token visits all
    of these), `expert`: one routed expert's, `head`: the untied head's}."""
    s = shapes(config)
    u, h, n = s["units"], s["heads"], s["streams"]
    return {
        # W_qa, W_qb, W_kva, W_kvb, W_o
        "mla": u * s["q_latent"] + s["q_latent"] * h * (s["nope"] + s["rope"])
        + u * (s["latent"] + s["rope"])
        + s["latent"] * h * (s["nope"] + s["value"]) + h * s["value"] * u,
        "hyper": n * u * (2 * n + n * n),
        "dense": 3 * u * s["dense"],
        "experts": u * s["experts_routed"] + 3 * u * s["shared"],
        "expert": 3 * u * config["moe_intermediate_size"],
        "head": config["vocab_size"] * u,
    }


def _ffn_of(s, i):
    return "dense" if i < s["dense_layers"] else "experts"


def parameter_count(config):
    """Every parameter this chip holds (759.3 M at the published widths)."""
    s, m = shapes(config), matmul_params(config)
    u, n = s["units"], s["streams"]
    maps = 2 * n + n * n
    # a layer's mixer with its two latent norms' gains, two
    # hyper-connections (P, b, three a), two norms
    layer = m["mla"] + s["q_latent"] + s["latent"] \
        + 2 * (m["hyper"] + maps + 3) + 2 * u
    ffn = {"dense": m["dense"],
           "experts": m["experts"] + s["experts_routed"]     # selection bias
           + s["experts_held"] * m["expert"]}
    return sum(layer + ffn[_ffn_of(s, i)] for i in range(s["layers"])) \
        + 2 * m["head"] + u


def always_visited_params(config):
    """The matmul weights every token visits (348.3 M): the mixers' maps,
    the hyper-connections' P, the dense FFN, the routers and shared
    experts, the head."""
    s, m = shapes(config), matmul_params(config)
    return sum(m["mla"] + 2 * m["hyper"] + m[_ffn_of(s, i)]
               for i in range(s["layers"])) + m["head"]


def latent_attention_flops_per_token(config, seq_len, passes=3):
    """Causal Q K^T (nope + rope wide) and P V (value wide) of every
    layer's heads for one token: the forward is 2 (192 + 128) S / 2 a head
    (83.9 MFLOP a layer at 8192); ``passes`` = 3 adds the backward's four
    gradient matmuls, what the model requires; 3.5 its recomputation of
    the scores as well. Padded lanes are no work."""
    s = shapes(config)
    flops = Fraction(passes) * s["layers"] * s["heads"] \
        * (s["nope"] + s["rope"] + s["value"]) * seq_len
    assert flops.denominator == 1
    return int(flops)


def latent_attention_bytes_per_token(config):
    """Bytes the layers' attention must move for one token in bfloat16
    (builders/ling3_lm.py's count a layer): q, k, v in and o out (the
    forward), the same four and dO in and dQ, dK, dV out (the backward), a
    float32 log-sum-exp a head each way."""
    s = shapes(config)
    qk, dv = s["nope"] + s["rope"], s["value"]
    forward = 2 * (2 * qk + 2 * dv) + 4
    backward = 2 * (2 * qk + 3 * dv) + 2 * (2 * qk + dv) + 4
    return s["layers"] * s["heads"] * (forward + backward)


def hyper_connection_bytes_per_token(config, stream_bytes=2):
    """Bytes the two mixes of every hyper-connection must move for one
    token with the streams in bfloat16: the forward reads X (n C), writes u
    (C), reads F(u) (C) and writes X' (n C); the backward reads X, F, dX'
    and writes dX, dF, then reads X, du and adds into dX: (5 n + 5) C
    elements a sublayer (179.2 kB at n 4, C 3584), two sublayers a layer.
    The maps and the Sinkhorn rounds move bytes of the order of n^2 a token
    and are not counted."""
    s = shapes(config)
    return 2 * s["layers"] * (5 * s["streams"] + 5) * s["units"] \
        * stream_bytes


def held_expert_flops_per_token(config):
    """Forward + backward operations of the held experts' three matmuls
    for one token, all expert layers, at the EXPECTED number of live rows."""
    s = shapes(config)
    visited = Fraction(config["num_experts_per_tok"] * s["experts_held"],
                       s["experts_routed"])
    flops = 6 * visited * matmul_params(config)["expert"] \
        * (s["layers"] - s["dense_layers"])
    assert flops.denominator == 1
    return int(flops)


def attention_flops_per_token(config, seq_len):
    """What the driver calls the attention's required operations: the
    latent attention's, forward + backward. The only term that grows with
    the length."""
    return latent_attention_flops_per_token(config, seq_len)


def model_flops_per_token(config, seq_len):
    """Forward + backward operations the algorithm requires of this chip
    per trained token: 6 x the matmul weights a token visits (a token's
    held experts at their expected number), the causal scores."""
    return 6 * always_visited_params(config) \
        + held_expert_flops_per_token(config) \
        + attention_flops_per_token(config, seq_len)


#: rounds of the balancing at build and how far a round moves a bias towards
#: the value that would give its expert the even load with every other bias
#: held. builders/ling3_lm.py's rule (a damped step in ln(even load / load))
#: left loads of 2 400 to 4 350 of an even 4 096 here (my chip runs, PR 53):
#: after five layers of this model the hidden states of different tokens lie
#: close, an expert's scores are nearly one number for all tokens, its load
#: is steep in its bias, and a step sized for Ling's slope over- and
#: undershoots. The quantile below is exact whatever the slope.
BALANCE_ROUNDS, BALANCE_DAMPING = 30, 0.5
#: batches the rule sees (builders/solar_open2_lm.py: on one batch the
#: bias fits that batch's ids)
BALANCE_BATCHES = 8


def _block_of(model, fn, what):
    """A HybridBlock that owns `model`'s parameters and computes `fn` of
    its NDArray inputs: a piece of the model compiled alone. `what` names
    the piece: `jit.EvalStep` shares executables between blocks of one
    class, repr, parameters and baked state, and two pieces of one model
    differ in `fn` alone."""
    from incubator_mxnet_tpu.gluon.block import HybridBlock

    class Piece(HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.model = model

        def forward(self, *args):
            return fn(*args)

        def __repr__(self):
            return "Piece(%s of %s)" % (what, super().__repr__())

    return Piece()


def _mixed(layer, i, experts=None):
    """X -> X' of sublayer `i` of `layer`, its block replaced by `experts`
    where one is given; a block that moves its bias hands out (y, bias)."""
    hc, norm, block = layer.sublayers(experts)[i]

    def fn(x):
        u, h_post, h_res = hc(x)
        y = block(norm(u))
        return hc.write(x, y[0] if isinstance(y, (tuple, list)) else y,
                        h_post, h_res)

    return fn


def balance_routers(net, tokens):
    """The selection bias as a deployment's is: moved until every expert
    is chosen equally often, BALANCE_ROUNDS damped rounds on the rows of
    all of `tokens`' batches (n, S) at once, layer by layer as the forward
    pass reaches them. -> ([(fewest, most) assignments an expert of
    the router's has on those batches, an expert layer], [the standard
    deviation over those positions of the entries of each layer's FFN
    hyper-connection's Hres, the largest of its n^2 entries], how far the
    streams lie from their mean after the last layer, relative rms)."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu import jit, nd

    def settle(moe, seen, gw, bias):
        """Every expert's bias moved towards the one at which exactly the
        even load of tokens would choose it, the other biases held: token t
        chooses expert e iff s[t, e] + b_e beats the k-th largest of the
        others' s + b (the token's own k-th, or its (k + 1)-th where e is
        among its k), so that bias is the even-load-th smallest of that
        threshold less s[t, e] over the tokens."""
        scores = jax.nn.sigmoid(jnp.einsum(
            "td,ed->te", seen, gw, preferred_element_type=jnp.float32))
        k = moe.top_k
        even = scores.shape[0] * k // scores.shape[1]

        def one(_, bias):
            c = scores + bias
            top = jax.lax.top_k(c, k + 1)[0]
            kth, nxt = top[:, k - 1:k], top[:, k:]
            wanted = jnp.sort(jnp.where(c >= kth, nxt, kth) - scores, 0)[even]
            return bias + BALANCE_DAMPING * (wanted - bias)

        bias = jax.lax.fori_loop(0, BALANCE_ROUNDS, one, bias)
        return bias, jnp.bincount(moe.choose(scores, bias).reshape(-1),
                                  length=scores.shape[1])

    # each piece through its compiled forward (`jit.EvalStep`), a batch at
    # a time: nothing larger than the step's own is resident
    spread, varied = [], []
    embed = jit.EvalStep(_block_of(
        net, lambda t: net.stream_in(net.tok_embed(t)), "embedding"))
    xs = [embed(nd.array(batch[None])) for batch in tokens]
    for i, layer in enumerate(net.layers):
        hc, norm, ffn = layer.sublayers()[1]
        mixer = jit.EvalStep(_block_of(net, _mixed(layer, 0),
                                       "mixer sublayer %d" % i))
        xs = [mixer(x) for x in xs]

        def normed_input(x, hc=hc, norm=norm):
            u, h_post, h_res = hc(x)
            return norm(u), h_res

        reader = jit.EvalStep(_block_of(net, normed_input,
                                        "FFN input %d" % i))
        read = [reader(x) for x in xs]
        varied.append(float(jnp.concatenate(
            [r[1]._data for r in read], -1).std(-1).max()))
        moe = getattr(ffn, "moe", None)
        if moe is not None:
            bias, loads = jax.jit(functools.partial(settle, moe))(
                jnp.concatenate([r[0]._data.reshape(-1, r[0].shape[-1])
                                 for r in read]),
                moe.gate_weight.data()._data, moe.router_bias.data()._data)
            moe.router_bias.set_data(nd.NDArray(bias))
            spread.append((int(loads.min()), int(loads.max())))
        del read
        ffn = jit.EvalStep(_block_of(net, _mixed(layer, 1),
                                     "FFN sublayer %d" % i))
        xs = [ffn(x) for x in xs]
    n = net.layers[0].hc_ffn._n

    @jax.jit
    def squares(x):
        """(the streams' squared distance from their mean, their squares),
        summed over a batch: two scalars, nothing as large as x kept."""
        c = x.shape[-1] // n
        parts = [x[..., i * c:(i + 1) * c].astype(jnp.float32)
                 for i in range(n)]
        mean = sum(parts) / n
        return (sum(((p - mean) ** 2).sum() for p in parts),
                sum((p * p).sum() for p in parts))

    apart, whole = (sum(float(v) for v in vs)
                    for vs in zip(*(squares(x._data) for x in xs)))
    return spread, varied, (apart / whole) ** 0.5


def _composites():
    """What the program's counter says of the slow form so far."""
    from incubator_mxnet_tpu import telemetry
    return telemetry.REGISTRY.get("mxtpu_latent_attention_total").value(
        route="composite")


def init_hyper_connections(net, config, seed):
    """The configuration's `hc_init` (values only): P rows normal of
    standard deviation `weight_std_units` (n C)^-1/2, so a row of x^ P has
    that standard deviation over tokens; b = 0 but `b_res_diagonal` on the
    diagonal of the n x n map; the three a as given. From the seed, on a
    stream of its own."""
    import numpy as np
    from incubator_mxnet_tpu import nd
    init = config["hc_init"]
    n = config["hc_mult"]
    rng = np.random.default_rng([seed, 53])
    bias = np.zeros(2 * n + n * n, np.float32)
    bias[2 * n:] = init["b_res_diagonal"] * np.eye(n, dtype=np.float32) \
        .reshape(-1)
    for layer in net.layers:
        for hc in (layer.hc_mixer, layer.hc_ffn):
            shape = hc.weight.shape
            hc.weight.set_data(nd.array(rng.standard_normal(
                shape, np.float32) * init["weight_std_units"]
                / np.sqrt(shape[1])))
            hc.bias.set_data(nd.array(bias))
            hc.scale.set_data(nd.array(np.asarray(init["a"], np.float32)))


def init_stream(net, config):
    """The configuration's `init_embed_scale` and `init_down_scale` (values
    only; builders/keye_vl2_lm.py's reason): at Xavier weights a sublayer's
    output is nearly the same vector at every position and a hundred times
    an embedding's row, every position's hidden state is then one vector, a
    router sends every token to the same experts and the held experts' rows
    swing between none and all from step to step (my chip run, PR 53:
    0 to 23 752 rows a layer a step). The embedding times
    `init_embed_scale` and every block's last map (W_o, the dense FFN's,
    the shared experts' and the experts' down-projections) times
    `init_down_scale`, so that a token's own row carries its streams."""
    def scale(param, by):
        param.set_data(param.data() * by)

    scale(net.tok_embed.weight, config["init_embed_scale"])
    for layer in net.layers:
        down = config["init_down_scale"]
        scale(layer.mixer.proj.weight, down)
        ffn = layer.experts
        if hasattr(ffn, "moe"):
            scale(ffn.moe.w2, down)
            ffn = ffn.shared
        scale(ffn.down.weight, down)


def make_model(config, remat=True):
    """The model of the configuration's keys, uninitialised."""
    from incubator_mxnet_tpu import models
    try:
        from incubator_mxnet_tpu.models.ling3 import (yarn_inv_freq,
                                                      yarn_mscale)
    except ImportError as e:
        raise SystemExit("xing4_lm: the system in this checkout cannot run "
                         "the configuration (%s)" % e)
    s = shapes(config)
    ys = config["rope_scaling"]
    if ys["type"] != "yarn" or ys["mscale"] != ys["mscale_all_dim"]:
        raise SystemExit("xing4_lm: rope_scaling %r: YaRN with cos and sin "
                         "times 1 is what the model is built for" % (ys,))
    return models.Xing4Model(
        config["vocab_size"], s["units"], s["layers"], s["streams"],
        latent=dict(
            num_heads=s["heads"], latent=s["latent"], nope_dim=s["nope"],
            rope_dim=s["rope"], v_dim=s["value"], q_latent=s["q_latent"],
            qk_norm=False, head_gate=False,
            inv_freq=yarn_inv_freq(
                s["rope"], float(config["rope_theta"]), ys["factor"],
                ys["original_max_position_embeddings"], ys["beta_fast"],
                ys["beta_slow"]),
            scale=yarn_mscale(ys["factor"], ys["mscale_all_dim"]) ** 2
            / (s["nope"] + s["rope"]) ** 0.5),
        moe=dict(num_experts=s["experts_routed"],
                 ffn_hidden=config["moe_intermediate_size"],
                 top_k=config["num_experts_per_tok"],
                 shared_hidden=s["shared"],
                 scale=float(config["routed_scaling_factor"]),
                 norm_topk_prob=config["norm_topk_prob"],
                 held=(config["first_held_expert"], s["experts_held"]),
                 bias_rate=config["router_bias_rate"]),
        dense_hidden=s["dense"],
        hyper=dict(rounds=config["hc_sinkhorn_iters"],
                   epsilon=config["hc_eps"],
                   clamp=(float(config["mhc_h_res_clamp_min"]),
                          float(config["mhc_h_res_clamp_max"]))),
        dense_layers=s["dense_layers"], epsilon=config["rms_norm_eps"],
        remat_layers=remat)


def build(config, seed, seq_len):
    import jax
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import models
    if config["n_group"] != 1 or config["topk_group"] != 1:
        raise SystemExit("xing4_lm: n_group %r / topk_group %r: the model is "
                         "built without a group step"
                         % (config["n_group"], config["topk_group"]))
    mx.random.seed(seed)
    net = make_model(config)
    net.initialize(mx.init.Xavier())
    init_hyper_connections(net, config, seed)
    init_stream(net, config)
    # Xavier over (V, U) gives logits too flat for the loss to depend on
    # the features (builders/solar_open2_lm.py; the configuration's
    # `assumed.init_head_scale`)
    head = net.lm_head.weight
    head.set_data(head.data() * config["init_head_scale"])
    net.cast("bfloat16")
    # batches of the traffic's law (Zipf(1) ids), on a stream of their own
    # from the seed: none that is trained on or checked
    rng = np.random.default_rng([seed, 48])
    weights = 1.0 / np.arange(1, config["vocab_size"] + 1)
    composites = _composites()
    spread, varied, apart = balance_routers(net, rng.choice(
        config["vocab_size"], (BALANCE_BATCHES, seq_len),
        p=weights / weights.sum()).astype(np.int32))
    print("routers balanced at build: fewest and most of %d assignments an "
          "expert has, an expert layer: %s" % (
              BALANCE_BATCHES * seq_len * config["num_experts_per_tok"],
              spread), flush=True)
    print("hyper-connections at build: the largest standard deviation over "
          "positions of an entry of Hres, a layer's FFN sublayer: %s; the "
          "streams' distance from their mean after the last layer, relative "
          "rms: %.3f" % (["%.3f" % v for v in varied], apart), flush=True)
    composites = _composites() - composites
    if jax.devices()[0].platform == "tpu" and composites:
        raise SystemExit(
            "xing4_lm: on a TPU %d latent attention(s) took the composite: "
            "the step that would be timed is not the kernels'" % composites)
    return {"model": net, "train_net": models.FeaturesView(net),
            "loss": models.ChunkedUntiedLMLoss(net),
            "eval_net": continuous_trunk(net)}


def continuous_trunk(model):
    """tokens -> what the reference's `compared` hands out, (B, S, C + n C
    + n + n + n^2) float32, side by side on the channel axis, each part
    scaled so that a position's squares sum to C:

    the final norm's output of `model`'s own blocks with the routed
    experts' sum left out of every layer: every hyper-connection and mixer,
    the dense FFN, the shared experts and the norms
    (builders/solar_open2_lm.py says why);

    X' of the FIRST layer ALONE ON THE EMBEDDINGS (an input both sides
    have to the bit), both sublayers, all n streams: the latent attention,
    the dense FFN and the mixes of both hyper-connections (the second
    one's Hres on streams that differ);

    the three maps of the LAST layer's mixer hyper-connection on that X',
    whose streams differ: Hpre | Hpost | Hres by rows."""
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ndarray import _apply

    c = model.norm_f.gamma.shape[0]

    def side_by_side(trunk, *parts):
        def scaled(t):
            t = t.astype(jnp.float32).reshape(trunk.shape[:2] + (-1,))
            return t * jnp.sqrt(c / (t * t).sum(-1, keepdims=True))

        return jnp.concatenate([trunk.astype(jnp.float32)]
                               + [scaled(t) for t in parts], -1)

    def maps_of(hc, x):
        """(B, S, n + n + n^2) of `hc`'s maps on x."""
        def fn(x, *params):
            h_pre, h_post, h_res = hc.maps(x, *params)
            return jnp.concatenate(
                [h_pre, h_post, h_res.reshape(-1, h_res.shape[-1])]).T \
                .reshape(x.shape[:2] + (-1,))

        return _apply(fn, x, hc.weight.data(), hc.bias.data(),
                      hc.scale.data())

    def forward(token_ids):
        def continuous(layer, x):
            return _mixed(layer, 1, getattr(layer.experts, "shared",
                                            layer.experts))(
                _mixed(layer, 0)(x))

        embedded = x = model.stream_in(model.tok_embed(token_ids))
        for layer in model.layers:
            x = continuous(layer, x)
        alone = continuous(model.layers[0], embedded)
        return _apply(side_by_side, model.norm_f(model.stream_out(x)), alone,
                      maps_of(model.layers[-1].hc_mixer, alone))

    return _block_of(model, forward, "continuous trunk")


def reference_params(model):
    def w(param):
        return param.data()._data

    def layer(l):
        m, e = l.mixer, l.experts
        own = {"q_down": w(m.q_down.weight), "q_norm": w(m.q_norm.gamma),
               "q_up": w(m.query.weight), "kv_down": w(m.kv_down.weight),
               "kv_norm": w(m.kv_norm.gamma), "kv_up": w(m.kv_up.weight),
               "o": w(m.proj.weight),
               "norm1": w(l.norm1.gamma), "norm2": w(l.norm2.gamma)}
        for name, hc in (("hc_mixer", l.hc_mixer), ("hc_ffn", l.hc_ffn)):
            own.update({name + "_w": w(hc.weight), name + "_b": w(hc.bias),
                        name + "_a": w(hc.scale)})
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
