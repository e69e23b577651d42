"""nemotron-3-super-120b-a12b -> models.NemotronHModel, through the public
package, as ONE CHIP'S SHARE of a TP 8 x EP 64 layout (the configuration's
`cut`): every Mamba-2 mixer and the attention layer hold 1 / `mixer_shards`
of their heads, every expert layer `n_routed_experts` of the
`reduced_from.n_routed_experts` the router chooses among, the vocabulary is
the `vocab_size`-row slice. The widths are the source's keys, untouched.

Xavier weights from the seed (stacked expert weights per expert, A_log,
dt_bias and D by Mamba-2's own rules), bfloat16 but for A_log, dt_bias, D
and the router's selection bias, which starts at zero and is moved until
the experts' loads are even, as a deployment's is (`balance_routers`; the
configuration's `assumed`); each layer recomputed in the backward
(`remat_layers`); trained as `FeaturesView(model)` +
`ChunkedUntiedLMLoss(model)` so the (S, V) logits never exist at once.
The forward that is compared with the reference's is `continuous_trunk`:
the same blocks with the routed sum left out (why: the reference's
docstring).

The arithmetic below counts what the algorithm requires of THIS chip, from
the configuration's keys alone, in integers.
"""
import functools
from fractions import Fraction


def shapes(config):
    """What this chip holds of each kind of layer, from the keys."""
    shards = config["mixer_shards"]
    heads = config["mamba_num_heads"] // shards
    groups = config["n_groups"] // shards
    return {
        "units": config["hidden_size"],
        "mamba_heads": heads, "mamba_groups": groups,
        "mamba_inner": heads * config["mamba_head_dim"],
        "state": config["ssm_state_size"],
        "q_heads": config["num_attention_heads"] // shards,
        "kv_heads": max(1, config["num_key_value_heads"] // shards),
        "experts_routed": config["reduced_from"]["n_routed_experts"],
        "experts_held": config["n_routed_experts"],
        "pattern": config["layer_pattern_run"],
    }


def matmul_params(config):
    """{letter: weights in one layer's matmuls that every token visits},
    `expert` one routed expert's, `head` the untied head's."""
    s = shapes(config)
    u, n = s["units"], s["state"]
    inner = s["mamba_inner"]
    d = config["head_dim"]
    return {
        "M": u * (2 * inner + 2 * s["mamba_groups"] * n + s["mamba_heads"])
        + inner * u,
        "*": 2 * u * s["q_heads"] * d + 2 * u * s["kv_heads"] * d,
        "E": u * s["experts_routed"] + 2 * u * config["moe_latent_size"]
        + 2 * u * config["moe_shared_expert_intermediate_size"],
        "expert": 2 * config["moe_latent_size"]
        * config["moe_intermediate_size"],
        "head": config["vocab_size"] * u,
    }


def parameter_count(config):
    """Every parameter this chip holds (700.9 M at the published widths)."""
    s, m = shapes(config), matmul_params(config)
    u = s["units"]
    conv = s["mamba_inner"] + 2 * s["mamba_groups"] * s["state"]
    extra = {"M": conv * (config["conv_kernel"] + 1) + s["mamba_inner"]
             + 3 * s["mamba_heads"] + u,                # conv, norms, A, D, dt
             "*": u,
             "E": s["experts_routed"] + u               # selection bias, norm
             + s["experts_held"] * m["expert"]}
    return sum(m[c] + extra[c] for c in s["pattern"]) \
        + 2 * m["head"] + u


def ssd_flops_per_token(config):
    """Forward + backward operations of the chunked scan for one token, all
    Mamba-2 layers: per group C B^T (2 Q N), per head (L o C B^T) X (2 Q P),
    the chunk's state and the carried state's contribution (2 P N each),
    one step of the recurrence between chunks a chunk (2 P N / Q);
    backward twice the forward, the recomputation not counted."""
    s = shapes(config)
    q, p, n = config["chunk_size"], config["mamba_head_dim"], s["state"]
    forward = s["mamba_groups"] * 2 * q * n \
        + s["mamba_heads"] * (2 * q * p + 4 * p * n + 2 * p * n // q)
    return 3 * forward * s["pattern"].count("M")


def ssd_bytes_per_token(config):
    """Bytes the scan must move for one token, all Mamba-2 layers: x, B, C
    in bfloat16 and dt in float32 in, y out (forward); the same in, dy in
    and the four gradients out (backward). What the chunked form keeps
    between its own ops is not required."""
    s = shapes(config)
    x = 2 * s["mamba_heads"] * config["mamba_head_dim"]
    ins = x + 4 * s["mamba_heads"] + 2 * 2 * s["mamba_groups"] * s["state"]
    return (3 * ins + 2 * x) * s["pattern"].count("M")


def held_expert_flops_per_token(config):
    """Forward + backward operations of the held experts' two matmuls for
    one token, all expert layers, at the EXPECTED number of live rows: a
    token's k choices fall on this chip's experts held / routed of the
    time. Dead rows of the static bound are not required."""
    s = shapes(config)
    visited = Fraction(config["num_experts_per_tok"] * s["experts_held"],
                       s["experts_routed"])
    flops = 6 * visited * matmul_params(config)["expert"] \
        * s["pattern"].count("E")
    assert flops.denominator == 1
    return int(flops)


def attention_flops_per_token(config, seq_len):
    """Causal Q K^T and P V of the held query heads, all attention layers:
    the streamed Pallas kernels'."""
    s = shapes(config)
    return s["pattern"].count("*") * 6 * seq_len \
        * s["q_heads"] * config["head_dim"]


def model_flops_per_token(config, seq_len):
    """Forward + backward operations the algorithm requires of this chip
    per trained token: 6 x the matmul weights a token visits (a token's
    held experts at their expected number), the scan, the causal scores."""
    s, m = shapes(config), matmul_params(config)
    return 6 * (sum(m[c] for c in s["pattern"]) + m["head"]) \
        + held_expert_flops_per_token(config) \
        + ssd_flops_per_token(config) \
        + attention_flops_per_token(config, seq_len)


#: rounds of the balancing rule at build, and how far one round moves a
#: bias per unit of ln(even load / load): a fifth of a Newton step at the
#: 22nd of 512 sigmoid scores, where d ln(load) / d bias is about 17.5.
#: Whole steps on every expert at once swing (the experts share each
#: token's threshold); a fifth settles in 60 rounds (loads 345..358 of 352
#: on the batch it was set on, 277..434 on a fresh one: my CPU run, PR 31)
BALANCE_ROUNDS, BALANCE_STEP = 60, 0.2 / 17.5


def balance_routers(net, tokens):
    """The selection bias as a deployment's is: moved until every expert
    is chosen about equally often (the source's rule raises the bias of an
    expert that is chosen too rarely and lowers it where too often; here
    the step is proportional to ln(even load / load), BALANCE_ROUNDS times,
    on one batch, layer by layer as the forward pass reaches them). An untrained router
    with no such bias follows the activations' common component — the
    shared expert's relu^2 output is never negative — and loads its experts
    between 2 and 2964 tokens of 8192 (PERF.md section 6), differently for
    every seed."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu import jit, nd

    @functools.partial(jax.jit, static_argnums=3)
    def settle(seen, gw, bias, k):
        scores = jax.nn.sigmoid(jnp.einsum(
            "td,ed->te", seen, gw, preferred_element_type=jnp.float32))
        target = scores.shape[0] * k / scores.shape[1]

        def one(_, bias):
            _, idx = jax.lax.top_k(scores + bias, k)
            load = jnp.bincount(idx.reshape(-1), length=scores.shape[1])
            return bias + BALANCE_STEP * jnp.log(
                target / jnp.maximum(load, 1.0))

        return jax.lax.fori_loop(0, BALANCE_ROUNDS, one, bias)

    # each block through its compiled forward (`jit.EvalStep`): run eagerly,
    # op by op, a fresh checkout spent 230 s here compiling primitives
    x = jit.EvalStep(net.tok_embed)(nd.array(tokens))
    for layer in net.layers:
        u = jit.EvalStep(layer.norm)(x)
        if hasattr(layer.mixer, "moe"):
            moe = layer.mixer.moe
            moe.router_bias.set_data(nd.NDArray(settle(
                u._data.reshape(-1, u.shape[-1]),
                moe.gate_weight.data()._data,
                moe.router_bias.data()._data, moe.top_k)))
        x = x + jit.EvalStep(layer.mixer)(u)


def build(config, seed, seq_len):
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import models
    s = shapes(config)
    mx.random.seed(seed)
    net = models.NemotronHModel(
        config["vocab_size"], s["units"], s["pattern"],
        mamba=dict(num_heads=config["mamba_num_heads"],
                   head_dim=config["mamba_head_dim"],
                   n_groups=config["n_groups"], state=s["state"],
                   conv_kernel=config["conv_kernel"],
                   chunk=config["chunk_size"], shards=config["mixer_shards"],
                   dt_min=config["time_step_min"],
                   dt_max=config["time_step_max"],
                   dt_floor=config["time_step_floor"]),
        attention=dict(num_heads=s["q_heads"], num_kv_heads=s["kv_heads"],
                       head_dim=config["head_dim"], attention="flash"),
        moe=dict(latent=config["moe_latent_size"],
                 num_experts=s["experts_routed"],
                 ffn_hidden=config["moe_intermediate_size"],
                 top_k=config["num_experts_per_tok"],
                 shared_hidden=config["moe_shared_expert_intermediate_size"],
                 scale=float(config["routed_scaling_factor"]),
                 norm_topk_prob=config["norm_topk_prob"],
                 held=(config["first_held_expert"], s["experts_held"])),
        epsilon=config["layer_norm_epsilon"], remat_layers=True)
    net.initialize(mx.init.Xavier())
    # Xavier over (V, U) gives logits of std 0.63: the loss would be near
    # ln V whatever the features are, and the check of it would be blind
    head = net.lm_head.weight
    head.set_data(head.data() * config["init_head_scale"])
    net.cast("bfloat16")
    # one batch of the traffic's law (Zipf(1) ids), on a stream of its own
    # from the seed: not a batch that is trained on or checked
    rng = np.random.default_rng([seed, 31])
    weights = 1.0 / np.arange(1, config["vocab_size"] + 1)
    balance_routers(net, rng.choice(
        config["vocab_size"], (1, seq_len), p=weights / weights.sum())
        .astype(np.int32))
    return {"model": net, "train_net": models.FeaturesView(net),
            "loss": models.ChunkedUntiedLMLoss(net),
            "eval_net": continuous_trunk(net)}


def continuous_trunk(model):
    """tokens -> the final norm's output of `model`'s own blocks with the
    routed experts' sum left out of every expert layer: every Mamba-2
    mixer, the attention layer, the shared experts and the norms, which is
    what of the forward pass is continuous in its inputs and so can be
    held to the size of bfloat16's rounding. The reference's `forward`
    hands out the same (its docstring says why)."""
    from incubator_mxnet_tpu.gluon.block import HybridBlock

    class ContinuousTrunk(HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.model = model

        def forward(self, token_ids):
            x = model.tok_embed(token_ids)
            for layer in model.layers:
                mixer = getattr(layer.mixer, "shared", layer.mixer)
                x = x + mixer(layer.norm(x))
            return model.norm_f(x)

    return ContinuousTrunk()


def reference_params(model):
    def w(param):
        return param.data()._data

    def layer(l):
        m = l.mixer
        if hasattr(m, "A_log"):
            own = {"in_proj": w(m.in_proj.weight), "conv_w": w(m.conv_weight),
                   "conv_b": w(m.conv_bias), "A_log": w(m.A_log),
                   "dt_bias": w(m.dt_bias), "D": w(m.D),
                   "gate_norm": w(m.norm_gamma),
                   "out_proj": w(m.out_proj.weight)}
        elif hasattr(m, "moe"):
            own = {"router": w(m.moe.gate_weight),
                   "router_bias": w(m.moe.router_bias),
                   "latent_down": w(m.latent_down.weight),
                   "latent_up": w(m.latent_up.weight),
                   "w1": w(m.moe.w1), "w2": w(m.moe.w2),
                   "shared_up": w(m.shared_up.weight),
                   "shared_down": w(m.shared_down.weight)}
        else:
            own = {"q": w(m.query.weight), "k": w(m.key.weight),
                   "v": w(m.value.weight), "o": w(m.proj.weight)}
        return dict(own, norm=w(l.norm.gamma))

    return {"tok_embed": w(model.tok_embed.weight),
            "layers": [layer(l) for l in model.layers],
            "norm_f": w(model.norm_f.gamma),
            "head": w(model.lm_head.weight)}
