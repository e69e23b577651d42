"""models/nemotron_h.py, ops/ssd.py and the held share of parallel/moe.py
against the float32 reference (tests/nemotron_h_reference.py) on seeded
weights at a small size. The tolerances are stated where they are used."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, jit, models, nd
from incubator_mxnet_tpu.gluon import utils as gutils
from incubator_mxnet_tpu.ops.ssd import ssd_chunked

import nemotron_h_reference as reference

#: the tiny model: the source's keys, the cut's own beside them
CFG = {"hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
       "num_key_value_heads": 2, "mamba_num_heads": 8, "mamba_head_dim": 8,
       "n_groups": 2, "ssm_state_size": 16, "conv_kernel": 4,
       "chunk_size": 16, "moe_latent_size": 32, "moe_intermediate_size": 24,
       "moe_shared_expert_intermediate_size": 48, "num_experts_per_tok": 4,
       "norm_topk_prob": True, "routed_scaling_factor": 5,
       "layer_norm_epsilon": 1e-5, "vocab_size": 128,
       "layer_pattern_run": "ME*ME", "first_held_expert": 4}
ROUTED, HELD, SHARDS = 16, 4, 2
B, S = 2, 40          # not a multiple of the chunk


def build(cfg=CFG, dtype=None, seed=0, remat=False, shards=SHARDS,
          held=(CFG["first_held_expert"], HELD), attention="dense"):
    mx.random.seed(seed)
    net = models.NemotronHModel(
        cfg["vocab_size"], cfg["hidden_size"], cfg["layer_pattern_run"],
        mamba=dict(num_heads=cfg["mamba_num_heads"],
                   head_dim=cfg["mamba_head_dim"], n_groups=cfg["n_groups"],
                   state=cfg["ssm_state_size"], chunk=cfg["chunk_size"],
                   shards=shards),
        attention=dict(num_heads=cfg["num_attention_heads"] // shards,
                       num_kv_heads=cfg["num_key_value_heads"] // shards,
                       head_dim=cfg["head_dim"], attention=attention),
        moe=dict(latent=cfg["moe_latent_size"], num_experts=ROUTED,
                 ffn_hidden=cfg["moe_intermediate_size"],
                 top_k=cfg["num_experts_per_tok"],
                 shared_hidden=cfg["moe_shared_expert_intermediate_size"],
                 scale=float(cfg["routed_scaling_factor"]), held=held),
        remat_layers=remat)
    net.initialize(mx.init.Xavier())
    head = net.lm_head.weight
    head.set_data(head.data() * 4.0)      # logits that depend on the features
    for name, p in net.collect_params().items():
        # gains, skips and selection biases that are not all 1 or 0, so a
        # misplaced one shows
        if name.endswith(("gamma", "_D")):
            p.set_data(p.data() * nd.random.uniform(0.5, 1.5, p.shape))
        if name.endswith(("router_bias", "conv_bias")):
            p.set_data(nd.random.uniform(-0.2, 0.2, p.shape))
    if dtype:
        net.cast(dtype)
    return net


def layer_params(layer):
    def w(p):
        return p.data()._data
    m = layer.mixer
    if isinstance(m, models.Mamba2Mixer):
        own = {"in_proj": w(m.in_proj.weight), "conv_w": w(m.conv_weight),
               "conv_b": w(m.conv_bias), "A_log": w(m.A_log),
               "dt_bias": w(m.dt_bias), "D": w(m.D),
               "gate_norm": w(m.norm_gamma), "out_proj": w(m.out_proj.weight)}
    elif isinstance(m, models.LatentMoE):
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
    return dict(own, norm=w(layer.norm.gamma))


def reference_params(net):
    return {"tok_embed": net.tok_embed.weight.data()._data,
            "layers": [layer_params(l) for l in net.layers],
            "norm_f": net.norm_f.gamma.data()._data,
            "head": net.lm_head.weight.data()._data}


def batch(seed=0, cfg=CFG, s=S):
    ids = onp.random.RandomState(seed).randint(
        0, cfg["vocab_size"], (B, s + 1)).astype("int32")
    return ids[:, :-1], ids[:, 1:]


def rel_rms(got, want):
    got, want = (onp.asarray(x, onp.float32) for x in (got, want))
    return float(onp.sqrt(onp.mean((got - want) ** 2))
                 / onp.sqrt(onp.mean(want ** 2)))


# ------------------------------------------------------------- the scan
def _scan_inputs(seed, b, s, h, p, g, n):
    rng = onp.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape),  # noqa: E731
                                   jnp.float32)
    dt = jnp.asarray(onp.exp(rng.uniform(onp.log(1e-3), onp.log(0.5),
                                         (b, s, h))), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, (h,)), jnp.float32)
    return f(b, s, h, p), dt, a, f(b, s, g, n), f(b, s, g, n), f(h)


def _sequential(x, dt, a, bm, cm, d):
    r = x.shape[2] // bm.shape[2]
    return reference._scan(x, dt, a, jnp.repeat(bm, r, 2),
                           jnp.repeat(cm, r, 2)) + d[:, None] * x


@pytest.mark.parametrize("s,chunk", [(64, 16), (100, 16), (100, 64),
                                     (7, 16)])
def test_chunked_scan_is_the_sequential_recurrence(s, chunk):
    """Two groups of two heads; S a multiple of the chunk, not one, and
    shorter than one. Float32 at "highest": the two algorithms differ by
    summation order only, 1e-5 of the largest output."""
    args = _scan_inputs(0, 2, s, 4, 8, 2, 16)
    with jax.default_matmul_precision("highest"):
        want = _sequential(*args)
        got = ssd_chunked(*args, chunk)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert float(jnp.abs(got - want).max()) \
        < 1e-5 * float(jnp.abs(want).max())


@pytest.mark.parametrize("every", [1, 16], ids=["position", "chunk"])
def test_a_bfloat16_state_fails_the_float32_tolerance(every):
    """The other reading of the 1e-5 above. The recurrence with its state
    rounded to bfloat16 after every position, or once a chunk of 16 as a
    kernel that carried a bfloat16 state between chunks would, is 2.4e-3
    and 1.1e-3 of the largest output from the float32 recurrence: a
    hundred times the tolerance the chunked form is held to (asserted at
    fifty). This float32 comparison is what holds the state's type:
    against bfloat16 activations it cannot be seen (PERF.md section 6,
    PR 31)."""
    x, dt, a, bm, cm, d = _scan_inputs(0, 2, 64, 4, 8, 2, 16)
    bm, cm = (jnp.repeat(t, 2, 2) for t in (bm, cm))

    def step(state, at):
        x_t, dt_t, b_t, c_t, rounds = at
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        state = jnp.where(rounds, jax.lax.reduce_precision(state, 8, 7),
                          state)
        return state, (state * c_t[:, :, None, :]).sum(-1)

    by_time = tuple(t.swapaxes(0, 1) for t in (x, dt, bm, cm)) \
        + (jnp.arange(64) % every == every - 1,)
    with jax.default_matmul_precision("highest"):
        want = reference._scan(x, dt, a, bm, cm)
        _, got = jax.lax.scan(step, jnp.zeros((2, 4, 8, 16), jnp.float32),
                              by_time)
    assert float(jnp.abs(got.swapaxes(0, 1) - want).max()) \
        > 5e-4 * float(jnp.abs(want).max())


def test_chunk_16_and_chunk_64_agree():
    args = _scan_inputs(1, 1, 128, 4, 8, 2, 16)
    with jax.default_matmul_precision("highest"):
        a, b = ssd_chunked(*args, 16), ssd_chunked(*args, 64)
    assert float(jnp.abs(a - b).max()) < 1e-5 * float(jnp.abs(a).max())


@pytest.mark.parametrize("arg", range(6),
                         ids=["x", "dt", "A", "B", "C", "D"])
def test_chunked_scan_gradients_are_the_sequential_ones(arg):
    """Autodiff of the chunked form against autodiff of the recurrence,
    each input in turn; S = 100 is padded to 112 inside. 1e-5 of the
    largest entry: float32 summation order."""
    args = _scan_inputs(2, 2, 100, 4, 8, 2, 16)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda *a: jnp.sum(_sequential(*a) ** 2), arg)(*args)
        got = jax.grad(lambda *a: jnp.sum(ssd_chunked(*a, 16) ** 2),
                       arg)(*args)
    assert float(jnp.abs(got - want).max()) \
        < 1e-5 * float(jnp.abs(want).max())


def test_a_long_strong_decay_neither_overflows_nor_leaks():
    """dt A of -80 a step: exp of the masked (upper) exponents would be
    inf; the mask goes in before the exp, values and gradients stay
    finite, and a position sees nothing of one 2 steps back."""
    x, dt, a, bm, cm, d = _scan_inputs(3, 1, 32, 2, 4, 1, 8)
    dt = jnp.full_like(dt, 5.0)
    a = jnp.full_like(a, -16.0)
    y, grads = jax.value_and_grad(
        lambda x: jnp.sum(ssd_chunked(x, dt, a, bm, cm, d, 16)))(x)
    assert bool(jnp.isfinite(y)) and bool(jnp.all(jnp.isfinite(grads)))


def test_scan_counter_counts_traces():
    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.ops import ssd
    before = ssd._SCANS.value(path="chunked")
    f = jax.jit(lambda *a: ssd_chunked(*a, 16))
    for _ in range(3):                       # traced once, run three times
        f(*_scan_inputs(4, 1, 16, 2, 4, 1, 8))
    assert ssd._SCANS.value(path="chunked") - before == 1
    assert 'mxtpu_ssd_scan_total{path="chunked"}' \
        in telemetry.REGISTRY.export_text()


# ------------------------------------------------------------- the shares
def _mamba_shard(whole, r, shards, cfg=CFG):
    """Rank r's rows of a whole mixer's parameters (reference layout)."""
    heads = cfg["mamba_num_heads"]
    hd, n, g = cfg["mamba_head_dim"], cfg["ssm_state_size"], cfg["n_groups"]
    inner, gn = heads * hd, g * n
    take = lambda start, size: onp.arange(                    # noqa: E731
        start + r * size // shards, start + (r + 1) * size // shards)
    conv = onp.concatenate([take(0, inner), take(inner, gn),
                            take(inner + gn, gn)])
    rows = onp.concatenate([take(0, inner), inner + conv,
                            take(2 * inner + 2 * gn, heads)])
    h = take(0, heads)
    return {"in_proj": whole["in_proj"][rows], "conv_w": whole["conv_w"][conv],
            "conv_b": whole["conv_b"][conv], "A_log": whole["A_log"][h],
            "dt_bias": whole["dt_bias"][h], "D": whole["D"][h],
            "gate_norm": whole["gate_norm"][take(0, inner)],
            "out_proj": whole["out_proj"][:, take(0, inner)]}


def _load_mamba(block, p):
    for param, name in ((block.in_proj.weight, "in_proj"),
                        (block.conv_weight, "conv_w"),
                        (block.conv_bias, "conv_b"), (block.A_log, "A_log"),
                        (block.dt_bias, "dt_bias"), (block.D, "D"),
                        (block.norm_gamma, "gate_norm"),
                        (block.out_proj.weight, "out_proj")):
        param.set_data(nd.array(onp.asarray(p[name])))


def test_mamba_shards_add_up_to_the_whole_mixer():
    """The two shards' out-projections (the system's blocks, each told it
    is one of two) sum to the whole mixer's output as the reference
    computes it from the whole parameters: the division by groups is
    exact. Float32; 2e-5 rel-rms is summation order."""
    whole_net = build(shards=1)
    whole = layer_params(whole_net.layers[0])
    u = jnp.asarray(onp.random.default_rng(0).standard_normal(
        (B, S, CFG["hidden_size"])), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = reference.mamba(whole, u, CFG)
        total = 0
        for r in range(SHARDS):
            block = build(shards=SHARDS).layers[0].mixer
            shard = _mamba_shard(whole, r, SHARDS)
            _load_mamba(block, shard)
            got = block(nd.array(onp.asarray(u)))._data
            # the system's shard is the reference's shard
            assert rel_rms(got, reference.mamba(shard, u, CFG)) < 2e-5
            total = total + got
    assert rel_rms(total, want) < 2e-5


def test_attention_shards_add_up_to_the_whole_layer():
    """Grouped-query attention with 4 query heads on 2 key-value heads,
    cut into 2 shards of 2 on 1: the shards' out-projections sum to the
    whole layer's."""
    whole = layer_params(build(shards=1).layers[2])
    d = CFG["head_dim"]
    u = jnp.asarray(onp.random.default_rng(1).standard_normal(
        (B, S, CFG["hidden_size"])), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = reference.attention(whole, u, CFG)
        total = 0
        for r in range(SHARDS):
            block = build(shards=SHARDS).layers[2].mixer
            q = slice(r * 2 * d, (r + 1) * 2 * d)
            kv = slice(r * d, (r + 1) * d)
            shard = {"q": whole["q"][q], "k": whole["k"][kv],
                     "v": whole["v"][kv], "o": whole["o"][:, q]}
            for param, name in ((block.query, "q"), (block.key, "k"),
                                (block.value, "v"), (block.proj, "o")):
                param.weight.set_data(nd.array(onp.asarray(shard[name])))
            got = block(nd.array(onp.asarray(u)))._data
            assert rel_rms(got, reference.attention(shard, u, CFG)) < 2e-5
            total = total + got
    assert rel_rms(total, want) < 2e-5


def test_expert_shares_add_up_to_the_uncut_layer():
    """The guide's share test. Four chips hold 4 of 16 experts each; every
    one computes the router, the latent maps and the shared expert alike.
    The routed parts all four give, with the shared expert counted once,
    add up to what the reference gives for the layer with all 16 experts;
    and each share is the reference's same share."""
    whole_net = build(held=None)
    layer = whole_net.layers[1]
    whole = layer_params(layer)
    u_np = onp.random.default_rng(2).standard_normal(
        (B, S, CFG["hidden_size"])).astype("float32")
    u = jnp.asarray(u_np)
    with jax.default_matmul_precision("highest"):
        want = reference.latent_moe(whole, u, CFG, 0)
        shared = reference._mm(whole["shared_down"], reference._relu2(
            reference._mm(whole["shared_up"], u)))
        # the whole layer through the system's held=None path
        assert rel_rms(layer.mixer(nd.array(u_np))._data, want) < 2e-5
        total = 0
        for first in range(0, ROUTED, HELD):
            block = build(held=(first, HELD)).layers[1].mixer
            share = dict(whole, w1=whole["w1"][first:first + HELD],
                         w2=whole["w2"][first:first + HELD])
            for param, name in (
                    (block.moe.gate_weight, "router"),
                    (block.moe.router_bias, "router_bias"),
                    (block.latent_down.weight, "latent_down"),
                    (block.latent_up.weight, "latent_up"),
                    (block.moe.w1, "w1"), (block.moe.w2, "w2"),
                    (block.shared_up.weight, "shared_up"),
                    (block.shared_down.weight, "shared_down")):
                param.set_data(nd.array(onp.asarray(share[name])))
            got = block(nd.array(u_np))._data
            assert rel_rms(got, reference.latent_moe(share, u, CFG, first)) \
                < 2e-5
            total = total + (got - shared)
    assert rel_rms(total + shared, want) < 2e-5


# ------------------------------------------------------------- the model
def test_pattern_string_builds_the_layers_it_names():
    net = build()
    kinds = {"M": models.Mamba2Mixer, "*": models.GroupedQueryAttention,
             "E": models.LatentMoE}
    assert [type(l.mixer) for l in net.layers] \
        == [kinds[c] for c in CFG["layer_pattern_run"]]
    mixer = net.layers[0].mixer
    assert (mixer.heads, mixer.groups, mixer.inner) == (4, 1, 32)
    assert net.layers[1].mixer.moe.w1.shape == (HELD, 32, 24)
    assert net.layers[1].mixer.moe.gate_weight.shape == (ROUTED, 64)
    with pytest.raises(ValueError):
        models.NemotronHModel(8, 8, "MXE", {}, {}, {})
    with pytest.raises(ValueError):
        models.Mamba2Mixer(64, 8, 8, 2, 16, shards=4)   # half a group


def test_mamba_initialisation_follows_the_configurations_rules():
    mixer = build().layers[0].mixer
    a = onp.exp(mixer.A_log.data().asnumpy())
    assert ((a >= 1) & (a <= 16)).all()
    dt = onp.log1p(onp.exp(mixer.dt_bias.data().asnumpy()))   # softplus
    assert ((dt >= 1e-3 * 0.999) & (dt <= 0.1 * 1.001)).all()
    net = build(dtype="bfloat16")
    mixer = net.layers[0].mixer
    assert {str(p.data().dtype) for p in (mixer.A_log, mixer.dt_bias,
                                          mixer.D)} == {"float32"}
    assert str(net.layers[1].mixer.moe.router_bias.data().dtype) == "float32"
    assert str(mixer.in_proj.weight.data().dtype) == "bfloat16"


def test_float32_model_matches_the_reference():
    """Forward and loss of the share (4 of 16 experts from the 4th on, one
    of two mixer shards), float32 at "highest", the dense attention path.
    Both sides compute the same function in float32: 5e-5 rel-rms on the
    final norm's output allows three layers' summation order and no more;
    the router's choices are then identical (no score is that close)."""
    net = build()
    tokens, labels = batch()
    with jax.default_matmul_precision("highest"):
        params = reference_params(net)
        want_out = reference.features(params, CFG, tokens)
        _, want_loss = reference.forward(params, CFG, tokens, labels, S)
        got = net.features(nd.array(tokens))._data
        loss = models.ChunkedUntiedLMLoss(net)(
            nd.array(onp.asarray(got)), nd.array(labels)).asnumpy()
    assert rel_rms(got, want_out) < 5e-5
    onp.testing.assert_allclose(loss, want_loss, rtol=2e-5)


def test_bfloat16_model_stays_near_the_reference():
    """The cell's own comparison at the tiny size: bfloat16 weights and
    activations against the float32 reference of the same (rounded)
    weights. The typical token is bfloat16 rounding through five layers
    (median 1 %, bound 2 %). A flipped choice among the 4 of 16 — two
    scores closer than the rounding of the router's input — moves a token
    by whole expert outputs at weights that sum to 5: one or two of the 80
    tokens read 35 to 60 %, which is no error of precision, so all
    together the bound is loose: 15 %."""
    net = build(dtype="bfloat16")
    tokens, labels = batch()
    want = onp.asarray(reference.features(reference_params(net), CFG,
                                          tokens))
    got = onp.asarray(net.features(nd.array(tokens))._data, onp.float32)
    per_token = onp.sqrt(((got - want) ** 2).mean(-1)) \
        / onp.sqrt((want ** 2).mean())
    assert onp.median(per_token) < 0.02
    assert rel_rms(got, want) < 0.15


def test_what_the_reference_hands_out_to_be_compared():
    """`forward`'s features are the trunk's with the routed sum left out —
    the same as the whole model's with every latent up-projection zeroed,
    and not the whole model's —, the last `tail` positions of them; its
    loss is the whole model's."""
    params = reference_params(build())
    tokens, labels = batch()
    tail = 7
    out, loss = reference.forward(params, CFG, tokens, labels, tail)
    whole = reference.features(params, CFG, tokens)
    silenced = dict(params, layers=[
        dict(l, latent_up=jnp.zeros_like(l["latent_up"]))
        if "latent_up" in l else l for l in params["layers"]])
    assert out.shape == (B, tail, CFG["hidden_size"])
    onp.testing.assert_allclose(
        out, reference.features(silenced, CFG, tokens)[:, -tail:],
        rtol=0, atol=1e-6)
    onp.testing.assert_array_equal(
        out, reference.features(params, CFG, tokens, routed=False)[:, -tail:])
    assert rel_rms(out, whole[:, -tail:]) > 0.05
    with jax.default_matmul_precision("highest"):
        logp = jax.nn.log_softmax(whole @ params["head"].T, -1)
    want = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0].mean(-1)
    onp.testing.assert_allclose(loss, want, rtol=2e-5)


def _loss_and_grads(net, tokens, labels):
    names = sorted(net.collect_params().keys())
    params = [net.collect_params()[n] for n in names
              if net.collect_params()[n].grad_req != "null"]
    loss_fn = models.ChunkedUntiedLMLoss(net)

    def fn(datas):
        arrs = [p.data() for p in params]
        saved = [a._data for a in arrs]
        for a, d in zip(arrs, datas):
            a._data = d
        try:
            out = loss_fn(net.features(nd.array(tokens)), nd.array(labels))
        finally:
            for a, s in zip(arrs, saved):
                a._data = s
        return out._data.sum()

    grads = jax.grad(fn)([p.data()._data for p in params])
    return dict(zip([p.name for p in params], grads))


@pytest.mark.parametrize("remat", [False, True],
                         ids=["stored", "recomputed"])
def test_gradients_match_the_reference(remat):
    """The checked parameters' gradients (the last Mamba-2 layer's A_log,
    dt_bias and in-projection; the last expert layer's router, latent
    maps and held experts) against the reference's, float32 at "highest",
    with and without per-layer recomputation: 1e-4 of each gradient's
    largest entry (summation order through two layers and the head). A
    recomputed layer of this model keeps nothing (its kernels are 2.6 ms of
    the cell's step and its scan is XLA ops): `recompute` gets no policy."""
    net = build(remat=remat)
    tokens, labels = batch()
    policies = [gutils._RECOMPUTES.value(policy=p) for p in ("none", "given")]
    with jax.default_matmul_precision("highest"):
        want = reference.checked_grads(reference_params(net), CFG,
                                       jnp.asarray(tokens),
                                       jnp.asarray(labels))
        got = _loss_and_grads(net, tokens, labels)
    assert [gutils._RECOMPUTES.value(policy=p) for p in ("none", "given")] \
        == [policies[0] + remat * len(net.layers), policies[1]]
    m, e = net.layers[3].mixer, net.layers[4].mixer
    mine = {"mamba_A_log": m.A_log, "mamba_dt_bias": m.dt_bias,
            "mamba_in_proj": m.in_proj.weight,
            "moe_router": e.moe.gate_weight,
            "moe_latent_down": e.latent_down.weight,
            "moe_latent_up": e.latent_up.weight}
    stacked = {"moe_%s_e%d" % (n, i): (p, i)
               for n, p in (("w1", e.moe.w1), ("w2", e.moe.w2))
               for i in range(HELD)}
    assert set(want) == set(mine) | set(stacked)
    for name in want:
        param, i = stacked.get(name, (mine.get(name), None))
        g = onp.asarray(got[param.name])
        w, g = onp.asarray(want[name]), g if i is None else g[i]
        assert onp.abs(g - w).max() < 1e-4 * onp.abs(w).max(), name
        assert onp.abs(w).max() > 0, name


def test_recompute_keeps_values_and_refuses_a_block_with_side_effects():
    net = build()
    x = nd.array(onp.random.default_rng(5).standard_normal(
        (B, S, CFG["hidden_size"])).astype("float32"))
    layer = net.layers[0]
    onp.testing.assert_array_equal(gutils.recompute(layer, x).asnumpy(),
                                   layer(x).asnumpy())
    from incubator_mxnet_tpu import autograd
    drop = gluon.nn.Dropout(0.5)
    with autograd.record(train_mode=True):
        with pytest.raises(ValueError):
            from incubator_mxnet_tpu.gluon import _functional
            with _functional.FunctionalScope(jax.random.PRNGKey(0)):
                gutils.recompute(drop, x)


def test_one_train_step_lowers_once_and_keeps_the_scopes_under_recompute(
        monkeypatch):
    """The normal path (FeaturesView + ChunkedUntiedLMLoss through
    TrainStep, bfloat16 with float32 masters, the interpreted streamed
    kernels, every layer recomputed): one program, a falling loss, and the
    blocks' and the scan's names on forward, recomputed and backward ops."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    cfg = dict(CFG, head_dim=128, hidden_size=128)
    net = build(cfg, dtype="bfloat16", remat=True, attention="flash")
    view = models.FeaturesView(net)
    trainer = gluon.Trainer(view.collect_params(), "adam",
                            {"learning_rate": 1e-3, "multi_precision": True})
    step = jit.TrainStep(view, models.ChunkedUntiedLMLoss(net), trainer)
    tokens, labels = batch(cfg=cfg, s=128)
    losses = [float(step(nd.array(tokens), nd.array(labels)).asnumpy().mean())
              for _ in range(4)]
    assert losses[-1] < losses[0]
    (text,) = [t for model_id, t in jit.compiled_train_programs()
               if model_id == step._model_id]
    for scope in ("ssd_scan", "ssd_conv", "ssd_gate_norm", "moe_dispatch",
                  "moe_combine", "shared_expert", "router"):
        paths = [l for l in text.splitlines() if "/" + scope + "/" in l]
        assert any("rematted_computation" in l for l in paths), scope
        assert any("transpose(" in l for l in paths), scope
        assert any("transpose(" not in l for l in paths), scope
