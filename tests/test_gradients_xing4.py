"""Xing 4.0's loss and checked gradients against the float32 reference the
benchmark uses, and the normal path (TrainStep, every layer recomputed) with
its scopes and counters, on the CPU at small widths. A file of its own,
early in the alphabet, so that another worker takes it (tests/
test_gradients_solar_open2.py says why). The helpers are
tests/test_xing4.py's.
"""
import jax
import jax.numpy as jnp
import numpy as onp

from incubator_mxnet_tpu import gluon, jit, models, nd, telemetry

from test_xing4 import (B, CFG, FIRST, HELD, S, STREAM, batch, build,
                        builder, close, reference)


def _favour_held(net, by=0.5):
    """The held experts' selection bias raised: at these widths the hidden
    states of different tokens are nearly one vector and an unfavoured
    expert can be left without a row, and a gradient of zeros compares
    nothing."""
    for layer in net.layers[CFG["first_k_dense_replace"]:]:
        bias = layer.experts.moe.router_bias
        raised = bias.data().asnumpy().copy()
        raised[FIRST:FIRST + HELD] += by
        bias.set_data(nd.array(raised))
    return net


def test_the_model_is_the_references_in_loss_and_checked_gradients():
    net = _favour_held(build())
    tokens, labels = batch()
    params = builder.reference_params(net)
    with jax.default_matmul_precision("highest"):
        # (the reference compiled whole: op by op it is several times slower)
        close(net.features(nd.array(tokens)).asnumpy(), jax.jit(
            lambda p: reference.features(p, CFG, tokens))(params))
        compared, ref_loss = jax.jit(lambda p: reference.forward(
            p, CFG, tokens, labels, S))(params)
        close(builder.continuous_trunk(net)(nd.array(tokens)).asnumpy(),
              compared)
        # the trunk without the routed sum | X' of the first layer alone
        # | the last mixer hyper-connection's maps, each part's squares
        # summing to C a position
        units = CFG["hidden_size"]
        close(compared[..., :units], jax.jit(lambda p: reference.features(
            p, CFG, tokens, routed=False))(params))
        assert compared.shape[-1] == units + STREAM + 24
        for part in (compared[..., units:units + STREAM],
                     compared[..., units + STREAM:]):
            close(onp.square(part).sum(-1), units * onp.ones((B, S)))
        want = jax.jit(lambda p: reference.checked_grads(
            p, CFG, tokens, labels))(params)
        held = [p for _, p in sorted(net.collect_params().items())
                if p.grad_req != "null"]
        loss = models.ChunkedUntiedLMLoss(net)

        def total(datas):
            arrs = [p.data() for p in held]
            saved = [a._data for a in arrs]
            for a, d in zip(arrs, datas):
                a._data = d
            try:
                return loss(net.features(nd.array(tokens)),
                            nd.array(labels))._data.sum()
            finally:
                for a, was in zip(arrs, saved):
                    a._data = was

        value, grads = jax.value_and_grad(total)(
            [p.data()._data for p in held])
        mine = dict(zip([p.name for p in held], grads))
        close(value, ref_loss.sum())
    last = net.layers[-1]
    m, e, hc = last.mixer, last.experts, last.hc_mixer
    got = {"hc_a": mine[hc.scale.name],
           "mla_q_down": mine[m.q_down.weight.name],
           "mla_q_up": mine[m.query.weight.name],
           "mla_kv_down": mine[m.kv_down.weight.name],
           "mla_kv_up": mine[m.kv_up.weight.name],
           "mla_q_norm": mine[m.q_norm.gamma.name],
           "mla_kv_norm": mine[m.kv_norm.gamma.name],
           "mla_o": mine[m.proj.weight.name],
           "moe_router": mine[e.moe.gate_weight.name],
           "moe_shared_gate_up": mine[e.shared.gate_up.weight.name],
           "moe_shared_down": mine[e.shared.down.weight.name]}
    for kind, param in (("P", hc.weight), ("b", hc.bias)):
        got.update(zip(("hc_%s_%s" % (kind, r) for r in reference.HC_ROWS),
                       jnp.split(mine[param.name], [4, 8], 0)))
    got.update({"moe_%s_e%d" % (n, i): mine[getattr(e.moe, n).name][i]
                for n in ("w1", "w2", "w3") for i in range(HELD)})
    assert set(got) == set(want) == set(reference.update_checked(params))
    assert onp.asarray(want["hc_P_res"]).shape == (16, STREAM)
    for name in want:
        close(got[name], want[name]), name
        assert onp.abs(onp.asarray(want[name])).max() > 0, name


def test_the_last_sublayers_hres_has_no_gradient():
    """Why the checked hyper-connection is the last layer's MIXER's: the
    last sublayer's X' is summed over the streams at once, the columns of
    Hres sum to 1, and the loss does not see that Hres."""
    net = build()
    tokens, labels = batch()
    params = builder.reference_params(net)
    with jax.default_matmul_precision("highest"):
        def loss_of(w):
            layers = params["layers"][:-1] + [
                dict(params["layers"][-1], hc_ffn_w=w)]
            return reference.forward(dict(params, layers=layers), CFG,
                                     tokens, labels, 1)[1].sum()

        grad = onp.asarray(jax.jit(jax.grad(loss_of))(
            params["layers"][-1]["hc_ffn_w"]))
    assert onp.abs(grad[8:]).max() < 1e-3 * onp.abs(grad[:8]).max()


def test_one_train_step_keeps_the_scopes_and_counts_its_routes(monkeypatch):
    """The normal path (FeaturesView + ChunkedUntiedLMLoss through
    TrainStep, bfloat16 with float32 masters, every layer recomputed, the
    selection bias moved by the rule): a falling loss, the new scopes on
    forward and backward ops, and the counters of what was traced."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")

    def count(name, **labels):
        return telemetry.REGISTRY.get(name).value(**labels)

    before = (count("mxtpu_latent_attention_total", route="composite"),
              count("mxtpu_hyper_connection_total", streams="4"))
    net = build(dtype="bfloat16", remat=True, router_bias_rate=0.05)
    view = models.FeaturesView(net)
    trainer = gluon.Trainer(view.collect_params(), "adam",
                            {"learning_rate": 1e-3, "multi_precision": True})
    step = jit.TrainStep(view, models.ChunkedUntiedLMLoss(net), trainer)
    tokens, labels = batch(s=128)
    bias = net.layers[1].experts.moe.router_bias.data().asnumpy()
    losses = [float(step(nd.array(tokens), nd.array(labels)).asnumpy().mean())
              for _ in range(4)]
    assert losses[-1] < losses[0]
    assert onp.abs(net.layers[1].experts.moe.router_bias.data().asnumpy()
                   - bias).max() > 0
    # 128 positions of heads 24 wide are no shape of the kernels'
    assert count("mxtpu_latent_attention_total", route="composite") \
        > before[0]
    assert count("mxtpu_hyper_connection_total", streams="4") \
        >= before[1] + 4
    (text,) = [t for model_id, t in jit.compiled_train_programs()
               if model_id == step._model_id]
    for scope in ("hc_maps", "hc_sinkhorn", "hc_pre", "hc_post",
                  "mla_q_down", "mla_down", "mla_up", "mla_rope"):
        paths = [l for l in text.splitlines() if "/" + scope + "/" in l]
        assert paths, scope
        assert any("transpose(" in l for l in paths), scope
        if scope.startswith("hc_"):
            assert all("hyperconnection" in l for l in paths), scope
    assert "/mla_gate/" not in text
    for stem in ("hyperconnection", "multiheadlatentattention",
                 "sharedexpertmoe", "swiglu", "xing4layer"):
        assert stem in text


def test_the_routes_counted_at_build_are_the_train_steps(monkeypatch):
    """perfbench/builders/xing4_lm.py refuses by the counter it reads
    across `balance_routers` (each piece's compiled forward on the step's
    own shapes): the label counted there is the label the train step
    counts, the route being a function of shape, type and platform alone.
    And what `balance_routers` reads of the hyper-connections."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    labels = [{"route": r} for r in ("streamed", "composite")]

    def counts():
        return [telemetry.REGISTRY.get("mxtpu_latent_attention_total")
                .value(**label) for label in labels]

    def taken(before):
        return [now > was for now, was in zip(counts(), before)]

    net = build(dtype="bfloat16", remat=True, router_bias_rate=0.05)
    tokens, targets = batch(s=128)
    before = counts()
    spread, varied, apart = builder.balance_routers(net, tokens)
    at_build = taken(before)
    assert any(at_build)
    assert len(spread) == 1 and len(varied) == 2
    assert all(low <= high for low, high in spread)
    assert min(varied) > 0.05 and apart > 0.05
    view = models.FeaturesView(net)
    trainer = gluon.Trainer(view.collect_params(), "adam",
                            {"learning_rate": 1e-3, "multi_precision": True})
    before = counts()
    jit.TrainStep(view, models.ChunkedUntiedLMLoss(net), trainer)(
        nd.array(tokens), nd.array(targets)).asnumpy()
    assert taken(before) == at_build
