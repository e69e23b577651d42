"""The held share of parallel/moe.py (`MoELayer(held=(first, count))`,
`dropless_moe_held`) and the sigmoid router with its selection bias,
against the dense form (tests/moe_dense.py); and that `held=None` is the
path OLMoE's cell runs, untouched."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from incubator_mxnet_tpu import nd, parallel, telemetry
from incubator_mxnet_tpu.parallel import moe

from moe_dense import dense_moe

T, D, H, E, K = 48, 16, 24, 12, 5


def _case(seed, dtype=jnp.float32):
    rng = onp.random.default_rng(seed)
    tokens = jnp.asarray(rng.standard_normal((T, D)), dtype)
    w_up = jnp.asarray(rng.standard_normal((E, D, H)) / 4, dtype)
    w_down = jnp.asarray(rng.standard_normal((E, H, D)) / 4, dtype)
    scores = rng.random((T, E))
    # uneven: expert 3 is in nearly every token's choice, expert 7 in none
    scores[:, 3] += 1.0
    scores[:, 7] -= 1.0
    top_vals, top_idx = jax.lax.top_k(jnp.asarray(scores, jnp.float32), K)
    return tokens, top_vals, top_idx, w_up, w_down


def _only(top_vals, top_idx, first, count):
    """The dense form's weights with every expert outside the share at 0."""
    held = (top_idx >= first) & (top_idx < first + count)
    return jnp.where(held, top_vals, 0.0)


@pytest.mark.parametrize("first,count", [(0, 4), (2, 3), (8, 4), (7, 1),
                                         (0, 12)])
@pytest.mark.parametrize("gated", [False, True])
def test_held_dispatch_is_the_held_experts_part_of_the_dense_sum(
        first, count, gated):
    """Shares at the start, in the middle, at the end, of one expert nobody
    chose, and of everything; float32, 1e-5: summation order."""
    tokens, top_vals, top_idx, w_up, w_down = _case(0)
    w_gate = w_up[::-1] if gated else None
    act = moe._ACTIVATIONS["relu2"]
    sl = slice(first, first + count)
    with jax.default_matmul_precision("highest"):
        got = moe.dropless_moe_held(
            tokens, top_vals, top_idx, w_up[sl], w_down[sl], act, first,
            None if w_gate is None else w_gate[sl])
        want = dense_moe(tokens, _only(top_vals, top_idx, first, count),
                         top_idx, w_up, w_down, act, w_gate)
    onp.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_held_dispatch_gradients_are_the_dense_ones():
    tokens, top_vals, top_idx, w_up, w_down = _case(1)
    first, count = 2, 4
    sl = slice(first, first + count)
    act = moe._ACTIVATIONS["relu2"]

    def held(tokens, top_vals, w_up_s, w_down_s):
        return jnp.sum(moe.dropless_moe_held(
            tokens, top_vals, top_idx, w_up_s, w_down_s, act, first) ** 2)

    def dense(tokens, top_vals, w_up_s, w_down_s):
        full_up = w_up.at[sl].set(w_up_s)
        full_down = w_down.at[sl].set(w_down_s)
        return jnp.sum(dense_moe(
            tokens, _only(top_vals, top_idx, first, count), top_idx,
            full_up, full_down, act) ** 2)

    args = (tokens, top_vals, w_up[sl], w_down[sl])
    with jax.default_matmul_precision("highest"):
        got = jax.grad(held, (0, 1, 2, 3))(*args)
        want = jax.grad(dense, (0, 1, 2, 3))(*args)
    for g, w in zip(got, want):
        onp.testing.assert_allclose(g, w, rtol=2e-5,
                                    atol=2e-5 * float(jnp.abs(w).max()))


def test_held_rows_are_the_shares_bound_not_t_times_k():
    """R = T x min(k, count) rows reach the grouped matmul, never T x k;
    no (T x k, D) tensor exists."""
    tokens, top_vals, top_idx, w_up, w_down = _case(2)
    jaxpr = jax.make_jaxpr(lambda t: moe.dropless_moe_held(
        t, top_vals, top_idx, w_up[:3], w_down[:3], jax.nn.relu, 0))(tokens)
    shapes = {v.aval.shape for eqn in jaxpr.jaxpr.eqns for v in eqn.outvars}
    assert (T * 3, D) in shapes and (T * 3, H) in shapes
    assert not any(s and s[0] == T * K and len(s) == 2 and s[1] in (D, H)
                   for s in shapes)


def _layer(**kwargs):
    layer = parallel.MoELayer(E, D, H, top_k=K, **kwargs)
    layer.initialize()
    return layer


def test_sigmoid_router_bias_chooses_and_does_not_weigh():
    layer = _layer(router="sigmoid_bias", scale=5.0, activation="relu2")
    rng = onp.random.default_rng(3)
    tokens = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    gw = layer.gate_weight.data()._data
    bias = jnp.zeros((E,)).at[7].set(10.0).at[3].set(-10.0)
    _, gates, vals, idx = layer.route(tokens, gw, bias)
    # expert 7 is always chosen, expert 3 never, whatever their scores
    assert bool(jnp.all(jnp.any(idx == 7, -1)))
    assert not bool(jnp.any(idx == 3))
    # the weights are the sigmoid scores themselves, renormalised, x 5
    s = jax.nn.sigmoid(tokens @ gw.T)
    picked = jnp.take_along_axis(s, idx, -1)
    onp.testing.assert_allclose(
        vals, 5.0 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    onp.testing.assert_allclose(vals.sum(-1), 5.0, rtol=1e-6)
    # with no bias the choice is the k largest scores
    _, _, _, plain = layer.route(tokens, gw, jnp.zeros((E,)))
    assert bool(jnp.all(plain == jax.lax.top_k(s, K)[1]))


def test_sigmoid_weights_are_used_as_they_are_without_renormalisation():
    layer = _layer(router="sigmoid_bias", norm_topk_prob=False)
    tokens = jnp.asarray(onp.random.default_rng(4).standard_normal((T, D)),
                         jnp.float32)
    gw = layer.gate_weight.data()._data
    _, gates, vals, idx = layer.route(tokens, gw, jnp.zeros((E,)))
    onp.testing.assert_array_equal(vals,
                                   jnp.take_along_axis(gates, idx, -1))


def test_layer_with_a_share_against_the_dense_form_and_routes_apart():
    """The block: sigmoid-bias router on a wider input than the experts',
    relu^2 experts, 4 of 12 held from the 5th on."""
    layer = parallel.MoELayer(E, D, H, top_k=K, router="sigmoid_bias",
                              scale=2.5, activation="relu2", held=(4, 4),
                              router_units=2 * D)
    layer.initialize()
    assert layer.w1.shape == (4, D, H) and layer.w2.shape == (4, H, D)
    assert layer.gate_weight.shape == (E, 2 * D)
    assert layer.router_bias.grad_req == "null"
    rng = onp.random.default_rng(5)
    layer.router_bias.set_data(nd.array(rng.uniform(-0.3, 0.3, (E,))))
    x = jnp.asarray(rng.standard_normal((2, T // 2, D)), jnp.float32)
    seen = jnp.asarray(rng.standard_normal((2, T // 2, 2 * D)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = layer(nd.NDArray(x), nd.NDArray(seen))._data
        _, _, vals, idx = layer.route(
            seen.reshape(T, -1), layer.gate_weight.data()._data,
            layer.router_bias.data()._data)
        full_up = jnp.zeros((E, D, H)).at[4:8].set(layer.w1.data()._data)
        full_down = jnp.zeros((E, H, D)).at[4:8].set(layer.w2.data()._data)
        want = dense_moe(x.reshape(T, D), vals, idx, full_up, full_down,
                         moe._ACTIVATIONS["relu2"]).reshape(x.shape)
    onp.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError):
        parallel.MoELayer(E, D, H, held=(10, 4))
    with pytest.raises(ValueError):
        parallel.MoELayer(E, D, H, router="tanh")


def test_held_none_is_the_dispatch_it_was():
    """`held=None` with the softmax router traces the composition OLMoE's
    cell has run since PR 27, equation for equation: the router's einsum,
    softmax and top-k, then `dropless_moe` on every expert. Written out
    here from the parent's `MoELayer._fn`; the layer's jaxpr is its text."""
    layer = _layer(activation="silu", gated=True, norm_topk_prob=False)

    def parent(xd, gw, w1, w2, w3):
        tokens = xd.reshape(-1, xd.shape[-1])
        logits = jnp.einsum("td,ed->te", tokens, gw,
                            preferred_element_type=jnp.float32)
        gates = jax.nn.softmax(logits, axis=-1)
        top_vals, top_idx = jax.lax.top_k(gates, K)
        return moe.dropless_moe(tokens, top_vals, top_idx, w3, w2,
                                jax.nn.silu, w1).reshape(xd.shape)

    x = jnp.ones((2, T // 2, D), jnp.bfloat16)
    weights = [w._data.astype(jnp.bfloat16) for w in layer._weights()]
    names = ("x",) + layer._weight_names()
    mine = jax.make_jaxpr(lambda *a: layer._fn(
        dict(zip(names, a)), False))(x, *weights)
    assert str(mine) == str(jax.make_jaxpr(parent)(x, *weights))
    assert "dropless_held" not in str(mine)


def test_the_held_path_has_its_own_counter():
    before = moe._DISPATCHES.value(path="dropless_held")
    tokens, top_vals, top_idx, w_up, w_down = _case(6)
    f = jax.jit(lambda t: moe.dropless_moe_held(
        t, top_vals, top_idx, w_up[:2], w_down[:2], jax.nn.relu, 0))
    for _ in range(3):
        f(tokens)
    assert moe._DISPATCHES.value(path="dropless_held") - before == 1
    assert 'mxtpu_moe_dispatch_total{path="dropless_held"}' \
        in telemetry.REGISTRY.export_text()
