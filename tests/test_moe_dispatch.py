"""parallel/moe.py's one dispatch — sort by expert, grouped matmul,
un-sort — against the dense O(T*E) form it replaced (tests/moe_dense.py),
forward and gradients, over routing as uneven as it gets."""
import warnings

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd, parallel, telemetry
from incubator_mxnet_tpu.parallel import moe

from moe_dense import dense_moe

T, D, H, E, K = 48, 16, 24, 8, 3


def _dropless(*args):
    """`dropless_moe`'s sum, the dense form's contract; the rows an expert
    got, which it also hands back, are counted below."""
    return moe.dropless_moe(*args)[0]


def _uneven_case(seed=0):
    """Tokens, stacked weights and a routing in which expert 0 is every
    token's first choice, expert 1 nobody's, and the rest as they fall."""
    rng = onp.random.RandomState(seed)
    tokens = jnp.asarray(rng.randn(T, D), jnp.float32)
    w = {n: jnp.asarray(rng.randn(*s) / onp.sqrt(s[1]), jnp.float32)
         for n, s in (("gate", (E, D, H)), ("up", (E, D, H)),
                      ("down", (E, H, D)))}
    logits = rng.randn(T, E).astype("float32")
    logits[:, 0] += 20.0
    logits[:, 1] -= 20.0
    gates = jax.nn.softmax(jnp.asarray(logits), -1)
    top_vals, top_idx = jax.lax.top_k(gates, K)
    counts = onp.bincount(onp.asarray(top_idx).ravel(), minlength=E)
    assert counts[0] == T and counts[1] == 0      # all of them, none of them
    return tokens, w, top_vals, top_idx


@pytest.mark.parametrize("gated", [False, True], ids=["plain", "swiglu"])
def test_dropless_matches_dense_on_uneven_routing(gated):
    tokens, w, top_vals, top_idx = _uneven_case()
    act = jax.nn.silu if gated else jax.nn.relu

    def run(fn, tokens, top_vals, w):
        return fn(tokens, top_vals, top_idx, w["up"], w["down"], act,
                  w["gate"] if gated else None)

    got = run(_dropless, tokens, top_vals, w)
    want = run(dense_moe, tokens, top_vals, w)
    onp.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)

    # gradients of a scalar of the output: tokens (through both custom
    # permutations), the router's weights, every stacked weight
    probe = jnp.asarray(onp.random.RandomState(1).randn(T, D), jnp.float32)

    def scalar(fn):
        return lambda *a: jnp.sum(run(fn, *a) * probe)

    g_got = jax.grad(scalar(_dropless), argnums=(0, 1, 2))(
        tokens, top_vals, w)
    g_want = jax.grad(scalar(dense_moe), argnums=(0, 1, 2))(
        tokens, top_vals, w)
    for a, b in zip(jax.tree_util.tree_leaves(g_got),
                    jax.tree_util.tree_leaves(g_want)):
        onp.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)
    # the expert nobody chose gets no gradient, and costs no row
    assert not onp.asarray(g_got[2]["up"][1]).any()
    if not gated:
        assert not onp.asarray(g_got[2]["gate"]).any()    # unused


def test_dropless_shapes_are_static_and_small():
    """Always exactly T*k rows: the jaxpr holds no (T, E, C) and no
    (E, T, H) tensor, whatever the routing."""
    tokens, w, top_vals, top_idx = _uneven_case()
    jaxpr = jax.make_jaxpr(lambda *a: moe.dropless_moe(
        *a, w["up"], w["down"], jax.nn.silu, w["gate"]))(
            tokens, top_vals, top_idx)
    shapes = {tuple(v.aval.shape) for eqn in jaxpr.eqns for v in eqn.outvars}
    assert (T * K, H) in shapes and (T * K, D) in shapes
    assert not any(len(s) == 3 and s[0] == E and s[1] == T for s in shapes)
    assert not any(len(s) == 3 and s[:2] == (T, E) for s in shapes)
    assert "ragged_dot" in str(jaxpr)


@pytest.mark.parametrize("norm_topk_prob", [True, False],
                         ids=["renormalised", "as-they-are"])
def test_layer_against_dense_form(norm_topk_prob):
    layer = parallel.MoELayer(num_experts=E, hidden_size=D, ffn_hidden=H,
                              top_k=K, activation="silu", gated=True,
                              norm_topk_prob=norm_topk_prob)
    layer.initialize()
    x = nd.random.normal(shape=(4, 12, D))
    got = layer(x).asnumpy()
    tokens = x._data.reshape(-1, D)
    gw, w1, w2, w3 = (p.data()._data for p in (
        layer.gate_weight, layer.w1, layer.w2, layer.w3))
    gates = jax.nn.softmax(tokens @ gw.T, -1)
    top_vals, top_idx = jax.lax.top_k(gates, K)
    total = onp.asarray(top_vals.sum(-1))
    assert (total < 0.999).all()             # k of E never hold it all
    if norm_topk_prob:
        top_vals = top_vals / top_vals.sum(-1, keepdims=True)
    want = dense_moe(tokens, top_vals, top_idx, w3, w2, jax.nn.silu, w1)
    onp.testing.assert_allclose(got.reshape(-1, D), want, rtol=2e-5,
                                atol=2e-6)


def test_router_runs_in_float32_on_bfloat16_inputs():
    layer = parallel.MoELayer(num_experts=E, hidden_size=D, ffn_hidden=H,
                              top_k=K)
    layer.initialize()
    layer.cast("bfloat16")
    tokens = jnp.asarray(onp.random.RandomState(0).randn(T, D), jnp.bfloat16)
    logits, gates, top_vals, top_idx = layer.route(
        tokens, layer.gate_weight.data()._data)
    assert logits.dtype == gates.dtype == top_vals.dtype == jnp.float32
    onp.testing.assert_allclose(onp.asarray(top_vals.sum(-1)), 1.0,
                                rtol=1e-6)
    out = layer(nd.NDArray(tokens))
    assert out.dtype == onp.dtype("bfloat16") or str(out.dtype) == "bfloat16"


def test_stacked_weights_are_scaled_per_expert():
    layer = parallel.MoELayer(num_experts=64, hidden_size=32, ffn_hidden=16,
                              top_k=8, gated=True)
    layer.initialize(mx.init.Xavier())
    bound = (3.0 / ((32 + 16) / 2.0)) ** 0.5
    for p in (layer.w1, layer.w2, layer.w3):
        w = p.data().asnumpy()
        assert 0.9 * bound < onp.abs(w).max() <= bound
        # every expert its own draw
        assert not onp.allclose(w[0], w[1])


def test_dispatch_counter_and_the_capacity_warning():
    labels = dict(path="dropless", combine="unsort")
    before = moe._DISPATCHES.value(**labels)
    layer = parallel.MoELayer(num_experts=4, hidden_size=8, ffn_hidden=16)
    layer.initialize()
    f = jax.jit(lambda x: layer(nd.NDArray(x))._data)
    for _ in range(3):                       # traced once, run three times
        f(jnp.ones((2, 3, 8)))
    assert moe._DISPATCHES.value(**labels) - before == 1
    assert 'mxtpu_moe_dispatch_total{path="dropless",combine="unsort"}' \
        in telemetry.REGISTRY.export_text()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        parallel.MoELayer(num_experts=8, hidden_size=4, ffn_hidden=8,
                          capacity_factor=1.25)
    assert any("dropless" in str(w.message) for w in rec)
    with pytest.raises(ValueError):
        parallel.MoELayer(num_experts=4, hidden_size=4, ffn_hidden=8, top_k=5)


def test_dispatch_hands_back_the_rows_each_expert_got():
    """Expert 0 every token's, expert 1 nobody's, T x K in all: the second
    output is a count of `top_idx`, and nothing is dropped."""
    tokens, w, top_vals, top_idx = _uneven_case()
    rows = moe.dropless_moe(tokens, top_vals, top_idx, w["up"], w["down"],
                            jax.nn.silu, w["gate"])[1]
    assert rows.dtype == jnp.int32
    onp.testing.assert_array_equal(
        rows, onp.bincount(onp.asarray(top_idx).ravel(), minlength=E))
    assert rows[0] == T and rows[1] == 0 and int(rows.sum()) == T * K
