"""The chunked gated delta rule (`ops/delta_rule.py`) on the CPU, float32:
against the recurrence a position at a time (the benchmark's reference,
perfbench/reference/solar-open2-250b.py `delta_rule`) in value and in all
five gradients; where a channel's decay is so strong that exp(-G) leaves
float32 inside a chunk; at b near 2 (a negative eigenvalue) and near 0;
and what a bfloat16 state would read against the same limit."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.ops import delta_rule as rule_mod
from incubator_mxnet_tpu.ops.delta_rule import gated_delta_rule

_spec = importlib.util.spec_from_file_location(
    "delta_rule_test_reference", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "perfbench", "reference", "solar-open2-250b.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

ARGS = ("q", "k", "v", "g", "beta")
#: float32 summation order, of the largest entry
LIMIT = 1e-5
B, H, DK, DV = 2, 2, 32, 16


def inputs(seed, t, a_log=None, softplus_in=-3.0, beta_logit=0.0, dv=DV):
    """q (scaled) and k of unit length, as the mixer hands them over;
    g = -A softplus(x) a channel, A a head in [1, 16] or exp(a_log);
    b = 2 sigmoid(.)."""
    rng = onp.random.default_rng(seed)

    def unit(x):
        return x / onp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.standard_normal((B, t, H, DK))) * DK ** -0.5
    k = unit(rng.standard_normal((B, t, H, DK)))
    v = rng.standard_normal((B, t, H, dv))
    a = rng.uniform(1, 16, H) if a_log is None else onp.exp(a_log) \
        * onp.ones(H)
    x = rng.standard_normal((B, t, H, DK)) + softplus_in
    g = -a[:, None] * onp.log1p(onp.exp(x))
    beta = 2 / (1 + onp.exp(-(rng.standard_normal((B, t, H)) + beta_logit)))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


#: the decay the issue names: A = 16 everywhere, the softplus's input +4 on
#: half of the channels (g = -64 a position: exp(-G) is inf from the second
#: position on) and -3 on the others, which remember for a hundred
STRONG = dict(a_log=onp.log(16.0),
              softplus_in=onp.where(onp.arange(DK) < DK // 2, 4.0, -3.0))


def close(got, want):
    return float(jnp.abs(got - want).max()) \
        < LIMIT * float(jnp.abs(want).max())


def grads(fn, args, arg, seed=5):
    w = jnp.asarray(onp.random.default_rng(seed).standard_normal(
        args[2].shape), jnp.float32)
    return jax.grad(lambda *a: jnp.sum(fn(*a) * w), arg)(*args)


@pytest.mark.parametrize("t,chunk", [(64, 16), (100, 16), (128, 64),
                                     (200, 64), (7, 64)])
def test_chunk_form_is_the_recurrence(t, chunk):
    """T a multiple of the chunk, not one, and shorter than one."""
    args = inputs(0, t)
    want = reference.delta_rule(*args)
    got = gated_delta_rule(*args, chunk=chunk)
    assert got.shape == want.shape == (B, t, H, DV)
    assert got.dtype == want.dtype
    assert close(got, want)


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("arg", range(5), ids=ARGS)
def test_gradients_are_the_recurrences(arg, chunk):
    """Autodiff of the chunk form (the solve's and the scan's own rules)
    against autodiff of the recurrence; T = 150 is padded inside."""
    args = inputs(1, 150)
    want = grads(reference.delta_rule, args, arg)
    got = grads(lambda *a: gated_delta_rule(*a, chunk=chunk), args, arg)
    assert close(got, want)


def test_the_naive_split_would_overflow_where_the_op_does_not():
    args = inputs(2, 130, **STRONG)
    G = jnp.cumsum(args[3][:, :64], 1)
    assert not bool(jnp.isfinite(jnp.exp(-G)).all())     # exp(G_i) exp(-G_j)
    got = gated_delta_rule(*args, chunk=64)
    assert bool(jnp.isfinite(got).all())
    assert close(got, reference.delta_rule(*args))
    # nothing was clamped: a channel that strong forgets within a position
    assert float(args[3].min()) < -100


@pytest.mark.parametrize("arg", range(5), ids=ARGS)
def test_strong_decay_gradients_are_finite_and_the_recurrences(arg):
    args = inputs(2, 130, **STRONG)
    want = grads(reference.delta_rule, args, arg)
    got = grads(lambda *a: gated_delta_rule(*a, chunk=64), args, arg)
    assert bool(jnp.isfinite(got).all())
    assert close(got, want)


@pytest.mark.parametrize("logit,low,high", [(6.0, 1.9, 2.0), (-6.0, 0.0, 0.1)],
                         ids=["near-2", "near-0"])
def test_beta_at_its_ends(logit, low, high):
    """b near 2: I - b k k^T has the eigenvalue -1 along k, the state
    flips sign there and does not grow; near 0 almost nothing is
    written."""
    args = inputs(3, 128, beta_logit=logit)
    assert low <= float(jnp.median(args[4])) <= high
    want = reference.delta_rule(*args)
    assert close(gated_delta_rule(*args, chunk=64), want)
    for arg in (1, 4):
        assert close(grads(lambda *a: gated_delta_rule(*a, chunk=64), args,
                           arg), grads(reference.delta_rule, args, arg))


@pytest.mark.parametrize("every", [1, 64], ids=["position", "chunk"])
def test_a_bfloat16_state_fails_the_float32_limit(every):
    """The other reading of LIMIT. The recurrence with its state rounded
    to bfloat16 after every position, or once a chunk of 64 as a kernel
    that carried a bfloat16 state between chunks would, reads 1e-3 of the
    largest output from the float32 recurrence: a hundred times the limit
    the chunk form is held to (asserted at fifty). This float32 comparison
    is what holds the state's type: against bfloat16 activations it cannot
    be seen (PERF.md section 6, PR 31)."""
    q, k, v, g, beta = inputs(0, 128)

    def step(state, at):
        q_t, k_t, v_t, g_t, b_t, rounds = at
        state = jnp.exp(g_t)[..., None] * state
        u = b_t[..., None] * (v_t - (state * k_t[..., None]).sum(-2))
        state = state + k_t[..., None] * u[..., None, :]
        state = jnp.where(rounds, jax.lax.reduce_precision(state, 8, 7),
                          state)
        return state, (state * q_t[..., None]).sum(-2)

    by_time = tuple(t.swapaxes(0, 1) for t in (q, k, v, g, beta)) \
        + (jnp.arange(128) % every == every - 1,)
    _, got = jax.lax.scan(step, jnp.zeros((B, H, DK, DV), jnp.float32),
                          by_time)
    want = reference.delta_rule(q, k, v, g, beta)
    assert float(jnp.abs(got.swapaxes(0, 1) - want).max()) \
        > 50 * LIMIT * float(jnp.abs(want).max())


def test_chunk_16_and_chunk_64_agree():
    args = inputs(4, 128)
    assert close(gated_delta_rule(*args, chunk=16),
                 gated_delta_rule(*args, chunk=64))


def test_bfloat16_values_come_back_in_their_type():
    q, k, v, g, beta = inputs(5, 64)
    got = gated_delta_rule(q, k, v.astype(jnp.bfloat16), g, beta)
    assert got.dtype == jnp.bfloat16
    want = reference.delta_rule(q, k, v.astype(jnp.bfloat16).astype(
        jnp.float32), g, beta)
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) \
        < 2 ** -7 * float(jnp.abs(want).max())


def test_no_state_a_position_is_ever_made():
    """What autodiff keeps of the states is the one at each chunk's start:
    no array of the forward and backward program has T x d_k x d_v
    entries a head (d_v = 40 here, so that the sub-blocks' 16 x 16 x d_k
    products are no array of that size)."""
    args = inputs(6, 256, dv=40)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(gated_delta_rule(*a, chunk=64)), (0, 1, 2, 3, 4)))(
            *args)
    per_position = B * 256 * H * DK * 40

    def sizes(j):
        for eqn in j.eqns:
            for var in eqn.outvars:
                yield int(onp.prod(var.aval.shape))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from sizes(sub)

    largest = max(sizes(jaxpr.jaxpr))
    assert largest < per_position
    # the states that ARE kept: one a chunk
    assert largest >= B * H * (256 // 64) * DK * 40


def test_the_counter_counts_traces():
    before = rule_mod._CALLS.value(path="xla")
    f = jax.jit(lambda *a: gated_delta_rule(*a, chunk=16))
    for _ in range(3):                       # traced once, run three times
        f(*inputs(7, 32))
    assert rule_mod._CALLS.value(path="xla") - before == 1
    assert 'mxtpu_delta_rule_total{path="xla"}' \
        in telemetry.REGISTRY.export_text()


def test_every_op_is_under_the_scope():
    text = jax.jit(lambda *a: gated_delta_rule(*a, chunk=16)).lower(
        *inputs(8, 32)).as_text(debug_info=True)
    assert "delta_rule" in text
