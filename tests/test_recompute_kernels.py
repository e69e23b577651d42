"""A recomputed layer keeps what its Pallas kernels wrote, by name (PR 46):
the forward rules of the streamed attention kernels (with and without the
log-sum-exp as an output, causal and under a window), of the selective
scan and of the delta rule name what they hand their backward
(`ops.attention.ATTENDED_NAME`, `ops.selective_scan.SCANNED_NAME`,
`ops.delta_rule.RULED_NAME`), and under `gluon.utils.recompute(...,
policy=)` / `jax.checkpoint(..., policy=)` a gradient runs each forward
kernel ONCE where no policy runs it twice; values and gradients are the
ones without a policy. A program with no recomputation is the program it
was: the name lowers to nothing. The counter names what a `recompute`
call was given. CPU, kernels interpreted: counts and values, no time."""
import re

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import models, nd, telemetry
from incubator_mxnet_tpu.gluon import _functional
from incubator_mxnet_tpu.gluon import utils as gutils
from incubator_mxnet_tpu.ops import attention, delta_rule, selective_scan

SAVE = jax.checkpoint_policies.save_only_these_names


def _normal(seed, *shape):
    return jnp.asarray(onp.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


def _qkv(dv=128):
    return (_normal(1, 1, 2, 256, 128), _normal(2, 1, 2, 256, 128),
            _normal(3, 1, 2, 256, dv))


def _streamed(q, k, v):
    return attention.flash_attention(q, k, v, True)


def _with_lse(q, k, v):
    o, lse = attention.attention_with_lse(q, k, v, True)
    # the statistics are an output too, as EVA's merge reads them
    return o * jnp.exp(lse - lse.max())[..., None]


def _window(q, k, v):
    return attention.flash_attention(q, k, v, True, window=128)


def _scan_args():
    b, s, c, n, r = 1, 128, selective_scan._KERNEL_CHANNELS, 4, 6
    return (_normal(1, b, s, c), _normal(2, b, s, r),
            -jnp.exp(_normal(3, c, n)), _normal(4, b, s, n),
            _normal(5, b, s, n), _normal(6, c), 0.5 * _normal(7, c, r),
            _normal(8, c) - 3)


def _scan(x, low, a, bm, cm, d, w, bias):
    return selective_scan.selective_scan(x, low, a, bm, cm, d, (w, bias), 64)


def _rule_args():
    b, t, h, d = 1, 64, 2, 128

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    return (unit(_normal(1, b, t, h, d)) * d ** -0.5,
            unit(_normal(2, b, t, h, d)), _normal(3, b, t, h, d),
            -jax.nn.softplus(_normal(4, b, t, h, d) - 3),
            2 * jax.nn.sigmoid(_normal(5, b, t, h)))


def _rule(q, k, v, g, beta):
    return delta_rule.gated_delta_rule(q, k, v, g, beta, chunk=16)


#: op -> (the call, its operands, the name its forward rule gives, the
#: forward and the backward kernel, the limit the op's own test file holds
#: a gradient to, of its largest entry)
OPS = {
    "streamed_attention": (_streamed, _qkv, attention.ATTENDED_NAME,
                           "flash_fwd", "flash_bwd_dkvq", 1e-5),
    "wide_value_attention": (_streamed, lambda: _qkv(256),
                             attention.ATTENDED_NAME, "flash_fwd",
                             "flash_bwd_dkvq", 1e-5),
    "attention_with_lse": (_with_lse, _qkv, attention.ATTENDED_NAME,
                           "flash_fwd", "flash_bwd_dkvq", 1e-5),
    "window_attention": (_window, _qkv, attention.ATTENDED_NAME,
                         "flash_window_fwd", "flash_window_bwd", 1e-5),
    "selective_scan": (_scan, _scan_args, selective_scan.SCANNED_NAME,
                       "selective_scan_fwd", "selective_scan_bwd", 1e-4),
    "delta_rule": (_rule, _rule_args, delta_rule.RULED_NAME,
                   "delta_rule_fwd", "delta_rule_bwd", 1e-5),
}


def _kernels(text, name):
    return len(re.findall(r"name=%s\b" % name, text))


@pytest.mark.parametrize("op", sorted(OPS))
def test_a_recomputed_call_runs_its_forward_kernel_once(monkeypatch, op):
    """Under a policy that saves the op's name the gradient's program holds
    ONE forward kernel and one backward; under no policy, or one that saves
    another name, two forward kernels. The operands are made inside the
    recomputed region, as a layer makes them, so XLA's part is computed
    again either way. Value and every gradient: those of the call with no
    checkpoint around it."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    call, make, name, fwd, bwd, limit = OPS[op]
    args = make()
    every = tuple(range(len(args)))
    weights = _normal(9, *jax.eval_shape(call, *args).shape)

    def loss(*a):
        a = tuple(x * 1.25 for x in a)
        return (call(*a).astype(jnp.float32) * weights).sum()

    def program(policy):
        return str(jax.make_jaxpr(jax.grad(
            jax.checkpoint(loss, policy=policy), every))(*args))

    kept, again, other = (program(p) for p in (
        SAVE(name), None, SAVE("some_other_name")))
    assert (_kernels(kept, fwd), _kernels(kept, bwd)) == (1, 1)
    assert (_kernels(again, fwd), _kernels(again, bwd)) == (2, 1)
    assert (_kernels(other, fwd), _kernels(other, bwd)) == (2, 1)
    with jax.default_matmul_precision("highest"):
        want_value, want = jax.value_and_grad(loss, every)(*args)
        got_value, got = jax.jit(jax.value_and_grad(
            jax.checkpoint(loss, policy=SAVE(name)), every))(*args)
    onp.testing.assert_allclose(got_value, want_value, rtol=1e-5)
    for i, (g, w) in enumerate(zip(got, want)):
        assert float(jnp.abs(w).max()) > 0, i
        assert float(jnp.abs(g - w).max()) \
            <= limit * float(jnp.abs(w).max()), i


# --------------------------------------------- a program with no recompute
U, HIDDEN, HEADS, S = 256, 512, 2, 128     # heads of 128: the streamed pair


def _block(kind):
    mx.random.seed(3)
    block = {"gpt": lambda: models.TransformerDecoderLayer(
                 U, HIDDEN, HEADS, attention="flash"),
             "bert": lambda: models.bert.TransformerEncoderLayer(
                 U, HIDDEN, HEADS, dropout=0.0, attention="flash")}[kind]()
    block.initialize(mx.init.Xavier())
    return block


@pytest.mark.parametrize("kind", ["gpt", "bert"])
def test_without_a_recompute_the_names_change_no_program(monkeypatch, kind):
    """One transformer block on the streamed kernels, value and every
    gradient, compiled: the optimised HLO text with the names is the text
    with `checkpoint_name` an identity in Python, instruction for
    instruction (the name's lowering hands its operand on)."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    block = _block(kind)
    params, param_arrs, pure_fn, _ = _functional.make_pure_fn(block, True)
    key = jax.random.PRNGKey(0)
    x = _normal(4, 2, S, U)
    datas = [a._data for a in param_arrs]

    def program():
        def loss(datas, x):
            return pure_fn(datas, [x], key)[0][0].sum()
        streamed = attention._ROUTES.value(route="streamed")
        traced = jax.jit(jax.value_and_grad(loss, (0, 1))).trace(datas, x)
        assert attention._ROUTES.value(route="streamed") == streamed + 1
        return (str(traced.jaxpr).count(attention.ATTENDED_NAME),
                traced.lower().compile().as_text())

    # (one call site: the text carries the Python frames' lines)
    (names, named), (none, plain) = (
        (monkeypatch.setattr(attention, "checkpoint_name", fn), program())[1]
        for fn in (attention.checkpoint_name, lambda x, name: x))
    assert (names, none) == (2, 0)          # o and lse of the one call
    assert named == plain


# ------------------------------------------------------------- the counter
def _counted():
    return {p: gutils._RECOMPUTES.value(policy=p) for p in ("none", "given")}


@pytest.mark.parametrize("policy,label", [
    (None, "none"), (SAVE(attention.ATTENDED_NAME), "given")],
    ids=["none", "given"])
def test_the_counter_says_what_a_recompute_was_given(policy, label):
    block = _block("gpt")
    x = nd.NDArray(_normal(5, 1, S, U))
    want = _counted()
    want[label] += 1
    got = gutils.recompute(block, x, policy=policy)
    onp.testing.assert_allclose(got.asnumpy(), block(x).asnumpy(),
                                rtol=1e-6, atol=1e-6)
    assert _counted() == want
    assert 'mxtpu_recompute_total{policy="%s"}' % label \
        in telemetry.REGISTRY.export_text()


@pytest.mark.parametrize("model,names", [
    (models.phi4flash, {attention.ATTENDED_NAME,
                        selective_scan.SCANNED_NAME}),
    (models.solar_open2, {attention.ATTENDED_NAME, delta_rule.RULED_NAME}),
], ids=["phi4flash", "solar_open2"])
def test_each_recomputing_model_states_what_its_layers_keep(model, names):
    """The policy is a constant of the model's own file, as Keye's: it
    saves the names of the kernels its layers run and nothing else."""
    name_p = jax.make_jaxpr(lambda x: jax.ad_checkpoint.checkpoint_name(
        x, "n"))(1.0).eqns[0].primitive
    every = names | {"sparse_topk", "sparse_attended", "something_else"}
    assert {n for n in every if model._KEPT(name_p, name=n)} == names
    assert not model._KEPT(jax.lax.dot_general_p)
