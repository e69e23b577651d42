"""A transformer block's MLP half carries its activations as (tokens,
channels) (`models.bert.mlp_tokens`): at B > 1 the GPT and BERT blocks, and
OLMoE's beside them (its expert layer flattens its own tokens), give what
they give the B sequences one at a time, output and every parameter's
gradient; no matmul of the lowered MLP half has a rank-3 operand, forward
or backward, and the attention half's still have; the counter names the
form. CPU, so nothing here is a time: what the form buys is read on the
chip (PERF.md section 6, PR 42) and, compiled for a described v5e, in
tests/test_kernels_compile_v5e.py."""
import re

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import models, nd
from incubator_mxnet_tpu.gluon import _functional
from incubator_mxnet_tpu.models import bert, olmoe

S, U, HIDDEN, HEADS = 64, 32, 128, 4


def gpt_block():
    return models.TransformerDecoderLayer(U, HIDDEN, HEADS, attention="dense")


def bert_block():
    return bert.TransformerEncoderLayer(U, HIDDEN, HEADS, dropout=0.0,
                                        attention="dense")


def olmoe_block():
    return olmoe.OLMoETransformerDecoderLayer(
        U, 16, HEADS, num_experts=8, top_k=2, attention="dense")


BLOCKS = {"gpt": gpt_block, "bert": bert_block, "olmoe": olmoe_block}


def build(kind, dtype):
    mx.random.seed(3)
    block = BLOCKS[kind]()
    block.initialize(mx.init.Xavier())
    for name, p in block.collect_params().items():
        # biases and gains that are not 0 and 1, so a misplaced one shows
        if name.endswith(("bias", "beta")):
            p.set_data(nd.random.uniform(-0.5, 0.5, p.shape))
        elif name.endswith("gamma"):
            p.set_data(nd.random.uniform(0.5, 1.5, p.shape))
    if dtype != "float32":
        block.cast(dtype)
    return block


def inputs(kind, batch, dtype):
    """x (B, S, U) and, for BERT, a padding mask (B, 1, 1, S) that hides
    another tail of every sequence."""
    rng = onp.random.RandomState(batch)
    arrs = [jnp.asarray(rng.standard_normal((batch, S, U)), dtype)]
    if kind == "bert":
        keep = S - 5 * (1 + onp.arange(batch))
        mask = onp.arange(S)[None, :] < keep[:, None]
        arrs.append(jnp.asarray(mask[:, None, None, :], dtype))
    return arrs


def value_and_grads(block, seed):
    """-> f(arrs) = (the block's output, every parameter's gradient of
    sum(output * a fixed float32 weighting)), as one pure function."""
    params, param_arrs, pure_fn, _ = _functional.make_pure_fn(block, True)
    key = jax.random.PRNGKey(0)

    def loss(datas, arrs):
        out = pure_fn(datas, arrs, key)[0][0]
        w = jax.random.normal(jax.random.PRNGKey(seed), out.shape[1:])
        return (out.astype(jnp.float32) * w).sum(), out

    def f(arrs):
        (_, out), grads = jax.value_and_grad(loss, has_aux=True)(
            [a._data for a in param_arrs], arrs)
        return out, dict(zip((p.name for p in params), grads))

    return f


def rel_rms(got, want):
    got, want = (onp.asarray(t, onp.float64) for t in (got, want))
    return float(onp.sqrt(onp.mean((got - want) ** 2)
                          / max(onp.mean(want ** 2), 1e-30)))


@pytest.mark.parametrize("batch", [1, 2, 3])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", sorted(BLOCKS))
def test_a_batch_is_its_sequences_one_at_a_time(kind, dtype, batch):
    block = build(kind, dtype)
    f = value_and_grads(block, seed=7)
    arrs = inputs(kind, batch, dtype)
    out, grads = f(arrs)
    assert out.shape == (batch, S, U) and out.dtype == jnp.dtype(dtype)
    singles = [f([a[i:i + 1] for a in arrs]) for i in range(batch)]
    want = jnp.concatenate([o for o, _ in singles])
    # a row's matmuls do not know how many rows there are: float32 outputs
    # agree to the last bits; bfloat16 may round an accumulation that
    # differs in the last float32 bit to the neighbouring value
    if dtype == "float32":
        assert rel_rms(out, want) < 1e-6
    else:
        assert rel_rms(out, want) < 4e-3
    for name, g in grads.items():
        total = sum(gs[name].astype(jnp.float32) for _, gs in singles)
        assert g.shape == total.shape
        if name.endswith("attention0_dense1_bias"):
            # the key's bias: a softmax does not see it, its gradient is
            # zero by the mathematics and round-off on both sides
            assert float(jnp.abs(g.astype(jnp.float32)).max()) < 1e-2 * max(
                float(jnp.abs(t.astype(jnp.float32)).max())
                for t in grads.values())
            continue
        # the batch's gradient is ONE sum over B x S rows, the singles' B
        # sums added: summation order in float32; in bfloat16 each single's
        # gradient is rounded (2^-8) before it is added
        limit = 2e-5 if dtype == "float32" else 2e-2
        assert rel_rms(g, total) < limit, (name, rel_rms(g, total))


_OPERANDS = re.compile(r":\s*\(tensor<([^>]*)>,\s*tensor<([^>]*)>\)")


def _shape(tensor_type):
    return tuple(int(d) for d in tensor_type.split("x")[:-1])


@pytest.mark.parametrize("kind", ["bert", "gpt"])
def test_no_matmul_of_the_mlp_half_has_a_rank_3_operand(kind):
    """Forward and gradient of a block at B = 2, as lowered. The MLP's
    matmuls are the only ones with a dimension of HIDDEN: fc1 and fc2
    forward, their input gradients and their weight gradients are six
    rank-2 matmuls over (B x S) rows. The attention half is as it was:
    q, k, v, o against (B, S, U), the heads' own with batching
    dimensions."""
    block = build(kind, "float32")
    f = value_and_grads(block, seed=7)
    text = jax.jit(f).lower(inputs(kind, 2, "float32")).as_text()
    dots = [("batching_dims" in line,)
            + tuple(_shape(t) for t in _OPERANDS.search(line).groups())
            for line in text.splitlines() if "stablehlo.dot_general" in line]
    plain = [d[1:] for d in dots if not d[0]]
    heads = [d[1:] for d in dots if d[0]]
    mlp = [d for d in plain if any(HIDDEN in shape for shape in d)]
    assert len(mlp) == 6, plain
    assert all(len(shape) == 2 and (2 * S in shape or shape in (
        (U, HIDDEN), (HIDDEN, U))) for d in mlp for shape in d), mlp
    projections = [d for d in plain if d not in mlp]
    assert any((2, S, U) in d for d in projections), projections
    assert heads and all(len(left) >= 3 and len(right) >= 3
                         for left, right in heads)


FORMS = ("batch_1", "tokens_2d", "batch_seq_3d")


def counts():
    return {f: bert._TRUNK_BLOCKS.value(form=f) for f in FORMS}


@pytest.mark.parametrize("batch", [1, 2, 3])
@pytest.mark.parametrize("kind", ["bert", "gpt"])
def test_the_counter_names_the_form(kind, batch):
    form = "batch_1" if batch == 1 else "tokens_2d"
    block = build(kind, "float32")
    arrs = [nd.NDArray(a) for a in inputs(kind, batch, "float32")]
    want = counts()
    want[form] += 1
    assert block(*arrs).shape == (batch, S, U)
    assert counts() == want
    assert 'mxtpu_trunk_block_total{form="%s"}' % form \
        in mx.telemetry.REGISTRY.export_text()


def test_olmoes_block_is_not_counted():
    """Its feed-forward is the expert layer, which takes its tokens as
    (B x S, U) by itself; there is no dense MLP half to flatten."""
    block = build("olmoe", "float32")
    before = counts()
    assert block(nd.NDArray(inputs("olmoe", 2, "float32")[0])).shape \
        == (2, S, U)
    assert counts() == before


@pytest.mark.parametrize("batch", [1, 2, 3])
def test_a_train_step_lowers_the_half_as_its_block_does(batch):
    """`jit.TrainStep.lower`: the whole step (forward, gradient, Adam)
    for shapes alone, nothing laid out or run. Its MLP matmuls are the
    six rank-2 ones of the block, and the parameters stay what they
    were."""
    from incubator_mxnet_tpu import gluon, jit
    block = build("gpt", "float32")
    trainer = gluon.Trainer(block.collect_params(), "adam",
                            {"learning_rate": 1e-3})
    step = jit.TrainStep(block, gluon.loss.L2Loss(), trainer)
    held = [p.data()._data for p in block.collect_params().values()]
    x = jax.ShapeDtypeStruct((batch, S, U), jnp.float32)
    text = step.lower(x, x).as_text()
    assert all(p.data()._data is d for p, d in zip(
        block.collect_params().values(), held))
    mlp = [shapes for shapes in (
        tuple(_shape(t) for t in _OPERANDS.search(line).groups())
        for line in text.splitlines() if "stablehlo.dot_general" in line)
        if any(HIDDEN in shape for shape in shapes)]
    rows = (batch * S,) if batch > 1 else (batch, S)
    assert len(mlp) == 6 and all(
        shape[:-1] == rows or shape in ((U, HIDDEN), (HIDDEN, U))
        for shapes in mlp for shape in shapes), mlp


def test_a_block_traced_by_a_mesh_step_stays_3d():
    """What `jit.TrainStep(mesh=...)` declares around its trace: the
    partitioned (B, S, U) program is the faster one there (PERF.md
    section 6, PR 42: dp4 under ZeRO-1)."""
    from incubator_mxnet_tpu import parallel
    from incubator_mxnet_tpu.parallel.mesh import step_mesh_scope
    mesh = parallel.make_mesh({"dp": 2}, devices=jax.devices()[:2])
    x = nd.NDArray(jnp.ones((2, S, U)))
    want = counts()
    want["batch_seq_3d"] += 1
    with step_mesh_scope(mesh, "dp"):
        assert bert.mlp_tokens(x) is x
    assert counts() == want
    assert bert.mlp_tokens(x).shape == (2 * S, U)


@pytest.mark.parametrize("batch", [1, 2])
def test_the_half_takes_rows_in_their_order(batch):
    x = nd.NDArray(jnp.arange(batch * S * U, dtype=jnp.float32).reshape(
        (batch, S, U)))
    tokens = bert.mlp_tokens(x)
    assert tokens.shape == ((batch * S, U) if batch > 1 else (1, S, U))
    onp.testing.assert_array_equal(
        tokens.asnumpy().reshape(batch, S, U), x.asnumpy())
