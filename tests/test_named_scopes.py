"""Names inside the compiled train step (PR 25): every block's forward runs
under `jax.named_scope(block.name)`, the transformer layers' feed-forward
expression under `ffn`, `TrainStep`'s loss and update loop under `loss` and
`optimizer`, and the Pallas kernels carry a name. The names are HLO
metadata: `jit.compiled_train_programs()` hands out the text that has them."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from incubator_mxnet_tpu import gluon, jit, nd, parallel
from incubator_mxnet_tpu.models.bert import BERTModel
from incubator_mxnet_tpu.models.gpt import (ChunkedLMLoss, FeaturesView,
                                            GPTModel)
from incubator_mxnet_tpu.ops import attention as A


def _bert():
    net = BERTModel(vocab_size=61, units=16, hidden_size=32, num_layers=1,
                    num_heads=2, max_length=8, dropout=0.0)
    return net, net, gluon.loss.SoftmaxCrossEntropyLoss(), \
        "transformerencoderlayer"


def _gpt():
    net = GPTModel(vocab_size=61, units=16, num_layers=1, num_heads=2,
                   max_length=8, attention="dense")
    return net, FeaturesView(net), ChunkedLMLoss(net), \
        "transformerdecoderlayer"


def _tokens():
    return nd.array(np.random.RandomState(0).randint(0, 61, (2, 8))
                    .astype("int32"))


@pytest.fixture(scope="module", params=[_bert, _gpt], ids=["bert", "gpt"])
def compiled(request):
    """(op_names of the compiled tiny step, the train net, the layer's
    stem); the step is kept alive by the fixture, as its cache entry is."""
    model, train_net, loss, stem = request.param()
    model.initialize()
    trainer = gluon.Trainer(model.collect_params(), "adam",
                            {"learning_rate": 1e-3})
    step = jit.TrainStep(train_net, loss, trainer)
    x = _tokens()
    assert np.isfinite(step(x, x).asnumpy()).all()
    mine = [text for model_id, text in jit.compiled_train_programs()
            if model_id == step._model_id]
    assert len(mine) == 1
    yield re.findall(r'op_name="([^"]*)"', mine[0]), train_net, stem


def _under(op_names, *scopes):
    """op_names that have every one of `scopes` in a path component."""
    return [n for n in op_names
            if all(any(s in part for part in n.split("/")[:-1])
                   for s in scopes)]


@pytest.mark.parametrize("scopes", [
    ("{root}",), ("{layer}",), ("{layer}", "multiheadattention"),
    ("{layer}", "ffn"), ("{layer}", "layernorm"), ("{root}", "embedding"),
    ("loss",), ("optimizer",)],
    ids=lambda s: "+".join(s))
def test_compiled_step_names_its_blocks(compiled, scopes):
    op_names, net, stem = compiled
    scopes = [s.format(root=net.name, layer=stem) for s in scopes]
    assert _under(op_names, *scopes), scopes


def test_backward_ops_keep_their_blocks_scope(compiled):
    op_names, net, stem = compiled
    backward = _under(op_names, "transpose(jvp(%s))" % net.name)
    assert _under(backward, stem, "ffn")
    assert _under(backward, stem, "multiheadattention")
    # the feed-forward's two Dense blocks sit inside `ffn`, the attention's
    # four outside it
    ffn = _under(op_names, stem, "ffn", "dense")
    assert ffn and not _under(ffn, "multiheadattention")
    # and nothing of the optimizer is booked to a block or the reverse
    assert not _under(op_names, "optimizer", stem)


def test_a_released_step_leaves_no_program():
    """The entries are the steps' own: one that is gone is not listed."""
    net = gluon.nn.Dense(4, in_units=4)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 1e-2})
    step = jit.TrainStep(net, gluon.loss.L2Loss(), trainer)
    x = nd.array(np.ones((4, 4), "float32"))
    step(x, x)
    model_id = step._model_id
    assert model_id in [m for m, _ in jit.compiled_train_programs()]
    del step
    assert model_id not in [m for m, _ in jit.compiled_train_programs()]


def test_mesh_step_is_a_compiled_program_with_text_and_stats():
    """A mesh step is compiled ahead like any other: it has a text that
    names its scopes, and the cost analysis devstats reads."""
    net = gluon.nn.Dense(4, in_units=4)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 1e-2})
    mesh = parallel.make_mesh({"dp": 2}, devices=jax.devices()[:2])
    try:
        step = parallel.DataParallelTrainStep(net, gluon.loss.L2Loss(),
                                              trainer, mesh=mesh)
        x = nd.array(np.ones((4, 4), "float32"))
        assert np.isfinite(step(x, x).asnumpy()).all()
        mine = [text for model_id, text in jit.compiled_train_programs()
                if model_id == step._model_id]
        assert len(mine) == 1
        assert _under(re.findall(r'op_name="([^"]*)"', mine[0]), "optimizer")
        assert step._last_stats["flops"] > 0
    finally:
        parallel.set_current_mesh(None)


def test_eval_step_names_the_root_too():
    net = gluon.nn.Dense(4, in_units=4)
    net.initialize()
    _, param_arrs, pure_fn, _ = gluon._functional.make_pure_fn(net, False)
    text = jax.jit(pure_fn).lower(
        [a._data for a in param_arrs], [jnp.ones((2, 4))],
        jax.random.PRNGKey(0)).compile().as_text()
    assert 'op_name="jit(pure_fn)/%s/' % net.name in text


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dkvq"])
def test_flash_kernels_carry_their_names(kernel, monkeypatch):
    """Lowered for 'tpu' from this CPU host, through the public entry
    point and jax.grad: each Mosaic call is named."""
    monkeypatch.delenv("MXTPU_FLASH_INTERPRET", raising=False)
    monkeypatch.setattr(A, "flash_attention_legal", lambda *a, **k: True)

    def loss(q, k, v):
        return A.flash_attention(q, k, v, True).astype(jnp.float32).sum()

    x = jax.ShapeDtypeStruct((1, 2, 2048, 128), jnp.bfloat16)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(x, x, x).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 2
    assert 'kernel_name = "%s"' % kernel in text
