"""End-to-end training convergence tests (ref tests/python/train/test_mlp.py,
test_conv.py) + fused TrainStep + optimizer correctness."""
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd, autograd, gluon, jit
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.test_utils import assert_almost_equal


def _toy_problem(n=64, d=8, classes=4, seed=0):
    rng = onp.random.RandomState(seed)
    w = rng.randn(d, classes).astype("float32")
    X = rng.randn(n, d).astype("float32")
    y = X.dot(w).argmax(axis=1).astype("float32")
    return nd.array(X), nd.array(y)


def test_mlp_convergence_eager():
    X, y = _toy_problem()
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu", in_units=8), nn.Dense(4, in_units=32))
    net.initialize(mx.init.Xavier())
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.5, "momentum": 0.9})
    for _ in range(30):
        with autograd.record():
            loss = loss_fn(net(X), y)
        loss.backward()
        trainer.step(X.shape[0])
    acc = float((net(X).argmax(axis=1) == y).mean().asscalar())
    assert acc > 0.9, "accuracy %f too low" % acc


def test_fused_trainstep_convergence():
    X, y = _toy_problem(seed=3)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu", in_units=8), nn.Dense(4, in_units=32))
    net.initialize(mx.init.Xavier())
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "adam", {"learning_rate": 0.01})
    step = jit.TrainStep(net, loss_fn, trainer)
    first = None
    for i in range(60):
        loss = step(X, y)
        if first is None:
            first = float(loss.mean().asscalar())
    last = float(loss.mean().asscalar())
    assert last < first * 0.3, (first, last)


def test_fused_matches_eager_sgd():
    """One fused step == one eager step bitwise-close (same init, same data)."""
    X, y = _toy_problem(n=16, seed=5)

    def build():
        net = nn.HybridSequential()
        net.add(nn.Dense(8, activation="tanh", in_units=8), nn.Dense(4, in_units=8))
        net.initialize(mx.init.Xavier())
        return net

    mx.random.seed(11)
    net1 = build()
    mx.random.seed(11)
    net2 = build()
    for p1, p2 in zip(net1.collect_params().values(), net2.collect_params().values()):
        assert_almost_equal(p1.data(), p2.data().asnumpy())

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    tr1 = gluon.Trainer(net1.collect_params(), "sgd", {"learning_rate": 0.1})
    tr2 = gluon.Trainer(net2.collect_params(), "sgd", {"learning_rate": 0.1})

    with autograd.record():
        l1 = loss_fn(net1(X), y)
    l1.backward()
    tr1.step(X.shape[0])

    step = jit.TrainStep(net2, loss_fn, tr2)
    step(X, y)

    for p1, p2 in zip(net1.collect_params().values(), net2.collect_params().values()):
        assert_almost_equal(p1.data(), p2.data().asnumpy(), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("opt_name,kwargs", [
    ("sgd", {"learning_rate": 0.1}),
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}),
    ("nag", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adam", {"learning_rate": 0.01}),
    ("adamw", {"learning_rate": 0.01}),
    ("rmsprop", {"learning_rate": 0.01}),
    ("rmsprop", {"learning_rate": 0.01, "centered": True}),
    ("adagrad", {"learning_rate": 0.1}),
    ("adadelta", {}),
    ("adamax", {"learning_rate": 0.01}),
    ("nadam", {"learning_rate": 0.01}),
    ("ftrl", {"learning_rate": 0.1}),
    ("ftml", {"learning_rate": 0.01}),
    ("lamb", {"learning_rate": 0.01}),
    ("lars", {"learning_rate": 0.1}),
    ("signum", {"learning_rate": 0.01}),
])
def test_optimizers_reduce_loss(opt_name, kwargs):
    X, y = _toy_problem(n=32, seed=7)
    net = nn.Dense(4, in_units=8)
    net.initialize(mx.init.Xavier())
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), opt_name, dict(kwargs))
    losses = []
    for _ in range(15):
        with autograd.record():
            loss = loss_fn(net(X), y).mean()
        loss.backward()
        trainer.step(1)
        losses.append(float(loss.asscalar()))
    assert losses[-1] < losses[0], (opt_name, losses[0], losses[-1])


def test_sgd_update_formula():
    opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9, wd=0.01)
    w = nd.array([1.0, 2.0])
    g = nd.array([0.5, 0.5])
    state = opt.create_state(0, w)
    opt.update(0, w, g, state)
    # mom = -lr*(g + wd*w); w += mom
    expect = onp.array([1.0, 2.0]) - 0.1 * (onp.array([0.5, 0.5]) +
                                            0.01 * onp.array([1.0, 2.0]))
    assert_almost_equal(w, expect, rtol=1e-5, atol=1e-6)


def test_multi_precision():
    opt = mx.optimizer.SGD(learning_rate=0.1, multi_precision=True)
    w = nd.array([1.0, 2.0]).astype("bfloat16")
    g = nd.array([1.0, 1.0]).astype("bfloat16")
    state = opt.create_state_multi_precision(0, w)
    master, _ = state
    assert master.dtype == onp.float32
    opt.update_multi_precision(0, w, g, state)
    assert_almost_equal(master, [0.9, 1.9], rtol=1e-3, atol=1e-3)


def test_lr_scheduler_integration():
    sched = mx.lr_scheduler.FactorScheduler(step=5, factor=0.5)
    opt = mx.optimizer.SGD(learning_rate=1.0, lr_scheduler=sched)
    assert opt.learning_rate == 1.0
    trainer_lr = []
    for i in range(12):
        opt._update_count(0)
        trainer_lr.append(opt._get_lr(0))
    assert trainer_lr[-1] < trainer_lr[0]


def test_lr_schedulers():
    s = mx.lr_scheduler.MultiFactorScheduler(step=[3, 6], factor=0.1, base_lr=1.0)
    assert s(1) == 1.0
    assert s(4) == pytest.approx(0.1)
    assert s(7) == pytest.approx(0.01)
    c = mx.lr_scheduler.CosineScheduler(10, base_lr=1.0, final_lr=0.0)
    assert c(0) == pytest.approx(1.0)
    assert c(10) == pytest.approx(0.0, abs=1e-6)
    p = mx.lr_scheduler.PolyScheduler(10, base_lr=1.0, pwr=2)
    assert p(0) == pytest.approx(1.0)
    w = mx.lr_scheduler.FactorScheduler(step=100, base_lr=1.0, warmup_steps=10,
                                        warmup_begin_lr=0.0)
    assert w(5) == pytest.approx(0.5)


def test_checkpoint_roundtrip(tmp_path):
    X, y = _toy_problem(n=16)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=8), nn.Dense(4, in_units=8))
    net.initialize()
    out1 = net(X).asnumpy()
    f = str(tmp_path / "ckpt.params")
    net.save_parameters(f)
    net.load_parameters(f)
    assert_almost_equal(net(X), out1)


def test_bn_dropout_training_flow():
    net = nn.HybridSequential()
    net.add(nn.Dense(16, in_units=8), nn.BatchNorm(), nn.Activation("relu"),
            nn.Dropout(0.5), nn.Dense(4, in_units=16))
    net.initialize()
    X, y = _toy_problem(n=32)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "adam", {"learning_rate": 0.01})
    step = jit.TrainStep(net, loss_fn, trainer)
    for _ in range(3):
        step(X, y)
    # inference deterministic (no dropout)
    o1 = jit.EvalStep(net)(X).asnumpy()
    o2 = jit.EvalStep(net)(X).asnumpy()
    assert_almost_equal(o1, o2)


def test_kvstore_updater_with_momentum_state():
    """Regression: Updater.__call__ used `x or y` on the returned state —
    NDArray momentum buffers raised on __bool__ (found by the distributed
    example; ref updater.py semantics)."""
    import incubator_mxnet_tpu as mx
    kv = mx.kv.create("local")
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.1, momentum=0.9))
    w = nd.ones((4,))
    kv.init(7, w)
    g = nd.ones((4,))
    kv.push(7, g)
    kv.pull(7, out=w)
    first = w.asnumpy().copy()
    kv.push(7, g)      # second step exercises the saved momentum state
    kv.pull(7, out=w)
    assert (w.asnumpy() < first).all()
    # momentum accelerates: second delta larger than the first
    assert abs((first - w.asnumpy()).mean()) > abs((1.0 - first).mean())


def test_trainstep_remat_matches_plain():
    """remat=True (jax.checkpoint over the forward) is numerically the
    same training step — only the memory/FLOPs schedule changes."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, jit

    def build():
        mx.random.seed(0)
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(16, activation="relu", in_units=8),
                gluon.nn.Dense(4, in_units=16))
        net.initialize(mx.init.Xavier())
        tr = gluon.Trainer(net.collect_params(), "adam",
                           {"learning_rate": 1e-2})
        return net, tr

    x = nd.array(onp.random.RandomState(0).randn(4, 8).astype("float32"))
    y = nd.array(onp.random.RandomState(1).randint(0, 4, 4).astype("float32"))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    outs = []
    for remat in (False, True):
        net, tr = build()
        step = jit.TrainStep(net, loss_fn, tr, remat=remat)
        for _ in range(3):
            loss = step(x, y)
        outs.append((loss.asnumpy().copy(),
                     [v.data().asnumpy().copy()
                      for v in net.collect_params().values()]))
    onp.testing.assert_allclose(outs[0][0], outs[1][0], rtol=1e-6)
    for a, b in zip(outs[0][1], outs[1][1]):
        onp.testing.assert_allclose(a, b, rtol=1e-6)


@pytest.mark.parametrize("on_store", [False, True])
def test_a_trainer_copies_parameters_into_its_store_only_to_update_there(
        on_store):
    """The store's copy of a parameter is what a server-style update writes
    and pulls. A local update and a compiled step never read the store, and
    a copy there is a parameter's bytes of device memory for nothing (1.53
    GiB beside EvaByte's 821 M parameters, with which its train step did
    not load on a v5e: PERF.md section 6, PR 45). Both flows still train."""
    mx.random.seed(0)
    net = nn.Dense(4, in_units=8)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1},
                            update_on_kvstore=on_store)
    x = nd.array(onp.ones((2, 8), "float32"))
    before = net.weight.data().asnumpy().copy()
    with autograd.record():
        loss = (net(x) ** 2).sum()
    loss.backward()
    trainer.step(2)
    assert len(trainer._kvstore._data) == (2 if on_store else 0)
    assert not onp.allclose(net.weight.data().asnumpy(), before)
