"""Gluon tests (ref tests/python/unittest/test_gluon.py)."""
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd, autograd, gluon
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.test_utils import assert_almost_equal


def test_parameter():
    p = gluon.Parameter("weight", shape=(2, 3))
    p.initialize(init="xavier")
    assert p.data().shape == (2, 3)
    assert p.grad().shape == (2, 3)
    p.set_data(nd.ones((2, 3)))
    assert_almost_equal(p.data(), onp.ones((2, 3)))
    p.zero_grad()
    assert_almost_equal(p.grad(), onp.zeros((2, 3)))


def test_parameter_deferred_init():
    dense = nn.Dense(4)
    dense.initialize()
    with pytest.raises(gluon.DeferredInitializationError):
        dense.weight.data()
    out = dense(nd.ones((2, 6)))
    assert out.shape == (2, 4)
    assert dense.weight.data().shape == (4, 6)


def test_uninitialized_raises():
    dense = nn.Dense(4, in_units=3)
    with pytest.raises(RuntimeError):
        dense.weight.data()


def test_dense_layer():
    layer = nn.Dense(5, activation="relu", in_units=3)
    layer.initialize()
    x = nd.random.normal(shape=(4, 3))
    out = layer(x)
    assert out.shape == (4, 5)
    assert (out.asnumpy() >= 0).all()
    ref = onp.maximum(
        x.asnumpy().dot(layer.weight.data().asnumpy().T)
        + layer.bias.data().asnumpy(), 0)
    assert_almost_equal(out, ref, rtol=1e-4, atol=1e-5)
    # no flatten
    layer2 = nn.Dense(5, flatten=False, in_units=3)
    layer2.initialize()
    assert layer2(nd.ones((2, 7, 3))).shape == (2, 7, 5)


def test_sequential_and_children():
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=4), nn.Dense(3, in_units=8))
    net.initialize()
    assert len(net) == 2
    assert isinstance(net[0], nn.Dense)
    params = net.collect_params()
    assert len(params) == 4
    out = net(nd.ones((2, 4)))
    assert out.shape == (2, 3)


def test_conv_block():
    net = nn.Conv2D(4, kernel_size=3, padding=1, in_channels=2)
    net.initialize()
    assert net(nd.ones((1, 2, 8, 8))).shape == (1, 4, 8, 8)
    net_t = nn.Conv2DTranspose(4, kernel_size=2, strides=2, in_channels=2)
    net_t.initialize()
    assert net_t(nd.ones((1, 2, 4, 4))).shape == (1, 4, 8, 8)


def test_pool_blocks():
    x = nd.random.normal(shape=(1, 2, 8, 8))
    assert nn.MaxPool2D(2, 2)(x).shape == (1, 2, 4, 4)
    assert nn.AvgPool2D(2, 2)(x).shape == (1, 2, 4, 4)
    assert nn.GlobalAvgPool2D()(x).shape == (1, 2, 1, 1)
    assert nn.GlobalMaxPool2D()(x).shape == (1, 2, 1, 1)


def test_norm_blocks():
    x = nd.random.normal(shape=(2, 3, 4, 4))
    bn = nn.BatchNorm()
    bn.initialize()
    assert bn(x).shape == x.shape
    ln = nn.LayerNorm()
    ln.initialize()
    assert ln(nd.ones((2, 5))).shape == (2, 5)
    gn = nn.GroupNorm(num_groups=3)
    gn.initialize()
    assert gn(x).shape == x.shape
    inorm = nn.InstanceNorm()
    inorm.initialize()
    assert inorm(x).shape == x.shape


def test_embedding_block():
    emb = nn.Embedding(10, 4)
    emb.initialize()
    out = emb(nd.array([[1, 2], [3, 4]]))
    assert out.shape == (2, 2, 4)


def test_block_save_load(tmp_path):
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=4), nn.Dense(3, in_units=8))
    net.initialize()
    x = nd.random.normal(shape=(2, 4))
    out1 = net(x).asnumpy()
    fname = str(tmp_path / "net.params")
    net.save_parameters(fname)

    net2 = nn.HybridSequential()
    net2.add(nn.Dense(8, in_units=4), nn.Dense(3, in_units=8))
    net2.load_parameters(fname)
    assert_almost_equal(net2(x), out1)


def test_trainer_sgd_matches_manual():
    net = nn.Dense(1, in_units=2, use_bias=False)
    net.initialize(mx.init.One())
    trainer = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    x = nd.array([[1.0, 2.0]])
    with autograd.record():
        y = net(x).sum()
    y.backward()
    trainer.step(1)
    # w -= lr * grad;  grad = x
    assert_almost_equal(net.weight.data(), onp.array([[1 - 0.1, 1 - 0.2]]),
                        rtol=1e-5, atol=1e-6)


def test_trainer_save_load_states(tmp_path):
    net = nn.Dense(2, in_units=2)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "adam", {"learning_rate": 0.01})
    x = nd.ones((1, 2))
    for _ in range(2):
        with autograd.record():
            loss = net(x).sum()
        loss.backward()
        trainer.step(1)
    fname = str(tmp_path / "trainer.states")
    trainer.save_states(fname)
    trainer2 = gluon.Trainer(net.collect_params(), "adam", {"learning_rate": 0.01})
    trainer2.load_states(fname)
    assert trainer2._states_initialized


def test_hybridize_consistency():
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu", in_units=8), nn.Dense(4, in_units=16))
    net.initialize()
    x = nd.random.normal(shape=(2, 8))
    eager = net(x).asnumpy()
    net.hybridize()
    compiled = net(x).asnumpy()
    assert_almost_equal(eager, compiled, rtol=1e-5, atol=1e-6)


def test_hybridize_backward():
    net = nn.Dense(3, in_units=4)
    net.initialize()
    net.hybridize()
    x = nd.random.normal(shape=(2, 4))
    with autograd.record():
        loss = (net(x) ** 2).sum()
    loss.backward()
    w = net.weight.data().asnumpy()
    b = net.bias.data().asnumpy()
    expected_grad_w = 2 * (x.asnumpy().dot(w.T) + b).T.dot(x.asnumpy())
    assert_almost_equal(net.weight.grad(), expected_grad_w, rtol=1e-3, atol=1e-4)


def test_losses():
    pred = nd.random.normal(shape=(4, 5))
    label_cls = nd.array([0, 1, 2, 3])
    l = gluon.loss.SoftmaxCrossEntropyLoss()(pred, label_cls)
    ref = -onp.log(onp.exp(pred.asnumpy()) /
                   onp.exp(pred.asnumpy()).sum(1, keepdims=True))[
        onp.arange(4), [0, 1, 2, 3]]
    assert_almost_equal(l, ref, rtol=1e-4, atol=1e-5)

    a, b = nd.random.normal(shape=(3, 4)), nd.random.normal(shape=(3, 4))
    assert_almost_equal(gluon.loss.L2Loss()(a, b),
                        ((a.asnumpy() - b.asnumpy()) ** 2).mean(axis=1) / 2,
                        rtol=1e-4, atol=1e-5)
    assert_almost_equal(gluon.loss.L1Loss()(a, b),
                        onp.abs(a.asnumpy() - b.asnumpy()).mean(axis=1),
                        rtol=1e-4, atol=1e-5)
    sig = gluon.loss.SigmoidBCELoss()(a, (b > 0))
    assert sig.shape == (3,)
    h = gluon.loss.HuberLoss()(a, b)
    assert h.shape == (3,)
    k = gluon.loss.KLDivLoss()(nd.log_softmax(a), nd.softmax(b))
    assert k.shape == (3,)


def test_split_and_load():
    data = nd.arange(0, 16).reshape((8, 2))
    parts = gluon.split_data(data, 4)
    assert len(parts) == 4 and parts[0].shape == (2, 2)
    loaded = gluon.split_and_load(data, [mx.cpu(0), mx.cpu(0)])
    assert len(loaded) == 2


def test_clip_global_norm():
    arrays = [nd.ones((2, 2)) * 3, nd.ones((3,)) * 4]
    norm = gluon.clip_global_norm(arrays, 1.0)
    total = onp.sqrt(sum((a.asnumpy() ** 2).sum() for a in arrays))
    assert total <= 1.01


def test_constant_param():
    c = gluon.Constant("c", nd.array([1.0, 2.0]))
    c.initialize()
    assert_almost_equal(c.data(), [1.0, 2.0])
    assert c.grad_req == "null"


def test_lambda_blocks():
    lam = nn.HybridLambda(lambda x: x * 2)
    assert_almost_equal(lam(nd.ones((2,))), [2.0, 2.0])
    act = nn.Activation("relu")
    assert_almost_equal(act(nd.array([-1.0, 1.0])), [0.0, 1.0])


def test_dataset_dataloader():
    X = onp.random.rand(10, 3).astype("float32")
    Y = onp.arange(10).astype("float32")
    ds = gluon.data.ArrayDataset(X, Y)
    assert len(ds) == 10
    x0, y0 = ds[0]
    loader = gluon.data.DataLoader(ds, batch_size=4, shuffle=False)
    batches = list(loader)
    assert len(batches) == 3
    xb, yb = batches[0]
    assert xb.shape == (4, 3)
    loader = gluon.data.DataLoader(ds, batch_size=4, last_batch="discard")
    assert len(list(loader)) == 2
    # threaded worker path
    loader = gluon.data.DataLoader(ds, batch_size=5, num_workers=2)
    assert sum(b[0].shape[0] for b in loader) == 10


def test_vision_dataset_synthetic():
    ds = gluon.data.vision.MNIST(train=False)
    assert len(ds) > 0
    img, label = ds[0]
    assert img.shape == (28, 28, 1)
    t = gluon.data.vision.transforms.ToTensor()
    out = t(img)
    assert out.shape == (1, 28, 28)


def test_model_zoo_small():
    net = gluon.model_zoo.vision.get_model("resnet18_v1", classes=10)
    net.initialize()
    out = net(nd.random.normal(shape=(1, 3, 32, 32)))
    assert out.shape == (1, 10)

    net = gluon.model_zoo.vision.get_model("mobilenet0.25", classes=10)
    net.initialize()
    out = net(nd.random.normal(shape=(1, 3, 32, 32)))
    assert out.shape == (1, 10)


def test_dataloader_multiprocess_workers():
    # spawned process workers (ref dataloader.py:27-131 mp+shm pipeline)
    X = onp.arange(40, dtype="float32").reshape(20, 2)
    y = onp.arange(20, dtype="float32")
    ds = gluon.data.ArrayDataset(nd.array(X), nd.array(y))
    dl = gluon.data.DataLoader(ds, batch_size=4, num_workers=2,
                               thread_pool=False)
    got = []
    for xb, yb in dl:
        assert xb.shape == (4, 2)
        got.extend(yb.asnumpy().tolist())
    assert sorted(got) == list(range(20))
    assert sum(1 for _ in dl) == 5  # second epoch reuses the worker pool


def test_vision_transforms_batch2():
    from incubator_mxnet_tpu.gluon.data.vision import transforms as T
    img = nd.array(onp.random.RandomState(0).randint(
        0, 255, (32, 40, 3)).astype("float32"))
    assert T.CenterCrop(24)(img).shape == (24, 24, 3)
    assert T.RandomCrop(16, pad=2)(img).shape == (16, 16, 3)
    assert T.RandomResizedCrop(20)(img).shape == (20, 20, 3)
    onp.random.seed(0)
    assert T.RandomFlipTopBottom()(img).shape == img.shape
    out = T.RandomColorJitter(brightness=0.3, contrast=0.3,
                              saturation=0.3)(img)
    assert out.shape == img.shape
    comp = T.Compose([T.RandomResizedCrop(16), T.ToTensor(),
                      T.Normalize([0.5] * 3, [0.5] * 3)])
    t = comp(img.astype("uint8") if hasattr(img, "astype") else img)
    assert t.shape == (3, 16, 16)


def test_image_jitter_augmenters():
    from incubator_mxnet_tpu import image
    img = nd.array(onp.random.RandomState(0).randint(
        0, 255, (32, 32, 3)).astype("float32"))
    augs = image.CreateAugmenter((3, 28, 28), rand_crop=True, rand_mirror=True,
                                 brightness=0.2, contrast=0.2, saturation=0.2,
                                 pca_noise=0.1, rand_gray=0.3,
                                 mean=True, std=True)
    names = [type(a).__name__ for a in augs]
    assert "ColorJitterAug" in names and "LightingAug" in names
    out = img
    for a in augs:
        out = a(out)
    assert out.shape == (28, 28, 3)
    assert bool(onp.isfinite(out.asnumpy()).all())
    # gray aug with p=1 collapses channels
    g = image.RandomGrayAug(1.0)(img).asnumpy()
    assert onp.allclose(g[..., 0], g[..., 1], atol=1e-4)


def test_sdml_loss_learns_alignment():
    """SDML pulls aligned pairs together (ref loss.py SDMLLoss)."""
    mx.random.seed(0)
    emb = gluon.nn.Dense(8, in_units=8, use_bias=False)
    emb.initialize(mx.init.Xavier())
    loss_fn = gluon.loss.SDMLLoss(smoothing_parameter=0.1)
    rng = onp.random.RandomState(0)
    base = rng.randn(6, 8).astype("float32")
    x1 = nd.array(base)
    x2 = nd.array(base + 0.05 * rng.randn(6, 8).astype("float32"))
    trainer = gluon.Trainer(emb.collect_params(), "adam",
                            {"learning_rate": 1e-2})
    losses = []
    for _ in range(10):
        with autograd.record():
            loss = loss_fn(emb(x1), emb(x2)).sum()
        loss.backward()
        trainer.step(6)
        losses.append(float(loss.asscalar()))
    assert losses[-1] < losses[0]


def test_train_step_releases_the_eager_gradient_buffers():
    """`jit.TrainStep` differentiates a pure function and never reads the
    buffers the eager tape writes gradients into: the net it takes keeps a
    scalar zero of each buffer's type, `grad()` reads as zeros of the
    parameter's shape again, and an eager backward on the same net writes
    its gradient as before."""
    import numpy as onp
    from incubator_mxnet_tpu import autograd, gluon, jit, nd
    net = gluon.nn.Dense(3, in_units=4)
    net.initialize()
    loss = gluon.loss.L2Loss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    x = nd.array(onp.ones((2, 4), "float32"))
    y = nd.array(onp.zeros((2, 3), "float32"))
    assert net.weight._grad.shape == (3, 4)
    before = net.weight.data().asnumpy()
    jit.TrainStep(net, loss, trainer)(x, y)
    assert (net.weight.data().asnumpy() != before).any()      # it trained
    assert all(p._grad.shape == () for p in net.collect_params().values())
    assert net.weight.grad().shape == (3, 4)
    assert not net.weight.grad().asnumpy().any()
    net.weight.release_grad()
    with autograd.record():
        out = loss(net(x), y)
    out.backward()
    assert net.weight.grad().shape == (3, 4)
    assert net.weight.grad().asnumpy().any()
    net.weight.zero_grad()
    assert net.weight.grad().shape == (3, 4)
    assert not net.weight.grad().asnumpy().any()
