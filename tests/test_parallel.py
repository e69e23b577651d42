"""Parallelism tests on the virtual 8-device CPU mesh
(SURVEY §4: distributed tested as real multi-(virtual-)device on one host)."""
import numpy as onp
import pytest

import jax

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd, gluon, jit, parallel
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.test_utils import assert_almost_equal


def _need_devices(n):
    if len(jax.devices()) < n:
        pytest.skip("needs %d devices" % n)


def test_make_mesh():
    _need_devices(8)
    mesh = parallel.make_mesh({"dp": 2, "tp": 4})
    assert mesh.shape == {"dp": 2, "tp": 4}
    mesh = parallel.make_mesh({"dp": -1})
    assert mesh.shape["dp"] == 8


def test_data_parallel_matches_single():
    _need_devices(8)

    def build():
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu", in_units=8), nn.Dense(4, in_units=16))
        mx.random.seed(3)
        net.initialize(mx.init.Xavier())
        return net

    X = nd.random.normal(shape=(16, 8))
    y = nd.array(onp.random.randint(0, 4, 16).astype("float32"))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    net1 = build()
    tr1 = gluon.Trainer(net1.collect_params(), "sgd", {"learning_rate": 0.1})
    step1 = jit.TrainStep(net1, loss_fn, tr1)
    for _ in range(3):
        l1 = step1(X, y)

    mesh = parallel.make_mesh({"dp": 8})
    net2 = build()
    tr2 = gluon.Trainer(net2.collect_params(), "sgd", {"learning_rate": 0.1})
    step2 = parallel.DataParallelTrainStep(net2, loss_fn, tr2, mesh=mesh)
    for _ in range(3):
        l2 = step2(X, y)

    assert_almost_equal(l1, l2.asnumpy(), rtol=1e-4, atol=1e-5)
    for p1, p2 in zip(net1.collect_params().values(), net2.collect_params().values()):
        assert_almost_equal(p1.data(), p2.data().asnumpy(), rtol=1e-4, atol=1e-5)


def test_tensor_parallel_dense():
    _need_devices(8)
    mesh = parallel.make_mesh({"dp": 2, "tp": 4})
    net = nn.HybridSequential()
    net.add(parallel.ColParallelDense(32, activation="relu", in_units=8),
            parallel.RowParallelDense(4, in_units=32))
    mx.random.seed(5)
    net.initialize(mx.init.Xavier())
    X = nd.random.normal(shape=(8, 8))
    y = nd.array(onp.random.randint(0, 4, 8).astype("float32"))
    expected = net(X).asnumpy()  # eager single-logical-copy forward

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    step = parallel.DataParallelTrainStep(net, loss_fn, tr, mesh=mesh)
    l = step(X, y)
    assert l.shape == (8,)
    assert bool(onp.isfinite(l.asnumpy()).all())


def test_shard_params_rules():
    _need_devices(8)
    from jax.sharding import PartitionSpec as P
    mesh = parallel.make_mesh({"dp": 2, "tp": 4})
    net = nn.Dense(16, in_units=4)
    net.initialize()
    parallel.shard_params(net, [("weight", P("tp", None))])
    assert net.weight.sharding == P("tp", None)


def test_ring_attention_matches_reference():
    _need_devices(8)
    import jax.numpy as jnp
    mesh = parallel.make_mesh({"sp": 8})
    B, H, S, D = 2, 2, 64, 16
    rng = onp.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))
    k = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))
    v = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))

    def ref_attn(q, k, v, causal):
        s = onp.einsum("bhqd,bhkd->bhqk", q, k) / onp.sqrt(D)
        if causal:
            mask = onp.tril(onp.ones((S, S), bool))
            s = onp.where(mask, s, -onp.inf)
        p = onp.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        return onp.einsum("bhqk,bhkd->bhqd", p, v)

    for causal in (False, True):
        out = parallel.ring_attention(q, k, v, mesh=mesh, causal=causal)
        ref = ref_attn(onp.asarray(q), onp.asarray(k), onp.asarray(v), causal)
        assert_almost_equal(onp.asarray(out), ref, rtol=1e-3, atol=1e-4)


def test_pipeline_spmd_matches_sequential():
    _need_devices(8)
    import jax.numpy as jnp
    mesh = parallel.make_mesh({"pp": 8})
    n_stages, D = 8, 16
    rng = onp.random.RandomState(1)
    Ws = jnp.asarray(rng.randn(n_stages, D, D).astype("float32") * 0.1)

    def stage_fn(W, x):
        return jnp.tanh(x @ W)

    X = jnp.asarray(rng.randn(32, D).astype("float32"))
    out = parallel.pipeline_spmd(stage_fn, Ws, X, mesh, n_microbatches=8)
    ref = onp.asarray(X)
    for i in range(n_stages):
        ref = onp.tanh(ref @ onp.asarray(Ws[i]))
    assert_almost_equal(onp.asarray(out), ref, rtol=1e-3, atol=1e-4)


class _FFNStage(gluon.HybridBlock):
    """LayerNorm + FFN + residual — a transformer-trunk ring stage."""

    def __init__(self, dim, hidden, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.norm = nn.LayerNorm(in_channels=dim)
            self.fc1 = nn.Dense(hidden, activation="relu", in_units=dim,
                                flatten=False)
            self.fc2 = nn.Dense(dim, in_units=hidden, flatten=False)

    def forward(self, x):
        return x + self.fc2(self.fc1(self.norm(x)))


def _build_pipelined_lm(mesh, n_stages=4, vocab=32, dim=16, seed=5):
    mx.random.seed(seed)
    embed = nn.Embedding(vocab, dim)
    stages = [_FFNStage(dim, 2 * dim) for _ in range(n_stages)]
    # microbatch dim stays sharded over dp while activations ring over pp
    trunk = parallel.PipelineStack(stages, mesh, n_microbatches=4,
                                   data_axis="dp")
    head = nn.Dense(vocab, in_units=dim, flatten=False)
    net = nn.HybridSequential()
    net.add(embed, trunk, head)
    net.initialize(mx.init.Xavier())
    return net, (embed, stages, head)


def test_gluon_pipeline_forward_matches_sequential():
    _need_devices(8)
    mesh = parallel.make_mesh({"dp": 2, "pp": 4})
    net, (embed, stages, head) = _build_pipelined_lm(mesh)
    tokens = nd.array(onp.random.RandomState(0).randint(0, 32, (8, 6)),
                      dtype="int32")
    out = net(tokens)
    # sequential reference through the SAME blocks, no pipeline
    h = embed(tokens)
    for s in stages:
        h = s(h)
    ref = head(h)
    assert_almost_equal(out.asnumpy(), ref.asnumpy(), rtol=1e-4, atol=1e-5)


def test_gluon_pipeline_train_step_matches_sequential():
    _need_devices(8)
    mesh = parallel.make_mesh({"dp": 2, "pp": 4})
    rng = onp.random.RandomState(1)
    tokens = nd.array(rng.randint(0, 32, (8, 6)), dtype="int32")
    labels = nd.array(rng.randint(0, 32, (8, 6)), dtype="int32")
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def run(pipelined):
        net, (embed, stages, head) = _build_pipelined_lm(mesh)
        if not pipelined:
            seq = nn.HybridSequential()
            seq.add(embed, *stages, head)
            net = seq
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1})
        # pipelined: one jitted program over the dp x pp mesh (batch over dp,
        # ring over pp); sequential reference: plain single-device TrainStep
        step = jit.TrainStep(net, loss_fn, trainer,
                             mesh=mesh if pipelined else None)
        losses = [float(step(tokens, labels).mean().asnumpy())
                  for _ in range(3)]
        return losses

    lp = run(True)
    ls = run(False)
    assert_almost_equal(onp.asarray(lp), onp.asarray(ls), rtol=1e-4, atol=1e-5)
    assert lp[-1] < lp[0]  # it actually trains


def test_pipeline_grad_through_ring():
    _need_devices(8)
    import jax.numpy as jnp
    mesh = parallel.make_mesh({"dp": 2, "pp": 4})
    n_stages, D = 4, 8
    rng = onp.random.RandomState(0)
    Ws = jnp.asarray(rng.randn(n_stages, D, D).astype("float32") * 0.3)
    X = jnp.asarray(rng.randn(8, D).astype("float32"))

    def loss_pipe(Ws):
        y = parallel.pipeline_spmd(lambda W, x: jnp.tanh(x @ W), Ws, X, mesh,
                                   n_microbatches=4)
        return jnp.sum(y ** 2)

    def loss_seq(Ws):
        h = X
        for i in range(n_stages):
            h = jnp.tanh(h @ Ws[i])
        return jnp.sum(h ** 2)

    gp = jax.grad(loss_pipe)(Ws)
    gs = jax.grad(loss_seq)(Ws)
    assert_almost_equal(onp.asarray(gp), onp.asarray(gs), rtol=1e-3, atol=1e-5)


def _dense_form_of(layer, x):
    """The layer's output by the dense O(T*E) form (tests/moe_dense.py)."""
    import jax.numpy as jnp
    from moe_dense import dense_moe
    tokens = x._data.reshape(-1, x.shape[-1])
    gates = jax.nn.softmax(tokens @ layer.gate_weight.data()._data.T, -1)
    top_vals, top_idx = jax.lax.top_k(gates, layer.top_k)
    top_vals = top_vals / jnp.sum(top_vals, -1, keepdims=True)
    return onp.asarray(dense_moe(
        tokens, top_vals, top_idx, layer.w1.data()._data,
        layer.w2.data()._data, jax.nn.relu)).reshape(x.shape)


def test_moe_layer():
    _need_devices(8)
    mesh = parallel.make_mesh({"ep": 8})
    layer = parallel.MoELayer(num_experts=8, hidden_size=16, ffn_hidden=32,
                              top_k=2)
    layer.initialize()
    assert layer.w1.sharding == jax.sharding.PartitionSpec("ep", None, None)
    x = nd.random.normal(shape=(4, 6, 16))
    out = layer(x)
    assert out.shape == (4, 6, 16)
    assert_almost_equal(out.asnumpy(), _dense_form_of(layer, x), rtol=1e-4,
                        atol=1e-5)
    # a capacity no longer exists: asking for one warns and drops nothing
    import warnings as _w
    with _w.catch_warnings(record=True) as rec:
        _w.simplefilter("always")
        capped = parallel.MoELayer(num_experts=8, hidden_size=4,
                                   ffn_hidden=8, capacity_factor=0.5)
    assert any("capacity_factor" in str(w.message) for w in rec)
    assert not hasattr(capped, "capacity_factor")


def test_moe_router_z_loss():
    """r3 (weak #8): aux includes the ST-MoE router z-loss — scaled-up
    router logits must RAISE the aux loss even with identical softmax."""
    import jax.numpy as jnp
    from incubator_mxnet_tpu.parallel.moe import router_z_loss
    logits = jnp.asarray(onp.random.RandomState(0).randn(16, 4)
                         .astype("float32"))
    z1 = float(router_z_loss(logits))
    z2 = float(router_z_loss(logits + 10.0))  # same softmax, bigger logits
    assert z2 > z1 >= 0.0
    # and the layer folds it into aux: same weights, z_loss_coef on vs off
    onp.random.seed(1)
    mkw = dict(num_experts=4, hidden_size=8, ffn_hidden=16, top_k=2)
    mx.random.seed(2)
    l1 = parallel.MoELayer(z_loss_coef=0.0, **mkw)
    l1.initialize()
    mx.random.seed(2)
    l2 = parallel.MoELayer(z_loss_coef=1.0, **mkw)
    l2.initialize()
    x = nd.random.normal(shape=(3, 5, 8))
    _, a1 = l1.forward_with_aux(x)
    _, a2 = l2.forward_with_aux(x)
    assert float(a2.asnumpy()) > float(a1.asnumpy())


def test_moe_dropless_and_aux_loss():
    # Nothing is dropped: the one dispatch equals the dense form, however
    # few tokens there are for how many experts.
    layer = parallel.MoELayer(num_experts=4, hidden_size=8, ffn_hidden=16,
                              top_k=2)
    layer.initialize()
    x = nd.random.normal(shape=(3, 5, 8))
    out, aux = layer.forward_with_aux(x)
    assert out.shape == (3, 5, 8)
    # aux loss is >= 1 (equals 1 at perfect balance) and finite
    a = float(aux.asnumpy())
    assert a >= 0.99 and onp.isfinite(a)
    assert_almost_equal(out.asnumpy(), _dense_form_of(layer, x), rtol=1e-4,
                        atol=1e-5)
    assert_almost_equal(layer(x).asnumpy(), out.asnumpy(), rtol=0, atol=0)
    # one token, more experts than tokens: still every choice served
    one = nd.random.normal(shape=(1, 1, 8))
    assert_almost_equal(layer(one).asnumpy(), _dense_form_of(layer, one),
                        rtol=1e-4, atol=1e-5)


def test_kvstore_pull_isolation():
    # pull() shares immutable buffers; later updates on either side must not
    # leak to the other (VERDICT weak #4 regression test).
    kv = mx.kv.create("local")
    kv.init("w", nd.ones((3,)))
    out = nd.zeros((3,))
    kv.pull("w", out=out)
    kv.push("w", nd.full((3,), 7.0))  # store now holds 7s
    assert_almost_equal(out, onp.ones((3,)))  # snapshot unchanged
    out[:] = 5.0  # caller-side in-place write
    fresh = nd.zeros((3,))
    kv.pull("w", out=fresh)
    assert_almost_equal(fresh, 7 * onp.ones((3,)))  # store unaffected


def test_gradient_compression():
    gc = parallel.GradientCompression(type="2bit", threshold=0.5)
    g = nd.array([0.6, -0.7, 0.2, 0.0])
    q1 = gc.compress_decompress(g, key="k")
    assert_almost_equal(q1, [0.5, -0.5, 0.0, 0.0])
    # error feedback: residual [0.1,-0.2,0.2,0] accumulates with the next push
    q2 = gc.compress_decompress(nd.array([0.4, 0.0, 0.2, 0.0]), key="k")
    assert_almost_equal(q2, [0.5, 0.0, 0.0, 0.0])


def test_kvstore_api():
    kv = mx.kv.create("local")
    kv.init("3", nd.ones((2, 3)))
    out = nd.zeros((2, 3))
    kv.pull("3", out=out)
    assert_almost_equal(out, onp.ones((2, 3)))
    kv.push("3", [nd.ones((2, 3))] * 4)  # aggregate multi-device push
    kv.pull("3", out=out)
    assert_almost_equal(out, 4 * onp.ones((2, 3)))
    # updater path (server-side optimizer)
    kv2 = mx.kv.create("local")
    kv2.set_optimizer(mx.optimizer.SGD(learning_rate=0.1))
    kv2.init(0, nd.ones((2,)))
    kv2.push(0, nd.ones((2,)))
    out2 = nd.zeros((2,))
    kv2.pull(0, out=out2)
    assert_almost_equal(out2, [0.9, 0.9], rtol=1e-5, atol=1e-6)


def test_ring_attention_gradients():
    _need_devices(8)
    import jax.numpy as jnp
    mesh = parallel.make_mesh({"sp": 8})
    rng = onp.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(1, 2, 64, 16).astype("float32"))
               for _ in range(3))

    def loss(q, k, v):
        return jnp.sum(jnp.sin(parallel.ring_attention(q, k, v, mesh=mesh,
                                                       causal=True)))

    def ref(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / 4.0
        qi = jnp.arange(64)[:, None]
        ki = jnp.arange(64)[None, :]
        s = jnp.where(qi >= ki, s, -jnp.inf)
        p = jax.nn.softmax(s, -1)
        return jnp.sum(jnp.sin(jnp.einsum("bhqk,bhkd->bhqd", p, v)))

    g = jax.grad(loss, (0, 1, 2))(q, k, v)
    gr = jax.grad(ref, (0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        assert float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))) < 1e-4


def test_ulysses_attention_matches_reference():
    """Head-scatter all-to-all SP (parallel/ulysses.py) == dense attention;
    heads divisible by the sp axis."""
    _need_devices(8)
    import jax.numpy as jnp
    mesh = parallel.make_mesh({"sp": 8})
    B, H, S, D = 2, 8, 64, 16
    rng = onp.random.RandomState(3)
    q = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))
    k = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))
    v = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))

    def ref_attn(q, k, v, causal):
        s = onp.einsum("bhqd,bhkd->bhqk", q, k) / onp.sqrt(D)
        if causal:
            mask = onp.tril(onp.ones((S, S), bool))
            s = onp.where(mask, s, -onp.inf)
        p = onp.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        return onp.einsum("bhqk,bhkd->bhqd", p, v)

    for causal in (False, True):
        out = parallel.ulysses_attention(q, k, v, mesh=mesh, causal=causal)
        ref = ref_attn(onp.asarray(q), onp.asarray(k), onp.asarray(v), causal)
        assert_almost_equal(onp.asarray(out), ref, rtol=1e-3, atol=1e-4)
    # ulysses and ring agree with each other too
    ring = parallel.ring_attention(q, k, v, mesh=mesh, causal=True)
    uly = parallel.ulysses_attention(q, k, v, mesh=mesh, causal=True)
    assert_almost_equal(onp.asarray(uly), onp.asarray(ring), rtol=1e-3,
                        atol=1e-4)


def test_ulysses_attention_gradients():
    _need_devices(8)
    import jax
    import jax.numpy as jnp
    mesh = parallel.make_mesh({"sp": 8})
    B, H, S, D = 1, 8, 32, 8
    rng = onp.random.RandomState(4)
    q = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))
    k = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))
    v = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))

    def loss_u(q, k, v):
        return jnp.sum(jnp.sin(parallel.ulysses_attention(
            q, k, v, mesh=mesh, causal=True)))

    def loss_d(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(D)
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.sum(jnp.sin(jnp.einsum("bhqk,bhkd->bhqd", p, v)))

    gu = jax.grad(loss_u, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gu, gd):
        assert_almost_equal(onp.asarray(a), onp.asarray(b), rtol=2e-3,
                            atol=2e-4)


def test_ring_attention_flash_local_step(monkeypatch):
    """r3: the ring's LOCAL step rides the Pallas flash kernel (interpret
    mode on CPU) — per-shard memory O(block^2), not O((S/n)^2). Forward +
    grad parity vs the dense single-device reference, causal and dense."""
    _need_devices(8)
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops.attention import (attention_route,
                                                   flash_attention_supported)
    mesh = parallel.make_mesh({"sp": 8})
    B, H, S, D = 1, 2, 1024, 128   # D=128: the streamed kernels, by shape
    assert flash_attention_supported((B, H, S // 8, D))  # kernel engages
    assert attention_route((B, H, S // 8, D)) == "streamed"
    rng = onp.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(B, H, S, D).astype("float32")) * 0.3
               for _ in range(3))

    def ref(q, k, v, causal):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(1.0 * D)
        if causal:
            qi = jnp.arange(S)[:, None]
            ki = jnp.arange(S)[None, :]
            s = jnp.where(qi >= ki, s, -jnp.inf)
        p = jax.nn.softmax(s, -1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    assert "pallas_call" in str(jax.make_jaxpr(
        lambda q, k, v: parallel.ring_attention(q, k, v, mesh=mesh))(q, k, v))
    for causal in (False, True):
        out = parallel.ring_attention(q, k, v, mesh=mesh, causal=causal)
        want = ref(q, k, v, causal)
        assert float(jnp.max(jnp.abs(out - want))) < 2e-4, causal

        g = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(
            parallel.ring_attention(q, k, v, mesh=mesh, causal=causal))),
            (0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(ref(q, k, v, causal))),
                      (0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            rel = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
            assert rel < 1e-3, causal


def test_ulysses_attention_flash_local_step(monkeypatch):
    """r3: Ulysses' post-all-to-all local attention rides the flash kernel
    (full S on H/n heads). Forward + grad parity vs dense reference."""
    _need_devices(8)
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops.attention import (attention_route,
                                                   flash_attention_supported)
    mesh = parallel.make_mesh({"sp": 8})
    B, H, S, D = 1, 8, 256, 128    # D=128: the streamed kernels, by shape
    assert flash_attention_supported((B, H // 8, S, D))  # kernel engages
    assert attention_route((B, H // 8, S, D)) == "streamed"
    rng = onp.random.RandomState(1)
    q, k, v = (jnp.asarray(rng.randn(B, H, S, D).astype("float32")) * 0.3
               for _ in range(3))

    def ref(q, k, v, causal):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(1.0 * D)
        if causal:
            qi = jnp.arange(S)[:, None]
            ki = jnp.arange(S)[None, :]
            s = jnp.where(qi >= ki, s, -jnp.inf)
        p = jax.nn.softmax(s, -1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    assert "pallas_call" in str(jax.make_jaxpr(
        lambda q, k, v: parallel.ulysses_attention(q, k, v, mesh=mesh))(q, k, v))
    for causal in (False, True):
        out = parallel.ulysses_attention(q, k, v, mesh=mesh, causal=causal)
        want = ref(q, k, v, causal)
        assert float(jnp.max(jnp.abs(out - want))) < 2e-4, causal
        g = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(
            parallel.ulysses_attention(q, k, v, mesh=mesh, causal=causal))),
            (0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(ref(q, k, v, causal))),
                      (0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            rel = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
            assert rel < 1e-3, causal


def test_dist_async_kvstore_priority_and_staleness():
    """r3: DistAsyncKVStore (the dist_async/P3 analog) — staleness window
    counts per key, sync resets counters, and the averaging propagates
    priority classes in DESCENDING order (P3's overlap idea). Single
    process: the collective itself degenerates, so we observe the batching
    order via a recording stub."""
    from incubator_mxnet_tpu.kvstore.kvstore import DistAsyncKVStore
    kv = DistAsyncKVStore(staleness=3)
    assert kv.type.startswith("dist_async")
    kv.init("low", nd.zeros((2,)))
    kv.init("hi", nd.zeros((2,)))
    order = []
    kv._num_workers = 2  # force the sync path; record instead of allgather
    kv._average_batch = lambda keys: order.append(tuple(keys))
    kv._key_priority["hi"] = 5   # P3: later layers pushed at higher prio
    for step in range(3):
        kv.push(["low", "hi"], [nd.ones((2,)), nd.ones((2,))])
    # both keys hit the staleness bound in the same push -> hi first
    assert order == [("hi",), ("low",)], order
    assert kv._push_count["low"] == 0 and kv._push_count["hi"] == 0
    order.clear()
    kv.push("low", nd.ones((2,)), priority=0)
    kv.sync()   # forced full sync mid-window, still priority-ordered
    assert order == [("hi",), ("low",)], order
    # mx.kv.create routes the reference's store names
    import incubator_mxnet_tpu as mx
    assert type(mx.kv.create("dist_async")).__name__ == "DistAsyncKVStore"
    assert type(mx.kv.create("dist_device_sync")).__name__ == "DistKVStore"


def test_dist_async_p3_slicing(monkeypatch):
    """P3 slicing (ref p3store_dist.h:40): within a priority class, no
    collective exceeds MXTPU_P3_SLICE elements, big tensors split across
    several bounded collectives, small ones batch together — and the
    reassembled averages are exact."""
    from incubator_mxnet_tpu.kvstore.kvstore import DistAsyncKVStore
    from jax.experimental import multihost_utils
    monkeypatch.setenv("MXTPU_P3_SLICE", "100")
    calls = []

    def fake_allgather(cat):
        calls.append(onp.asarray(cat).size)
        return onp.stack([onp.asarray(cat)] * 2)  # 2 identical workers

    monkeypatch.setattr(multihost_utils, "process_allgather", fake_allgather)
    kv = DistAsyncKVStore(staleness=1)
    kv._num_workers = 2
    kv.init("big", nd.arange(250).astype("float32"))    # 3 slices
    kv.init("s1", nd.arange(30).astype("float32"))
    kv.init("s2", nd.arange(40).astype("float32"))
    kv._key_priority = {"big": 0, "s1": 5, "s2": 5}
    kv._sync_keys(["big", "s1", "s2"])
    # every collective bounded; smalls batched into ONE (30+40<=100); big
    # split into ceil(250/100)=3; high-priority class runs FIRST
    assert max(calls) <= 100, calls
    assert calls[0] == 70, calls          # s1+s2 batch leads (priority 5)
    assert calls[1:] == [100, 100, 50], calls
    # values: identical-worker average == original
    onp.testing.assert_allclose(kv._data["big"].asnumpy(),
                                onp.arange(250, dtype="float32"))
    onp.testing.assert_allclose(kv._data["s2"].asnumpy(),
                                onp.arange(40, dtype="float32"))


def test_dist_async_epoch_budget_caps_collectives():
    """Uneven-shard contract: begin_epoch caps staleness rounds at
    min_steps//staleness so a straggler worker reaches every collective;
    pushes past the cap stay local; sync() lifts the cap."""
    from incubator_mxnet_tpu.kvstore.kvstore import DistAsyncKVStore
    kv = DistAsyncKVStore(staleness=2)
    kv.init("w", nd.zeros((2,)))
    rounds = []
    kv._num_workers = 2
    kv._average_batch = lambda keys: rounds.append(tuple(keys))
    # single-process: the step-count allgather degenerates to local min
    orig_workers = kv._num_workers
    kv._num_workers = 1
    budget = kv.begin_epoch(5)      # min_steps=5, staleness=2 -> 2 rounds
    kv._num_workers = orig_workers
    assert budget == 2
    for _ in range(8):              # run PAST the agreed schedule
        kv.push("w", nd.ones((2,)))
    assert len(rounds) == 2, rounds  # capped: pushes 5..8 stayed local
    kv.sync()                        # epoch boundary forces the average
    assert len(rounds) == 3, rounds
    # after sync the schedule is lifted: staleness windows fire again
    kv.push("w", nd.ones((2,)))
    kv.push("w", nd.ones((2,)))
    assert len(rounds) == 4, rounds


def test_pipeline_1f1b_matches_gpipe_and_sequential():
    """r3: hand-scheduled 1F1B (pipeline_1f1b_grads) produces the same loss
    and gradients as running the stage stack sequentially under autodiff
    (and hence as the GPipe path, which is autodiff over the fwd ring).
    Also checks the stated memory bound: the stash is n_stages slots, not
    n_microbatches."""
    _need_devices(8)
    import jax.numpy as jnp
    mesh = parallel.make_mesh({"pp": 8})
    p, D, m, mb = 8, 8, 16, 2   # m=16 microbatches of 2 rows each
    rng = onp.random.RandomState(5)
    Ws = jnp.asarray(rng.randn(p, D, D).astype("float32") * 0.3)
    bs = jnp.asarray(rng.randn(p, D).astype("float32") * 0.1)
    params = {"w": Ws, "b": bs}
    x = jnp.asarray(rng.randn(m * mb, D).astype("float32"))
    y = jnp.asarray(rng.randn(m * mb, D).astype("float32"))

    def stage_fn(par, h):
        return jnp.tanh(h @ par["w"] + par["b"])

    def loss_fn(out, yb):
        return jnp.sum((out - yb) ** 2)

    loss, grads, dx = parallel.pipeline_1f1b_grads(
        stage_fn, loss_fn, params, x, y, mesh, n_microbatches=m)

    # sequential reference: same math under plain autodiff
    def seq_loss(par, x, y):
        # identical microbatching: per-microbatch loss summed, /m at the end
        def one(xm, ym):
            h = xm
            for s in range(p):
                h = stage_fn({"w": par["w"][s], "b": par["b"][s]}, h)
            return loss_fn(h, ym)
        xs = x.reshape(m, mb, D)
        ys = y.reshape(m, mb, D)
        return sum(one(xs[i], ys[i]) for i in range(m)) / m

    ref_loss, (ref_g, ref_dx) = jax.value_and_grad(
        lambda par, xx: seq_loss(par, xx, y), argnums=(0, 1))(params, x)
    assert abs(float(loss) - float(ref_loss)) / abs(float(ref_loss)) < 1e-5
    for k in ("w", "b"):
        a = onp.asarray(grads[k]) / m   # 1F1B sums per microbatch; ref /m
        b = onp.asarray(ref_g[k])
        assert onp.abs(a - b).max() / (onp.abs(b).max() + 1e-9) < 1e-4, k
    a = onp.asarray(dx) / m
    b = onp.asarray(ref_dx)
    assert onp.abs(a - b).max() / (onp.abs(b).max() + 1e-9) < 1e-4


def test_pipeline_interleaved_matches_sequential():
    """r4: interleaved (virtual-stage) 1F1B — v chunks per device, static
    greedy-scheduled tick tables — reproduces the sequential loss and
    gradients exactly (arXiv:2104.04473 §2.2 schedule idea)."""
    _need_devices(4)
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from incubator_mxnet_tpu.parallel.pipeline_interleaved import (
        pipeline_interleaved_grads)
    mesh = Mesh(onp.array(jax.devices()[:4]), ("pp",))
    p, v, D, m, mb = 4, 2, 8, 8, 2
    V = v * p
    rng = onp.random.RandomState(7)
    Ws = jnp.asarray(rng.randn(V, D, D).astype("float32") * 0.3)
    bs = jnp.asarray(rng.randn(V, D).astype("float32") * 0.1)
    x = jnp.asarray(rng.randn(m * mb, D).astype("float32"))
    y = jnp.asarray(rng.randn(m * mb, D).astype("float32"))

    def stage_fn(par, h):
        W, b = par
        return jnp.tanh(h @ W + b)

    def loss_fn(out, yb):
        return jnp.sum((out - yb) ** 2)

    # chunk-major stacking: virtual stage S = c*p + d
    params = (Ws.reshape(v, p, D, D), bs.reshape(v, p, D))
    loss, grads, dx = pipeline_interleaved_grads(
        stage_fn, loss_fn, params, x, y, mesh, n_microbatches=m, v=v)

    def seq_loss(par, xx, yy):
        Wv, bv = par
        def one(xm, ym):
            h = xm
            for S in range(V):
                h = stage_fn((Wv[S], bv[S]), h)
            return loss_fn(h, ym)
        xs = xx.reshape(m, mb, D)
        ys = yy.reshape(m, mb, D)
        return sum(one(xs[i], ys[i]) for i in range(m)) / m

    ref_loss, (ref_g, ref_dx) = jax.value_and_grad(
        lambda par, xx: seq_loss(par, xx, y), argnums=(0, 1))(
        (Ws, bs), x)
    assert abs(float(loss) - float(ref_loss)) / abs(float(ref_loss)) < 1e-5
    for a, b in zip(grads, ref_g):
        got = onp.asarray(a).reshape(b.shape) / m
        ref = onp.asarray(b)
        assert onp.abs(got - ref).max() / (onp.abs(ref).max() + 1e-9) < 1e-4
    got = onp.asarray(dx) / m
    ref = onp.asarray(ref_dx)
    assert onp.abs(got - ref).max() / (onp.abs(ref).max() + 1e-9) < 1e-4


def test_interleaved_schedule_invariants():
    """The greedy scheduler's output is a VALID pipeline schedule: every op
    exactly once, one op per device-tick, dependencies respected with the
    executor's 1-tick ring latency, and interleaving strictly shrinks the
    equal-cost bubble at m >= 2p (the regime the docs table reports)."""
    from incubator_mxnet_tpu.parallel.pipeline_interleaved import (
        interleaved_schedule, schedule_stats)
    m, p, v = 16, 4, 2
    V = v * p
    ticks = interleaved_schedule(m, p, v)
    seen = set()
    fin_F, fin_B = {}, {}
    for t, row in enumerate(ticks):
        assert len(row) == p
        for d, op in enumerate(row):
            if op is None:
                continue
            typ, c, i = op
            S = c * p + d
            assert (typ, S, i) not in seen
            seen.add((typ, S, i))
            if typ == "F":
                if S > 0:
                    assert fin_F[(S - 1, i)] < t, (S, i, t)
                fin_F[(S, i)] = t
            else:
                if S == V - 1:
                    assert fin_F[(S, i)] < t
                else:
                    assert fin_B[(S + 1, i)] < t
                fin_B[(S, i)] = t
    assert len(seen) == 2 * V * m
    b1 = schedule_stats(interleaved_schedule(m, p, 1), p,
                        f_cost=1, b_cost=1)["bubble_fraction"]
    b2 = schedule_stats(ticks, p, f_cost=1, b_cost=1)["bubble_fraction"]
    assert b2 < b1, (b1, b2)
    # the stash bound must saturate with m (schedule-depth, not
    # n_microbatches — the docs/PERF_PIPELINE.md memory claim)
    from incubator_mxnet_tpu.parallel.pipeline_interleaved import \
        _stash_bound
    bounds = [_stash_bound(interleaved_schedule(mm, p, v), p, v, mm)
              for mm in (8, 16, 32)]
    assert bounds[1] == bounds[2], bounds
    assert bounds[2] <= 2 * (p + v), bounds


def test_zero1_optimizer_state_sharding():
    """r3 (arXiv:2004.13336, PAPERS.md): TrainStep(zero=True) shards
    optimizer states (incl. fp32 masters) over dp — state memory / update
    FLOPs divide by |dp| while params stay replicated — and the training
    trajectory matches the unsharded step."""
    _need_devices(8)
    from jax.sharding import PartitionSpec as P
    mesh = parallel.make_mesh({"dp": 8})

    def build():
        net = nn.HybridSequential()
        net.add(nn.Dense(32, activation="relu", in_units=16),
                nn.Dense(8, in_units=32))
        mx.random.seed(7)
        net.initialize(mx.init.Xavier())
        net.cast("float16")  # multi_precision -> fp32 masters in the state
        return net

    X = nd.random.normal(shape=(16, 16)).astype("float16")
    y = nd.array(onp.random.RandomState(0).randint(0, 8, 16).astype("float32"))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def run(zero):
        net = build()
        tr = gluon.Trainer(net.collect_params(), "adam",
                           {"learning_rate": 1e-2, "multi_precision": True})
        step = jit.TrainStep(net, loss_fn, tr, mesh=mesh, zero=zero)
        losses = [float(step(X, y).mean().asnumpy()) for _ in range(3)]
        return net, tr, losses

    net0, tr0, l0 = run(False)
    net1, tr1, l1 = run(True)
    onp.testing.assert_allclose(l1, l0, rtol=2e-3, atol=1e-4)
    for p0, p1 in zip(net0.collect_params().values(),
                      net1.collect_params().values()):
        onp.testing.assert_allclose(p1.data().asnumpy().astype("float32"),
                                    p0.data().asnumpy().astype("float32"),
                                    rtol=2e-2, atol=1e-3)

    # states are genuinely dp-sharded; params replicated
    sharded = 0
    for st in tr1._states:
        leaves = jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(lambda x: x._data
                                   if hasattr(x, "_data") else x, st))
        for leaf in leaves:
            spec = getattr(leaf.sharding, "spec", None)
            if spec and len(spec) and spec[0] == "dp":
                sharded += 1
                n_shard = leaf.sharding.num_devices_sharded \
                    if hasattr(leaf.sharding, "num_devices_sharded") else 8
                # per-device shard holds 1/8 of the leaf
                db = leaf.addressable_shards[0].data.size
                assert db * 8 == leaf.size, (db, leaf.size)
    assert sharded >= 4, "no dp-sharded optimizer state found"
    for p in net1.collect_params().values():
        spec = getattr(p.data()._data.sharding, "spec", ())
        assert not spec or all(s is None for s in spec), spec


# ---- one build path: the mesh step is compiled ahead, its state laid out
# once at the build, and a dispatch passes what it holds ------------------
def _mlp(seed=11):
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu", in_units=8),
            nn.Dense(4, in_units=16))
    mx.random.seed(seed)
    net.initialize(mx.init.Xavier())
    return net


def _mlp_batch():
    rng = onp.random.RandomState(5)
    return (nd.array(rng.randn(8, 8).astype("float32")),
            nd.array(rng.randint(0, 4, 8).astype("float32")))


def _state_leaves(trainer):
    return [x for st in trainer._states
            for x in jax.tree_util.tree_leaves(jit._tree_to_data(st))]


@pytest.mark.parametrize("zero", [False, True])
def test_mesh_state_is_laid_out_once_and_passed_as_held(monkeypatch, zero):
    _need_devices(2)
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = parallel.make_mesh({"dp": 2}, devices=jax.devices()[:2])
    net = _mlp()
    tr = gluon.Trainer(net.collect_params(), "adam", {"learning_rate": 1e-2})
    step = parallel.DataParallelTrainStep(net, gluon.loss.
                                          SoftmaxCrossEntropyLoss(), tr,
                                          mesh=mesh, zero=zero)
    X, y = _mlp_batch()
    step(X, y)
    repl = NamedSharding(mesh, P())
    for p in net.collect_params().values():
        d = p.data()._data
        assert d.sharding.is_equivalent_to(repl, d.ndim), p.name
    sharded = 0
    for leaf in _state_leaves(tr):
        over_dp = zero and leaf.ndim and leaf.shape[0] % 2 == 0
        want = NamedSharding(mesh, P("dp")) if over_dp else repl
        assert leaf.sharding.is_equivalent_to(want, leaf.ndim), leaf.shape
        sharded += bool(over_dp)
    assert sharded == (8 if zero else 0)

    # the second call puts its two inputs on the mesh, and no state leaf
    put = []
    real = jax.device_put
    monkeypatch.setattr(jax, "device_put",
                        lambda x, *a, **k: put.append(x) or real(x, *a, **k))
    step(X, y)
    assert len(put) == 2 and all(
        any(x is a._data for a in (X, y)) for x in put), put


@pytest.mark.parametrize("moved", ["set_data", "load_states"])
def test_state_moved_between_mesh_steps_is_laid_out_again(tmp_path, moved):
    """A `set_data` or `trainer.load_states` between two steps leaves a
    single-device array where the compiled mesh program expects its
    layout: the step puts it back and trains as the one-device step."""
    _need_devices(2)
    mesh = parallel.make_mesh({"dp": 2}, devices=jax.devices()[:2])
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    X, y = _mlp_batch()
    new_w = onp.random.RandomState(9).randn(16, 8).astype("float32") * 0.1

    def run(**mesh_kw):
        net = _mlp()
        tr = gluon.Trainer(net.collect_params(), "adam",
                           {"learning_rate": 1e-2})
        step = jit.TrainStep(net, loss_fn, tr, **mesh_kw)
        losses = [step(X, y).asnumpy()]
        if moved == "set_data":
            list(net.collect_params().values())[0].set_data(new_w)
        else:
            fname = str(tmp_path / ("states%d" % len(mesh_kw)))
            tr.save_states(fname)
            tr.load_states(fname)
        losses += [step(X, y).asnumpy() for _ in range(2)]
        return net, tr, losses

    net1, tr1, l1 = run()
    net2, tr2, l2 = run(mesh=mesh, zero=True)
    onp.testing.assert_allclose(l2, l1, rtol=1e-6, atol=1e-6)
    for p1, p2 in zip(net1.collect_params().values(),
                      net2.collect_params().values()):
        onp.testing.assert_allclose(p2.data().asnumpy(), p1.data().asnumpy(),
                                    rtol=1e-6, atol=1e-6)
    for s1, s2 in zip(_state_leaves(tr1), _state_leaves(tr2)):
        onp.testing.assert_allclose(onp.asarray(s2), onp.asarray(s1),
                                    rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("fault", ["unknown_axis", "unshaped_state_leaf"])
def test_a_spec_that_cannot_be_built_raises_out_of_the_step(fault):
    """No second, lazily compiling path behind a failed spec: the first
    error reaches the caller and nothing is cached."""
    _need_devices(2)
    from jax.sharding import PartitionSpec as P
    net = _mlp()
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    X, y = _mlp_batch()
    if fault == "unknown_axis":
        mesh = parallel.make_mesh({"dp": 2}, devices=jax.devices()[:2])
        list(net.collect_params().values())[0].sharding = P("tp")
        step = parallel.DataParallelTrainStep(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), tr, mesh=mesh)
        error = ValueError
    else:
        step = jit.TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), tr)
        tr._init_kvstore()
        tr._init_states()
        tr._states[0] = 0.0          # no shape, no dtype
        error = AttributeError
    with pytest.raises(error):
        step(X, y)
    assert not step._cache_keys


def test_dp_tp_step_keeps_a_sharded_parameter_sharded():
    _need_devices(4)
    from jax.sharding import NamedSharding
    mesh = parallel.make_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    net = nn.HybridSequential()
    net.add(parallel.ColParallelDense(32, activation="relu", in_units=8),
            parallel.RowParallelDense(4, in_units=32))
    mx.random.seed(5)
    net.initialize(mx.init.Xavier())
    tr = gluon.Trainer(net.collect_params(), "adam", {"learning_rate": 1e-2})
    step = parallel.DataParallelTrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), tr, mesh=mesh, zero=True)
    X, y = _mlp_batch()
    params = list(net.collect_params().values())
    assert sum(p.sharding is not None for p in params) >= 2
    losses = []
    for _ in range(3):
        losses.append(float(step(X, y).mean().asnumpy()))
        for idx, p in enumerate(params):
            if p.sharding is None:
                continue
            want = NamedSharding(mesh, p.sharding)
            d = p.data()._data
            assert d.sharding.is_equivalent_to(want, d.ndim), p.name
            assert d.addressable_shards[0].data.size * 2 == d.size
            for leaf in jax.tree_util.tree_leaves(
                    jit._tree_to_data(tr._states[idx])):
                assert leaf.sharding.is_equivalent_to(want, leaf.ndim)
    assert losses[2] < losses[0]
    assert step._last_stats["flops"] > 0
