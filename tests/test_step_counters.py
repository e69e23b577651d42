"""Step counters (PR 50): small integer values a block computes anyway leave
a compiled train step as ONE int32 vector and are booked to that step once
the host knows them, never waiting for the device
(`gluon/_functional.collect_step_counter`, `gluon.utils.recompute`,
`jit.TrainStep._count` / `_resolve_counters`, `jit.flush_step_counters`).
`MoELayer`'s rows an expert are the only user: what is published is held
here to a NumPy count of the same step's `top_idx`."""
import collections
import re

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, jit, nd, parallel
from incubator_mxnet_tpu.gluon import _functional, nn, utils
from incubator_mxnet_tpu.parallel import moe
from incubator_mxnet_tpu.telemetry import spans

T, D, H, E, K = 64, 16, 12, 8, 3
_KEEP_NOTHING = jax.checkpoint_policies.nothing_saveable


class _Routed(gluon.HybridBlock):
    """x -> a MoELayer, called directly, recomputed, or recomputed under a
    policy; with `bias_rate` the layer returns (y, moved) and the moved
    bias is booked here, outside the recomputed block, as the models do."""

    def __init__(self, how, **moe_kwargs):
        super().__init__()
        self._how = how
        with self.name_scope():
            self.moe = parallel.MoELayer(E, D, H, top_k=K, ep_axis=None,
                                         **moe_kwargs)

    def forward(self, x):
        if self._how == "direct":
            y = self.moe(x)
        else:
            y = utils.recompute(
                self.moe, x,
                policy=_KEEP_NOTHING if self._how == "policy" else None)
        if isinstance(y, tuple):
            y, moved = y
            self.moe.move_bias(moved)
        return y


def _net(how="direct", **moe_kwargs):
    mx.random.seed(7)
    net = _Routed(how, **moe_kwargs)
    net.initialize(mx.init.Xavier())
    return net


def _step(net, mesh=None):
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-2})
    if mesh is None:
        return jit.TrainStep(net, gluon.loss.L2Loss(), trainer)
    return parallel.DataParallelTrainStep(net, gluon.loss.L2Loss(), trainer,
                                          mesh=mesh)


def _batch(seed, rows=T):
    rng = onp.random.default_rng(seed)
    return (nd.array(rng.standard_normal((rows, D)).astype("float32")),
            nd.zeros((rows, D)))


def _host_count(layer, x):
    """The rows each (held) expert gets of `x`, counted in NumPy from the
    router's own choice at the weights as they stand."""
    bias = (layer.router_bias.data()._data,) \
        if layer._router == "sigmoid_bias" else ()
    top_idx = onp.asarray(layer.route(
        x._data.reshape(-1, D), layer.gate_weight.data()._data, *bias)[3])
    first, count = layer.held or (0, E)
    return onp.bincount(top_idx.ravel(), minlength=E)[first:first + count]


def _records():
    return [r for r in spans.snapshot() if r["name"] == "train:counters"]


@pytest.fixture(autouse=True)
def _fresh_ring():
    """An empty span ring, and no series of the layers other tests built:
    the registry clamps a family's label sets at 64 a process."""
    jit.flush_step_counters()
    spans.reset()
    for family in (moe._ROWS, moe._WINDOWS, moe._EXPERT_ROWS, moe._STARVED,
                   moe._WINDOW_ROWS):
        for labels, _ in family.series():
            family.remove(**labels)
    yield
    jit.flush_step_counters()


# ------------------------------------------------- what a step publishes
@pytest.mark.parametrize("how", ["direct", "recompute", "policy"])
@pytest.mark.parametrize("held", [None, (2, 4)], ids=["all", "held"])
@pytest.mark.parametrize("router", [
    dict(router="softmax"),
    dict(router="sigmoid_bias", scale=2.5),
    dict(router="sigmoid_bias", bias_rate=0.05)],
    ids=["softmax", "sigmoid_bias", "bias_rule"])
def test_published_rows_are_a_count_of_the_steps_own_choices(
        how, held, router):
    net = _net(how, held=held, **router)
    step = _step(net)
    want = []
    for i in range(3):
        x, y = _batch(i)
        want.append(_host_count(net.moe, x))      # before the step moves it
        step(x, y)
    jit.flush_step_counters()
    records = _records()
    assert [r["args"]["step"] for r in records] == [1, 2, 3]
    for record, rows in zip(records, want):
        (counter,) = record["args"]["counters"]
        assert counter["name"] == net.moe.name
        assert counter["values"] == rows.tolist()
        assert counter["held"] == (held is not None)
        assert counter["even_rows"] == T * K / E
        if held is None:
            assert sum(counter["values"]) == T * K      # nothing dropped
    layer = net.moe.name
    assert moe._ROWS.value(layer=layer) == sum(int(r.sum()) for r in want)
    assert moe._EXPERT_ROWS.value(layer=layer, stat="min") == want[-1].min()
    assert moe._EXPERT_ROWS.value(layer=layer, stat="max") == want[-1].max()
    assert moe._STARVED.value(layer=layer) == int((want[-1] == 0).sum())
    # held at these sizes: one window of the worst case a step
    assert moe._WINDOWS.value(layer=layer) == (3 if held else 0)


def test_a_record_sits_on_its_steps_own_dispatch_clock():
    net = _net(held=(2, 4))
    step = _step(net)
    for i in range(3):
        step(*_batch(i))
    jit.flush_step_counters()
    dispatches = {r["args"]["step"]: r for r in spans.snapshot()
                  if r["name"] == "train:dispatch"}
    assert sorted(dispatches) == [1, 2, 3]
    for record in _records():
        dispatch = dispatches[record["args"]["step"]]
        assert record["start_us"] == dispatch["start_us"]
        assert record["parent_id"] == dispatch["span_id"]
        assert record["dur_us"] >= 0


def test_on_a_mesh_the_vector_counts_the_whole_batch():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    mesh = parallel.make_mesh({"dp": 4}, devices=jax.devices()[:4])
    try:
        net = _net("recompute", held=(2, 4), router="sigmoid_bias")
        step = _step(net, mesh)
        x, y = _batch(5)
        want = _host_count(net.moe, x)
        step(x, y)
        vector = step._unresolved[-1][2] if step._unresolved else None
        jit.flush_step_counters()
    finally:
        parallel.mesh.set_current_mesh(None)
    (record,) = _records()
    assert record["args"]["counters"][0]["values"] == want.tolist()
    if vector is not None:      # every chip holds its own copy
        assert vector.sharding.is_fully_replicated


def test_a_router_forced_onto_one_expert_runs_a_second_window():
    """2048 tokens, 2 of 16 experts held, top-2: W = 1024 of a worst case
    of 4096. Every token's first choice is held expert 0, so 2048 + live
    rows need at least two windows, and the count says so."""
    tokens, experts, first = 2048, 16, 4
    mx.random.seed(3)
    layer = parallel.MoELayer(experts, 8, 8, top_k=2, held=(first, 2),
                              ep_axis=None)
    layer.initialize(mx.init.Xavier())
    gw = onp.asarray(layer.gate_weight.data()._data).copy()
    gw[first] = 50.0                      # positive inputs: always the top
    layer.gate_weight.set_data(nd.array(gw))
    window = moe.held_window_rows(tokens, 2, 2, experts)
    assert window == 1024 < tokens * 2
    step = _step(layer)
    rng = onp.random.default_rng(0)
    x = nd.array(rng.uniform(0.5, 1.5, (tokens, 8)).astype("float32"))
    before = moe._WINDOWS.value(layer=layer.name)
    step(x, nd.zeros((tokens, 8)))
    jit.flush_step_counters()
    (counter,) = _records()[0]["args"]["counters"]
    live = sum(counter["values"])
    assert counter["values"][0] == tokens and counter["window_rows"] == window
    ran = -(-live // window)
    assert ran >= 2
    assert moe._WINDOWS.value(layer=layer.name) - before == ran
    assert moe._WINDOW_ROWS.value(layer=layer.name) == window


# ------------------------------------------------------- what it leaves alone
def _dense():
    mx.random.seed(1)
    net = nn.HybridSequential()
    net.add(nn.Dense(24, activation="relu", in_units=D), nn.Dense(D,
                                                                  in_units=24))
    net.initialize(mx.init.Xavier())
    return net


def _lowered(net):
    spec = jax.ShapeDtypeStruct((T, D), jnp.float32)
    return _step(net).lower(spec, spec).as_text()


def _body(text):
    """The text's lines but each function's head and return (which name
    every result), value numbers taken out, as a multiset."""
    return collections.Counter(
        re.sub(r"%\d+(#\d+)?(:\d+)?", "%N", line.strip())
        for line in text.splitlines()
        if "func.func" not in line and not line.strip().startswith("return"))


def _results(text):
    return text.split("func.func public @main")[1].split("\n")[0] \
        .split("->")[-1]


def test_a_step_that_counts_nothing_is_the_program_it_was(monkeypatch):
    """No MoELayer: the step's text is the same with the channel there and
    with its collector stubbed out, and no result is an int32 vector. With
    one MoELayer the two texts differ by the one result and no operation
    (a concatenation of one vector is that vector)."""
    dense, routed = _lowered(_dense()), _lowered(_net("policy", held=(2, 4)))
    monkeypatch.setattr(_functional, "collect_step_counter",
                        lambda *a, **kw: None)
    assert _lowered(_dense()) == dense
    assert "xi32>" not in _results(dense)
    without = _lowered(_net("policy", held=(2, 4)))
    assert _body(without) == _body(routed)
    assert "tensor<4xi32>" in _results(routed)
    assert "xi32>" not in _results(without)


def test_an_eager_call_and_a_forward_register_nothing():
    net = _net("recompute", held=(2, 4))
    x, _ = _batch(0)
    assert _functional._STATE.step_counters is None
    net(x)                                            # eager
    assert _functional._STATE.step_counters is None
    jit.EvalStep(net)(x)                              # a compiled forward
    assert _functional._STATE.step_counters is None
    assert not _records()


# ------------------------------------------------------- never waiting
class _Late:
    """A step's vector that has not arrived: `is_ready()` says no, and
    reading it is noted."""

    def __init__(self, values):
        self.values, self.read = onp.asarray(values, onp.int32), False

    def is_ready(self):
        return False

    def copy_to_host_async(self):
        pass

    def __array__(self, dtype=None, copy=None):
        self.read = True
        return self.values


def _layout(net):
    return [(net.moe.name, 4, moe._publish_load,
             dict(held=True, window_rows=T * K, even_rows=T * K / E))]


def test_no_call_waits_for_a_vector_that_has_not_arrived():
    net = _net(held=(2, 4))
    step = _step(net)
    step(*_batch(0))
    jit.flush_step_counters()
    late = _Late([1, 2, 3, 4])
    with spans.span("train:dispatch", step=99) as dispatch:
        pass
    step._unresolved.append((99, dispatch, late, _layout(net)))
    # two more real steps: theirs queue up behind it, nothing is read
    step(*_batch(1))
    step(*_batch(2))
    assert not late.read and len(step._unresolved) == 3
    assert [r["args"]["step"] for r in _records()] == [1]
    assert jit.flush_step_counters() == 3
    assert late.read and not step._unresolved
    assert [r["args"]["step"] for r in _records()] == [1, 99, 2, 3]
    assert _records()[1]["args"]["counters"][0]["values"] == [1, 2, 3, 4]


def test_the_list_is_bounded_and_what_it_drops_is_counted(monkeypatch):
    monkeypatch.setattr(jit, "_COUNTERS_KEPT", 3)
    net = _net(held=(2, 4))
    step = _step(net)
    before = jit._COUNTERS_DROPPED.value()
    lates = [_Late([i, 0, 0, 0]) for i in range(5)]
    for late in lates:
        step._step_count += 1
        with spans.span("train:dispatch", step=step._step_count) as dispatch:
            pass
        step._count(dispatch, late, _layout(net))
    assert len(step._unresolved) == 3
    assert jit._COUNTERS_DROPPED.value() - before == 2
    assert not any(late.read for late in lates)
    jit.flush_step_counters()
    assert [r["args"]["counters"][0]["values"][0] for r in _records()] \
        == [2, 3, 4]


def test_a_step_object_that_goes_books_what_has_arrived_and_waits_for_nothing():
    """A loop that ends leaves its last steps unresolved (nothing calls the
    step again): the object's end books those whose values are there and
    drops the one that is not, unread."""
    import gc
    net = _net(held=(2, 4))
    step = _step(net)
    for i in range(2):
        step(*_batch(i))
    assert step._unresolved
    step._unresolved[-1][2].block_until_ready()    # the device has finished
    late = _Late([0, 0, 0, 0])
    with spans.span("train:dispatch", step=3) as dispatch:
        pass
    step._unresolved.append((3, dispatch, late, _layout(net)))
    del step
    gc.collect()
    assert [r["args"]["step"] for r in _records()] == [1, 2]
    assert jit.flush_step_counters() == 0 and not late.read
