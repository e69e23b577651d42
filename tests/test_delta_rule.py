"""The chunked gated delta rule (`ops/delta_rule.py`) on the CPU, float32,
BOTH schedules (`xla`: heads of 32 x 16, which the kernels refuse; `pallas`:
the kernel pair interpreted under MXTPU_FLASH_INTERPRET=1 at heads of 128 x
128): against the recurrence a position at a time (the benchmark's
reference, perfbench/reference/solar-open2-250b.py `delta_rule`) in value
and in all five gradients; where a channel's decay is so strong that
exp(-G) leaves float32 inside a chunk; at b near 2 (a negative eigenvalue)
and near 0; what a bfloat16 state would read against the same limit; which
schedule a call takes, that a refused shape traces the parent's program,
that the lanes entry (b, t, h d) is the 4-D entry's to the bit, and that
three layers trace each kernel once."""
import contextlib
import functools
import hashlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.ops import kernel_trace
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
B, H = 2, 2
#: (d_k, d_v) a schedule is tested at: the kernels take whole lane tiles
#: only, so heads of 32 x 16 stay on the XLA form even where kernels run
DIMS = {"xla": (32, 16), "pallas": (128, 128)}
PATHS = tuple(DIMS)


@contextlib.contextmanager
def on(path):
    """A context in which a call of `path`'s shapes takes `path`: the
    kernels interpreted for `pallas`, nothing set for `xla`."""
    with pytest.MonkeyPatch.context() as patch:
        if path == "pallas":
            patch.setenv("MXTPU_FLASH_INTERPRET", "1")
        else:
            patch.delenv("MXTPU_FLASH_INTERPRET", raising=False)
        yield


@pytest.fixture(params=PATHS)
def path(request):
    """The schedule under test; every call inside the test takes it and
    the counter says so."""
    before = {p: rule_mod._CALLS.value(path=p) for p in PATHS}
    with on(request.param):
        yield request.param
    other = PATHS[1 - PATHS.index(request.param)]
    assert rule_mod._CALLS.value(path=other) == before[other]


def inputs(seed, t, path, a_log=None, softplus_in=-3.0, beta_logit=0.0,
           dv=None):
    """q (scaled) and k of unit length, as the mixer hands them over;
    g = -A softplus(x) a channel, A a head in [1, 16] or exp(a_log);
    b = 2 sigmoid(.)."""
    rng = onp.random.default_rng(seed)
    dk = DIMS[path][0]
    dv = dv or DIMS[path][1]

    def unit(x):
        return x / onp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.standard_normal((B, t, H, dk))) * dk ** -0.5
    k = unit(rng.standard_normal((B, t, H, dk)))
    v = rng.standard_normal((B, t, H, dv))
    a = rng.uniform(1, 16, H) if a_log is None else onp.exp(a_log) \
        * onp.ones(H)
    if softplus_in == "strong":
        softplus_in = onp.where(onp.arange(dk) < dk // 2, 4.0, -3.0)
    x = rng.standard_normal((B, t, H, dk)) + softplus_in
    g = -a[:, None] * onp.log1p(onp.exp(x))
    beta = 2 / (1 + onp.exp(-(rng.standard_normal((B, t, H)) + beta_logit)))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


#: the decay the issue names: A = 16 everywhere, the softplus's input +4 on
#: half of the channels (g = -64 a position: exp(-G) is inf from the second
#: position on) and -3 on the others, which remember for a hundred
STRONG = dict(a_log=onp.log(16.0), softplus_in="strong")
#: the gradients' cases: (seed, t, the inputs' other arguments)
CASES = {"plain": (1, 150, {}), "strong": (2, 130, STRONG),
         "near-2": (3, 128, dict(beta_logit=6.0)),
         "near-0": (3, 128, dict(beta_logit=-6.0))}


def close(got, want):
    return float(jnp.abs(got - want).max()) \
        < LIMIT * float(jnp.abs(want).max())


def cotangent(shape, seed=5):
    return jnp.asarray(onp.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


@functools.lru_cache(maxsize=None)
def gradients(path, case, chunk):
    """All five gradients of sum(o w) at one case, by the op on `path`
    (ONE backward: the five tests of a case share it) and by autodiff of
    the recurrence -> (args, got, want)."""
    seed, t, more = CASES[case]
    args = inputs(seed, t, path, **more)
    w = cotangent(args[2].shape)
    want = jax.grad(lambda *a: jnp.sum(reference.delta_rule(*a) * w),
                    (0, 1, 2, 3, 4))(*args)
    with on(path):
        got = jax.jit(jax.grad(
            lambda *a: jnp.sum(gated_delta_rule(*a, chunk=chunk) * w),
            (0, 1, 2, 3, 4)))(*args)
    return args, got, want


@pytest.mark.parametrize("t,chunk", [(64, 16), (100, 16), (128, 64),
                                     (200, 64), (7, 64)])
def test_chunk_form_is_the_recurrence(path, t, chunk):
    """T a multiple of the chunk, not one, and shorter than one."""
    args = inputs(0, t, path)
    want = reference.delta_rule(*args)
    got = gated_delta_rule(*args, chunk=chunk)
    assert got.shape == want.shape == (B, t, H, DIMS[path][1])
    assert got.dtype == want.dtype
    assert close(got, want)


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("arg", range(5), ids=ARGS)
def test_gradients_are_the_recurrences(path, arg, chunk):
    """Autodiff of the chunk form (the solve's and the scan's own rules) or
    the backward kernel, against autodiff of the recurrence; T = 150 is
    padded inside."""
    _, got, want = gradients(path, "plain", chunk)
    assert close(got[arg], want[arg])


def test_the_naive_split_would_overflow_where_the_op_does_not(path):
    args = inputs(2, 130, path, **STRONG)
    G = jnp.cumsum(args[3][:, :64], 1)
    assert not bool(jnp.isfinite(jnp.exp(-G)).all())     # exp(G_i) exp(-G_j)
    got = gated_delta_rule(*args, chunk=64)
    assert bool(jnp.isfinite(got).all())
    assert close(got, reference.delta_rule(*args))
    # nothing was clamped: a channel that strong forgets within a position
    assert float(args[3].min()) < -100


@pytest.mark.parametrize("arg", range(5), ids=ARGS)
def test_strong_decay_gradients_are_finite_and_the_recurrences(path, arg):
    _, got, want = gradients(path, "strong", 64)
    assert bool(jnp.isfinite(got[arg]).all())
    assert close(got[arg], want[arg])


@pytest.mark.parametrize("case,low,high", [("near-2", 1.9, 2.0),
                                           ("near-0", 0.0, 0.1)])
def test_beta_at_its_ends(path, case, low, high):
    """b near 2: I - b k k^T has the eigenvalue -1 along k, the state
    flips sign there and does not grow; near 0 almost nothing is
    written."""
    args, got, want = gradients(path, case, 64)
    assert low <= float(jnp.median(args[4])) <= high
    assert close(gated_delta_rule(*args, chunk=64),
                 reference.delta_rule(*args))
    for arg in (1, 4):
        assert close(got[arg], want[arg])


@pytest.mark.parametrize("every", [1, 64], ids=["position", "chunk"])
def test_a_bfloat16_state_fails_the_float32_limit(every):
    """The other reading of LIMIT. The recurrence with its state rounded
    to bfloat16 after every position, or once a chunk of 64 as a kernel
    that carried a bfloat16 state between chunks would, reads 1e-3 of the
    largest output from the float32 recurrence: a hundred times the limit
    the chunk form is held to (asserted at fifty). This float32 comparison
    is what holds the state's type: against bfloat16 activations it cannot
    be seen (PERF.md section 6, PR 31)."""
    q, k, v, g, beta = inputs(0, 128, "xla")

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
    _, got = jax.lax.scan(step, jnp.zeros((B, H) + DIMS["xla"], jnp.float32),
                          by_time)
    want = reference.delta_rule(q, k, v, g, beta)
    assert float(jnp.abs(got.swapaxes(0, 1) - want).max()) \
        > 50 * LIMIT * float(jnp.abs(want).max())


def test_chunk_16_and_chunk_64_agree(path):
    args = inputs(4, 128, path)
    assert close(gated_delta_rule(*args, chunk=16),
                 gated_delta_rule(*args, chunk=64))


def test_bfloat16_values_come_back_in_their_type(path):
    q, k, v, g, beta = inputs(5, 64, path)
    got = gated_delta_rule(q, k, v.astype(jnp.bfloat16), g, beta)
    assert got.dtype == jnp.bfloat16
    want = reference.delta_rule(q, k, v.astype(jnp.bfloat16).astype(
        jnp.float32), g, beta)
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) \
        < 2 ** -7 * float(jnp.abs(want).max())


def test_no_state_a_position_is_ever_made(path):
    """What the gradient keeps of the states is the one at each chunk's
    start: no array of the forward and backward program, the kernels'
    bodies among it, has T x d_k x d_v entries a head (d_v = 40 on the XLA
    form, so that the sub-blocks' 16 x 16 x d_k products are no array of
    that size)."""
    dk, dv = (DIMS[path][0], 40) if path == "xla" else DIMS[path]
    args = inputs(6, 256, path, dv=dv)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(gated_delta_rule(*a, chunk=64)), (0, 1, 2, 3, 4)))(
            *args)
    per_position = B * 256 * H * dk * dv

    def sizes(j):
        for eqn in j.eqns:
            for var in eqn.outvars:
                yield int(onp.prod(var.aval.shape))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from sizes(sub)

    largest = max(sizes(jaxpr.jaxpr))
    assert largest < per_position
    # the states that ARE kept: one a chunk
    assert largest >= B * H * (256 // 64) * dk * dv


def test_the_counter_counts_traces(path):
    before = rule_mod._CALLS.value(path=path)
    f = jax.jit(lambda *a: gated_delta_rule(*a, chunk=16))
    for _ in range(3):                       # traced once, run three times
        f(*inputs(7, 32, path))
    assert rule_mod._CALLS.value(path=path) - before == 1
    assert 'mxtpu_delta_rule_total{path="%s"}' % path \
        in telemetry.REGISTRY.export_text()


def test_the_lanes_entry_is_the_4d_entrys_to_the_bit(path):
    """`gated_delta_rule_lanes` on (b, t, h d) operands, t no multiple of
    the chunk: values and all five gradients are the 4-D entry's bit for
    bit on either schedule, and a call by either entry counts once."""
    args = inputs(11, 100, path)
    b, t, h, _ = args[0].shape
    w = cotangent(args[2].shape)

    def by_heads(*a):
        return jnp.sum(gated_delta_rule(*a, chunk=16) * w)

    def by_lanes(*a):
        q, k, v, g = (x.reshape(b, t, -1) for x in a[:4])
        o = rule_mod.gated_delta_rule_lanes(q, k, v, g, a[4], h, chunk=16)
        assert o.shape == (b, t, h * w.shape[-1])
        return jnp.sum(o.reshape(w.shape) * w)

    before = rule_mod._CALLS.value(path=path)
    want = jax.jit(jax.value_and_grad(by_heads, (0, 1, 2, 3, 4)))(*args)
    assert rule_mod._CALLS.value(path=path) == before + 1
    got = jax.jit(jax.value_and_grad(by_lanes, (0, 1, 2, 3, 4)))(*args)
    assert rule_mod._CALLS.value(path=path) == before + 2
    for mine, theirs in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(want)):
        onp.testing.assert_array_equal(onp.asarray(mine),
                                       onp.asarray(theirs))


def test_every_op_is_under_the_scope(path, monkeypatch):
    """... and both kernels: lowered for the TPU platform (no chip, no
    compile) a gradient on the kernels' schedule is two Mosaic calls, each
    with the scope in its path, and no loop of the XLA form."""
    def loss(*a):
        return jnp.sum(gated_delta_rule(*a, chunk=16))

    args = inputs(8, 32, path)
    if path == "xla":
        text = jax.jit(loss).lower(*args).as_text(debug_info=True)
        assert "delta_rule" in text and "tpu_custom_call" not in text
        return
    monkeypatch.delenv("MXTPU_FLASH_INTERPRET")
    monkeypatch.setattr(rule_mod, "_kernels_run_here", lambda: True)
    text = jax.jit(jax.grad(loss, (0, 1, 2, 3, 4))).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 2 and "stablehlo.while" not in text
    paths = [line for line in text.splitlines() if "/pallas_call" in line]
    assert len(paths) == 2
    for line, kernel in zip(sorted(paths, key=lambda p: "bwd" in p),
                            ("delta_rule_fwd", "delta_rule_bwd")):
        assert "delta_rule)/%s/pallas_call" % kernel in line \
            or "delta_rule/%s/pallas_call" % kernel in line


# ------------------------------------------- the two schedules, side by side
def test_the_two_schedules_agree_on_values_and_gradients():
    """One input through both: the kernels interpreted against the XLA
    form (which the same shape takes where no kernel runs)."""
    args = inputs(9, 200, "pallas")
    w = cotangent(args[2].shape)

    def both(*a):
        return jax.value_and_grad(
            lambda *t: jnp.sum(gated_delta_rule(*t, chunk=64) * w),
            (0, 1, 2, 3, 4))(*a)

    read = {}
    for name in PATHS:
        before = rule_mod._CALLS.value(path=name)
        with on(name):
            # (a function of its own: jit's cache does not see the schedule)
            read[name] = jax.jit(lambda *a: both(*a))(*args)
        assert rule_mod._CALLS.value(path=name) == before + 1
    assert abs(float(read["xla"][0] - read["pallas"][0])) \
        < LIMIT * float(jnp.abs(w).sum()) ** 0.5
    for got, want in zip(read["pallas"][1], read["xla"][1]):
        assert close(got, want)


@pytest.mark.parametrize("dk,dv,chunk,takes", [
    (128, 128, 64, True), (256, 128, 16, True), (128, 256, 128, True),
    (64, 64, 64, False), (128, 64, 64, False), (96, 128, 64, False),
    (128, 128, 24, False), (128, 128, 8, False), (128, 128, 48, False),
    (128, 128, 256, True), (128, 128, 512, False)])
def test_the_kernels_take_whole_lane_tiles_and_sub_blocks(dk, dv, chunk,
                                                           takes):
    assert rule_mod._kernel_takes(dk, dv, chunk) is takes


def test_the_counter_reads_the_schedule_by_platform_and_shape(monkeypatch):
    """Off the TPU nothing runs a kernel; where kernels run (interpreted
    here) the shape decides, and a refused shape says so on the counter
    alone."""
    def traced(path, chunk=64):
        args = inputs(7, 64, path)
        return jax.make_jaxpr(
            lambda *a: gated_delta_rule(*a, chunk=chunk))(*args)

    def counted():
        return tuple(rule_mod._CALLS.value(path=p) for p in PATHS)

    monkeypatch.delenv("MXTPU_FLASH_INTERPRET", raising=False)
    xla, kernels = counted()
    assert "pallas_call" not in str(traced("pallas"))
    assert counted() == (xla + 1, kernels)
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    assert "pallas_call" in str(traced("pallas"))
    assert counted() == (xla + 1, kernels + 1)
    assert "pallas_call" not in str(traced("xla"))          # heads of 32 x 16
    assert "pallas_call" not in str(traced("pallas", 24))   # no 16-row blocks
    assert counted() == (xla + 3, kernels + 1)


#: sha256 of str(jaxpr) of the op and of its gradient at the PARENT commit
#: (439e48d, before the kernels came), by (shapes (b, t, h, d_k, d_v),
#: chunk, are kernels run): off the TPU at the cell's head, and where
#: kernels run at a head and at a chunk they refuse
PARENT_JAXPR = {
    ((1, 256, 2, 128, 128), 64, False):
        ("ba0697a3bdd0c36a", "153626e9ca36c1e7"),
    ((2, 100, 2, 32, 16), 16, True):
        ("c93423900b85723f", "6587bf9c2076d222"),
    ((1, 128, 2, 128, 128), 24, True):
        ("52b1d0ee49eb4064", "d2672c7a0bd756ed"),
}


@pytest.mark.parametrize("shape,chunk,kernels_run", list(PARENT_JAXPR),
                         ids=["off-the-tpu", "narrow-head", "chunk-of-24"])
def test_a_call_the_kernels_do_not_take_is_the_program_it_was(
        monkeypatch, shape, chunk, kernels_run):
    if kernels_run:
        monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    else:
        monkeypatch.delenv("MXTPU_FLASH_INTERPRET", raising=False)
    b, t, h, dk, dv = shape
    f32 = jnp.float32
    specs = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((b, t, h, dk), f32), ((b, t, h, dk), f32),
        ((b, t, h, dv), jnp.bfloat16), ((b, t, h, dk), f32), ((b, t, h), f32))]

    def rule(*a):
        return gated_delta_rule(*a, chunk=chunk)

    def loss(*a):
        return jnp.sum(rule(*a).astype(f32))

    texts = [str(jax.make_jaxpr(f)(*specs))
             for f in (rule, jax.grad(loss, (0, 1, 2, 3, 4)))]
    assert tuple(hashlib.sha256(t.encode()).hexdigest()[:16]
                 for t in texts) == PARENT_JAXPR[shape, chunk, kernels_run]


# --------------------------------------------------- set-up: traces counted
def traces(kernel):
    return kernel_trace._TRACES.value(kernel=kernel)


def test_three_layers_trace_each_kernel_once_and_a_warm_call_nothing():
    """`kernel_trace.traced_once`: three calls of one shape in one program
    (the cell's three `K` layers) bind ONE traced body a kernel, forward
    (the one that keeps the chunk starts) and backward; the calls after
    the first trace nothing and count nothing."""
    args = inputs(10, 80, "pallas")     # a length no other test traces
    names = ("delta_rule_fwd", "delta_rule_bwd")

    def three_layers(q, k, v, g, beta):
        o = v
        for _ in range(3):
            o = gated_delta_rule(q, k, o, g, beta, chunk=16)
        return jnp.sum(o)

    with on("pallas"):
        step = jax.jit(jax.grad(three_layers, (0, 1, 2, 3, 4)))
        before = [traces(n) for n in names]
        calls = rule_mod._CALLS.value(path="pallas")
        jax.block_until_ready(step(*args))
        assert [traces(n) for n in names] == [b + 1 for b in before]
        assert rule_mod._CALLS.value(path="pallas") == calls + 3
        settled = ("mxtpu_kernel_traces_total",
                   "mxtpu_kernel_trace_seconds_total",
                   "mxtpu_delta_rule_total",
                   "mxtpu_compile_phase_events_total")
        everything = [telemetry.REGISTRY.get(n).series() for n in settled]
        for _ in range(2):
            jax.block_until_ready(step(*args))
        assert everything == [telemetry.REGISTRY.get(n).series()
                              for n in settled]
