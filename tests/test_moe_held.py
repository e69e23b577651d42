"""The held share of parallel/moe.py (`MoELayer(held=(first, count))`,
`dropless_moe_held`) and the sigmoid router with its selection bias,
against the dense form (tests/moe_dense.py); and that `held=None` is the
path OLMoE's cell runs, untouched."""
import re

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from incubator_mxnet_tpu import nd, parallel, telemetry
from incubator_mxnet_tpu.parallel import moe

from moe_dense import dense_moe

T, D, H, E, K = 48, 16, 24, 12, 5


def _held_sum(*args):
    """`dropless_moe_held`'s sum; the rows it also hands back (what each
    held expert got) have tests of their own at the end of this file."""
    return moe.dropless_moe_held(*args)[0]


def _case(seed, dtype=jnp.float32):
    rng = onp.random.default_rng(seed)
    tokens = jnp.asarray(rng.standard_normal((T, D)), dtype)
    w_up = jnp.asarray(rng.standard_normal((E, D, H)) / 4, dtype)
    w_down = jnp.asarray(rng.standard_normal((E, H, D)) / 4, dtype)
    scores = rng.random((T, E))
    # uneven: expert 3 is in nearly every token's choice, expert 7 in none
    scores[:, 3] += 1.0
    scores[:, 7] -= 1.0
    top_vals, top_idx = jax.lax.top_k(jnp.asarray(scores, jnp.float32), K)
    return tokens, top_vals, top_idx, w_up, w_down


def _only(top_vals, top_idx, first, count):
    """The dense form's weights with every expert outside the share at 0."""
    held = (top_idx >= first) & (top_idx < first + count)
    return jnp.where(held, top_vals, 0.0)


@pytest.mark.parametrize("first,count", [(0, 4), (2, 3), (8, 4), (7, 1),
                                         (0, 12)])
@pytest.mark.parametrize("gated", [False, True])
def test_held_dispatch_is_the_held_experts_part_of_the_dense_sum(
        first, count, gated):
    """Shares at the start, in the middle, at the end, of one expert nobody
    chose, and of everything; float32, 1e-5: summation order."""
    tokens, top_vals, top_idx, w_up, w_down = _case(0)
    w_gate = w_up[::-1] if gated else None
    act = moe._ACTIVATIONS["relu2"]
    sl = slice(first, first + count)
    with jax.default_matmul_precision("highest"):
        got = _held_sum(
            tokens, top_vals, top_idx, w_up[sl], w_down[sl], act, first, E,
            None if w_gate is None else w_gate[sl])
        want = dense_moe(tokens, _only(top_vals, top_idx, first, count),
                         top_idx, w_up, w_down, act, w_gate)
    onp.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("sorts", [True, False], ids=["sort", "scatter"])
def test_held_dispatch_gradients_are_the_dense_ones(sorts, monkeypatch):
    """Under both forms of `_rows_to_tokens` (the width chooses: told here
    what to say)."""
    monkeypatch.setattr(moe, "_sorts_the_window", lambda width: sorts)
    tokens, top_vals, top_idx, w_up, w_down = _case(1)
    first, count = 2, 4
    sl = slice(first, first + count)
    act = moe._ACTIVATIONS["relu2"]

    def held(tokens, top_vals, w_up_s, w_down_s):
        return jnp.sum(_held_sum(
            tokens, top_vals, top_idx, w_up_s, w_down_s, act, first, E) ** 2)

    def dense(tokens, top_vals, w_up_s, w_down_s):
        full_up = w_up.at[sl].set(w_up_s)
        full_down = w_down.at[sl].set(w_down_s)
        return jnp.sum(dense_moe(
            tokens, _only(top_vals, top_idx, first, count), top_idx,
            full_up, full_down, act) ** 2)

    args = (tokens, top_vals, w_up[sl], w_down[sl])
    with jax.default_matmul_precision("highest"):
        got = jax.grad(held, (0, 1, 2, 3))(*args)
        want = jax.grad(dense, (0, 1, 2, 3))(*args)
    for g, w in zip(got, want):
        onp.testing.assert_allclose(g, w, rtol=2e-5,
                                    atol=2e-5 * float(jnp.abs(w).max()))


@pytest.mark.parametrize("shape,rows", [
    ((8192, 22, 8, 512), 6144),     # Nemotron's share: 5632 in whole 1024s
    ((1024, 4, 4, 64), 1024),       # 512 -> one 1024
    ((4096, 8, 64, 64), 4096 * 8),  # every expert held: T x k, the worst case
    ((48, 5, 3, 12), 48 * 3),       # a small share: the worst case is less
])
def test_window_rows_come_from_the_shapes(shape, rows):
    """W = min(T min(k, count), roundup(2 T k count / E, 1024))."""
    assert moe.held_window_rows(*shape) == rows


def _scoped_eqns(jaxpr, outer=""):
    """Every equation of a jaxpr, those of its loops' and calls' bodies
    among them, each with the named scopes it was traced under (a body's
    equations are under their call's scopes too)."""
    for eqn in jaxpr.eqns:
        path = outer + "/" + str(eqn.source_info.name_stack)
        yield eqn, path
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scoped_eqns(sub, path)


def _eqns(jaxpr):
    return (eqn for eqn, _ in _scoped_eqns(jaxpr))


def _row_counts(jaxpr, widths):
    return {v.aval.shape[0] for eqn in _eqns(jaxpr) for v in eqn.outvars
            if len(v.aval.shape) == 2 and v.aval.shape[1] in widths}


@pytest.mark.parametrize("sorts", [True, False], ids=["sort", "scatter"])
@pytest.mark.parametrize("gated", [False, True])
def test_no_op_reads_or_writes_more_rows_than_the_window(gated, sorts,
                                                         monkeypatch):
    """At Nemotron's ratio (8 of 512 held, 22 a token) W is 6144 of the
    65 536 worst-case rows. The value's jaxpr holds no (n, D) or (n, H)
    tensor with n > W but the tokens' own T rows and, where
    `_rows_to_tokens` sorts the window, its rows in token order, which
    carry min(k, count) - 1 rows more for the shifted reads of a token's
    run. The gradient's holds the
    kept buffers of 11 windows beside them, and the only ops that touch
    those are the zeros they start as, the loop that carries them and a
    window's slice in and out: no dead row is gathered, multiplied, squared
    or cast. The loop is a `while`."""
    monkeypatch.setattr(moe, "_sorts_the_window", lambda width: sorts)
    n_tokens, k, count, n_experts = 8192, 22, 8, 512
    window = moe.held_window_rows(n_tokens, k, count, n_experts)
    assert window == 6144
    tokens = jnp.zeros((n_tokens, D))
    top_vals = jnp.ones((n_tokens, k))
    top_idx = jnp.zeros((n_tokens, k), jnp.int32)
    w_up, w_down = jnp.zeros((count, D, H)), jnp.zeros((count, H, D))
    w_gate = w_up if gated else None

    def value(tokens, top_vals, w_up, w_down, w_gate):
        return jnp.sum(_held_sum(
            tokens, top_vals, top_idx, w_up, w_down, moe.relu2, 0, n_experts,
            w_gate))

    args = (tokens, top_vals, w_up, w_down, w_gate)
    jaxpr = jax.make_jaxpr(value)(*args).jaxpr
    assert any(e.primitive.name == "while" for e in _eqns(jaxpr))
    in_runs = {window + min(k, count) - 1} if sorts else set()
    assert _row_counts(jaxpr, (D, H)) - {D, H} \
        == {window, n_tokens} | in_runs
    jaxpr = jax.make_jaxpr(jax.grad(value, (0, 1, 2, 3)))(*args).jaxpr
    kept = 11 * window
    assert _row_counts(jaxpr, (D, H)) - {D, H} \
        == {window, n_tokens, kept} | in_runs
    touching = {e.primitive.name for e in _eqns(jaxpr)
                if any(getattr(v.aval, "shape", ()) in ((kept, D), (kept, H))
                       for v in list(e.invars) + list(e.outvars))}
    assert touching == {"broadcast_in_dim", "while", "dynamic_update_slice",
                        "dynamic_slice"}


def test_holding_every_expert_is_one_window_and_no_loop():
    """`held=(0, E)`: the window is T x k, and the program is straight-line
    in value and gradient."""
    tokens, top_vals, top_idx, w_up, w_down = _case(2)

    def value(tokens, top_vals, w_up, w_down):
        return jnp.sum(_held_sum(
            tokens, top_vals, top_idx, w_up, w_down, jax.nn.relu, 0, E))

    args = (tokens, top_vals, w_up, w_down)
    for fn in (value, jax.grad(value, (0, 1, 2, 3))):
        jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
        assert not any(e.primitive.name in ("while", "scan", "cond")
                       for e in _eqns(jaxpr))
        assert T * K in _row_counts(jaxpr, (D, H))


def _window_case(kind, rng):
    """-> (token (W,), live (W,), T, most) of one window: `kind` says how
    many rows a token has and which rows are live."""
    n_tokens, most = 40, 4
    if kind in ("one", "two", "most"):          # every token that many rows
        each = {"one": 1, "two": 2, "most": most}[kind]
        token = rng.permutation(onp.repeat(
            rng.choice(n_tokens, 24, replace=False), each))
        live = onp.ones(token.shape, bool)
    elif kind == "wider":                       # W = 2 T, Keye's ratio
        n_tokens, most = 32, 8
        token = rng.permutation(onp.repeat(onp.arange(n_tokens), most))[:64]
        live = onp.arange(64) < 40
    else:                       # 1 .. most rows a token, in any order
        token = rng.permutation(onp.repeat(onp.arange(n_tokens), most))[:96]
        live = {"none": onp.zeros(96, bool), "all": onp.ones(96, bool),
                "between": rng.random(96) < 0.5,
                "tail": onp.arange(96) < 31}[kind]
    return token.astype(onp.int32), live, n_tokens, most


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("kind", ["one", "two", "most", "none", "all",
                                  "between", "tail", "wider"])
def test_a_windows_rows_reach_their_tokens_as_a_scatter_add_sums_them(
        kind, dtype):
    """`_rows_to_tokens`, the sorted form (rows 20 wide), against
    `total.at[token].add(rows)` over the live rows, in float32: a token
    with 1, 2 and `most` rows, no live row, every row live, dead rows
    between live ones and behind them (a window's), more rows than tokens;
    rows of the forward's type and of the backward's, one array and two to
    be added. The order of a token's additions is all that may differ. A
    dead row's token and contents reach no sum."""
    rng = onp.random.default_rng(54)
    token, live, n_tokens, most = _window_case(kind, rng)
    width = 20
    assert moe._sorts_the_window(width)
    total = jnp.asarray(rng.standard_normal((n_tokens, width)), jnp.float32)
    rows, more = (jnp.asarray(rng.standard_normal((token.size, width)),
                              dtype) for _ in range(2))
    fn = jax.jit(moe._rows_to_tokens, static_argnums=4)
    for parts in (rows, (rows, more)):
        added = rows if parts is rows else rows.astype(jnp.float32) + more
        want = total.at[jnp.where(live, token, n_tokens)].add(
            added.astype(jnp.float32), mode="drop")
        got = fn(total, parts, token, live, most)
        assert got.dtype == jnp.float32 and got.shape == total.shape
        onp.testing.assert_allclose(got, want, rtol=1e-6, atol=2e-6)
        untouched = onp.setdiff1d(onp.arange(n_tokens), token[live])
        onp.testing.assert_array_equal(onp.asarray(got)[untouched],
                                       onp.asarray(total)[untouched])
    if kind == "none":
        onp.testing.assert_array_equal(got, total)


def test_the_rows_width_alone_chooses_between_the_two_forms():
    """A width of no prime factor over 3 keeps XLA's row scatter-add, in
    place (what the Keye, Solar and Nemotron cells' 2048, 4096 and 1024
    trace: the program it was); any other (Ling's 2560, Xing's 3584)
    sorts the window to its tokens, and its jaxpr holds a sort, two row
    gathers and no scatter. Both are the same sum."""
    for width, sorts in ((1024, False), (2048, False), (4096, False),
                         (1536, False), (3072, False), (16, False),
                         (2560, True), (3584, True), (5120, True)):
        assert moe._sorts_the_window(width) is sorts
    rng = onp.random.default_rng(5)
    token, live, n_tokens, most = _window_case("tail", rng)
    token = onp.where(live, token, onp.arange(token.size) % n_tokens)
    names, sums = {}, {}
    wide = jnp.asarray(rng.standard_normal((token.size, 40))
                       * live[:, None], jnp.bfloat16)
    for width in (32, 40):
        total = jnp.zeros((n_tokens, width), jnp.float32)
        rows = wide[:, :width]
        jaxpr = jax.make_jaxpr(lambda *a: moe._rows_to_tokens(*a, most))(
            total, rows, token, live).jaxpr
        names[width] = [e.primitive.name for e in _eqns(jaxpr)
                        if len(e.outvars[0].aval.shape) == 2
                        or e.primitive.name == "sort"]
        sums[width] = moe._rows_to_tokens(total, rows, token, live, most)
    assert names[32].count("scatter-add") == 1
    assert not {"sort", "gather"} & set(names[32])
    assert names[40].count("sort") == 1
    assert names[40].count("gather") == 2
    assert not {"scatter", "scatter-add"} & set(names[40])
    onp.testing.assert_allclose(sums[40][:, :32], sums[32],
                                rtol=1e-6, atol=2e-6)


def _routing(kind, n_tokens, k, first, count, n_experts, rng):
    """(T, k) distinct experts a token, by what the held experts get."""
    held = onp.arange(first, first + count)
    others = onp.setdiff1d(onp.arange(n_experts), held)
    idx = onp.stack([rng.permutation(others)[:k] for _ in range(n_tokens)])
    if kind == "every_token_on_every_held":          # the most windows
        idx[:, :count] = held
    elif kind == "two_held_a_token":                 # two windows
        idx[:, :2] = held[:2]
    elif kind == "all_on_one_expert":                # one window, full
        idx[:, 0] = held[1]
    elif kind == "random":                           # one window, part full
        idx = onp.stack([rng.permutation(n_experts)[:k]
                         for _ in range(n_tokens)])
    else:
        assert kind == "none_held"                   # no window at all
    return jnp.asarray(rng.permuted(idx, axis=1), jnp.int32)


@pytest.fixture(params=[True, False], ids=["kept", "computed-again"])
def kept(request, monkeypatch):
    """The backward reads the forward's rows from the kept buffers, or
    (buffers over `HELD_KEEP_BYTES`) computes its window's again."""
    if not request.param:
        monkeypatch.setattr(moe, "HELD_KEEP_BYTES", 0)
    return request.param


@pytest.mark.parametrize("gated", [False, True])
@pytest.mark.parametrize("kind,windows", [
    ("none_held", 0), ("random", 1), ("all_on_one_expert", 1),
    ("two_held_a_token", 2), ("every_token_on_every_held", 4)])
def test_windows_are_exact_at_any_load(kind, windows, gated, kept):
    """T = 1024, 4 of 64 held from the 9th on, 4 a token: W = 1024 of 4096
    rows. Value and every gradient against the dense form, whether no
    window runs, one, two or all four: nothing is dropped at any load."""
    n_tokens, k, first, count, n_experts = 1024, 4, 8, 4, 64
    window = moe.held_window_rows(n_tokens, k, count, n_experts)
    assert window == 1024
    rng = onp.random.default_rng(7)
    top_idx = _routing(kind, n_tokens, k, first, count, n_experts, rng)
    live = int(((top_idx >= first) & (top_idx < first + count)).sum())
    assert -(-live // window) == windows
    tokens = jnp.asarray(rng.standard_normal((n_tokens, D)), jnp.float32)
    top_vals = jnp.asarray(rng.random((n_tokens, k)), jnp.float32)
    shape = (n_experts, D, H)
    w_up = jnp.asarray(rng.standard_normal(shape) / 4, jnp.float32)
    w_gate = jnp.asarray(rng.standard_normal(shape) / 4, jnp.float32) \
        if gated else None
    w_down = jnp.asarray(rng.standard_normal((n_experts, H, D)) / 4,
                         jnp.float32)
    sl = slice(first, first + count)
    act = moe._ACTIVATIONS["relu2"]
    out_weights = jnp.asarray(rng.standard_normal((n_tokens, D)), jnp.float32)

    def held(tokens, top_vals, w_up_s, w_down_s, w_gate_s):
        return _held_sum(tokens, top_vals, top_idx, w_up_s,
                                     w_down_s, act, first, n_experts,
                                     w_gate_s)

    def dense(tokens, top_vals, w_up_s, w_down_s, w_gate_s):
        return dense_moe(
            tokens, _only(top_vals, top_idx, first, count), top_idx,
            w_up.at[sl].set(w_up_s), w_down.at[sl].set(w_down_s), act,
            None if w_gate_s is None else w_gate.at[sl].set(w_gate_s))

    args = (tokens, top_vals, w_up[sl], w_down[sl],
            None if w_gate is None else w_gate[sl])
    which = (0, 1, 2, 3, 4) if gated else (0, 1, 2, 3)
    def graded(fn):
        def loss(*a):
            out = fn(*a)
            return jnp.sum(out * out_weights), out
        return jax.value_and_grad(loss, which, has_aux=True)(*args)

    with jax.default_matmul_precision("highest"):
        (_, got), got_grads = graded(held)
        (_, want), want_grads = graded(dense)
    onp.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for g, w in zip(got_grads, want_grads):
        onp.testing.assert_allclose(
            g, w, rtol=2e-5, atol=2e-5 * max(float(jnp.abs(w).max()), 1e-6))
    if windows == 0:
        assert not any(bool(jnp.any(g)) for g in got_grads)


@pytest.mark.parametrize("sorts", [True, False], ids=["sort", "scatter"])
@pytest.mark.parametrize("gated", [False, True])
def test_what_a_kernel_leaves_in_dead_rows_reaches_no_sum(gated, monkeypatch,
                                                          kept, sorts):
    """A grouped matmul answers for the rows its groups own; on the chip
    the others hold whatever was there. With every such row of every
    grouped matmul's output and of its transpose poisoned (NaN), value and
    gradients are still the dense form's. Off the TPU those rows are
    zeros, which is why nothing else here can see a missing mask. Under
    both forms of `_rows_to_tokens`: the scatter-add ADDS its dead rows,
    the sorted form reads none."""
    monkeypatch.setattr(moe, "_sorts_the_window", lambda width: sorts)
    n_tokens, k, first, count, n_experts = 1024, 4, 8, 4, 64
    window = moe.held_window_rows(n_tokens, k, count, n_experts)
    rng = onp.random.default_rng(11)
    top_idx = _routing("random", n_tokens, k, first, count, n_experts, rng)
    live = int(((top_idx >= first) & (top_idx < first + count)).sum())
    assert 0 < live < window

    def poisoned(x):
        if getattr(x, "shape", ())[:1] != (window,) or x.ndim != 2:
            return x
        return jnp.where(jnp.arange(window)[:, None] < live, x, jnp.nan)

    ragged_dot, transpose = jax.lax.ragged_dot, jax.linear_transpose
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        lambda *a, **kw: poisoned(ragged_dot(*a, **kw)))
    monkeypatch.setattr(
        jax, "linear_transpose", lambda fn, *primals: lambda g: tuple(
            poisoned(d) for d in transpose(fn, *primals)(g)))
    tokens = jnp.asarray(rng.standard_normal((n_tokens, D)), jnp.float32)
    top_vals = jnp.asarray(rng.random((n_tokens, k)), jnp.float32)
    w_up = jnp.asarray(rng.standard_normal((count, D, H)) / 4, jnp.float32)
    w_down = jnp.asarray(rng.standard_normal((count, H, D)) / 4, jnp.float32)
    w_gate = w_up[::-1] if gated else None
    act = moe._ACTIVATIONS["relu2"]

    def held(tokens, top_vals, w_up, w_down, w_gate):
        return jnp.sum(_held_sum(
            tokens, top_vals, top_idx, w_up, w_down, act, first, n_experts,
            w_gate) ** 2)

    def dense(tokens, top_vals, w_up, w_down, w_gate):
        full = [jnp.zeros((n_experts,) + w.shape[1:]).at[
            first:first + count].set(w) for w in (w_up, w_down)]
        return jnp.sum(dense_moe(
            tokens, _only(top_vals, top_idx, first, count), top_idx, *full,
            act, None if w_gate is None else jnp.zeros(
                (n_experts, D, H)).at[first:first + count].set(w_gate)) ** 2)

    args = (tokens, top_vals, w_up, w_down, w_gate)
    which = (0, 1, 2, 3, 4) if gated else (0, 1, 2, 3)
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(held, which)(*args)
        monkeypatch.undo()
        want = jax.value_and_grad(dense, which)(*args)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        onp.testing.assert_allclose(g, w, rtol=2e-5,
                                    atol=2e-5 * float(jnp.abs(w).max()))


def _largest_rows(fn, args, width):
    """The most rows any (rows, width) array of fn's program has."""
    return max(_row_counts(jax.make_jaxpr(fn)(*args).jaxpr, (width,)))


def test_what_is_kept_follows_from_the_shapes(monkeypatch):
    """Kept: 8 of 512 experts in a 1024-wide latent space, 2688 hidden
    (65 536 rows x 3712 x 2 B = 486 MB); computed again: 8 of 320 SwiGLU
    experts 1280 wide on 4096 (872 MB). Where nothing is kept the gradient's
    program has no array of all the windows' rows."""
    def shapes(d, h, gated):
        tokens = jax.ShapeDtypeStruct((8192, d), jnp.bfloat16)
        w_up = jax.ShapeDtypeStruct((8, d, h), jnp.bfloat16)
        w_down = jax.ShapeDtypeStruct((8, h, d), jnp.bfloat16)
        order = jax.ShapeDtypeStruct((65536,), jnp.int32)
        return tokens, (w_up, w_up) if gated else (w_up,), w_down, order

    assert moe._keeps(*shapes(1024, 2688, False))
    assert not moe._keeps(*shapes(4096, 1280, True))
    n_tokens, k, first, count, n_experts = 1024, 4, 8, 4, 64
    rng = onp.random.default_rng(3)
    top_idx = _routing("random", n_tokens, k, first, count, n_experts, rng)
    tokens = jnp.zeros((n_tokens, D))
    top_vals = jnp.ones((n_tokens, k))
    w_up, w_down = jnp.zeros((count, D, H)), jnp.zeros((count, H, D))

    def grads():
        # a function of its own a reading: JAX keeps a function's trace
        return lambda *args: jax.grad(lambda *a: jnp.sum(
            _held_sum(a[0], a[1], top_idx, a[2], a[3],
                                  jax.nn.relu, first, n_experts)),
            (0, 1, 2, 3))(*args)

    args = (tokens, top_vals, w_up, w_down)
    assert _largest_rows(grads(), args, H) == n_tokens * k   # all windows'
    monkeypatch.setattr(moe, "HELD_KEEP_BYTES", 0)
    assert _largest_rows(grads(), args, H) == moe.held_window_rows(
        n_tokens, k, count, n_experts)


@pytest.mark.parametrize("gated,forward,both", [(False, 2, 8), (True, 3, 12)])
def test_one_body_the_grouped_matmuls_are_traced_once_a_pass(
        gated, forward, both):
    """The forward holds each grouped matmul once and the gradient at most
    four times (forward, recomputed, and the two transposes): one body run
    once a window, never a bounded path beside a full one. A second traced
    copy of the dispatch is a second set of Mosaic kernels in every expert
    layer of both compiled programs, which is set-up time (PERF.md: PR 32)."""
    layer = parallel.MoELayer(512, D, H, top_k=22, router="sigmoid_bias",
                              activation="relu2", held=(8, 8), gated=gated)
    layer.initialize()
    names = ("x",) + layer._weight_names()
    x = jnp.ones((2, 512, D), jnp.float32)
    weights = [w._data for w in layer._weights()]
    trained = tuple(i for i, n in enumerate(names) if n != "router_bias")

    def value(*arrays):
        return jnp.sum(layer._fn(dict(zip(names, arrays)), False))

    def grouped(fn):
        jaxpr = jax.make_jaxpr(fn)(x, *weights).jaxpr
        return sum(e.primitive.name.startswith("ragged_dot")
                   for e in _eqns(jaxpr))

    assert any(e.primitive.name == "while" for e in _eqns(
        jax.make_jaxpr(value)(x, *weights).jaxpr))
    assert grouped(value) == forward
    assert forward < grouped(jax.grad(value, trained)) <= both


def _layer(**kwargs):
    layer = parallel.MoELayer(E, D, H, top_k=K, **kwargs)
    layer.initialize()
    return layer


def test_sigmoid_router_bias_chooses_and_does_not_weigh():
    layer = _layer(router="sigmoid_bias", scale=5.0, activation="relu2")
    rng = onp.random.default_rng(3)
    tokens = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    gw = layer.gate_weight.data()._data
    bias = jnp.zeros((E,)).at[7].set(10.0).at[3].set(-10.0)
    _, gates, vals, idx = layer.route(tokens, gw, bias)
    # expert 7 is always chosen, expert 3 never, whatever their scores
    assert bool(jnp.all(jnp.any(idx == 7, -1)))
    assert not bool(jnp.any(idx == 3))
    # the weights are the sigmoid scores themselves, renormalised, x 5
    s = jax.nn.sigmoid(tokens @ gw.T)
    picked = jnp.take_along_axis(s, idx, -1)
    onp.testing.assert_allclose(
        vals, 5.0 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    onp.testing.assert_allclose(vals.sum(-1), 5.0, rtol=1e-6)
    # with no bias the choice is the k largest scores
    _, _, _, plain = layer.route(tokens, gw, jnp.zeros((E,)))
    assert bool(jnp.all(plain == jax.lax.top_k(s, K)[1]))


def test_sigmoid_weights_are_used_as_they_are_without_renormalisation():
    layer = _layer(router="sigmoid_bias", norm_topk_prob=False)
    tokens = jnp.asarray(onp.random.default_rng(4).standard_normal((T, D)),
                         jnp.float32)
    gw = layer.gate_weight.data()._data
    _, gates, vals, idx = layer.route(tokens, gw, jnp.zeros((E,)))
    onp.testing.assert_array_equal(vals,
                                   jnp.take_along_axis(gates, idx, -1))


def test_layer_with_a_share_against_the_dense_form_and_routes_apart():
    """The block: sigmoid-bias router on a wider input than the experts',
    relu^2 experts, 4 of 12 held from the 5th on."""
    layer = parallel.MoELayer(E, D, H, top_k=K, router="sigmoid_bias",
                              scale=2.5, activation="relu2", held=(4, 4),
                              router_units=2 * D)
    layer.initialize()
    assert layer.w1.shape == (4, D, H) and layer.w2.shape == (4, H, D)
    assert layer.gate_weight.shape == (E, 2 * D)
    assert layer.router_bias.grad_req == "null"
    rng = onp.random.default_rng(5)
    layer.router_bias.set_data(nd.array(rng.uniform(-0.3, 0.3, (E,))))
    x = jnp.asarray(rng.standard_normal((2, T // 2, D)), jnp.float32)
    seen = jnp.asarray(rng.standard_normal((2, T // 2, 2 * D)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = layer(nd.NDArray(x), nd.NDArray(seen))._data
        _, _, vals, idx = layer.route(
            seen.reshape(T, -1), layer.gate_weight.data()._data,
            layer.router_bias.data()._data)
        full_up = jnp.zeros((E, D, H)).at[4:8].set(layer.w1.data()._data)
        full_down = jnp.zeros((E, H, D)).at[4:8].set(layer.w2.data()._data)
        want = dense_moe(x.reshape(T, D), vals, idx, full_up, full_down,
                         moe._ACTIVATIONS["relu2"]).reshape(x.shape)
    onp.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError):
        parallel.MoELayer(E, D, H, held=(10, 4))
    with pytest.raises(ValueError):
        parallel.MoELayer(E, D, H, router="tanh")


@pytest.mark.parametrize("norm", [True, False],
                         ids=["renormalised", "as-scored"])
@pytest.mark.parametrize("shares", [8, 2])
def test_softmax_routed_shares_add_up_to_the_uncut_layer(shares, norm):
    """`held=` under `router="softmax"` (the Keye share: SwiGLU experts,
    weights renormalised over the k chosen): 16 experts over 8 chips, 2
    each (and over 2, 8 each). Every share routes over all 16 alike and
    computes its own experts' part; the parts add up to the uncut layer's
    output (`held=None`, the dispatch OLMoE's cell runs) and each is the
    dense form's same part. The renormalisation is over all k chosen,
    wherever they are held."""
    experts, k = 16, 4
    count = experts // shares
    whole = parallel.MoELayer(experts, D, H, top_k=k, router="softmax",
                              activation="silu", gated=True,
                              norm_topk_prob=norm)
    whole.initialize()
    x = jnp.asarray(onp.random.default_rng(8).standard_normal(
        (2, T // 2, D)), jnp.float32)
    names = ("w1", "w2", "w3")
    full = {n: getattr(whole, n).data()._data for n in names}
    gw = whole.gate_weight.data()._data
    with jax.default_matmul_precision("highest"):
        want = whole(nd.NDArray(x))._data
        _, gates, vals, idx = whole.route(x.reshape(T, D), gw)
        picked = jnp.take_along_axis(gates, idx, -1)
        onp.testing.assert_allclose(
            vals, picked / picked.sum(-1, keepdims=True) if norm else picked,
            rtol=1e-6)
        total, loads = 0, []
        for first in range(0, experts, count):
            share = parallel.MoELayer(
                experts, D, H, top_k=k, router="softmax", activation="silu",
                gated=True, norm_topk_prob=norm, held=(first, count))
            share.initialize()
            assert share.w1.shape == (count, D, H)
            assert not hasattr(share, "router_bias") \
                or "router_bias" not in share.collect_params()
            share.gate_weight.set_data(nd.NDArray(gw))
            for n in names:
                getattr(share, n).set_data(
                    nd.NDArray(full[n][first:first + count]))
            got = share(nd.NDArray(x))._data
            part = dense_moe(
                x.reshape(T, D), _only(vals, idx, first, count), idx,
                full["w3"], full["w2"], moe._ACTIVATIONS["silu"],
                full["w1"]).reshape(x.shape)
            onp.testing.assert_allclose(got, part, rtol=1e-5, atol=1e-5)
            total = total + got
            loads.append(int(((idx >= first) & (idx < first + count)).sum()))
    onp.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-5)
    assert sum(loads) == T * k and min(loads) > 0


@pytest.mark.parametrize("held", [None, (4, 4)])
def test_the_balancing_rule_hands_out_the_bias_it_moves_to(held):
    """`bias_rate`: forward gives (y, b + rate ln(even load / load)) over
    the call's own choices, an expert nobody chose counted once; y is the
    layer's without the rule."""
    kw = dict(top_k=K, router="sigmoid_bias", activation="relu2", held=held)
    plain = parallel.MoELayer(E, D, H, **kw)
    ruled = parallel.MoELayer(E, D, H, bias_rate=0.05, **kw)
    plain.initialize()
    ruled.initialize()
    rng = onp.random.default_rng(8)
    bias = rng.uniform(-0.3, 0.3, (E,))
    bias[7] = -10.0                                   # never chosen
    for layer in (plain, ruled):
        for name in layer._weight_names():
            getattr(layer, name).set_data(getattr(plain, name).data())
        layer.router_bias.set_data(nd.array(bias))
    x = nd.array(rng.standard_normal((2, T // 2, D)).astype("float32"))
    y, moved = ruled(x)
    onp.testing.assert_array_equal(y.asnumpy(), plain(x).asnumpy())
    _, _, _, idx = ruled.route(x._data.reshape(T, D),
                               ruled.gate_weight.data()._data,
                               ruled.router_bias.data()._data)
    load = onp.bincount(onp.asarray(idx).reshape(-1), minlength=E)
    assert load[7] == 0 and load.sum() == T * K
    want = bias + 0.05 * onp.log((T * K / E) / onp.maximum(load, 1))
    onp.testing.assert_allclose(moved.asnumpy(), want, rtol=1e-6, atol=1e-7)
    assert str(moved.dtype) == "float32"
    # the busiest expert's bias falls, the idle one's rises
    assert moved.asnumpy()[load.argmax()] < bias[load.argmax()]
    assert moved.asnumpy()[7] > bias[7]


def test_the_bias_moves_in_training_only_and_only_a_sigmoid_router_has_one():
    from incubator_mxnet_tpu import autograd
    layer = parallel.MoELayer(E, D, H, top_k=K, router="sigmoid_bias",
                              bias_rate=0.05)
    layer.initialize()
    x = nd.array(onp.random.default_rng(9).standard_normal(
        (T, D)).astype("float32"))
    _, moved = layer(x)
    layer.move_bias(moved)
    assert not layer.router_bias.data().asnumpy().any()
    with autograd.record():
        layer.move_bias(layer(x)[1])
    onp.testing.assert_array_equal(layer.router_bias.data().asnumpy(),
                                   moved.asnumpy())
    with pytest.raises(ValueError):
        parallel.MoELayer(E, D, H, bias_rate=0.05)


def test_held_none_is_the_dispatch_it_was():
    """`held=None` with the softmax router traces the composition OLMoE's
    cell has run since PR 27, equation for equation: the router's einsum,
    softmax and top-k, then `dropless_moe` on every expert. Written out
    here from the parent's `MoELayer._fn`; the layer's jaxpr is its text."""
    layer = _layer(activation="silu", gated=True, norm_topk_prob=False)

    def parent(xd, gw, w1, w2, w3):
        tokens = xd.reshape(-1, xd.shape[-1])
        logits = jnp.einsum("td,ed->te", tokens, gw,
                            preferred_element_type=jnp.float32)
        gates = jax.nn.softmax(logits, axis=-1)
        top_vals, top_idx = jax.lax.top_k(gates, K)
        return moe.dropless_moe(tokens, top_vals, top_idx, w3, w2,
                                jax.nn.silu, w1)[0].reshape(xd.shape)

    x = jnp.ones((2, T // 2, D), jnp.bfloat16)
    weights = [w._data.astype(jnp.bfloat16) for w in layer._weights()]
    names = ("x",) + layer._weight_names()
    mine = jax.make_jaxpr(lambda *a: layer._fn(
        dict(zip(names, a)), False))(x, *weights)
    assert str(mine) == str(jax.make_jaxpr(parent)(x, *weights))
    assert "dropless_held" not in str(mine)


def test_the_held_path_has_its_own_counter():
    labels = dict(path="dropless_held", combine="scatter")  # rows of 16
    before = moe._DISPATCHES.value(**labels)
    tokens, top_vals, top_idx, w_up, w_down = _case(6)
    f = jax.jit(lambda t: _held_sum(
        t, top_vals, top_idx, w_up[:2], w_down[:2], jax.nn.relu, 0, E))
    for _ in range(3):
        f(tokens)
    assert moe._DISPATCHES.value(**labels) - before == 1
    text = telemetry.REGISTRY.export_text()
    assert 'mxtpu_moe_dispatch_total{path="dropless_held",' \
        'combine="scatter"}' in text
    sorted_before = moe._DISPATCHES.value(path="dropless_held",
                                          combine="sort")
    _held_sum(jnp.ones((T, 20)), top_vals, top_idx, jnp.ones((2, 20, H)),
              jnp.ones((2, H, 20)), jax.nn.relu, 0, E)
    assert moe._DISPATCHES.value(
        path="dropless_held", combine="sort") - sorted_before == 1
    # W of each held layer as traced, by the layer's name: at these sizes
    # the worst case, T x min(k, count) (one window, no loop)
    for labels, _ in moe._WINDOW_ROWS.series():     # 64 label sets a family
        moe._WINDOW_ROWS.remove(**labels)
    for count, rows in ((2, T * 2), (6, T * K)):
        layer = _layer(held=(0, count))
        layer(nd.ones((T, D)))
        text = telemetry.REGISTRY.export_text()
        assert 'mxtpu_moe_window_rows{layer="%s"} %d' % (layer.name, rows) \
            in text


# ---- the sigmoid router's chosen scores, read by comparison (PR 43) ----

def _router_case(experts, dtype):
    """A `sigmoid_bias` router over `experts` with a bias that changes the
    choice: tokens, gate weight (both `dtype`) and the float32 bias."""
    rng = onp.random.default_rng(11)
    n_tokens, units = 64, 32
    tokens = jnp.asarray(rng.standard_normal((n_tokens, units)), dtype)
    gw = jnp.asarray(rng.standard_normal((experts, units)) / 4, dtype)
    bias = jnp.asarray(rng.standard_normal(experts) / 2, jnp.float32)
    return tokens, gw, bias


def _gathered_route(k, norm, scale, tokens, gw, bias):
    """`MoELayer.route`'s `sigmoid_bias` branch as it stood up to PR 42:
    the chosen scores by `take_along_axis` (an XLA gather; its transpose
    a scatter-add into the scores)."""
    logits = jnp.einsum("td,ed->te", tokens, gw,
                        preferred_element_type=jnp.float32)
    gates = jax.nn.sigmoid(logits)
    _, top_idx = jax.lax.top_k(gates + bias.astype(jnp.float32), k)
    top_vals = jnp.take_along_axis(gates, top_idx, -1)
    if norm:
        top_vals = top_vals / (jnp.sum(top_vals, -1, keepdims=True) + 1e-20)
    if scale != 1.0:
        top_vals = top_vals * scale
    return logits, gates, top_vals, top_idx


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("norm", [True, False],
                         ids=["renormalised", "as-scored"])
@pytest.mark.parametrize("experts, k, scale", [(512, 22, 5.0), (320, 8, 1.0)],
                         ids=["nemotron-512-top22", "solar-320-top8"])
def test_chosen_scores_by_comparison_are_the_gathered_ones_to_the_bit(
        experts, k, scale, norm, dtype):
    """`route` reads the chosen experts' scores out of `gates` by comparing
    expert ids. Against the gather written out above: the weights, the
    indices, and the gradients of a scalar of the weights into the
    router's two inputs are the same bits (one non-zero term a sum, forward
    and transposed), compiled as the step compiles them."""
    layer = parallel.MoELayer(experts, 32, H, top_k=k, router="sigmoid_bias",
                              norm_topk_prob=norm, scale=scale)
    tokens, gw, bias = _router_case(experts, dtype)
    # each chosen score weighed differently, so a misplaced one shows
    weigh = jnp.asarray(onp.random.default_rng(12).standard_normal(
        (tokens.shape[0], k)), jnp.float32)

    def scalar(route):
        def value(tokens, gw):
            _, _, top_vals, top_idx = route(tokens, gw, bias)
            return jnp.sum(top_vals * weigh), (top_vals, top_idx)
        return jax.jit(jax.value_and_grad(value, (0, 1), has_aux=True))

    (got, (vals, idx)), grads = scalar(layer.route)(tokens, gw)
    (want, (want_vals, want_idx)), want_grads = scalar(
        lambda *a: _gathered_route(k, norm, scale, *a))(tokens, gw)
    # the bias chooses: not the k largest scores
    plain = layer.route(tokens, gw, jnp.zeros_like(bias))[3]
    assert not onp.array_equal(idx, plain)
    assert onp.array_equal(idx, want_idx)
    assert vals.dtype == jnp.float32 and onp.array_equal(vals, want_vals)
    assert onp.array_equal(got, want)
    for g, w in zip(grads, want_grads):
        assert g.dtype == w.dtype == dtype
        assert float(jnp.max(jnp.abs(w.astype(jnp.float32)))) > 0
        assert onp.array_equal(g, w)


def _scoped_primitives(jaxpr, scope):
    """The primitives of every equation traced under the named scope
    `scope` (its transposes and recomputed copies keep the name)."""
    return {eqn.primitive.name for eqn, path in _scoped_eqns(jaxpr)
            if scope in path}


def _held_sigmoid_layer():
    """-> (value and gradient of a held `sigmoid_bias` layer with the
    balancing rule, its arguments)."""
    layer = parallel.MoELayer(32, D, H, top_k=4, router="sigmoid_bias",
                              activation="relu2", scale=2.0, held=(8, 4),
                              bias_rate=0.01)
    layer.initialize()
    names = ("x",) + layer._weight_names()
    x = jnp.asarray(onp.random.default_rng(13).standard_normal((2, T, D)),
                    jnp.float32)
    weights = [w._data for w in layer._weights()]

    def value(x, *weights):
        out, moved = layer._fn(dict(zip(names, (x,) + weights)), False)
        return jnp.sum(out * out), moved

    trained = tuple(i for i, n in enumerate(names) if n != "router_bias")
    return jax.value_and_grad(value, trained, has_aux=True), (x, *weights)


@pytest.mark.parametrize("sorts", [True, False], ids=["sort", "scatter"])
def test_a_sigmoid_routed_step_holds_no_gather_or_scatter_of_the_routers(
        monkeypatch, sorts):
    """Value and gradient of a held `sigmoid_bias` layer with the balancing
    rule: nothing traced under `router` is a gather or a scatter, in the
    jaxpr and in the compiled module; the held dispatch's own moves between
    tokens and rows stay, under `moe_dispatch` / `moe_combine`: gathers of
    whole rows, a scatter-add of W scalars at distinct slots (the router
    weights' gradient), and by `_rows_to_tokens`' form either a sort and no
    scatter of a row, or the two row scatter-adds of up to PR 53. With the
    gather written back into `route` the same reading finds both, so it
    can see what it says is gone."""
    monkeypatch.setattr(moe, "_sorts_the_window", lambda width: sorts)
    moves = {"gather", "scatter", "scatter-add"}
    fn, args = _held_sigmoid_layer()
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    router = _scoped_primitives(jaxpr, "router")
    assert {"dot_general", "logistic", "top_k", "eq"} <= router
    assert not router & moves
    held = _scoped_primitives(jaxpr, "moe_dispatch") \
        | _scoped_primitives(jaxpr, "moe_combine")
    assert {"sort", "gather", "scatter-add"} <= held
    assert not held & {"scatter"}
    assert sorted(len(eqn.outvars[0].aval.shape)
                  for eqn, path in _scoped_eqns(jaxpr)
                  if eqn.primitive.name == "scatter-add"
                  and ("moe_dispatch" in path or "moe_combine" in path)) \
        == ([1] if sorts else [1, 2, 2])

    def router_lines(fn):
        """The compiled program's gathers and scatters whose `op_name`
        passes through `router`."""
        text = jax.jit(fn).lower(*args).compile().as_text()
        return [line for line in text.splitlines()
                if re.search(r" (gather|scatter)\(", line)
                and "router" in re.search(r'op_name="([^"]*)"', line).group(1)]

    assert router_lines(fn) == []
    monkeypatch.setattr(
        parallel.MoELayer, "route",
        lambda self, tokens, gw, bias: _gathered_route(
            self.top_k, self.norm_topk_prob, self._scale, tokens, gw, bias))
    old, _ = _held_sigmoid_layer()
    assert {"gather", "scatter-add"} <= _scoped_primitives(
        jax.make_jaxpr(old)(*args).jaxpr, "router")
    assert len(router_lines(old)) >= 2


@pytest.mark.parametrize("norm", [True, False],
                         ids=["renormalised", "as-scored"])
def test_the_softmax_routers_weights_are_top_ks_own_values(norm):
    """The `softmax` branch (OLMoE's and Keye's cells) is the parent's,
    equation for equation: its weights are `top_k`'s first output, read by
    nothing, so nothing of the comparison reaches it."""
    layer = _layer(router="softmax", norm_topk_prob=norm, held=(4, 4))

    def parent(tokens, gw):
        logits = jnp.einsum("td,ed->te", tokens, gw,
                            preferred_element_type=jnp.float32)
        gates = jax.nn.softmax(logits, axis=-1)
        top_vals, top_idx = jax.lax.top_k(gates, K)
        if norm:
            top_vals = top_vals / jnp.sum(top_vals, -1, keepdims=True)
        return logits, gates, top_vals, top_idx

    tokens = jnp.ones((T, D), jnp.bfloat16)
    gw = layer.gate_weight.data()._data.astype(jnp.bfloat16)
    mine = jax.make_jaxpr(layer.route)(tokens, gw)
    assert str(mine) == str(jax.make_jaxpr(parent)(tokens, gw))
    names = {e.primitive.name for e in _eqns(mine.jaxpr)}
    assert "top_k" in names and not names & {"eq", "iota", "gather"}


# ---- the rows each held expert got, handed back (PR 50) ----

@pytest.mark.parametrize("first,count", [(0, 4), (2, 3), (8, 4), (7, 1),
                                         (0, 12)])
def test_held_dispatch_hands_back_the_rows_each_held_expert_got(first,
                                                                count):
    """The second output is a count of `top_idx` over the held experts
    (expert 3 nearly every token's, expert 7 nobody's), under `jit` too."""
    tokens, top_vals, top_idx, w_up, w_down = _case(9)
    sl = slice(first, first + count)
    want = onp.bincount(onp.asarray(top_idx).ravel(), minlength=E)[sl]
    rows = jax.jit(lambda t: moe.dropless_moe_held(
        t, top_vals, top_idx, w_up[sl], w_down[sl], jax.nn.relu, first,
        E)[1])(tokens)
    assert rows.dtype == jnp.int32
    onp.testing.assert_array_equal(rows, want)
    if first <= 7 < first + count:
        assert rows[7 - first] == 0
    if count == E:
        assert int(rows.sum()) == T * K
