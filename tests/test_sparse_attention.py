"""`ops.sparse_attention` on the CPU at small sizes, seeded: the op against
the float32 reference the benchmark uses
(perfbench/reference/keye-vl-2.0-30b-a3b.py: `lax.top_k` a row and a masked
softmax, where the op bisects on the bits and streams key blocks) in
value, in the KL and in the six gradients, on the plain strips and on the
Pallas kernels interpreted; the selection alone (how many keys, which on a
tie); what the forward kernel's online softmax leans on since PR 49 (a row
may be empty tile after tile, never over all its live key blocks: rows
whose first chosen key lies in their last live block, rows that choose
their diagonal alone, all scores alike); what has a gradient into what;
what a recomputed layer keeps; the counters.
"""
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import pytest

from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.ops import sparse_attention as op

PERFBENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench")
_spec = importlib.util.spec_from_file_location(
    "sparse_attention_test_reference",
    os.path.join(PERFBENCH, "reference", "keye-vl-2.0-30b-a3b.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

H, G, D, J, DI = 4, 2, 16, 3, 8
NAMES = ("q", "k", "v", "qi", "ki", "w")


@pytest.fixture(autouse=True)
def _full_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(params=["xla_masked_strips", "pallas_masked_strips"])
def path(request, monkeypatch):
    """Both schedules: off the TPU the strips in jax.numpy, and the five
    kernels interpreted."""
    if request.param.startswith("pallas"):
        monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    else:
        monkeypatch.delenv("MXTPU_FLASH_INTERPRET", raising=False)
    return request.param


def recent(s):
    """The rows `operands(.., indexer="recent")` gives scores that rise
    with the key's position: eight of every sixteen."""
    return (jnp.arange(s) // 8) % 2 == 1


def operands(b, s, seed=0, indexer=None):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    shapes = ((b, s, H, D), (b, s, G, D), (b, s, G, D), (b, s, J, DI),
              (b, s, DI), (b, s, J))
    q, k, v, qi, ki, w = (jax.random.normal(key, shape, jnp.float32)
                          for key, shape in zip(keys, shapes))
    w = w * 0.1
    if indexer == "tie":
        # keys 3, 7, 8 and 20 alike to the indexer: every later query
        # scores them the same to the bit
        ki = ki.at[:, (7, 8, 20)].set(ki[:, 3:4])
    elif indexer == "recent":
        # key s carries (s + 1) / S in its first component and the rows of
        # `recent` look at nothing else: I[t, s] = 10 (s + 1) / S, so they
        # take their LAST min(topk, t + 1) keys (their diagonal alone at
        # topk 1); the other rows score as they were drawn
        ki = ki.at[..., 0].set((jnp.arange(s) + 1.0) / s)
        rows = recent(s)[None, :, None]
        first = jnp.zeros((DI,)).at[0].set(10.0)
        qi = jnp.where(rows[..., None], first, qi)
        w = jnp.where(rows, 1.0 / J, w)
    elif indexer == "alike":
        # every score +0.0 or -0.0, the same key: all keys tie and query t
        # takes positions 0 .. min(topk, t + 1) - 1
        w = w * jnp.where(jnp.arange(J) % 2, -0.0, 0.0)
    return q, k, v, qi, ki, w


def both(args, topk, block_q=16, block_k=32):
    cot = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def of(attend, **blocks):
        def loss(*a):
            o, kl = attend(*a, topk, **blocks)
            return (o * cot).sum() + (kl * 3.0).sum(), (o, kl)
        return jax.value_and_grad(loss, argnums=tuple(range(6)),
                                  has_aux=True)(*args)

    return of(op.sparse_attention, block_q=block_q, block_k=block_k), \
        of(reference.sparse_attention)


def close(got, want, tol=2e-5, floor=1e-6):
    scale = max(float(jnp.abs(want).max()), floor)
    return float(jnp.abs(got - want).max()) <= tol * scale


#: four key blocks of 32 under eight strips of 16 (`both`'s blocks): what
#: the forward kernel's finite floor leans on (PR 49)
EMPTY_TILES = [
    ("the first chosen key in the last live block", 1, 128, 4, "recent"),
    ("the diagonal alone", 1, 128, 1, "recent"),
    ("all scores alike", 1, 128, 5, "alike"),
]


@pytest.mark.parametrize("case,b,s,topk,indexer", [
    ("keys are dropped", 1, 64, 16, None),
    ("no key is dropped", 1, 64, 64, None),
    ("more keys asked for than there are", 1, 64, 100, None),
    ("batch 2", 2, 64, 24, None),
    ("a tie at the threshold", 1, 64, 6, "tie"),
    ("one strip, one key block", 1, 32, 8, None),
] + EMPTY_TILES)
def test_the_op_is_the_reference(path, case, b, s, topk, indexer):
    ((_, (o, kl)), grads), ((_, (o_ref, kl_ref)), grads_ref) = both(
        operands(b, s, indexer=indexer), topk)
    assert o.shape == (b, s, H, D) and kl.shape == (b,)
    assert close(o, o_ref), case
    assert close(kl, kl_ref), case
    # one key a row: dq and dk are 0 but for the rounding of dO . v - dO . o,
    # and are held against dv's size
    floors = dict.fromkeys(NAMES, 1e-6)
    if topk == 1:
        floors["q"] = floors["k"] = float(jnp.abs(grads_ref[2]).max())
    # at scores of exactly 0 the reference's `where(scores == 0, ..)` stops
    # the indexer's gradient: q, k, v alone are compared there
    compared = NAMES[:3] if indexer == "alike" else NAMES
    for name, got, want in zip(compared, grads, grads_ref):
        assert close(got, want, floor=floors[name]), (case, name)
    # what the indexer learns from is not nothing (but with one key a row,
    # where the KL is 0 whatever the indexer says, and at w = 0)
    if topk > 1 and indexer != "alike":
        assert float(jnp.abs(grads[3]).max()) > 0


@pytest.mark.parametrize("case,b,s,topk,indexer", EMPTY_TILES)
def test_a_row_empty_tile_after_tile_ends_with_its_chosen_keys_alone(
        path, case, b, s, topk, indexer):
    """The forward's statistics, which the op hands nobody but its own
    backward and the head mean: `lse` of every row and head is finite and
    is the log-sum-exp over the row's chosen keys alone, whatever its
    empty tiles gathered before the first chosen key came. And the case is
    in the data."""
    block_q, block_k = 16, 32
    q, k, v, qi, ki, w = operands(b, s, indexer=indexer)
    tau, cut = op.select_thresholds(qi, ki, w, topk, block_q, block_k)
    heads = jnp.moveaxis(q[0], 0, 1).reshape(G, H // G, s, D)
    keys = jnp.moveaxis(k[0], 0, 1)
    (o, _), res = op._attend_fwd(
        block_q, block_k, D ** -0.5, heads, keys, jnp.moveaxis(v[0], 0, 1),
        jnp.moveaxis(qi[0], 1, 0), ki[0], w[0], tau[0], cut[0])
    lse = res[-1]
    assert lse.shape == (G, H // G, s) and bool(jnp.isfinite(lse).all())
    assert bool(jnp.isfinite(o).all())
    mask = reference.chosen(qi, ki, w, topk)[0]                  # (s, s)
    scores = jnp.einsum("grtd,gsd->grts", heads, keys) * D ** -0.5
    want = jax.nn.logsumexp(jnp.where(mask, scores, -jnp.inf), -1)
    assert close(lse, want), case
    t = jnp.arange(s)
    first = jnp.argmax(mask, -1)                 # a row's first chosen key
    last_live = ((t // block_q + 1) * block_q - 1) // block_k
    if indexer == "alike":
        # after block 0 nothing: every later live tile of a row is empty
        assert bool((mask[:, topk:] == 0).all()) and int(last_live[-1]) == 3
    elif topk == 1:
        assert bool((mask[recent(s)].sum(-1) == 1).all())
        assert bool((first[recent(s)] == t[recent(s)]).all())
    else:
        late = recent(s) & (first // block_k == last_live) & (last_live > 0)
        assert int(late.sum()) >= s // 8


def test_the_selection_takes_exactly_the_keys_top_k_takes(path):
    """min(topk, t + 1) keys a row, the reference's set to the key, with a
    tie at the threshold resolved to the lower positions."""
    s, topk = 64, 6
    _, _, _, qi, ki, w = operands(2, s, indexer="tie")
    tau, cut = op.select_thresholds(qi, ki, w, topk, block_q=16, block_k=32)
    assert tau.shape == cut.shape == (2, s)
    want = reference.chosen(qi, ki, w, topk)
    tied = 0
    for b in range(2):
        mine = jnp.concatenate([
            op.chosen_strip(qi[b], ki[b], w[b], tau[b], cut[b], strip,
                            block_q=16, block_k=32)
            for strip in range(s // 16)])
        assert (mine.sum(-1) == jnp.minimum(topk, jnp.arange(s) + 1)).all()
        assert (mine == want[b]).all()
        scores = op.index_scores(qi[b], ki[b], w[b])
        at = (scores == tau[b][:, None]) & jnp.tril(jnp.ones((s, s), bool))
        tied += int(((at.sum(-1) > 1) & (at & ~mine).any(-1)).sum())
    # the case is in the data: rows whose threshold value is shared and
    # only the lower positions are in
    assert tied > 0


def test_all_scores_alike_choose_the_first_keys(path):
    """w = 0: every score is +0.0 (or -0.0, the same key), every key ties,
    and query t takes positions 0 .. min(topk, t + 1) - 1."""
    s, topk = 32, 5
    q, k, v, qi, ki, zero = operands(1, s, indexer="alike")
    tau, cut = op.select_thresholds(qi, ki, zero, topk, block_q=16,
                                    block_k=16)
    mine = jnp.concatenate([
        op.chosen_strip(qi[0], ki[0], zero[0], tau[0], cut[0], strip,
                        block_q=16, block_k=16) for strip in range(2)])
    want = jnp.arange(s)[None, :] < jnp.minimum(
        topk, jnp.arange(s) + 1)[:, None]
    assert (mine == want).all()
    o, kl = op.sparse_attention(q, k, v, qi, ki, zero, topk, block_q=16,
                                block_k=16)
    o_ref, kl_ref = reference.sparse_attention(q, k, v, qi, ki, zero, topk)
    assert close(o, o_ref) and close(kl, kl_ref)


def test_the_sortable_key_orders_floats_and_knows_one_zero():
    x = jnp.array([-jnp.inf, -3.5, -1e-30, -0.0, 0.0, 1e-30, 2.0, jnp.inf],
                  jnp.float32)
    key = op._sortable(x)
    assert (jnp.diff(key) >= 0).all()
    assert int(key[3]) == int(key[4]) == 0
    assert (jnp.diff(jnp.delete(key, 3)) > 0).all()
    back = op._unsortable(key)
    assert (back == x).all()


def test_each_loss_reaches_its_own_operands_only(path):
    """The output has no gradient into the indexer's operands (the choice
    is not differentiable) and the KL none into q, k, v (its target is
    stopped)."""
    args = operands(1, 64)

    def out(*a):
        return op.sparse_attention(*a, 16, block_q=16, block_k=32)[0].sum()

    def kl(*a):
        return op.sparse_attention(*a, 16, block_q=16, block_k=32)[1].sum()

    d_out = jax.grad(out, argnums=tuple(range(6)))(*args)
    d_kl = jax.grad(kl, argnums=tuple(range(6)))(*args)
    for i, name in enumerate(NAMES):
        mine, other = (d_out, d_kl) if i < 3 else (d_kl, d_out)
        assert float(jnp.abs(mine[i]).max()) > 0, name
        assert float(jnp.abs(other[i]).max()) == 0, name


def _loops(jaxpr_text):
    """How many times the bisection (the only left shift of the op) and the
    forward kernel of the attention are in a program."""
    return jaxpr_text.count("shift_left"), len(re.findall(
        r"name=sparse_flash_fwd", jaxpr_text))


def test_a_recomputed_layer_keeps_what_carries_the_names(monkeypatch, capsys):
    """Under `jax.checkpoint`, saving `sparse_topk` keeps the thresholds
    AND the three operands they were found on (the same bits mask the
    backward), and `sparse_attended` the output and the statistics: the
    gradient selects once and runs the forward kernel once. Saving nothing
    it does both twice."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    args = operands(1, 64)

    def loss(*a):
        # operands made inside the recomputed region, as a layer makes them
        a = tuple(x * 1.5 for x in a)
        o, kl = op.sparse_attention(*a, 16, block_q=16, block_k=32)
        return o.sum() + kl.sum()

    def program(policy):
        return str(jax.make_jaxpr(jax.grad(jax.checkpoint(
            loss, policy=policy), argnums=tuple(range(6))))(*args))

    save = jax.checkpoint_policies.save_only_these_names
    assert _loops(program(save(op.TOPK_NAME, op.ATTENDED_NAME))) == (1, 1)
    assert _loops(program(save(op.TOPK_NAME))) == (1, 2)
    assert _loops(program(None)) == (2, 2)
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(
        jax.checkpoint(loss, policy=save(op.TOPK_NAME)), *args)
    # (a named float prints as the rounding the name lowers to)
    named = re.findall(r"^\w+\[([\d,]+)\] (?:named '%s'|output of "
                       r"reduce_precision) from \S*sparse_attention\.py"
                       % op.TOPK_NAME, capsys.readouterr().out, re.M)
    # qi, ki, w and the two thresholds a query
    assert sorted(named) == sorted(
        ["1,64,%d,%d" % (J, DI), "1,64,%d" % DI, "1,64,%d" % J, "1,64",
         "1,64"])
    full = jax.grad(loss, argnums=tuple(range(6)))(*args)
    again = jax.grad(jax.checkpoint(loss, policy=save(
        op.TOPK_NAME, op.ATTENDED_NAME)), argnums=tuple(range(6)))(*args)
    for got, want in zip(again, full):
        assert close(got, want)


def test_the_counters_name_the_path_taken(path):
    before = (op._ATTENTIONS.value(path=path),
              op._SELECTS.value(path="bisect_bits"))
    op.sparse_attention(*operands(1, 32), 8, block_q=16, block_k=16)
    assert op._ATTENTIONS.value(path=path) == before[0] + 1
    assert op._SELECTS.value(path="bisect_bits") == before[1] + 1
    text = telemetry.REGISTRY.export_text()
    assert 'mxtpu_sparse_attention_total{path="%s"}' % path in text
    assert 'mxtpu_topk_select_total{path="bisect_bits"}' in text


def test_scores_never_exist_whole():
    """Nothing in the traced gradient is (S, S) or larger but a strip: the
    widest array is (block_q, S)."""
    s, block_q = 256, 32
    args = operands(1, s)

    def loss(*a):
        o, kl = op.sparse_attention(*a, 16, block_q=block_q, block_k=64)
        return o.sum() + kl.sum()

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=tuple(range(6))))(
        *args))
    assert not re.search(r"\[(\d+,)*%d,%d\]" % (s, s), text)
    assert re.search(r"\[%d,%d\]" % (block_q, s), text)
