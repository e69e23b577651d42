"""Kernels of the main path compiled at their cells' real shapes for a v5e
that is DESCRIBED, not attached (the TPU's compiler is installed here):
what Mosaic refuses (a slice off the tiling, more VMEM than a kernel may
use) is met at no chip time. Nothing runs, so nothing here is a result or
a time. The topology is described inside a fixture and only here: one
process loads the TPU's library at a time, and every xdist worker imports
every test file."""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from incubator_mxnet_tpu.ops import delta_rule

#: the Solar cell's rule: 1 x 8192 positions, 8 heads of 128 x 128, chunks
#: of 64 (perfbench/configs/solar-open2-250b.json)
B, T, H, D, CHUNK = 1, 8192, 8, 128, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    # (such a compile is written to the persistent cache and cannot be read
    # back without a chip: off for these tests)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def rule_operands(one_chip):
    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return (spec((B, T, H * D)), spec((B, T, H * D)),
            spec((B, T, H * D), jnp.bfloat16), spec((B, T, H * D)),
            spec((B, H, T, 1)))


@pytest.mark.parametrize("keep", [False, True], ids=["o-only", "keeps-starts"])
def test_the_delta_rule_forward_compiles_at_the_cells_shape(one_chip, keep):
    compiled = jax.jit(lambda *a: delta_rule._fwd_call(
        a, H, CHUNK, keep, False)).lower(*rule_operands(one_chip)).compile()
    assert "delta_rule_fwd" in compiled.as_text()


def test_the_delta_rule_backward_compiles_at_the_cells_shape(one_chip):
    args = rule_operands(one_chip)
    starts = jax.ShapeDtypeStruct((B, H, T // CHUNK, D, D), jnp.float32,
                                  sharding=one_chip)
    compiled = jax.jit(lambda starts, do, *a: delta_rule._bwd_call(
        a, starts, do, H, CHUNK, False)).lower(starts, args[2], *args) \
        .compile()
    assert "delta_rule_bwd" in compiled.as_text()


#: an entry-computation instruction that moves an array: its opcode and
#: the array's type and dimensions
_MOVE = re.compile(
    r"^\s*(?:ROOT )?\S+ = ([a-z0-9]+\[[0-9,]*\])\S* (copy|reshape|transpose)\(",
    re.M)


@pytest.mark.parametrize("cell, heads, kwargs", [
    ("ling", 32, dict(rank="full", decay=("bounded", -5.0),
                      neg_eigval=False)),
    ("solar", 8, {})])
def test_a_k_block_moves_no_operand_of_the_rule_between_two_layouts(
        one_chip, monkeypatch, cell, heads, kwargs):
    """One `KimiDeltaAttention._mix`, forward + backward, at the Ling
    cell's shape (32 heads of 128, full-rank maps, the bounded decay) and
    at the Solar cell's (8 heads a shard, low-rank maps, softplus), 1 x
    8192 in bfloat16: q, k, g, v, o and their gradients stay (1, 8192,
    h 128) from in_proj's output to out_proj's input. Up to PR 50 the
    entry computation held 9 `copy -> [1024,8,32,128]` and 7 `reshape ->
    [1,8192,4096]` that were passes over the array, not bitcasts (Ling; at
    Solar's 8 heads 8 + 6 and 2 `copy -> [1,8192,8,128]`): (b, s, h, d) is
    tiled by (h, d), (b, t, h d) by (t, h d). The sums over a head are
    taken on (b, s / 8, h, 8, d), the one view of (b, s, h d) that is the
    same bytes. The two kernels are the ones they were."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import models
    from incubator_mxnet_tpu.ops import attention
    monkeypatch.delenv("MXTPU_FLASH_INTERPRET", raising=False)
    # the rule asks where it runs: on the described chip
    monkeypatch.setattr(attention, "_kernels_run_here", lambda: True)
    monkeypatch.setattr(delta_rule, "_kernels_run_here", lambda: True)
    mx.random.seed(1)
    block = models.KimiDeltaAttention(256, heads, D, chunk=CHUNK, **kwargs)
    block.initialize(mx.init.Xavier())
    block.cast("bfloat16")
    own = (block.conv_weight, block.A_log, block.dt_bias, block.norm_gamma) \
        + ((block.decay_up, block.gate_up) if block.rank else ())
    specs = [jax.ShapeDtypeStruct((B, T, block.in_proj.weight.shape[0]),
                                  jnp.bfloat16, sharding=one_chip)] \
        + [jax.ShapeDtypeStruct(p.shape, p.data()._data.dtype,
                                sharding=one_chip) for p in own]
    text = jax.jit(jax.grad(
        lambda *a: block._mix(*a).astype(jnp.float32).sum(),
        tuple(range(len(specs))))).lower(*specs).compile().as_text()
    moved = [m.group(0).strip() for m in _MOVE.finditer(
        text[text.index("ENTRY"):])
        if m.group(1).split("[")[1] in ("1024,8,%d,128]" % heads,
                                        "1,8192,%d,128]" % heads,
                                        "1,8192,%d]" % (heads * D))
        # in_proj's bfloat16 output cut into its parts is the parent's too
        and not (m.group(2) == "copy" and m.group(1).startswith("bf16[1,"))]
    assert not moved, moved
    assert text.count("tpu_custom_call") == 2
    assert "delta_rule_fwd" in text and "delta_rule_bwd" in text


#: a scatter whose result is a float32 matrix, with its dimensions
_ROW_SCATTER = re.compile(r"= f32\[(\d+),(\d+)\]\S* scatter\(")


@pytest.mark.parametrize("cell, width, hidden, experts, k", [
    ("ling", 2560, 768, 512, 8), ("xing", 3584, 1024, 64, 4)])
def test_a_held_layers_rows_reach_their_tokens_by_no_row_scatter(
        one_chip, monkeypatch, cell, width, hidden, experts, k):
    """Value and gradient of one held expert layer's dispatch at the Ling
    cell's shape (8192 tokens of 2560, 8 of 512 SwiGLU experts of 768 held,
    8 a token: windows of 2048 rows) and at the Xing cell's (3584 wide, 8
    of 64 experts of 1024, 4 a token: windows of 8192), bfloat16: the
    compiled program holds no scatter into a float32 (8192, width), which
    up to PR 53 was the forward's combine and the backward's token
    gradient, 2.3 and 2.7 ms a call on the chip for 0.1-0.2 ms of bytes
    (`parallel.moe._rows_to_tokens`, which sorts a window to its tokens at
    these widths). Told to keep the scatter-add, as at other widths, the
    same reading finds both, so it can see what it says is gone."""
    from incubator_mxnet_tpu.parallel import moe
    n_tokens, count = 8192, 8

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    specs = (spec((n_tokens, width)), spec((n_tokens, k), jnp.float32),
             spec((count, width, hidden)), spec((count, width, hidden)),
             spec((count, hidden, width)), spec((n_tokens, k), jnp.int32))

    def value(tokens, top_vals, w_gate, w_up, w_down, top_idx):
        return moe.dropless_moe_held(
            tokens, top_vals, top_idx, w_up, w_down, jax.nn.silu, 0, experts,
            w_gate)[0].astype(jnp.float32).sum()

    def row_scatters():
        text = jax.jit(jax.value_and_grad(value, (0, 1, 2, 3, 4))).lower(
            *specs).compile().as_text()
        assert "ragged-dot" in text
        return [dims for dims in _ROW_SCATTER.findall(text)
                if dims == (str(n_tokens), str(width))]

    assert row_scatters() == []
    monkeypatch.setattr(moe, "_sorts_the_window", lambda width: False)
    assert len(row_scatters()) >= 2


#: the Cerebras cells' widths (perfbench/configs/cerebras-gpt-1.3b.json)
UNITS, INNER, HEADS = 2048, 8192, 16
_UPDATE = re.compile(
    r"= \(bf16\[(\d+),(\d+)\]\S*, f32\[\1,\2\]\S*, f32\[\1,\2\]\S*, "
    r"f32\[\1,\2\]\S*\) fusion\(.*\"iteration_bounds\":\[([^\]]*)\]")


@pytest.mark.parametrize("batch, seq", [(8, 2048), (1, 16384)],
                         ids=["8x2048", "1x16384"])
def test_a_decoder_blocks_mlp_weight_updates_are_2d_matmuls_at_any_batch(
        one_chip, monkeypatch, batch, seq):
    """Two `TransformerDecoderLayer`s through `jit.TrainStep` with
    multi-precision Adam, the streamed kernels real: fc1's and fc2's
    weight gradients with their Adam update fused behind are matmuls over
    (tokens, channels), three `iteration_bounds`, at 8 x 2048 as at
    1 x 16384 (with the batch a window dimension of a convolution there
    were five), and nothing (8, 2048, 8192) exists. The attention half is
    left as XLA makes it (PERF.md section 6, PR 42)."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, jit, models
    from incubator_mxnet_tpu.gluon import nn
    from incubator_mxnet_tpu.ops import attention
    monkeypatch.delenv("MXTPU_FLASH_INTERPRET", raising=False)
    mx.random.seed(1)
    net = nn.HybridSequential()
    for _ in range(2):
        net.add(models.TransformerDecoderLayer(UNITS, INNER, HEADS,
                                               attention="flash"))
    net.initialize(mx.init.Xavier())
    net.cast("bfloat16")
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-4, "multi_precision": True})
    step = jit.TrainStep(net, gluon.loss.L2Loss(), trainer)
    # the attention asks where it runs: on the described chip
    monkeypatch.setattr(attention, "_kernels_run_here", lambda: True)
    x = jax.ShapeDtypeStruct((batch, seq, UNITS), jnp.bfloat16)
    text = step.lower(x, x, sharding=one_chip).compile().as_text()
    assert text.count("tpu_custom_call") >= 4
    bounds = {}
    for line in text.splitlines():
        m = _UPDATE.search(line)
        if m:
            bounds.setdefault(m.group(1, 2), []).append(
                len(m.group(3).split(",")))
    # q, k, v, o and fc1, fc2 of two layers
    assert {k: len(v) for k, v in bounds.items()} == {
        ("2048", "2048"): 8, ("8192", "2048"): 2, ("2048", "8192"): 2}
    assert bounds["8192", "2048"] + bounds["2048", "8192"] == [3] * 4
    assert "[%d,%d,%d]" % (8, 2048, INNER) not in text


def test_eva_attention_compiles_at_the_evabyte_cells_shape(one_chip,
                                                           monkeypatch):
    """`ops.eva_attention` forward and gradient at 1 x 32 x 16 384 x 128,
    windows of 2048, chunks of 16, bfloat16 (perfbench/configs/evabyte.json):
    the exact part is the streamed kernels over 256 windows a batch entry
    each, `flash_fwd` and ONE `flash_bwd_dkvq` that takes the log-sum-exp's
    cotangent; the seven strips and the merge are XLA's. The counter names
    the path."""
    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.ops import attention, eva_attention
    monkeypatch.delenv("MXTPU_FLASH_INTERPRET", raising=False)
    monkeypatch.setattr(attention, "_kernels_run_here", lambda: True)
    heads, seq, dim, window, chunk = 32, 16384, 128, 2048, 16

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    wide = spec((1, heads, seq, dim), jnp.bfloat16)
    vector = spec((heads, dim), jnp.float32)
    calls = telemetry.REGISTRY.get("mxtpu_eva_attention_total")
    before = calls.value(local="streamed", remote="strips")
    grad = jax.jit(jax.grad(
        lambda q, k, v, phi, mu: eva_attention.eva_attention(
            q, k, v, phi, mu, window, chunk).astype(jnp.float32).sum(),
        (0, 1, 2, 3, 4)))
    text = grad.lower(wide, wide, wide, vector, vector).compile().as_text()
    assert calls.value(local="streamed", remote="strips") == before + 1
    assert text.count("tpu_custom_call") == 2
    assert "flash_fwd" in text and "flash_bwd_dkvq" in text
    # no strip's scores are all of (S, S / 16): the widest is 2048 x 896
    assert "[1,32,16384,1024]" not in text and "[32,16384,1024]" not in text


@pytest.mark.parametrize("dtype, precision", [
    (jnp.bfloat16, "default"), (jnp.float32, "highest")],
    ids=["bf16-as-the-cell", "f32-highest-as-the-chip-smoke"])
def test_the_sparse_forward_compiles_at_the_keye_cells_shape(
        one_chip, monkeypatch, dtype, precision):
    """`ops.sparse_attention`'s forward kernel, one strip call of the Keye
    cell (perfbench/configs/keye-vl-2.0-30b-a3b.json): 4 key-value heads x
    8 query heads x 512 queries over 16 384 keys of 128, the mask a strip
    wide in int8. Since PR 49 its running max and sum are (8, 512, 128)
    float32 scratch each (2 MB where the columns were 16 KB), beside the
    2 MB accumulator: a layout that interpret mode takes whatever its
    size."""
    from incubator_mxnet_tpu.ops import sparse_attention
    monkeypatch.delenv("MXTPU_FLASH_INTERPRET", raising=False)
    groups, heads, block, seq, dim = 4, 8, 512, 16384, 128

    def spec(shape, of=dtype):
        return jax.ShapeDtypeStruct(shape, of, sharding=one_chip)

    with jax.default_matmul_precision(precision):
        text = jax.jit(lambda *a: sparse_attention._flash_fwd_strip_pallas(
            *a, block, dim ** -0.5)).lower(
            spec((), jnp.int32), spec((groups, heads, block, dim)),
            spec((groups, seq, dim)), spec((groups, seq, dim)),
            spec((block, seq), jnp.int8)).compile().as_text()
    assert text.count("tpu_custom_call") == 1 and "sparse_flash_fwd" in text
