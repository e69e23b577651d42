"""Guards around chip_smoke.py that a CPU host can check in seconds: the
script refuses to pass without a TPU, the flash kernels still lower to
Mosaic at the smoke's shapes, and the compile-cache placement rule."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import chip_smoke
from incubator_mxnet_tpu import config
from incubator_mxnet_tpu.ops import attention as A

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_smoke_without_a_chip_fails_and_names_the_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    assert "no TPU" in r.stderr and "platform='cpu'" in r.stderr, r.stderr
    # no result line, and no model was built on the way to the refusal
    assert '"ok"' not in r.stdout and ": ok" not in r.stdout, r.stdout


def _bert_gpt_shapes():
    b, g = chip_smoke.FULL["bert"], chip_smoke.FULL["gpt"]
    return [((b["B"], b["H"], b["S"], b["U"] // b["H"]), False),
            ((1, g["H"], g["S"], g["U"] // g["H"]), True)]


@pytest.mark.parametrize("shape,causal", _bert_gpt_shapes())
def test_flash_kernels_lower_to_mosaic_at_smoke_shapes(shape, causal,
                                                       monkeypatch):
    """forward + backward lower for platform 'tpu' from a CPU host as two
    tpu_custom_calls — not the XLA composite, not the interpreter."""
    monkeypatch.delenv("MXTPU_FLASH_INTERPRET", raising=False)
    bq, bk = A._resolve_blocks(shape[2], None, None)
    scale = shape[-1] ** -0.5

    def fwd_bwd(q, k, v, do):
        out, lse = A._fa_call(q, k, v, causal, scale, bq, bk)
        return A._fa_bwd_call(q, k, v, out, lse, do, causal, scale, bq, bk)

    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    text = jax.jit(fwd_bwd).trace(x, x, x, x).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 2, text.count("tpu_custom_call")


def test_compile_cache_placement_rule(monkeypatch):
    set_calls = {}
    monkeypatch.setattr(jax.config, "update", set_calls.__setitem__)
    # a CPU-pinned process (this suite) gets no cache from the code
    assert jax.config.jax_platforms == "cpu"
    assert config.place_compile_cache() is None and not set_calls
    # placed from outside: the code sets no directory
    monkeypatch.setattr(type(jax.config), "jax_platforms",
                        property(lambda self: "tpu,cpu"))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert config.place_compile_cache() is None
    assert "jax_compilation_cache_dir" not in set_calls
    # otherwise: one fixed path inside the checkout
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(REPO, ".jax_cache")
    assert config.place_compile_cache() == want
    assert set_calls["jax_compilation_cache_dir"] == want


def _lower_for_tpu(fn, *specs):
    return jax.jit(fn).trace(*specs).lower(
        lowering_platforms=("tpu",)).as_text()


def test_flash_on_a_mesh_is_wrapped_in_shard_map(monkeypatch):
    """GSPMD refuses to partition a Mosaic kernel (what the first run on
    four real chips died of); the models' mesh path and ring attention
    must hand JAX the kernels inside a fully-manual shard_map instead.
    Checked by lowering for 'tpu' over a CPU mesh — the interpreter the
    numeric tests use never meets this rule."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import numpy as onp
    from incubator_mxnet_tpu.parallel import ring_attention
    monkeypatch.delenv("MXTPU_FLASH_INTERPRET", raising=False)
    monkeypatch.setattr(A, "flash_attention_legal", lambda *a, **k: True)
    mesh = Mesh(onp.array(jax.devices()[:4]).reshape(2, 2), ("dp", "sp"))
    x = jax.ShapeDtypeStruct((4, 2, 512, 128), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("dp")))

    def grads(attn):
        return jax.grad(lambda q, k, v: attn(q, k, v).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))

    with pytest.raises(NotImplementedError, match="shard_map"):
        _lower_for_tpu(grads(lambda q, k, v: A.flash_attention(q, k, v)),
                       x, x, x)
    text = _lower_for_tpu(grads(lambda q, k, v: A.flash_attention_on_mesh(
        q, k, v, mesh, batch_axis="dp")), x, x, x)
    assert text.count("tpu_custom_call") == 2
    text = _lower_for_tpu(grads(lambda q, k, v: ring_attention(
        q, k, v, mesh=mesh, axis="sp")), x, x, x)
    assert "tpu_custom_call" in text


def test_short_kernels_on_a_mesh_lower_inside_shard_map(monkeypatch):
    """The same guard for the short family, at the dp4 cell's shape: each
    device gets BERT-large's (16, 16, 512, 64) and runs flash_short_fwd and
    flash_short_bwd under the manual shard_map."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import numpy as onp
    monkeypatch.delenv("MXTPU_FLASH_INTERPRET", raising=False)
    monkeypatch.setattr(A, "_kernels_run_here", lambda: True)
    mesh = Mesh(onp.array(jax.devices()[:4]), ("dp",))
    x = jax.ShapeDtypeStruct((64, 16, 512, 64), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("dp")))

    def grads(attn):
        return jax.grad(lambda q, k, v: attn(q, k, v).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))

    with pytest.raises(NotImplementedError, match="shard_map"):
        _lower_for_tpu(grads(lambda q, k, v: A.flash_attention(q, k, v)),
                       x, x, x)
    text = _lower_for_tpu(grads(lambda q, k, v: A.flash_attention_on_mesh(
        q, k, v, mesh, batch_axis="dp")), x, x, x)
    assert text.count("tpu_custom_call") == 2
    for kernel in ("flash_short_fwd", "flash_short_bwd"):
        assert 'kernel_name = "%s"' % kernel in text


def test_mesh_train_step_hands_flash_its_mesh(monkeypatch):
    """attention='flash' under DataParallelTrainStep (kernels interpreted
    here) is told the step's mesh and takes the shard_map path; the
    numbers are compared with the one-device step by chip_smoke.py."""
    from incubator_mxnet_tpu import gluon, nd, parallel
    from incubator_mxnet_tpu.models.bert import MultiHeadAttention
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    net = MultiHeadAttention(128, 1, attention="flash")
    net.initialize()
    x = nd.random.normal(shape=(4, 128, 128))
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 1e-2})
    seen = []
    monkeypatch.setattr(
        A, "flash_attention_on_mesh",
        lambda *a, _real=A.flash_attention_on_mesh, **k:
        seen.append((a[3], k["batch_axis"])) or _real(*a, **k))
    mesh = parallel.make_mesh({"dp": 4}, devices=jax.devices()[:4])
    try:
        step = parallel.DataParallelTrainStep(net, gluon.loss.L2Loss(),
                                              trainer, mesh=mesh)
        loss = float(step(x, x).mean().asscalar())
    finally:
        parallel.set_current_mesh(None)
    assert seen == [(mesh, "dp")], seen
    assert loss == loss and parallel.mesh.step_mesh() is None


def test_the_scan_alone_reads_between_a_float32_and_a_bfloat16_state():
    """The hybrid phase's own limit at the rehearsal shape: the chunked
    scan in float32 is under it (summation order: 3e-7 here, 1.9e-5 on a
    v5e at the cell's shape), the recurrence with a state rounded to
    bfloat16 once a chunk is over it (2.7e-3 here, 9.3e-4 there)."""
    sound, rounded = chip_smoke.scan_alone(chip_smoke.TOY["hybrid"])
    assert sound < chip_smoke.SCAN_ALONE_LIMIT / 10
    assert rounded > chip_smoke.SCAN_ALONE_LIMIT * 5


@pytest.mark.parametrize("path", ["xla", "pallas"])
def test_the_delta_rule_alone_reads_between_a_float32_and_a_bfloat16_state(
        monkeypatch, path):
    """The same for the gated delta rule alone at the rehearsal shape, on
    the XLA form (nothing set: no kernel runs off the TPU) and on the
    kernel pair interpreted (what `--rehearse` runs): the chunk form and
    its five gradients in float32 are under the hybrid phase's limit
    (summation order: 1e-6 here), the recurrence with a state rounded to
    bfloat16 once a chunk is over it (1e-3 here); off the chip it reports
    no milliseconds."""
    if path == "pallas":
        monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    else:
        monkeypatch.delenv("MXTPU_FLASH_INTERPRET", raising=False)
    read = chip_smoke.delta_rule_alone(chip_smoke.TOY["hybrid"], False)
    assert read["path"] == path
    assert read["sound"] < chip_smoke.DELTA_RULE_ALONE_LIMIT / 10
    assert read["gradients"] < chip_smoke.DELTA_RULE_ALONE_LIMIT / 10
    assert read["rounded"] > chip_smoke.DELTA_RULE_ALONE_LIMIT * 5
    assert read["forward_ms"] is None and read["both_ms"] is None \
        and read["xla_ms"] is None


@pytest.mark.parametrize("path", ["xla_masked_strips",
                                  "pallas_masked_strips"])
def test_sparse_attention_alone_reads_under_its_limits_and_a_rounded_choice_over(
        monkeypatch, path):
    """The same for sparse attention alone at the rehearsal shape, on the
    plain strips (nothing set: no kernel runs off the TPU) and on the five
    kernels interpreted (what `--rehearse` runs): outputs, the KL and the
    six gradients in float32 are under the hybrid phase's limits
    (summation order: 2e-7 and 8e-7 here), the reference choosing its keys
    on scores rounded to bfloat16 is over the outputs' (8e-2 here); off the
    chip it reports no milliseconds."""
    if path.startswith("pallas"):
        monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    else:
        monkeypatch.delenv("MXTPU_FLASH_INTERPRET", raising=False)
    read = chip_smoke.sparse_attention_alone(chip_smoke.TOY["hybrid"], False)
    limits = chip_smoke.SPARSE_ALONE_LIMITS
    assert read["path"] == path
    assert read["sound"] < limits["outputs"] / 10
    assert read["kl"] < limits["outputs"] / 10
    assert read["gradients"] < limits["gradients"] / 10
    assert read["rounded"] > limits["outputs"] * 5
    assert read["forward_ms"] is None and read["both_ms"] is None


@pytest.mark.parametrize("local", ["dense", "streamed"])
def test_eva_attention_alone_reads_under_its_limits_and_no_summaries_over(
        monkeypatch, local):
    """The same for EVA attention alone at the rehearsal shape, the exact
    part dense (nothing set: no kernel runs off the TPU) and on the
    streamed kernels interpreted (what `--rehearse` runs): the output and
    the five gradients in float32 are under the hybrid phase's limits
    (summation order), the plain form without the summaries is over the
    outputs'; off the chip it reports no milliseconds."""
    if local == "streamed":
        monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    else:
        monkeypatch.delenv("MXTPU_FLASH_INTERPRET", raising=False)
    read = chip_smoke.eva_attention_alone(chip_smoke.TOY["hybrid"], False)
    limits = chip_smoke.EVA_ALONE_LIMITS
    assert read["local"] == local
    assert read["sound"] < limits["outputs"] / 10
    assert read["gradients"] < limits["gradients"] / 10
    assert read["no_remote"] > limits["outputs"] * 5
    assert read["forward_ms"] is None and read["both_ms"] is None


def test_the_streams_phase_reads_float32_maps_and_bfloat16_blocks():
    """The streams phase's own limits at the rehearsal shape: the
    hyper-connection's maps are float32 (7e-7 of their largest entry from
    the plain form a position at a time here; one bfloat16 pass of x^ P
    would read 2e-3), the mixed streams and the latent block with a
    low-rank query are a bfloat16 rounding from their float32 forms; off
    the chip neither reports milliseconds."""
    cfg = chip_smoke.TOY["streams"]
    hyper = chip_smoke.hyper_connection_alone(cfg, False)
    assert hyper["maps"] < chip_smoke.STREAMS_LIMITS["maps"] / 10
    assert 1e-4 < hyper["mixed"] < chip_smoke.STREAMS_LIMITS["bfloat16"]
    latent = chip_smoke.latent_block_alone(cfg, False)
    assert 1e-4 < latent["outputs"] < chip_smoke.STREAMS_LIMITS["bfloat16"]
    assert latent["route"] == "composite"
    assert hyper["forward_ms"] is None and latent["both_ms"] is None
