"""Compile-only: the streamed attention kernels at the Ling cell's shape,
(1, 32, 8192, 192 | 128) causal, for a described v5e chip (nothing runs; a
pass is not a chip run): which block sizes Mosaic takes at a q.k width of
1.5 lane tiles, in bfloat16 and in float32 under `precision=HIGHEST`
(`ops/attention.py`'s docstring quotes these).

The topology is described inside a fixture (on-chip-measurement guide,
section 2)."""
import os

import jax
import jax.numpy as jnp
import pytest

SHAPE, DV = (1, 32, 8192, 192), 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
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


def _compile(one_chip, monkeypatch, dtype, block, precision=None):
    from incubator_mxnet_tpu.ops import attention
    monkeypatch.delenv("MXTPU_FLASH_INTERPRET", raising=False)
    monkeypatch.setattr(attention, "_kernels_run_here", lambda: True)

    def spec(width):
        return jax.ShapeDtypeStruct(SHAPE[:3] + (width,), dtype,
                                    sharding=one_chip)

    def loss(q, k, v):
        return attention.flash_attention(q, k, v, True, None, block,
                                         block).astype(jnp.float32).sum()

    with jax.default_matmul_precision(precision or "default"):
        return jax.jit(jax.grad(loss, (0, 1, 2))).lower(
            spec(192), spec(192), spec(DV)).compile().as_text()


@pytest.mark.parametrize("block", [1024, 512])
def test_bfloat16_compiles_at_192(one_chip, monkeypatch, block):
    text = _compile(one_chip, monkeypatch, jnp.bfloat16, block)
    assert text.count("tpu_custom_call") == 2
    assert "flash_fwd" in text and "flash_bwd_dkvq" in text


def test_float32_at_highest_compiles_at_blocks_of_512(one_chip, monkeypatch):
    assert _compile(one_chip, monkeypatch, jnp.float32, 512,
                    "highest").count("tpu_custom_call") == 2


def test_float32_at_highest_is_refused_at_blocks_of_1024(one_chip,
                                                         monkeypatch):
    """The forward's tiles pass its scoped VMEM (as at D_v = 256): a float32
    comparison of the layer alone sets MXTPU_FLASH_BLOCK_Q/K=512."""
    with pytest.raises(Exception, match="vmem"):
        _compile(one_chip, monkeypatch, jnp.float32, 1024, "highest")
