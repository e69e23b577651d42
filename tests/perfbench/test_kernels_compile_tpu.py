"""Compile-only: the three flash-attention kernels at the GPT cell's real
shape, for a described v5e chip (nothing runs; a pass is not a chip run).
Guards what interpret mode cannot: tiling, VMEM budget, Mosaic lowering.

The topology is described inside a fixture, and only here: one process at a
time may load libtpu, so no other test file does this (on-chip-measurement
guide, section 2)."""
import os

import jax
import jax.numpy as jnp
import pytest

SHAPE = (1, 16, 16384, 128)      # cerebras-gpt-1.3b.train-s16k: (B, H, S, D)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def as_on_the_chip(topo, monkeypatch):
    """The public entry point asks JAX where it runs and takes the XLA
    composite anywhere but on a TPU: answer with the described chip, and
    leave the kernels real (not interpreted)."""
    monkeypatch.delenv("MXTPU_FLASH_INTERPRET", raising=False)
    monkeypatch.setattr(jax, "devices", lambda *a, **k: list(topo.devices))


@pytest.fixture()
def no_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile(fn, specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    return compiled.as_text().count("tpu_custom_call")


def test_cell_shape_is_the_workload_files(bench, harness):
    _, workload, config = harness.resolve(
        bench, "cerebras-gpt-1.3b.train-s16k", rehearse=False)
    t = workload["traffic"]
    assert SHAPE == (t["batch"], config["n_head"], t["seq_len"],
                     config["n_embd"] // config["n_head"])


def test_flash_forward_compiles_at_the_gpt_cells_shape(one_chip, no_cache,
                                                       as_on_the_chip):
    """Through the public entry point, as models/ call it: how many
    kernels there are and what they take is the program's business."""
    from incubator_mxnet_tpu.ops.attention import flash_attention
    x = jax.ShapeDtypeStruct(SHAPE, jnp.bfloat16, sharding=one_chip)
    assert _compile(lambda q, k, v: flash_attention(q, k, v, True),
                    (x, x, x)) >= 1


def test_flash_backward_compiles_at_the_gpt_cells_shape(one_chip, no_cache,
                                                        as_on_the_chip):
    """jax.grad of the same call: the backward kernels, with the larger
    VMEM footprints, on top of the forward one."""
    from incubator_mxnet_tpu.ops.attention import flash_attention
    x = jax.ShapeDtypeStruct(SHAPE, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v, True).astype(jnp.float32).sum()

    assert _compile(jax.grad(loss, argnums=(0, 1, 2)), (x, x, x)) >= 2
