"""Test config: run the whole suite on a virtual 8-device CPU mesh so
multi-chip SPMD paths are exercised without TPU hardware (SURVEY §4: the
GPU suite = CPU suite with a different default device; here the device
pluggability is the JAX platform + forced host device count).

The platform is also pinned in code, so a plain ``pytest`` on a host that
has a chip stays off it."""
import os

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as onp
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: repo-wide analysis passes excluded from the tier-1 run "
        "(the default invocation is -m 'not slow')")


@pytest.fixture(autouse=True)
def _seed_everything():
    import incubator_mxnet_tpu as mx
    onp.random.seed(0)
    mx.random.seed(0)
    yield
