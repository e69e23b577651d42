"""The selective scan's Pallas kernel pair (ops/selective_scan.py),
interpreted on the CPU at small shapes: the cases tests/test_phi4flash.py
holds the XLA form to, against the same recurrence a position at a time,
with two channel blocks (the sums of dB, dC and of the step sizes' input
gradient cross them) and up to seven chunks (the state and the adjoint
state are carried); what a bfloat16 state reads; and which route a call
takes."""
import jax
import jax.numpy as jnp
import pytest

from incubator_mxnet_tpu.ops import selective_scan as scan_mod
from test_phi4flash import (_SCAN_ARGS, _chunked, _scan_inputs, _sequential,
                            _step_sizes)

CHANNELS = 2 * scan_mod._KERNEL_CHANNELS


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")


def _counted(path):
    return scan_mod._SCANS.value(path=path)


@pytest.mark.parametrize("s,chunk", [(64, 16), (100, 16), (100, 64),
                                     (48, 64), (96, 96)])
def test_the_kernel_is_the_sequential_recurrence(interpreted, s, chunk):
    """As test_selective_scan_is_the_sequential_recurrence: 1e-4 of the
    largest output, chunks that do and do not divide S, decays near 0 and
    near 1, batch 2; the state crosses up to seven chunks."""
    args = _scan_inputs(1, 2, s, CHANNELS, 16)
    decay = jnp.exp(_step_sizes(*args[1:4])[..., None] * args[4])
    assert decay.min() < 1e-6 and decay.max() > 0.9999
    before = _counted("pallas")
    want = _sequential(*args)
    got = jax.jit(lambda *a: _chunked(*a, chunk))(*args)
    assert _counted("pallas") == before + 1
    assert got.dtype == args[0].dtype and got.shape == want.shape
    assert jnp.abs(got - want).max() < 1e-4 * jnp.abs(want).max()


@pytest.fixture(scope="module")
def both_gradients():
    """Every input's gradient, batch 2, five chunks of 16 with the last one
    padded, by the kernels and by the recurrence: one backward each."""
    args = _scan_inputs(2, 2, 72, CHANNELS, 16)
    every = tuple(range(len(args)))
    want = jax.grad(lambda *a: jnp.sum(_sequential(*a) ** 2), every)(*args)
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("MXTPU_FLASH_INTERPRET", "1")
        before = _counted("pallas")
        got = jax.jit(jax.grad(
            lambda *a: jnp.sum(_chunked(*a) ** 2), every))(*args)
        assert _counted("pallas") == before + 1
    return args, got, want


@pytest.mark.parametrize("arg", range(8), ids=_SCAN_ARGS)
def test_the_kernels_gradients_are_the_sequential_ones(both_gradients, arg):
    args, got, want = both_gradients
    assert got[arg].shape == args[arg].shape
    assert got[arg].dtype == args[arg].dtype
    assert jnp.abs(got[arg] - want[arg]).max() \
        < 1e-4 * jnp.abs(want[arg]).max()


@pytest.mark.parametrize("kernel", ["forward", "backward"])
def test_a_bfloat16_state_fails_the_float32_tolerance_in_the_kernels(
        interpreted, monkeypatch, kernel):
    """What the 1e-4 is for: with the states and sums in bfloat16 the
    forward's output, and the backward's gradient of x (the adjoint state
    carried over three chunks), read over 1e-3 of their largest entry."""
    args = _scan_inputs(1, 1, 48, scan_mod._KERNEL_CHANNELS, 4)

    def run(scan):
        if kernel == "forward":
            return jax.jit(scan)(*args)
        return jax.jit(jax.grad(lambda *a: jnp.sum(
            scan(*a).astype(jnp.float32) ** 2)))(*args)

    want = run(_sequential)
    monkeypatch.setattr(scan_mod, "_F32", jnp.bfloat16)
    got = run(_chunked).astype(jnp.float32)
    assert jnp.abs(got - want).max() > 1e-3 * jnp.abs(want).max()


def test_the_route_is_the_platform_and_the_shape(monkeypatch):
    """Off the TPU and uninterpreted a call is the XLA form and holds no
    custom call; interpreted (or on a TPU) it is the kernels, unless the
    shape is one their layout does not take, and the counter says which."""
    monkeypatch.delenv("MXTPU_FLASH_INTERPRET", raising=False)
    args = _scan_inputs(3, 1, 32, scan_mod._KERNEL_CHANNELS, 16)

    def lowered(*a):
        return jax.jit(lambda *t: _chunked(*t)).lower(*a).as_text()

    xla, kernels = _counted("chunked_xla"), _counted("pallas")
    assert "custom_call" not in lowered(*args)
    assert (_counted("chunked_xla"), _counted("pallas")) == (xla + 1, kernels)
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    lowered(*args)
    assert (_counted("chunked_xla"), _counted("pallas")) \
        == (xla + 1, kernels + 1)
    # 40 channels are no whole vreg; 24 positions a chunk no bfloat16 tile
    lowered(*_scan_inputs(3, 1, 32, 40, 16))
    jax.jit(lambda *t: _chunked(*t, 24)).lower(*args)
    assert (_counted("chunked_xla"), _counted("pallas")) \
        == (xla + 3, kernels + 1)


def test_on_a_tpu_one_call_is_kernels_alone(monkeypatch):
    """Lowered for the TPU platform (no chip, no compile): the forward is
    one Mosaic call and a gradient two, forward and backward, with none of
    the XLA form's loops beside them."""
    monkeypatch.delenv("MXTPU_FLASH_INTERPRET", raising=False)
    monkeypatch.setattr(scan_mod, "_kernels_run_here", lambda: True)
    args = _scan_inputs(3, 1, 200, CHANNELS, 16)

    def lowered(fn):
        return jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()

    forward = lowered(lambda *a: _chunked(*a, 64))
    assert forward.count("tpu_custom_call") == 1
    assert "selective_scan_fwd" in forward
    both = lowered(jax.grad(lambda *a: _chunked(*a, 64).sum(),
                            tuple(range(8))))
    assert both.count("tpu_custom_call") == 2
    assert "selective_scan_fwd" in both and "selective_scan_bwd" in both
    for text in (forward, both):
        assert "stablehlo.while" not in text
