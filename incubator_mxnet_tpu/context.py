"""Device context — TPU-first re-design of MXNet's Context.

Reference parity: include/mxnet/base.h:102-128 (DeviceType kCPU/kGPU/...),
base.h:422-434 (Context::GPU()/CPU()), python/mxnet/context.py.

TPU-native design: ``tpu()`` is the first-class accelerator context. A Context
maps onto a ``jax.Device``; placement is realised with ``jax.device_put``
rather than per-device CUDA streams — XLA/PJRT owns streams and ordering.
``gpu()`` is accepted as a migration alias for the accelerator so existing
MXNet scripts run unchanged. CPU stays a first-class backend (the test
suite runs on it): with no TPU present, accelerator contexts map onto CPU
devices.
"""
from __future__ import annotations

import threading

import jax

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context", "num_tpus", "num_gpus"]

_DEVTYPE2ID = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}
_ID2DEVTYPE = {v: k for k, v in _DEVTYPE2ID.items()}


def _accelerator_platform():
    """'tpu' when this process addresses one, else 'cpu'."""
    try:
        # local: under jax.distributed a context must resolve to a device
        # THIS process can address, never a peer's
        platforms = {d.platform for d in jax.local_devices()}
    except RuntimeError:
        return "cpu"
    return "tpu" if "tpu" in platforms else "cpu"


def _local_devices(platform=None):
    """This process's addressable devices for a platform (multi-host safe)."""
    if platform is None:
        return jax.local_devices()
    return [d for d in jax.local_devices() if d.platform == platform] or \
        jax.devices(platform)


class Context:
    """A device context. Compare mxnet.context.Context.

    Parameters
    ----------
    device_type : {'cpu', 'tpu', 'gpu', 'cpu_pinned'}
        'tpu' is the native accelerator; 'gpu' aliases it (migration
        compatibility).
    device_id : int
    """

    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            device_type, device_id = device_type.device_type, device_type.device_id
        if device_type not in _DEVTYPE2ID:
            raise ValueError("unknown device_type %r" % (device_type,))
        self.device_type = device_type
        self.device_id = device_id
        self._old_ctx = None

    @property
    def device_typeid(self):
        return _DEVTYPE2ID[self.device_type]

    # -- jax mapping ---------------------------------------------------
    @property
    def jax_device(self):
        """The jax.Device this context denotes."""
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            try:
                devs = _local_devices("cpu")
                return devs[self.device_id % len(devs)]
            except RuntimeError:
                # single-platform TPU-only runtime: fall back to default device
                return jax.local_devices()[0]
        plat = _accelerator_platform()
        if plat == "cpu":
            # no accelerator present (unit tests on CPU): map onto cpu devices
            devs = _local_devices("cpu")
            return devs[self.device_id % len(devs)]
        devs = _local_devices(plat)
        return devs[self.device_id % len(devs)]

    # -- scope ---------------------------------------------------------
    def __enter__(self):
        if not hasattr(Context._default_ctx, "value"):
            Context._default_ctx.value = Context("cpu", 0)
        self._old_ctx = Context._default_ctx.value
        Context._default_ctx.value = self
        return self

    def __exit__(self, *args):
        Context._default_ctx.value = self._old_ctx

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __str__(self):
        return self.__repr__()

    def empty_cache(self):
        """Release cached device memory (ref: MXNet Context.empty_cache).

        XLA/PJRT owns the allocator; deleting unreferenced buffers is what
        frees memory, so this only triggers a GC-style sync point.
        """
        import gc

        gc.collect()


def cpu(device_id=0):
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def tpu(device_id=0):
    """First-class TPU context (the north-star device)."""
    return Context("tpu", device_id)


def gpu(device_id=0):
    """Migration alias: on a TPU-only system this resolves to tpu(device_id)."""
    return Context("gpu", device_id)


def num_tpus():
    plat = _accelerator_platform()
    if plat == "cpu":
        return 0
    return len(jax.devices(plat))


def num_gpus():
    # Migration shim: report accelerators so ``if mx.num_gpus():`` scripts work.
    return num_tpus()


def current_context():
    if not hasattr(Context._default_ctx, "value"):
        # default to the accelerator when one exists — TPU-first
        Context._default_ctx.value = tpu(0) if num_tpus() > 0 else cpu(0)
    return Context._default_ctx.value
