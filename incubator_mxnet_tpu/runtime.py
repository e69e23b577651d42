"""Runtime feature detection (ref python/mxnet/runtime.py, include/mxnet/libinfo.h)."""
from __future__ import annotations

__all__ = ["Feature", "Features", "feature_list", "require_tpu"]


class Feature:
    def __init__(self, name, enabled):
        self.name = name
        self.enabled = enabled

    def __repr__(self):
        return "[%s %s]" % ("✔" if self.enabled else "✖", self.name)


def require_tpu():
    """The device gate of every entry point that reports on the chip
    (chip_smoke.py): JAX's default backend must be a TPU whose
    ``device_kind`` has a row in the peak table, and the Pallas kernels
    must not be interpreted. Returns ``(device, peaks)`` — the device as
    JAX reports it ``{"platform", "kind", "count"}`` and its
    ``devstats.device_peaks`` row — or raises naming what was found; it
    never falls back to another backend."""
    import jax

    from . import config
    from .telemetry import devstats

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise RuntimeError(
            "no TPU: JAX's default backend is platform=%r device_kind=%r "
            "(%d device(s)); this entry point measures the chip and does "
            "not fall back" % (d.platform, d.device_kind, len(devs)))
    if config.get_env("MXTPU_FLASH_INTERPRET"):
        raise RuntimeError(
            "MXTPU_FLASH_INTERPRET is set: the flash kernels would run "
            "interpreted, not as Mosaic programs — unset it on the chip")
    return ({"platform": d.platform, "kind": d.device_kind,
             "count": len(devs)},
            devstats.device_peaks(d.device_kind))


def _detect():
    import jax

    feats = {
        "TPU": any(d.platform == "tpu" for d in _safe_devices(jax)),
        "CUDA": False,
        "CUDNN": False,
        "NCCL": False,
        "XLA": True,
        "PALLAS": True,
        "BF16": True,
        "INT64_TENSOR_SIZE": True,
        "DIST_KVSTORE": True,
        "SPMD_SHARDING": True,
        "RING_ATTENTION": True,
        "OPENMP": True,
        "NATIVE_RECORDIO": _has_native(),
        "SSE": True,
        "F16C": True,
        "MKLDNN": False,
        "OPENCV": _has_pil(),
    }
    return {k: Feature(k, v) for k, v in feats.items()}


def _safe_devices(jax):
    try:
        return jax.devices()
    except RuntimeError:
        return []


def _has_native():
    try:
        from .native import lib as _lib
        return _lib.available()
    except Exception:
        return False


def _has_pil():
    try:
        import PIL  # noqa
        return True
    except ImportError:
        return False


class Features(dict):
    """ref runtime.py Features."""

    def __init__(self):
        super().__init__(_detect())

    def is_enabled(self, feature_name):
        feature_name = feature_name.upper()
        if feature_name not in self:
            raise RuntimeError("Feature '%s' is unknown" % feature_name)
        return self[feature_name].enabled


def feature_list():
    return list(Features().values())
