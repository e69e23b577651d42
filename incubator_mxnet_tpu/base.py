"""Shared small utilities (ref: python/mxnet/base.py, python/mxnet/registry.py)."""
from __future__ import annotations

import numpy as onp

__all__ = ["MXNetError", "string_types", "numeric_types", "registry",
           "Registry", "public_op_names", "enable_x64"]


class MXNetError(RuntimeError):
    """Framework error type (ref: python/mxnet/base.py MXNetError)."""


def distributed_is_initialized():
    """True once ``jax.distributed.initialize`` ran in this process
    (package import under tools/launch.py, or the user) — callers use it
    to avoid double-initialization."""
    import jax
    return bool(jax.distributed.is_initialized())


def enable_x64(enabled=True):
    """Scoped 64-bit-dtype switch (``jax.enable_x64``) for the int64 /
    float64 code paths (ndarray dtype handling, kvstore wide-dtype
    batching)."""
    import jax
    return jax.enable_x64(enabled)


string_types = (str,)
numeric_types = (float, int, onp.generic)


class Registry:
    """Name→class registry with alias support (ref: python/mxnet/registry.py)."""

    def __init__(self, name):
        self.name = name
        self._registry = {}

    def register(self, klass, name=None):
        nm = (name or klass.__name__).lower()
        self._registry[nm] = klass
        return klass

    def alias(self, *aliases):
        def reg(klass):
            self.register(klass)
            for a in aliases:
                self.register(klass, a)
            return klass

        return reg

    def get(self, name):
        if isinstance(name, str):
            key = name.lower()
            if key not in self._registry:
                raise ValueError(
                    "%s %r not registered; known: %s" % (self.name, name, sorted(self._registry))
                )
            return self._registry[key]
        return name

    def create(self, name, *args, **kwargs):
        if not isinstance(name, str):
            return name
        return self.get(name)(*args, **kwargs)

    def __contains__(self, name):
        return isinstance(name, str) and name.lower() in self._registry

    def keys(self):
        return self._registry.keys()


_registries = {}


def registry(name):
    if name not in _registries:
        _registries[name] = Registry(name)
    return _registries[name]


def public_op_names(namespace, exclude=()):
    """Public operator-like callables of a namespace: everything that is
    not underscored, a module, a class, or in ``exclude``. The ONE
    eligibility rule shared by the nd→sym auto-registration
    (symbol/__init__.py), the registry sweep coverage contract
    (test_utils.sweep_coverage), and the parity tests — so the three can
    never disagree about what counts as an op."""
    import inspect
    import types
    out = []
    for n in sorted(dir(namespace)):
        if n.startswith("_") or n in exclude:
            continue
        o = getattr(namespace, n)
        if isinstance(o, types.ModuleType) or inspect.isclass(o) or \
                not callable(o):
            continue
        out.append(n)
    return out
