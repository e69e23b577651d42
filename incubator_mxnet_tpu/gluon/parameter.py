"""Parameter & ParameterDict (ref python/mxnet/gluon/parameter.py).

Reference parity: deferred shape init, grad_req, lr_mult/wd_mult,
initialize/reset_ctx/cast, save/load. TPU-native difference: a parameter holds
ONE logical copy (optionally sharded over a jax Mesh via its ``sharding``
attribute) instead of one replica per GPU context — replication is an SPMD
sharding decision, not a storage layout (SURVEY §2.5 north star).
"""
from __future__ import annotations

import re

import numpy as onp

from .. import autograd, initializer as init_mod
from .. import ndarray as nd
from ..context import Context, cpu, current_context
from ..ndarray import NDArray

__all__ = ["Parameter", "Constant", "ParameterDict", "DeferredInitializationError"]


class DeferredInitializationError(Exception):
    """Parameter used before its shape was known (ref parameter.py:36)."""


class Parameter:
    """A trainable parameter (ref gluon/parameter.py Parameter)."""

    def __init__(self, name, grad_req="write", shape=None, dtype="float32",
                 lr_mult=1.0, wd_mult=1.0, init=None, allow_deferred_init=False,
                 differentiable=True, stype="default", grad_stype="default"):
        self.name = name
        self._grad_req = grad_req if differentiable else "null"
        if isinstance(shape, int):
            shape = (shape,)
        self._shape = tuple(shape) if shape is not None else None
        self.dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self._data = None          # NDArray
        self._grad = None          # NDArray
        self._deferred_init = None  # (initializer, ctx, default_init)
        self._ctx = None
        self.sharding = None       # optional jax.sharding spec for SPMD layouts

    # ----------------------------------------------------------------
    @property
    def shape(self):
        return self._shape

    @shape.setter
    def shape(self, new_shape):
        if self._shape is None:
            self._shape = tuple(new_shape)
            return
        unknown_ok = all(s1 == s2 or s1 in (0, -1, None)
                         for s1, s2 in zip(self._shape, new_shape))
        if not unknown_ok or len(self._shape) != len(new_shape):
            raise ValueError("Cannot overwrite shape %s with %s for Parameter %s"
                             % (self._shape, new_shape, self.name))
        self._shape = tuple(new_shape)

    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        assert req in ("write", "add", "null")
        self._grad_req = req
        if req == "null":
            self._grad = None
            if self._data is not None:
                self._data.grad_buf = None
        elif self._data is not None and self._grad is None:
            self._init_grad()

    def _shape_known(self):
        return self._shape is not None and all(s > 0 for s in self._shape)

    # ----------------------------------------------------------------
    def initialize(self, init=None, ctx=None, default_init=None, force_reinit=False):
        if default_init is None:
            default_init = init_mod.Uniform()
        if self._data is not None and not force_reinit:
            return
        if ctx is None:
            ctx = current_context()
        if isinstance(ctx, Context):
            ctx = [ctx]
        self._ctx = list(ctx)
        if not self._shape_known():
            if self.allow_deferred_init:
                self._deferred_init = (init, self._ctx, default_init)
                return
            raise ValueError(
                "Cannot initialize Parameter %s because it has invalid shape %s."
                % (self.name, self._shape))
        self._finish_init(init, default_init)

    def _finish_init(self, init, default_init):
        data = nd.zeros(self._shape, ctx=self._ctx[0] if self._ctx else None,
                        dtype=self.dtype)
        initializer = self.init if init is None else init
        if initializer is None:
            default_init(self.name, data)
        else:
            # explicit per-parameter init bypasses name-suffix dispatch
            # (ref initializer.py __call__: attrs['__init__'] → _init_weight)
            if isinstance(initializer, str):
                initializer = init_mod.create(initializer)
            if isinstance(initializer, init_mod.Initializer):
                initializer._init_weight(self.name, data)
            else:
                initializer(self.name, data)
        if data.dtype != nd._np_dtype(self.dtype):
            data = data.astype(self.dtype)
        self._data = data
        self._deferred_init = None
        if self._grad_req != "null":
            self._init_grad()

    def _finish_deferred_init(self):
        if self._deferred_init is None:
            return
        if not self._shape_known():
            raise DeferredInitializationError(
                "Parameter %s has unknown shape %s" % (self.name, self._shape))
        init, ctx, default_init = self._deferred_init
        self._ctx = ctx
        self._finish_init(init, default_init)

    def _init_grad(self):
        self._grad = NDArray(nd.zeros(self._shape, dtype=self._data.dtype)._data)
        autograd.mark_variables([self._data], [self._grad], self._grad_req)

    # ----------------------------------------------------------------
    def _check_initialized(self):
        if self._data is not None:
            return
        if self._deferred_init is not None:
            raise DeferredInitializationError(
                "Parameter %s was not initialized because it has unknown shape %s. "
                "Run a forward pass first." % (self.name, self._shape))
        raise RuntimeError(
            "Parameter %s has not been initialized. Call .initialize() first."
            % self.name)

    def data(self, ctx=None):
        self._check_initialized()
        return self._data

    def list_data(self):
        self._check_initialized()
        return [self._data]

    def grad(self, ctx=None):
        self._check_initialized()
        if self._grad is None:
            raise RuntimeError("Parameter %s has grad_req='null'" % self.name)
        if self._grad.shape != tuple(self._shape):     # released: zeros again
            self.zero_grad()
        return self._grad

    def release_grad(self):
        """Free the eager tape's gradient buffer (2 bytes a bfloat16
        parameter of device memory). A compiled train step differentiates a
        pure function and never reads it, so `jit.TrainStep` releases the
        buffers of the net it takes. What stays is a scalar zero of the
        buffer's type in the same NDArray: an eager `backward` writes (or
        adds) its gradient into it as before, and `grad()` of a buffer that
        nothing wrote since reads as zeros of the parameter's shape."""
        if self._grad is not None:
            self._grad._data = nd.zeros((), dtype=self._grad.dtype)._data

    def list_grad(self):
        return [self.grad()]

    def list_ctx(self):
        if self._ctx is None:
            self._check_initialized()
        return self._ctx or []

    def set_data(self, data):
        self.shape = data.shape
        if self._data is None:
            if self._deferred_init is not None:
                self._finish_deferred_init()
            else:
                raise RuntimeError("Parameter %s not initialized" % self.name)
        if not isinstance(data, NDArray):
            data = nd.array(data)
        self._data._data = data.astype(self._data.dtype)._data

    def zero_grad(self):
        if self._grad is not None:
            self._grad._data = nd.zeros(self._shape, dtype=self._grad.dtype)._data

    def reset_ctx(self, ctx):
        if isinstance(ctx, Context):
            ctx = [ctx]
        self._ctx = list(ctx)
        if self._data is not None:
            self._data = self._data.as_in_context(ctx[0])
            if self._grad is not None:
                self._init_grad()

    def cast(self, dtype):
        self.dtype = dtype
        if self._data is not None:
            self._data = self._data.astype(dtype)
            if self._grad is not None:
                self._init_grad()

    def var(self):
        from ..symbol import Symbol, var
        return var(self.name, shape=self._shape, dtype=self.dtype)

    def __repr__(self):
        return "Parameter %s (shape=%s, dtype=%s)" % (self.name, self._shape, self.dtype)


class Constant(Parameter):
    """Non-trainable constant parameter (ref parameter.py Constant)."""

    def __init__(self, name, value):
        if not isinstance(value, NDArray):
            value = nd.array(value)
        self.value = value

        class _Init(init_mod.Initializer):
            def _init_weight(self, _, arr):
                arr._data = value._data

        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=value.dtype, init=_Init())


class ParameterDict:
    """Ordered dict of Parameters with prefix (ref gluon/parameter.py ParameterDict)."""

    def __init__(self, prefix="", shared=None):
        self._prefix = prefix
        self._params = {}
        self._shared = shared

    @property
    def prefix(self):
        return self._prefix

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    def __iter__(self):
        return iter(self._params)

    def __getitem__(self, key):
        return self._params[key]

    def __contains__(self, key):
        return key in self._params

    def __len__(self):
        return len(self._params)

    def __repr__(self):
        s = "%s(" % (self._prefix + " " if self._prefix else "")
        s += "\n  ".join(repr(p) for p in self.values())
        return s + ")"

    def get(self, name, **kwargs):
        """Retrieve or create parameter ``prefix+name`` (ref ParameterDict.get)."""
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            param = Parameter(name, **kwargs)
            self._params[name] = param
        else:
            for k, v in kwargs.items():
                if hasattr(param, k) and getattr(param, k) is not None:
                    if k == "shape" and v is not None:
                        param.shape = v
                else:
                    setattr(param, k, v)
        return param

    def get_constant(self, name, value=None):
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            if value is None:
                raise KeyError("No constant named %s" % name)
            param = Constant(name, value)
            self._params[name] = param
        return param

    def _get_impl(self, name):
        if name in self._params:
            return self._params[name]
        if self._shared is not None and name in self._shared._params:
            self._params[name] = self._shared._params[name]
            return self._params[name]
        return None

    def update(self, other):
        for k, v in other.items():
            if k in self._params and self._params[k] is not v:
                raise ValueError("Cannot update because keys overlap: %s" % k)
            self._params[k] = v

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        if init is None:
            init = init_mod.Uniform()
        elif isinstance(init, str):
            init = init_mod.create(init)
        for _, v in self.items():
            v.initialize(None, ctx, init, force_reinit=force_reinit)

    def zero_grad(self):
        for v in self.values():
            v.zero_grad()

    def reset_ctx(self, ctx):
        for v in self.values():
            v.reset_ctx(ctx)

    def setattr(self, name, value):
        for v in self.values():
            setattr(v, name, value)

    def save(self, filename, strip_prefix=""):
        arg_dict = {}
        for param in self.values():
            block = param.list_data()
            weight = block[0]
            if not param.name.startswith(strip_prefix):
                raise ValueError("Prefix %s is to be stripped before saving, but "
                                 "Parameter %s does not start with it" % (strip_prefix, param.name))
            arg_dict[param.name[len(strip_prefix):]] = weight
        nd.save(filename, arg_dict)

    def load(self, filename, ctx=None, allow_missing=False, ignore_extra=False,
             restore_prefix=""):
        arg_dict = {restore_prefix + k: v for k, v in nd.load(filename).items()}
        if not allow_missing:
            for name in self.keys():
                if name not in arg_dict:
                    raise AssertionError("Parameter %s missing in file %s" % (name, filename))
        for name, data in arg_dict.items():
            if name not in self._params:
                if not ignore_extra:
                    raise AssertionError("Parameter %s in file is not in this dict" % name)
                continue
            param = self._params[name]
            if param._data is None:
                param.shape = data.shape
                if param._deferred_init is not None:
                    param._finish_deferred_init()
                else:
                    param.initialize(ctx=ctx)
            param.set_data(data)
