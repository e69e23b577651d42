"""NDArray — eager tensor over jax.Array, plus the ``nd`` op namespace.

Reference parity: python/mxnet/ndarray/ndarray.py:220 (NDArray class),
src/ndarray/ndarray.cc (C++ NDArray), and the generated op namespace
(python/mxnet/ndarray/register.py:265). Operator-style ops (FullyConnected,
Convolution, BatchNorm, ...) mirror src/operator/nn/*.

TPU-native design: there is no dependency engine and no per-op kernels —
every op is a pure JAX function executed eagerly (XLA-compiled & cached by
PJRT). Async semantics come for free: jax.Array is a future-like buffer;
``wait_to_read`` maps to ``block_until_ready`` (ref engine WaitForVar,
include/mxnet/engine.h:229). Autograd taping hooks into ``_apply``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as onp
from jax import lax

from .. import autograd
from ..context import Context, current_context
from .. import base as _base

__all__ = ["NDArray", "array", "zeros", "ones", "full", "empty", "arange", "eye", "concat",
           "concatenate", "stack", "dot", "batch_dot", "waitall"]


def _ctx_put(data, ctx):
    if ctx is None:
        ctx = current_context()
    return jax.device_put(data, ctx.jax_device)


def _dtype_of(dtype, default=onp.float32):
    if dtype is None:
        return default
    return onp.dtype(dtype) if not isinstance(dtype, str) or dtype != "bfloat16" else jnp.bfloat16


class NDArray:
    """Eager tensor bound to a device context (ref ndarray.py:220)."""

    __slots__ = ("_data", "_ctx", "_in_graph", "_grad_req", "grad_buf", "__weakref__")
    __array_priority__ = 100.0

    def __init__(self, data, ctx=None):
        if isinstance(data, NDArray):
            data = data._data
        self._data = data if isinstance(data, jax.Array) else jnp.asarray(data)
        self._ctx = ctx
        self._in_graph = False
        self._grad_req = "write"
        self.grad_buf = None

    # ------------------------------------------------------------- basics
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return self._data.dtype

    @property
    def size(self):
        return int(self._data.size)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def context(self):
        if self._ctx is not None:
            return self._ctx
        try:
            dev = list(self._data.devices())[0]
            plat = dev.platform
        except Exception:
            return current_context()
        if plat == "tpu":
            return Context("tpu", dev.id)
        return Context("cpu", dev.id)

    ctx = context

    @property
    def stype(self):
        return "default"  # sparse stypes: dense-only on TPU (SURVEY §7 hard part f)

    def __getstate__(self):
        # pickle as host numpy: crosses process boundaries (DataLoader
        # multiprocessing workers) without dragging device buffers along.
        # NB: a pickle round-trip (or deepcopy) lands on the DEFAULT device
        # — device placement is process-local state, not data
        return {"data": self.asnumpy()}

    def __setstate__(self, state):
        self._data = jnp.asarray(state["data"])
        self._ctx = None
        self._in_graph = False
        self._grad_req = "write"
        self.grad_buf = None

    def asnumpy(self):
        return onp.asarray(self._data)

    def asscalar(self):
        return self.asnumpy().item()

    def item(self):
        return self.asnumpy().item()

    def __float__(self):
        return float(self.asnumpy())

    def __int__(self):
        return int(self.asnumpy())

    def __bool__(self):
        return bool(self.asnumpy())

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __repr__(self):
        return "%s\n<NDArray %s @%s>" % (
            onp.asarray(self._data), "x".join(str(s) for s in self.shape), self.context)

    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    def wait_to_read(self):
        """Block until the buffer is ready (≙ Engine::WaitForVar)."""
        self._data.block_until_ready()

    wait_to_write = wait_to_read

    # ------------------------------------------------------------- autograd
    def attach_grad(self, grad_req="write", stype=None):
        """Allocate gradient buffer and mark for autograd (ref ndarray.py attach_grad)."""
        grad = NDArray(jnp.zeros_like(self._data), ctx=self._ctx)
        autograd.mark_variables([self], [grad], grad_req)

    @property
    def grad(self):
        return self.grad_buf

    def detach(self):
        out = NDArray(self._data, ctx=self._ctx)
        return out

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph, train_mode)

    # ------------------------------------------------------------- movement
    def copy(self):
        # identity through _apply: gradients flow through copies (the
        # reference's _copy op is differentiable too)
        out = _apply(lambda x: jnp.array(x), self)
        out._ctx = self._ctx
        return out

    def copyto(self, other):
        if isinstance(other, NDArray):
            other._data = jax.device_put(self._data, other.context.jax_device)
            return other
        if isinstance(other, Context):
            return NDArray(jax.device_put(self._data, other.jax_device), ctx=other)
        raise TypeError("copyto expects NDArray or Context")

    def as_in_context(self, ctx):
        if ctx == self.context:
            return self
        return NDArray(jax.device_put(self._data, ctx.jax_device), ctx=ctx)

    as_in_ctx = as_in_context

    def astype(self, dtype, copy=True):
        return _apply(lambda x: x.astype(_np_dtype(dtype)), self)

    def as_nd_ndarray(self):
        return self

    @property
    def stype(self):
        return "default"

    def tostype(self, stype):
        """Convert to a storage type (ref ndarray.py cast_storage).

        Compiled programs never need this: row_sparse grads are an XLA
        scatter in the fused step (see ndarray/sparse.py). Conversion is
        eager (data-dependent nnz can't live under jit)."""
        if stype == "default":
            return self
        from . import sparse as _sp
        if stype == "row_sparse":
            return _sp.row_sparse_array(self)
        if stype == "csr":
            return _sp.csr_matrix(self)
        raise ValueError("unknown stype %r" % stype)

    # ------------------------------------------------------------- indexing
    @staticmethod
    def _key_past_int32(key):
        """Integer indices beyond int32 range need a scoped x64 enable —
        jax passes dynamic index scalars as int32 by default, which
        overflows on >2^31-element axes (the int64-tensor-size story)."""
        lim = 2 ** 31 - 1
        # NOTE: module-level `abs` is the nd operator — plain comparisons
        def big(v):
            return isinstance(v, int) and (v > lim or v < -lim)

        for k in key if isinstance(key, tuple) else (key,):
            if big(k):
                return True
            if isinstance(k, slice) and any(
                    big(v) for v in (k.start, k.stop, k.step)
                    if v is not None):
                return True
        return False

    def __getitem__(self, key):
        key = _index_fixup(key)
        if self._key_past_int32(key):
            with _base.enable_x64(True):
                return _apply(lambda x: x[key], self)
        return _apply(lambda x: x[key], self)

    def __setitem__(self, key, value):
        key = _index_fixup(key)
        if isinstance(value, NDArray):
            value = value._data
        if self._key_past_int32(key):
            with _base.enable_x64(True):
                self._data = self._data.at[key].set(value)
        else:
            self._data = self._data.at[key].set(value)

    def take(self, indices, axis=0, mode="clip"):
        from . import op as _op  # noqa
        return take(self, indices, axis=axis, mode=mode)

    # ------------------------------------------------------------- arithmetic
    def _binop(self, other, fn, reverse=False):
        if isinstance(other, NDArray):
            if reverse:
                return _apply(lambda b, a: fn(a, b), other, self)
            return _apply(fn, self, other)
        # scalar
        if reverse:
            return _apply(lambda a: fn(other, a), self)
        return _apply(lambda a: fn(a, other), self)

    def __add__(self, o): return self._binop(o, jnp.add)
    def __radd__(self, o): return self._binop(o, jnp.add, True)
    def __sub__(self, o): return self._binop(o, jnp.subtract)
    def __rsub__(self, o): return self._binop(o, jnp.subtract, True)
    def __mul__(self, o): return self._binop(o, jnp.multiply)
    def __rmul__(self, o): return self._binop(o, jnp.multiply, True)
    def __div__(self, o): return self._binop(o, jnp.divide)
    def __truediv__(self, o): return self._binop(o, jnp.divide)
    def __rtruediv__(self, o): return self._binop(o, jnp.divide, True)
    def __mod__(self, o): return self._binop(o, jnp.mod)
    def __rmod__(self, o): return self._binop(o, jnp.mod, True)
    def __pow__(self, o): return self._binop(o, jnp.power)
    def __rpow__(self, o): return self._binop(o, jnp.power, True)
    def __floordiv__(self, o): return self._binop(o, jnp.floor_divide)
    def __matmul__(self, o): return self._binop(o, jnp.matmul)

    def __iadd__(self, o):
        self._data = (self + o)._data
        return self

    def __isub__(self, o):
        self._data = (self - o)._data
        return self

    def __imul__(self, o):
        self._data = (self * o)._data
        return self

    def __itruediv__(self, o):
        self._data = (self / o)._data
        return self

    def __neg__(self): return _apply(jnp.negative, self)
    def __abs__(self): return _apply(jnp.abs, self)

    def __eq__(self, o): return self._binop(o, lambda a, b: (a == b).astype(a.dtype))
    def __ne__(self, o): return self._binop(o, lambda a, b: (a != b).astype(a.dtype))
    def __lt__(self, o): return self._binop(o, lambda a, b: (a < b).astype(a.dtype))
    def __le__(self, o): return self._binop(o, lambda a, b: (a <= b).astype(a.dtype))
    def __gt__(self, o): return self._binop(o, lambda a, b: (a > b).astype(a.dtype))
    def __ge__(self, o): return self._binop(o, lambda a, b: (a >= b).astype(a.dtype))

    def __hash__(self):
        return id(self)

    # ------------------------------------------------------------- shape ops
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        if "shape" in kwargs:
            shape = kwargs["shape"]
            if isinstance(shape, int):
                shape = (shape,)
        new_shape = _mx_reshape(self.shape, tuple(shape))
        return _apply(lambda x: x.reshape(new_shape), self)

    def reshape_like(self, other):
        return self.reshape(other.shape)

    def flatten(self):
        """MXNet Flatten: collapse all but first axis (ref tensor/matrix_op.cc)."""
        n = self.shape[0] if self.ndim > 0 else 1
        return _apply(lambda x: x.reshape(n, -1), self)

    @property
    def T(self):
        return _apply(jnp.transpose, self)

    def transpose(self, axes=None):
        return _apply(lambda x: jnp.transpose(x, axes), self)

    def swapaxes(self, dim1, dim2):
        return _apply(lambda x: jnp.swapaxes(x, dim1, dim2), self)

    def expand_dims(self, axis):
        return _apply(lambda x: jnp.expand_dims(x, axis), self)

    def squeeze(self, axis=None):
        return _apply(lambda x: jnp.squeeze(x, axis), self)

    def broadcast_to(self, shape):
        return _apply(lambda x: jnp.broadcast_to(x, shape), self)

    def broadcast_like(self, other):
        return self.broadcast_to(other.shape)

    def tile(self, reps):
        return _apply(lambda x: jnp.tile(x, reps), self)

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        return split(self, num_outputs, axis, squeeze_axis)

    def slice(self, begin, end, step=None):
        return slice_op(self, begin, end, step)

    def slice_axis(self, axis, begin, end):
        return slice_axis(self, axis, begin, end)

    def pick(self, index, axis=-1, keepdims=False):
        return pick(self, index, axis, keepdims)

    def one_hot(self, depth, on_value=1.0, off_value=0.0, dtype="float32"):
        return one_hot(self, depth, on_value, off_value, dtype)

    # ------------------------------------------------------------- reductions
    def _reduce(self, fn, axis=None, keepdims=False):
        ax = _norm_axis(axis)
        return _apply(lambda x: fn(x, axis=ax, keepdims=keepdims), self)

    def sum(self, axis=None, keepdims=False, **kw): return self._reduce(jnp.sum, axis, keepdims)
    def mean(self, axis=None, keepdims=False, **kw): return self._reduce(jnp.mean, axis, keepdims)
    def max(self, axis=None, keepdims=False, **kw): return self._reduce(jnp.max, axis, keepdims)
    def min(self, axis=None, keepdims=False, **kw): return self._reduce(jnp.min, axis, keepdims)
    def prod(self, axis=None, keepdims=False, **kw): return self._reduce(jnp.prod, axis, keepdims)

    def _argreduce(self, jfn, axis, keepdims):
        # MXNet convention: float indices. Past 2^24 the float32 mantissa
        # can no longer hold exact indices (and jax's default int32 index
        # dtype wraps past 2^31) — large extents compute under a scoped
        # x64 enable and return float64 (the int64-tensor-size story,
        # ref USE_INT64_TENSOR_SIZE / tests/nightly/test_large_vector.py)
        extent = self.size if axis is None else self.shape[axis]
        if extent > (1 << 24):
            with _base.enable_x64(True):
                return _apply(lambda x: jfn(x, axis=axis, keepdims=keepdims)
                              .astype(onp.float64), self)
        return _apply(lambda x: jfn(x, axis=axis, keepdims=keepdims)
                      .astype(onp.float32), self)

    def argmax(self, axis=None, keepdims=False):
        return self._argreduce(jnp.argmax, axis, keepdims)

    def argmin(self, axis=None, keepdims=False):
        return self._argreduce(jnp.argmin, axis, keepdims)

    def norm(self, ord=2, axis=None, keepdims=False):
        return norm(self, ord, axis, keepdims)

    def clip(self, a_min=None, a_max=None):
        return _apply(lambda x: jnp.clip(x, a_min, a_max), self)

    # unary math conveniences
    def abs(self): return _apply(jnp.abs, self)
    def exp(self): return _apply(jnp.exp, self)
    def log(self): return _apply(jnp.log, self)
    def sqrt(self): return _apply(jnp.sqrt, self)
    def square(self): return _apply(jnp.square, self)
    def sign(self): return _apply(jnp.sign, self)
    def round(self): return _apply(jnp.round, self)
    def floor(self): return _apply(jnp.floor, self)
    def ceil(self): return _apply(jnp.ceil, self)
    def sigmoid(self): return _apply(jax.nn.sigmoid, self)
    def tanh(self): return _apply(jnp.tanh, self)
    def relu(self): return _apply(jax.nn.relu, self)
    def softmax(self, axis=-1): return _apply(lambda x: jax.nn.softmax(x, axis=axis), self)
    def log_softmax(self, axis=-1): return _apply(lambda x: jax.nn.log_softmax(x, axis=axis), self)

    def dot(self, other):
        return dot(self, other)

    def topk(self, axis=-1, k=1, ret_typ="indices", is_ascend=False):
        return topk(self, axis, k, ret_typ, is_ascend)

    def sort(self, axis=-1, is_ascend=True):
        return sort(self, axis, is_ascend)

    def argsort(self, axis=-1, is_ascend=True):
        return argsort(self, axis, is_ascend)


# =================================================================== helpers

def _np_dtype(dtype):
    if dtype in ("bfloat16", jnp.bfloat16):
        return jnp.bfloat16
    return onp.dtype(dtype)


def _norm_axis(axis):
    if isinstance(axis, list):
        return tuple(axis)
    return axis


def _index_fixup(key):
    def fix(k):
        if isinstance(k, NDArray):
            return k._data
        return k
    if isinstance(key, tuple):
        return tuple(fix(k) for k in key)
    return fix(key)


def _mx_reshape(old, new):
    """MXNet reshape special codes: 0 = copy dim, -1 = infer, -2 = copy rest,
    -3 = merge two dims, -4 = split (ref tensor/matrix_op.cc Reshape)."""
    if -2 not in new and -3 not in new and -4 not in new:
        return tuple(old[i] if d == 0 else d for i, d in enumerate(new))
    out, i = [], 0
    it = iter(range(len(new)))
    j = 0
    while j < len(new):
        d = new[j]
        if d == 0:
            out.append(old[i]); i += 1
        elif d == -1:
            out.append(-1); i += 1
        elif d == -2:
            out.extend(old[i:]); i = len(old)
        elif d == -3:
            out.append(old[i] * old[i + 1]); i += 2
        elif d == -4:
            d1, d2 = new[j + 1], new[j + 2]
            if d1 == -1:
                d1 = old[i] // d2
            if d2 == -1:
                d2 = old[i] // d1
            out.extend([d1, d2]); i += 1; j += 2
        else:
            out.append(d); i += 1
        j += 1
    return tuple(out)


def _apply(fn, *inputs):
    """Execute a pure JAX function on NDArray inputs, eagerly; tape if recording.

    This is the single choke point every op goes through — the TPU analog of
    Imperative::Invoke (src/imperative/imperative.cc:89). When the profiler
    runs with profile_imperative, every op is timed (synced) and aggregated
    — the per-op engine instrumentation of the reference's profiler.
    """
    from .. import profiler as _prof
    profiling = _prof.imperative_active()
    if profiling:
        # epoch-anchored monotonic us (NTP-step safe; profiler.now_us)
        t0 = _prof.now_us()
    data = [x._data for x in inputs]
    out = fn(*data)
    if profiling:
        name = getattr(fn, "__qualname__", None) or \
            getattr(fn, "__name__", "op")
        _prof.record_op(name, t0,
                        list(out) if isinstance(out, (tuple, list)) else [out])
    if isinstance(out, (tuple, list)):
        outs = [NDArray(o) for o in out]
        if autograd.is_recording():
            autograd._record_op(fn, inputs, outs)
        return outs if isinstance(out, list) else tuple(outs)
    res = NDArray(out)
    if autograd.is_recording():
        autograd._record_op(fn, inputs, [res])
    return res


def _to_nd(x, ctx=None, dtype=None):
    if isinstance(x, NDArray):
        return x
    return array(x, ctx=ctx, dtype=dtype)


# =================================================================== creation

def array(source_array, ctx=None, dtype=None):
    if isinstance(source_array, NDArray):
        data = source_array._data
    elif isinstance(source_array, (list, tuple, int, float)) and dtype is None:
        # MXNet semantics: python containers default to float32
        data = onp.asarray(source_array, dtype=onp.float32)
    else:
        data = onp.asarray(source_array)
        if dtype is None and data.dtype == onp.float64:
            data = data.astype(onp.float32)
    if dtype is not None:
        data = jnp.asarray(data, dtype=_np_dtype(dtype))
    return NDArray(_ctx_put(data, ctx), ctx=ctx)


def zeros(shape, ctx=None, dtype="float32", **kwargs):
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(_ctx_put(jnp.zeros(shape, _np_dtype(dtype)), ctx), ctx=ctx)


def ones(shape, ctx=None, dtype="float32", **kwargs):
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(_ctx_put(jnp.ones(shape, _np_dtype(dtype)), ctx), ctx=ctx)


def full(shape, val, ctx=None, dtype="float32", **kwargs):
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(_ctx_put(jnp.full(shape, val, _np_dtype(dtype)), ctx), ctx=ctx)


def empty(shape, ctx=None, dtype="float32"):
    return zeros(shape, ctx, dtype)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype="float32"):
    out = jnp.arange(start, stop, step, _np_dtype(dtype))
    if repeat != 1:
        out = jnp.repeat(out, repeat)
    return NDArray(_ctx_put(out, ctx), ctx=ctx)


def eye(N, M=0, k=0, ctx=None, dtype="float32"):
    return NDArray(_ctx_put(jnp.eye(N, M if M else None, k, dtype=_np_dtype(dtype)), ctx), ctx=ctx)


def zeros_like(a):
    return _apply(jnp.zeros_like, a)


def ones_like(a):
    return _apply(jnp.ones_like, a)


def waitall():
    """Block until all launched work is done (≙ Engine::WaitForAll)."""
    try:
        (jax.device_put(0.0) + 0).block_until_ready()
        jax.effects_barrier()
    except Exception:
        pass


# =================================================================== op tables
# Unary ops: one-liner parity with src/operator/tensor/elemwise_unary_op_basic.cc
_UNARY = {
    "abs": jnp.abs, "sign": jnp.sign, "round": jnp.round, "rint": jnp.rint,
    "ceil": jnp.ceil, "floor": jnp.floor, "trunc": jnp.trunc,
    "fix": jnp.trunc, "square": jnp.square, "sqrt": jnp.sqrt,
    "rsqrt": lambda x: lax.rsqrt(x), "cbrt": jnp.cbrt,
    "rcbrt": lambda x: 1.0 / jnp.cbrt(x),
    "exp": jnp.exp, "log": jnp.log, "log10": jnp.log10, "log2": jnp.log2,
    "log1p": jnp.log1p, "expm1": jnp.expm1,
    "sin": jnp.sin, "cos": jnp.cos, "tan": jnp.tan,
    "arcsin": jnp.arcsin, "arccos": jnp.arccos, "arctan": jnp.arctan,
    "sinh": jnp.sinh, "cosh": jnp.cosh, "tanh": jnp.tanh,
    "arcsinh": jnp.arcsinh, "arccosh": jnp.arccosh, "arctanh": jnp.arctanh,
    "degrees": jnp.degrees, "radians": jnp.radians,
    "reciprocal": jnp.reciprocal, "negative": jnp.negative,
    "erf": jax.scipy.special.erf, "erfinv": jax.scipy.special.erfinv,
    "gamma": lambda x: jnp.exp(jax.scipy.special.gammaln(x)),
    "gammaln": jax.scipy.special.gammaln,
    "sigmoid": jax.nn.sigmoid, "relu": jax.nn.relu,
    "softsign": jax.nn.soft_sign,
    "logical_not": lambda x: (x == 0).astype(x.dtype),
}

_BINARY = {
    "add": jnp.add, "subtract": jnp.subtract, "multiply": jnp.multiply,
    "divide": jnp.divide, "modulo": jnp.mod, "power": jnp.power,
    "maximum": jnp.maximum, "minimum": jnp.minimum,
    "hypot": jnp.hypot, "arctan2": jnp.arctan2,
    "equal": lambda a, b: (a == b).astype(jnp.result_type(a, b)),
    "not_equal": lambda a, b: (a != b).astype(jnp.result_type(a, b)),
    "greater": lambda a, b: (a > b).astype(jnp.result_type(a, b)),
    "greater_equal": lambda a, b: (a >= b).astype(jnp.result_type(a, b)),
    "lesser": lambda a, b: (a < b).astype(jnp.result_type(a, b)),
    "lesser_equal": lambda a, b: (a <= b).astype(jnp.result_type(a, b)),
    "logical_and": lambda a, b: ((a != 0) & (b != 0)).astype(jnp.result_type(a, b)),
    "logical_or": lambda a, b: ((a != 0) | (b != 0)).astype(jnp.result_type(a, b)),
    "logical_xor": lambda a, b: ((a != 0) ^ (b != 0)).astype(jnp.result_type(a, b)),
}


def _make_unary(fn):
    def op(data, **kwargs):
        return _apply(fn, _to_nd(data))
    return op


def _make_binary(fn, name):
    def op(lhs, rhs, **kwargs):
        if isinstance(lhs, NDArray) and isinstance(rhs, NDArray):
            return _apply(fn, lhs, rhs)
        if isinstance(lhs, NDArray):
            return _apply(lambda a: fn(a, rhs), lhs)
        return _apply(lambda b: fn(lhs, b), rhs)
    op.__name__ = name
    return op


_g = globals()
for _name, _fn in _UNARY.items():
    _g[_name] = _make_unary(_fn)
    __all__.append(_name)
for _name, _fn in _BINARY.items():
    _g[_name] = _make_binary(_fn, _name)
    __all__.append(_name)
    # broadcast_* aliases (MXNet families map to the same XLA broadcasting op)
    _g["broadcast_" + _name] = _g[_name]
    __all__.append("broadcast_" + _name)

# extra broadcast family aliases used by MXNet code
broadcast_sub = _g["broadcast_subtract"]
broadcast_mul = _g["broadcast_multiply"]
broadcast_div = _g["broadcast_divide"]
broadcast_mod = _g["broadcast_modulo"]
broadcast_plus = _g["broadcast_add"]
broadcast_minus = _g["broadcast_subtract"]
__all__ += ["broadcast_sub", "broadcast_mul", "broadcast_div", "broadcast_mod",
            "broadcast_plus", "broadcast_minus", "mod",
            "elemwise_add", "elemwise_sub", "elemwise_mul", "elemwise_div"]
elemwise_add = _g["add"]
elemwise_sub = _g["subtract"]
elemwise_mul = _g["multiply"]
elemwise_div = _g["divide"]
mod = _g["modulo"]


# =================================================================== shape ops

def reshape(data, shape, **kwargs):
    return data.reshape(shape)


def reshape_like(lhs, rhs):
    return lhs.reshape(rhs.shape)


def flatten(data):
    return data.flatten()


def transpose(data, axes=None):
    return data.transpose(axes)


def swapaxes(data, dim1=0, dim2=1):
    return data.swapaxes(dim1, dim2)


SwapAxis = swapaxes


def expand_dims(data, axis):
    return data.expand_dims(axis)


def squeeze(data, axis=None):
    return data.squeeze(axis)


def broadcast_to(data, shape):
    return data.broadcast_to(shape)


def broadcast_like(lhs, rhs):
    return lhs.broadcast_to(rhs.shape)


def broadcast_axis(data, axis=None, size=None):
    axes = axis if isinstance(axis, (list, tuple)) else (axis,)
    sizes = size if isinstance(size, (list, tuple)) else (size,)
    shape = list(data.shape)
    for a, s in zip(axes, sizes):
        shape[a] = s
    return data.broadcast_to(tuple(shape))


def tile(data, reps):
    return data.tile(reps)


def repeat(data, repeats, axis=None):
    return _apply(lambda x: jnp.repeat(x, repeats, axis=axis), data)


def pad(data, mode="constant", pad_width=None, constant_value=0):
    """ref src/operator/pad.cc — pad_width in MXNet flat (before,after)*ndim order."""
    pw = [(pad_width[2 * i], pad_width[2 * i + 1]) for i in range(len(pad_width) // 2)]
    jmode = {"constant": "constant", "edge": "edge", "reflect": "reflect"}[mode]
    if jmode == "constant":
        return _apply(lambda x: jnp.pad(x, pw, mode="constant", constant_values=constant_value), data)
    return _apply(lambda x: jnp.pad(x, pw, mode=jmode), data)


def flip(data, axis):
    return _apply(lambda x: jnp.flip(x, axis), data)


reverse = flip


def concat(*data, dim=1, **kwargs):
    if len(data) == 1 and isinstance(data[0], (list, tuple)):
        data = tuple(data[0])
    axis = kwargs.get("axis", dim)
    return _apply(lambda *xs: jnp.concatenate(xs, axis=axis), *data)


Concat = concat


def concatenate(arrays, axis=0):
    return concat(*arrays, dim=axis)


def stack(*data, axis=0, **kwargs):
    if len(data) == 1 and isinstance(data[0], (list, tuple)):
        data = tuple(data[0])
    return _apply(lambda *xs: jnp.stack(xs, axis=axis), *data)


def split(data, num_outputs, axis=1, squeeze_axis=False):
    """ref src/operator/slice_channel.cc (SliceChannel)."""
    def fn(x):
        parts = jnp.split(x, num_outputs, axis=axis)
        if squeeze_axis:
            parts = [jnp.squeeze(p, axis=axis) for p in parts]
        return parts
    out = _apply(fn, data)
    return out if num_outputs > 1 else out[0]


SliceChannel = split


def slice_op(data, begin, end, step=None):
    """ref src/operator/tensor/matrix_op.cc Slice."""
    nd_ = data.ndim
    begin = list(begin) + [None] * (nd_ - len(begin))
    end = list(end) + [None] * (nd_ - len(end))
    step = list(step) + [None] * (nd_ - len(step)) if step else [None] * nd_
    idx = tuple(builtins_slice(b, e, s) for b, e, s in zip(begin, end, step))
    return _apply(lambda x: x[idx], data)


builtins_slice = slice  # keep python builtin accessible


def slice_axis(data, axis, begin, end):
    idx = [builtins_slice(None)] * data.ndim
    if end is None or end == 0 and begin < 0:
        end = None
    idx[axis] = builtins_slice(begin, end)
    idx = tuple(idx)
    return _apply(lambda x: x[idx], data)


def slice_like(data, shape_like, axes=None):
    tgt = shape_like.shape
    idx = [builtins_slice(None)] * data.ndim
    axes_ = axes if axes is not None else range(data.ndim)
    for a in axes_:
        idx[a] = builtins_slice(0, tgt[a])
    idx = tuple(idx)
    return _apply(lambda x: x[idx], data)


# =================================================================== reductions

def _make_reduce(fn, name):
    def op(data, axis=None, keepdims=False, exclude=False, **kwargs):
        ax = _norm_axis(axis)
        if exclude and ax is not None:
            axs = (ax,) if isinstance(ax, int) else tuple(ax)
            ax = tuple(i for i in range(data.ndim) if i not in axs)
        return _apply(lambda x: fn(x, axis=ax, keepdims=keepdims), data)
    op.__name__ = name
    return op


sum = _make_reduce(jnp.sum, "sum")
mean = _make_reduce(jnp.mean, "mean")
prod = _make_reduce(jnp.prod, "prod")
nansum = _make_reduce(jnp.nansum, "nansum")
nanprod = _make_reduce(jnp.nanprod, "nanprod")
max = _make_reduce(jnp.max, "max")
min = _make_reduce(jnp.min, "min")
sum_axis = sum
max_axis = max
min_axis = min


def norm(data, ord=2, axis=None, keepdims=False):
    ax = _norm_axis(axis)
    def fn(x):
        if ord == 1:
            return jnp.sum(jnp.abs(x), axis=ax, keepdims=keepdims)
        return jnp.sqrt(jnp.sum(jnp.square(x), axis=ax, keepdims=keepdims))
    return _apply(fn, data)


L2Normalization = None  # defined below


def argmax(data, axis=None, keepdims=False):
    return data.argmax(axis, keepdims)


def argmin(data, axis=None, keepdims=False):
    return data.argmin(axis, keepdims)


def clip(data, a_min, a_max):
    return data.clip(a_min, a_max)


def where(condition, x, y):
    return _apply(lambda c, a, b: jnp.where(c != 0, a, b), condition, x, y)


def maximum_scalar(data, scalar):
    return _apply(lambda x: jnp.maximum(x, scalar), data)


# =================================================================== linalg-ish

def dot(lhs, rhs, transpose_a=False, transpose_b=False):
    """MXNet dot: contract last axis of lhs with first axis of rhs
    (ref src/operator/tensor/dot-inl.h) — maps straight onto the MXU."""
    def fn(a, b):
        if transpose_a:
            a = jnp.transpose(a)
        if transpose_b:
            b = jnp.transpose(b)
        if a.ndim == 1 and b.ndim == 1:
            return jnp.dot(a, b)
        return jnp.tensordot(a, b, axes=1)
    return _apply(fn, lhs, rhs)


def batch_dot(lhs, rhs, transpose_a=False, transpose_b=False):
    """ref src/operator/tensor/dot-inl.h batch_dot → batched MXU matmul."""
    def fn(a, b):
        if transpose_a:
            a = jnp.swapaxes(a, -1, -2)
        if transpose_b:
            b = jnp.swapaxes(b, -1, -2)
        return jnp.matmul(a, b)
    return _apply(fn, lhs, rhs)


linalg_gemm2 = batch_dot


def khatri_rao(*args):
    def fn(*ms):
        out = ms[0]
        for m in ms[1:]:
            out = jnp.einsum("i...,j...->ij...", out, m).reshape(-1, out.shape[-1])
        return out
    return _apply(fn, *args)


# =================================================================== indexing ops

def take(a, indices, axis=0, mode="clip"):
    """ref src/operator/tensor/indexing_op.cc Take."""
    def fn(x, idx):
        i = idx.astype(jnp.int32)
        if mode == "clip":
            i = jnp.clip(i, 0, x.shape[axis] - 1)
        elif mode == "wrap":
            i = jnp.mod(i, x.shape[axis])
        return jnp.take(x, i, axis=axis)
    return _apply(fn, a, _to_nd(indices))


def Embedding(data, weight, input_dim=None, output_dim=None, dtype=None, sparse_grad=False, **kw):
    """ref src/operator/tensor/indexing_op.cc Embedding — gather rows."""
    return _apply(lambda idx, w: jnp.take(w, idx.astype(jnp.int32), axis=0), data, weight)


def gather_nd(data, indices):
    def fn(x, idx):
        idx = idx.astype(jnp.int32)
        return x[tuple(idx[i] for i in range(idx.shape[0]))]
    return _apply(fn, data, indices)


def scatter_nd(data, indices, shape):
    def fn(d, idx):
        idx = idx.astype(jnp.int32)
        out = jnp.zeros(shape, d.dtype)
        return out.at[tuple(idx[i] for i in range(idx.shape[0]))].set(d)
    return _apply(fn, data, indices)


def pick(data, index, axis=-1, keepdims=False, mode="clip"):
    """ref src/operator/tensor/broadcast_reduce_op.h Pick."""
    def fn(x, idx):
        i = jnp.clip(idx.astype(jnp.int32), 0, x.shape[axis] - 1)
        picked = jnp.take_along_axis(x, jnp.expand_dims(i, axis), axis=axis)
        return picked if keepdims else jnp.squeeze(picked, axis=axis)
    return _apply(fn, data, _to_nd(index))


def one_hot(indices, depth, on_value=1.0, off_value=0.0, dtype="float32"):
    def fn(idx):
        oh = jax.nn.one_hot(idx.astype(jnp.int32), depth, dtype=_np_dtype(dtype))
        return oh * (on_value - off_value) + off_value
    return _apply(fn, _to_nd(indices))


def topk(data, axis=-1, k=1, ret_typ="indices", is_ascend=False, dtype="float32"):
    """ref src/operator/tensor/ordering_op-inl.h TopK."""
    def fn(x):
        xm = jnp.moveaxis(x, axis, -1)
        neg = xm if is_ascend else -xm
        vals, idxs = lax.top_k(-neg, k) if is_ascend else lax.top_k(xm, k)
        if is_ascend:
            vals = -vals
        vals = jnp.moveaxis(vals, -1, axis)
        idxs = jnp.moveaxis(idxs, -1, axis).astype(_np_dtype(dtype))
        if ret_typ == "value":
            return vals
        if ret_typ == "both":
            return (vals, idxs)
        return idxs
    return _apply(fn, data)


def sort(data, axis=-1, is_ascend=True):
    def fn(x):
        s = jnp.sort(x, axis=axis)
        return s if is_ascend else jnp.flip(s, axis=axis)
    return _apply(fn, data)


def argsort(data, axis=-1, is_ascend=True, dtype="float32"):
    def fn(x):
        s = jnp.argsort(x, axis=axis)
        if not is_ascend:
            s = jnp.flip(s, axis=axis)
        return s.astype(_np_dtype(dtype))
    return _apply(fn, data)


def shuffle(data):
    from . import random as _rnd
    def fn(x):
        return jax.random.permutation(_rnd._next_key(), x, axis=0)
    return _apply(fn, data)


def diag(data, k=0):
    return _apply(lambda x: jnp.diag(x, k) if x.ndim <= 2 else jnp.diagonal(x, k), data)


def cast(data, dtype):
    return data.astype(dtype)


Cast = cast


def amp_cast(data, dtype):
    """ref src/operator/tensor/amp_cast.cc — AMP-inserted cast."""
    return data.astype(dtype)


def amp_multicast(*data, num_outputs=None):
    dtypes = [d.dtype for d in data]
    widest = jnp.result_type(*dtypes)
    return [d.astype(widest) for d in data]


# =================================================================== neural ops
# Operator-style ops, parity with src/operator/nn/* — all lower to XLA HLO that
# the TPU compiler fuses onto MXU/VPU. Gluon layers call these.

def FullyConnected(data, weight, bias=None, num_hidden=None, no_bias=False, flatten=True, **kw):
    """ref src/operator/nn/fully_connected.cc — y = x W^T + b (MXU matmul)."""
    def fn_b(x, w, b):
        xx = x.reshape(x.shape[0], -1) if flatten else x
        y = jnp.matmul(xx, w.T)
        return y + b
    def fn_nb(x, w):
        xx = x.reshape(x.shape[0], -1) if flatten else x
        return jnp.matmul(xx, w.T)
    if no_bias or bias is None:
        return _apply(fn_nb, data, weight)
    return _apply(fn_b, data, weight, bias)


def _tuple2(v):
    if v is None:
        return None
    if isinstance(v, int):
        return (v, v)
    return tuple(v)


def Convolution(data, weight, bias=None, kernel=None, stride=(1, 1), dilate=(1, 1),
                pad=(0, 0), num_filter=None, num_group=1, no_bias=False, layout="NCHW", **kw):
    """ref src/operator/nn/convolution-inl.h — lax.conv_general_dilated on MXU.

    API is NCHW like MXNet; XLA's TPU backend internally picks optimal layout.
    Supports 1D (NCW) and 2D (NCHW) and 3D (NCDHW) via kernel rank.
    """
    n = len(kernel)
    stride = tuple(stride)[:n] if stride else (1,) * n
    dilate = tuple(dilate)[:n] if dilate else (1,) * n
    pad_ = tuple(pad)[:n] if pad else (0,) * n
    if len(stride) < n: stride = stride + (1,) * (n - len(stride))
    if len(dilate) < n: dilate = dilate + (1,) * (n - len(dilate))
    if len(pad_) < n: pad_ = pad_ + (0,) * (n - len(pad_))
    spatial = "".join("DHW"[3 - n:][i] for i in range(n))
    dn_str = ("NC" + spatial, "OI" + spatial, "NC" + spatial)

    def conv(x, w):
        dn = lax.conv_dimension_numbers(x.shape, w.shape, dn_str)
        # bf16 operands accumulate in fp32 on the MXU natively; keeping the
        # output dtype == input dtype keeps the VJP dtype-consistent
        return lax.conv_general_dilated(
            x, w, window_strides=stride, padding=[(p, p) for p in pad_],
            rhs_dilation=dilate, dimension_numbers=dn, feature_group_count=num_group)

    if no_bias or bias is None:
        return _apply(conv, data, weight)

    def fn(x, w, b):
        y = conv(x, w)
        return y + b.reshape((1, -1) + (1,) * n)
    return _apply(fn, data, weight, bias)


def Deconvolution(data, weight, bias=None, kernel=None, stride=(1, 1), dilate=(1, 1),
                  pad=(0, 0), adj=(0, 0), num_filter=None, num_group=1, no_bias=False,
                  target_shape=None, **kw):
    """ref src/operator/nn/deconvolution-inl.h — transposed conv expressed as
    the gradient-of-conv: input dilation by stride + flipped kernel, which XLA
    lowers to the same MXU conv kernels as the forward pass."""
    n = len(kernel)
    stride = tuple(stride)[:n] if stride else (1,) * n
    if len(stride) < n:
        stride = stride + (1,) * (n - len(stride))
    dilate = tuple(dilate)[:n] if dilate else (1,) * n
    if len(dilate) < n:
        dilate = dilate + (1,) * (n - len(dilate))
    pad_ = tuple(pad)[:n] if pad else (0,) * n
    if len(pad_) < n:
        pad_ = pad_ + (0,) * (n - len(pad_))
    adj_ = tuple(adj)[:n] if adj else (0,) * n
    if len(adj_) < n:
        adj_ = adj_ + (0,) * (n - len(adj_))
    spatial = "".join("DHW"[3 - n:][i] for i in range(n))
    dn_str = ("NC" + spatial, "IO" + spatial, "NC" + spatial)

    def conv_t(x, w):
        dn = lax.conv_dimension_numbers(x.shape, w.shape, dn_str)
        w_flip = jnp.flip(w, axis=tuple(range(2, 2 + n)))
        pads = [(d * (k - 1) - p, d * (k - 1) - p + a)
                for k, p, a, d in zip(kernel, pad_, adj_, dilate)]
        return lax.conv_general_dilated(
            x, w_flip, window_strides=(1,) * n, padding=pads,
            lhs_dilation=stride, rhs_dilation=dilate, dimension_numbers=dn,
            feature_group_count=num_group)

    def fn(x, w, *maybe_b):
        y = conv_t(x, w)
        if maybe_b:
            y = y + maybe_b[0].reshape((1, -1) + (1,) * n)
        return y
    if no_bias or bias is None:
        return _apply(fn, data, weight)
    return _apply(fn, data, weight, bias)


def Activation(data, act_type="relu", **kw):
    """ref src/operator/nn/activation.cc."""
    fns = {"relu": jax.nn.relu, "sigmoid": jax.nn.sigmoid, "tanh": jnp.tanh,
           "softrelu": jax.nn.softplus, "softsign": jax.nn.soft_sign,
           "log_sigmoid": jax.nn.log_sigmoid, "mish": lambda x: x * jnp.tanh(jax.nn.softplus(x))}
    return _apply(fns[act_type], data)


def LeakyReLU(data, gamma=None, act_type="leaky", slope=0.25, lower_bound=0.125,
              upper_bound=0.334, **kw):
    """ref src/operator/leaky_relu.cc (leaky/prelu/elu/selu/gelu/rrelu)."""
    if act_type == "leaky":
        return _apply(lambda x: jnp.where(x >= 0, x, slope * x), data)
    if act_type == "elu":
        return _apply(lambda x: jnp.where(x >= 0, x, slope * jnp.expm1(x)), data)
    if act_type == "selu":
        return _apply(jax.nn.selu, data)
    if act_type == "gelu":
        return _apply(lambda x: jax.nn.gelu(x, approximate=False), data)
    if act_type == "prelu":
        def fn(x, g):
            gb = g.reshape((1, -1) + (1,) * (x.ndim - 2)) if x.ndim > 1 and g.ndim == 1 else g
            return jnp.where(x >= 0, x, gb * x)
        return _apply(fn, data, gamma)
    if act_type == "rrelu":
        s = (lower_bound + upper_bound) / 2.0
        return _apply(lambda x: jnp.where(x >= 0, x, s * x), data)
    raise ValueError(act_type)


def softmax(data, axis=-1, temperature=None, length=None, **kw):
    """ref src/operator/nn/softmax-inl.h."""
    def fn(x):
        xx = x / temperature if temperature else x
        return jax.nn.softmax(xx, axis=axis)
    if length is not None:
        def fnl(x, ln):
            xx = x / temperature if temperature else x
            mask = jnp.arange(x.shape[axis]) < jnp.expand_dims(ln.astype(jnp.int32), axis)
            xx = jnp.where(mask, xx, -jnp.inf)
            out = jax.nn.softmax(xx, axis=axis)
            return jnp.where(mask, out, 0.0)
        return _apply(fnl, data, length)
    return _apply(fn, data)


def log_softmax(data, axis=-1, temperature=None, **kw):
    def fn(x):
        xx = x / temperature if temperature else x
        return jax.nn.log_softmax(xx, axis=axis)
    return _apply(fn, data)


def softmin(data, axis=-1, **kw):
    return _apply(lambda x: jax.nn.softmax(-x, axis=axis), data)


def SoftmaxActivation(data, mode="instance"):
    axis = -1 if mode == "instance" else 1
    return softmax(data, axis=axis)


@functools.lru_cache(maxsize=64)
def _softmax_output_fn(grad_scale, ignore_label, use_ignore, normalization):
    """Custom-VJP op matching src/operator/softmax_output.cc: forward =
    softmax(data); backward = (softmax - one_hot(label)) * grad_scale,
    independent of the incoming head gradient (loss-layer semantics)."""

    @jax.custom_vjp
    def op(x, lbl):
        return jax.nn.softmax(x, axis=-1)

    def fwd(x, lbl):
        probs = jax.nn.softmax(x, axis=-1)
        return probs, (probs, lbl)

    def bwd(res, g):
        probs, lbl = res
        oh = jax.nn.one_hot(lbl.astype(jnp.int32), probs.shape[-1],
                            dtype=probs.dtype)
        grad = (probs - oh) * grad_scale
        if use_ignore:
            mask = (lbl != ignore_label).astype(probs.dtype)
            grad = grad * jnp.expand_dims(mask, -1)
        if normalization == "valid" and use_ignore:
            n = jnp.maximum(jnp.sum(lbl != ignore_label), 1).astype(probs.dtype)
            grad = grad / n
        elif normalization == "batch":
            grad = grad / probs.shape[0]
        return grad, None

    op.defvjp(fwd, bwd)
    return op


def SoftmaxOutput(data, label, grad_scale=1.0, ignore_label=-1, use_ignore=False,
                  multi_output=False, preserve_shape=False, normalization="null",
                  out_grad=False, smooth_alpha=0.0, **kw):
    """ref src/operator/softmax_output.cc (loss-layer backward semantics)."""
    op = _softmax_output_fn(float(grad_scale), int(ignore_label), bool(use_ignore),
                            str(normalization))
    return _apply(op, data, label)


@functools.lru_cache(maxsize=16)
def _regression_output_fn(kind, grad_scale):
    """ref src/operator/regression_output.cc Linear/Logistic/MAE."""

    @jax.custom_vjp
    def op(x, lbl):
        return jax.nn.sigmoid(x) if kind == "logistic" else x

    def fwd(x, lbl):
        out = jax.nn.sigmoid(x) if kind == "logistic" else x
        return out, (out, lbl)

    def bwd(res, g):
        out, lbl = res
        lblr = lbl.reshape(out.shape)
        if kind == "mae":
            grad = jnp.sign(out - lblr) * grad_scale
        else:
            grad = (out - lblr) * grad_scale
        return grad, None

    op.defvjp(fwd, bwd)
    return op


def LinearRegressionOutput(data, label, grad_scale=1.0, **kw):
    return _apply(_regression_output_fn("linear", float(grad_scale)), data, label)


def LogisticRegressionOutput(data, label, grad_scale=1.0, **kw):
    return _apply(_regression_output_fn("logistic", float(grad_scale)), data, label)


def MAERegressionOutput(data, label, grad_scale=1.0, **kw):
    return _apply(_regression_output_fn("mae", float(grad_scale)), data, label)


def Pooling(data, kernel=None, pool_type="max", global_pool=False, stride=None,
            pad=None, pooling_convention="valid", count_include_pad=True, layout=None, **kw):
    """ref src/operator/nn/pooling.cc — lax.reduce_window on VPU."""
    nd_sp = data.ndim - 2
    if global_pool:
        axes = tuple(range(2, data.ndim))
        if pool_type == "max":
            return _apply(lambda x: jnp.max(x, axis=axes, keepdims=True), data)
        return _apply(lambda x: jnp.mean(x, axis=axes, keepdims=True), data)
    kernel = tuple(kernel)
    stride = tuple(stride) if stride else (1,) * nd_sp
    pad = tuple(pad) if pad else (0,) * nd_sp
    dims = (1, 1) + kernel
    strides = (1, 1) + stride
    spad = ((0, 0), (0, 0)) + tuple((p, p) for p in pad)

    if pooling_convention == "full":
        # ceil-mode: pad extra on the high side so last partial window counts
        extra = []
        for i in range(nd_sp):
            in_sz = data.shape[2 + i] + 2 * pad[i]
            rem = (in_sz - kernel[i]) % stride[i]
            extra.append((stride[i] - rem) % stride[i] if rem else 0)
        spad = ((0, 0), (0, 0)) + tuple((p, p + e) for p, e in zip(pad, extra))

    if pool_type == "max":
        def fn(x):
            # init must carry the operand dtype (an int python literal binds
            # as int32 and reduce_window rejects the mismatch for int8/int16)
            init = (-jnp.inf if jnp.issubdtype(x.dtype, jnp.floating)
                    else x.dtype.type(jnp.iinfo(x.dtype).min))
            return lax.reduce_window(x, init, lax.max, dims, strides, spad)
        return _apply(fn, data)
    if pool_type in ("avg", "sum"):
        def fn(x):
            s = lax.reduce_window(x, 0.0, lax.add, dims, strides, spad)
            if pool_type == "sum":
                return s
            if count_include_pad:
                denom = 1.0
                for k in kernel:
                    denom *= k
                return s / denom
            ones_ = jnp.ones_like(x)
            cnt = lax.reduce_window(ones_, 0.0, lax.add, dims, strides, spad)
            return s / cnt
        return _apply(fn, data)
    if pool_type == "lp":
        p = kw.get("p_value", 2)
        def fn(x):
            s = lax.reduce_window(jnp.abs(x) ** p, 0.0, lax.add, dims, strides, spad)
            return s ** (1.0 / p)
        return _apply(fn, data)
    raise ValueError(pool_type)


def Dropout(data, p=0.5, mode="training", axes=(), cudnn_off=False, **kw):
    """ref src/operator/nn/dropout-inl.h — jax.random bernoulli mask."""
    if not autograd.is_training() or p <= 0:
        return data
    from . import random as _rnd
    def fn(x):
        shape = list(x.shape)
        for a in axes or ():
            shape[a] = 1
        keep = 1.0 - p
        mask = jax.random.bernoulli(_rnd._next_key(), keep, tuple(shape)).astype(x.dtype)
        return x * mask / keep
    return _apply(fn, data)


def BatchNorm(data, gamma, beta, moving_mean, moving_var, eps=1e-5, momentum=0.9,
              fix_gamma=False, use_global_stats=False, output_mean_var=False, axis=1,
              cudnn_off=False, **kw):
    """ref src/operator/nn/batch_norm.cc.

    Training mode computes batch statistics and UPDATES moving_mean/moving_var
    in place (matching MXNet's aux-state side effect); inference uses them.
    """
    training = autograd.is_training() and not use_global_stats
    red_axes = tuple(i for i in range(data.ndim) if i != axis)
    bshape = tuple(1 if i != axis else data.shape[axis] for i in range(data.ndim))

    if training:
        # side-effect on aux states: eager writes normally; collected (returned
        # as extra outputs) when tracing inside a compiled program
        from ..gluon import _functional

        def _stats(x, shift):
            # SHIFTED one-pass batch stats: E[(x-s)^2] - (E[x]-s)^2 in fp32,
            # s = running mean (a resident (C,) vector, so the broadcast
            # subtraction fuses and both reductions happen in a single read
            # of the activation — ~19% faster than two-pass mean/var on TPU,
            # which is bandwidth-bound here). In steady state s ~= m keeps
            # the subtraction free of catastrophic cancellation even when
            # |mean| >> std (the failure mode of naive E[x^2]-E[x]^2); for
            # the first steps after init (s=0) this degrades to the naive
            # form, which only loses precision for |mean|/std > ~1000 —
            # not reachable with standard inits. (A slice-derived shift was
            # tried and defeated XLA's fusion: 2112 vs 2568 img/s.)
            xf = x.astype(jnp.float32)
            s = lax.stop_gradient(shift.astype(jnp.float32)).reshape(bshape)
            m = jnp.mean(xf, axis=red_axes)
            d2 = jnp.mean(jnp.square(xf - s), axis=red_axes)
            v = d2 - jnp.square(m - s.reshape(m.shape))
            return m, jnp.maximum(v, 0.0)

        x = data._data
        mean_, var_ = _stats(x, moving_mean._data)
        new_mm = (momentum * moving_mean._data + (1 - momentum) * mean_).astype(moving_mean.dtype)
        new_mv = (momentum * moving_var._data + (1 - momentum) * var_).astype(moving_var.dtype)
        if _functional.in_functional_mode():
            _functional.collect_aux_update(moving_mean, new_mm)
            _functional.collect_aux_update(moving_var, new_mv)
        else:
            moving_mean._data = new_mm
            moving_var._data = new_mv

        def fn(x, g, b, mm):
            m, v = _stats(x, mm)
            m = m.reshape(bshape)
            v = v.reshape(bshape)
            gg = jnp.ones_like(g) if fix_gamma else g
            out = (x.astype(jnp.float32) - m) * lax.rsqrt(v + eps) \
                * gg.reshape(bshape) + b.reshape(bshape)
            return out.astype(x.dtype)
        return _apply(fn, data, gamma, beta, moving_mean)

    def fn(x, g, b, mm, mv):
        gg = jnp.ones_like(g) if fix_gamma else g
        scale = gg.reshape(bshape) * lax.rsqrt(mv.reshape(bshape) + eps)
        out = (x - mm.reshape(bshape)) * scale + b.reshape(bshape)
        return out.astype(x.dtype)
    return _apply(fn, data, gamma, beta, moving_mean, moving_var)


def LayerNorm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False, **kw):
    """ref src/operator/nn/layer_norm.cc — fused by XLA on TPU."""
    def fn(x, g, b):
        xf = x.astype(jnp.float32)
        m = jnp.mean(xf, axis=axis, keepdims=True)
        v = jnp.var(xf, axis=axis, keepdims=True)
        shp = [1] * x.ndim
        shp[axis if axis >= 0 else x.ndim + axis] = x.shape[axis]
        out = (xf - m) * lax.rsqrt(v + eps) * g.reshape(shp) + b.reshape(shp)
        return out.astype(x.dtype)
    return _apply(fn, data, gamma, beta)


def GroupNorm(data, gamma, beta, num_groups=1, eps=1e-5, **kw):
    """ref src/operator/nn/group_norm.cc (NCHW)."""
    def fn(x, g, b):
        n, c = x.shape[0], x.shape[1]
        rest = x.shape[2:]
        xf = x.astype(jnp.float32).reshape((n, num_groups, c // num_groups) + rest)
        axes = tuple(range(2, xf.ndim))
        m = jnp.mean(xf, axis=axes, keepdims=True)
        v = jnp.var(xf, axis=axes, keepdims=True)
        xn = ((xf - m) * lax.rsqrt(v + eps)).reshape(x.shape)
        shp = (1, c) + (1,) * (x.ndim - 2)
        return (xn * g.reshape(shp) + b.reshape(shp)).astype(x.dtype)
    return _apply(fn, data, gamma, beta)


def InstanceNorm(data, gamma, beta, eps=1e-3, **kw):
    """ref src/operator/instance_norm.cc."""
    def fn(x, g, b):
        axes = tuple(range(2, x.ndim))
        m = jnp.mean(x, axis=axes, keepdims=True)
        v = jnp.var(x, axis=axes, keepdims=True)
        shp = (1, x.shape[1]) + (1,) * (x.ndim - 2)
        out = (x - m) * lax.rsqrt(v + eps) * g.reshape(shp) + b.reshape(shp)
        return out.astype(x.dtype)
    return _apply(fn, data, gamma, beta)


def L2Normalization(data, eps=1e-10, mode="instance"):
    """ref src/operator/l2_normalization.cc."""
    def fn(x):
        if mode == "instance":
            axes = tuple(range(1, x.ndim))
        elif mode == "channel":
            axes = (1,)
        else:  # spatial
            axes = tuple(range(2, x.ndim))
        nrm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes, keepdims=True) + eps)
        return x / nrm
    return _apply(fn, data)


def LRN(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    """ref src/operator/nn/lrn.cc — local response norm across channels."""
    def fn(x):
        sq = jnp.square(x)
        half = nsize // 2
        pad_sq = jnp.pad(sq, ((0, 0), (half, half)) + ((0, 0),) * (x.ndim - 2))
        acc = jnp.zeros_like(x)
        for i in range(nsize):
            acc = acc + lax.dynamic_slice_in_dim(pad_sq, i, x.shape[1], axis=1)
        return x / jnp.power(knorm + alpha * acc / nsize, beta)
    return _apply(fn, data)


def UpSampling(*data, scale=2, sample_type="nearest", num_args=1, **kw):
    """ref src/operator/upsampling.cc (nearest via repeat)."""
    x = data[0]
    def fn(x):
        return jnp.repeat(jnp.repeat(x, scale, axis=2), scale, axis=3)
    return _apply(fn, x)


def BilinearResize2D(data, height=None, width=None, scale_height=None, scale_width=None, **kw):
    """ref src/operator/contrib/bilinear_resize.cc → jax.image.resize."""
    def fn(x):
        h = height or int(x.shape[2] * scale_height)
        w = width or int(x.shape[3] * scale_width)
        return jax.image.resize(x, (x.shape[0], x.shape[1], h, w), method="linear")
    return _apply(fn, data)


def sequence_mask(data, sequence_length=None, use_sequence_length=False, value=0.0, axis=0):
    """ref src/operator/sequence_mask.cc (time-major by default)."""
    if not use_sequence_length or sequence_length is None:
        return data
    def fn(x, slen):
        T = x.shape[axis]
        pos = jnp.arange(T)
        shp = [1] * x.ndim
        shp[axis] = T
        pos = pos.reshape(shp)
        batch_axis = 1 - axis if axis in (0, 1) else 0
        lshp = [1] * x.ndim
        lshp[batch_axis] = x.shape[batch_axis]
        mask = pos < slen.astype(jnp.int32).reshape(lshp)
        return jnp.where(mask, x, value)
    return _apply(fn, data, sequence_length)


SequenceMask = sequence_mask


def SequenceLast(data, sequence_length=None, use_sequence_length=False, axis=0):
    """ref src/operator/sequence_last.cc."""
    if not use_sequence_length or sequence_length is None:
        return slice_axis(data, axis, -1, None).squeeze(axis)
    def fn(x, slen):
        idx = (slen.astype(jnp.int32) - 1)
        xm = jnp.moveaxis(x, axis, 0)
        return xm[idx, jnp.arange(xm.shape[1])]
    return _apply(fn, data, sequence_length)


def SequenceReverse(data, sequence_length=None, use_sequence_length=False, axis=0):
    """ref src/operator/sequence_reverse.cc."""
    if not use_sequence_length or sequence_length is None:
        return flip(data, axis)
    def fn(x, slen):
        T = x.shape[0]
        pos = jnp.arange(T)[:, None]
        ln = slen.astype(jnp.int32)[None, :]
        rev_idx = jnp.where(pos < ln, ln - 1 - pos, pos)
        return jnp.take_along_axis(x, rev_idx.reshape((T, x.shape[1]) + (1,) * (x.ndim - 2)), axis=0)
    return _apply(fn, data, sequence_length)


def make_loss(data, grad_scale=1.0, valid_thresh=0.0, normalization="null"):
    """ref src/operator/make_loss.cc."""
    return data * grad_scale if grad_scale != 1.0 else data


def BlockGrad(data):
    """ref src/operator/tensor/elemwise_unary_op_basic.cc BlockGrad."""
    return _apply(lax.stop_gradient, data)


stop_gradient = BlockGrad


def identity(data):
    return _apply(lambda x: x, data)


def moments(data, axes=None, keepdims=False):
    ax = _norm_axis(axes)
    return _apply(lambda x: (jnp.mean(x, axis=ax, keepdims=keepdims),
                             jnp.var(x, axis=ax, keepdims=keepdims)), data)


def CTCLoss(data, label, data_lengths=None, label_lengths=None,
            use_data_lengths=False, use_label_lengths=False, blank_label="first"):
    """ref src/operator/nn/ctc_loss.cc — forward-backward in log space via scan."""
    from ..ops.ctc import ctc_loss as _ctc
    def fn(x, lbl, *rest):
        dl = rest[0] if use_data_lengths else None
        ll = rest[1] if use_label_lengths and len(rest) > 1 else (
            rest[0] if use_label_lengths else None)
        return _ctc(x, lbl, dl, ll, blank_label)
    args = [data, label]
    if use_data_lengths and data_lengths is not None:
        args.append(data_lengths)
    if use_label_lengths and label_lengths is not None:
        args.append(label_lengths)
    return _apply(fn, *args)


ctc_loss = CTCLoss


# =================================================================== loading
def save(fname, data):
    """Save dict/list of NDArray in the reference's binary list format
    (ref src/ndarray/ndarray.cc:1841-1849) — files are interchangeable with
    upstream MXNet ``.params`` checkpoints. See serialization.py."""
    from . import serialization
    if isinstance(data, NDArray):
        data = [data]
    if isinstance(data, (list, tuple)):
        arrays = [d.asnumpy() for d in data]
        names = []
    else:
        names = list(data.keys())
        arrays = [data[k].asnumpy() for k in names]
    serialization.save_ndarray_list(fname, arrays, names)


def load(fname):
    """Load a ``.params`` file (reference binary format, with npz fallback
    for files written by older versions of this package)."""
    from . import serialization
    if serialization.is_ndarray_list_file(fname):
        arrays, names = serialization.load_ndarray_list(fname)
        if names:
            return {k: array(v) for k, v in zip(names, arrays)}
        return [array(v) for v in arrays]
    with open(fname, "rb") as fh:
        if fh.read(2) != b"PK":  # not an npz archive either
            raise ValueError(
                "%s is neither a binary NDArray list file (magic 0x112) nor "
                "an .npz archive" % fname)
    with onp.load(fname, allow_pickle=False) as f:
        fmt = str(f["__mx_format__"]) if "__mx_format__" in f else "dict"
        items = {k: array(f[k]) for k in f.files if k != "__mx_format__"}
    if fmt == "list":
        return [items[str(i)] for i in range(len(items))]
    return items


def smooth_l1(data, scalar=1.0, **kw):
    """ref tensor/elemwise_unary_op.cc smooth_l1 (Huber with sigma=scalar)."""
    s2 = float(scalar) ** 2

    def fn(x):
        ax = jnp.abs(x)
        return jnp.where(ax < 1.0 / s2, 0.5 * s2 * x * x, ax - 0.5 / s2)

    return _apply(fn, data)


def hard_sigmoid(data, alpha=0.2, beta=0.5, **kw):
    """ref elemwise_unary_op: clip(alpha*x + beta, 0, 1)."""
    return _apply(lambda x: jnp.clip(alpha * x + beta, 0.0, 1.0), data)


def softmax_cross_entropy(data, label, **kw):
    """ref loss_binary_op.cc softmax_cross_entropy — summed batch loss."""

    def fn(x, y):
        logp = jax.nn.log_softmax(x, axis=-1)
        picked = jnp.take_along_axis(logp, y.astype(jnp.int32)[:, None],
                                     axis=-1)
        return -jnp.sum(picked)

    return _apply(fn, data, _to_nd(label))


def digamma(data, **kw):
    """ref elemwise_unary_op psi/digamma."""
    import jax.scipy.special as jss
    return _apply(jss.digamma, data)


def khatri_rao(*args, **kw):
    """ref contrib/krprod.cc khatri_rao — column-wise Kronecker product."""

    def fn(*mats):
        out = mats[0]
        for m in mats[1:]:
            out = (out[:, None, :] * m[None, :, :]).reshape(-1, out.shape[-1])
        return out

    return _apply(fn, *args)


def linspace(start, stop, num, endpoint=True, ctx=None, dtype="float32"):
    """ref init_op linspace."""
    return NDArray(jnp.linspace(start, stop, int(num), endpoint=endpoint,
                                dtype=_np_dtype(dtype)))


def trace(data, offset=0, axis1=0, axis2=1, **kw):
    return _apply(lambda x: jnp.trace(x, offset, axis1, axis2), data)


def meshgrid(*arrays, indexing="xy"):
    outs = jnp.meshgrid(*[a._data for a in arrays], indexing=indexing)
    return [NDArray(o) for o in outs]


def unravel_index(data, shape=None, **kw):
    """ref ravel.cc unravel_index: flat ids -> (ndim, N) coordinates."""

    def fn(x):
        coords = jnp.unravel_index(x.astype(jnp.int32), shape)
        return jnp.stack(coords, axis=0)

    return _apply(fn, data)


def ravel_multi_index(data, shape=None, **kw):
    """ref ravel.cc ravel_multi_index: (ndim, N) coords -> flat ids."""

    def fn(x):
        idx = tuple(x[i].astype(jnp.int32) for i in range(x.shape[0]))
        return jnp.ravel_multi_index(idx, shape, mode="clip")

    return _apply(fn, data)


def multinomial(data, shape=1, get_prob=False, dtype="int32", **kw):
    """ref sample_multinomial_op — rows of probabilities -> samples."""
    from . import random as _rnd
    n = shape if isinstance(shape, int) else int(onp.prod(shape))

    def fn(p, key):
        logits = jnp.log(jnp.maximum(p, 1e-37))
        return jax.random.categorical(key, logits, axis=-1,
                                      shape=(n,) + p.shape[:-1]).T

    key = _rnd._next_key()
    out = _apply(lambda p: fn(p, key), data)
    return out.astype(dtype) if dtype != "int32" else out


def arange_like(data, start=0.0, step=1.0, axis=None, **kw):
    from .contrib import arange_like as _al
    return _al(data, start, step, axis)


__all__ += ["smooth_l1", "hard_sigmoid", "softmax_cross_entropy", "digamma",
            "khatri_rao", "linspace", "trace", "meshgrid", "unravel_index",
            "ravel_multi_index", "multinomial", "arange_like"]


def im2col(data, kernel, stride=None, dilate=None, pad=None):
    """ref src/operator/tensor/im2col.cc: (N,C,*spatial) -> sliding patches
    (N, C*prod(kernel), L). Lowered to lax.conv_general_dilated_patches —
    XLA's native patch extraction, MXU-layout friendly."""
    kernel = tuple(kernel)
    d = len(kernel)
    stride = tuple(stride) if stride else (1,) * d
    dilate = tuple(dilate) if dilate else (1,) * d
    pad = tuple(pad) if pad else (0,) * d

    def fn(x):
        out = lax.conv_general_dilated_patches(
            x, filter_shape=kernel, window_strides=stride,
            padding=[(p, p) for p in pad], rhs_dilation=dilate)
        return out.reshape(out.shape[0], out.shape[1], -1)
    return _apply(fn, data)


def col2im(data, output_size, kernel, stride=None, dilate=None, pad=None):
    """ref src/operator/tensor/im2col.cc col2im: scatter-add patches back to
    (N, C, *output_size) — computed as the exact linear transpose (jax.vjp)
    of im2col, which IS the reference's definition of the op."""
    kernel = tuple(kernel)
    output_size = tuple(output_size)
    d = len(kernel)
    stride = tuple(stride) if stride else (1,) * d
    dilate = tuple(dilate) if dilate else (1,) * d
    pad = tuple(pad) if pad else (0,) * d
    k_prod = 1
    for k in kernel:
        k_prod *= k

    def fn(col):
        N = col.shape[0]
        C = col.shape[1] // k_prod

        def fwd(img):
            out = lax.conv_general_dilated_patches(
                img, filter_shape=kernel, window_strides=stride,
                padding=[(p, p) for p in pad], rhs_dilation=dilate)
            return out.reshape(out.shape[0], out.shape[1], -1)

        import jax as _jax
        _, vjp = _jax.vjp(fwd, jnp.zeros((N, C) + output_size, col.dtype))
        return vjp(col)[0]
    return _apply(fn, data)


__all__ += ["im2col", "col2im"]


def add_n(*args, **kw):
    """Sum of all inputs (ref tensor/elemwise_sum.cc add_n)."""
    import functools
    import operator
    return _apply(lambda *xs: functools.reduce(operator.add, xs), *args)


def batch_take(a, indices):
    """out[i] = a[i, indices[i]] (ref tensor/indexing_op.cc batch_take)."""
    def fn(x, idx):
        return jnp.take_along_axis(
            x, idx.astype(jnp.int32).reshape(-1, 1), axis=1)[:, 0]
    return _apply(fn, a, _to_nd(indices))


def depth_to_space(data, block_size):
    """(N, C*b^2, H, W) -> (N, C, H*b, W*b) (ref tensor/matrix_op.cc
    depth_to_space, DCR order)."""
    b = block_size

    def fn(x):
        N, C, H, W = x.shape
        c = C // (b * b)
        y = x.reshape(N, b, b, c, H, W)
        y = y.transpose(0, 3, 4, 1, 5, 2)
        return y.reshape(N, c, H * b, W * b)
    return _apply(fn, data)


def space_to_depth(data, block_size):
    """(N, C, H*b, W*b) -> (N, C*b^2, H, W), inverse of depth_to_space."""
    b = block_size

    def fn(x):
        N, C, Hb, Wb = x.shape
        H, W = Hb // b, Wb // b
        y = x.reshape(N, C, H, b, W, b)
        y = y.transpose(0, 3, 5, 1, 2, 4)
        return y.reshape(N, C * b * b, H, W)
    return _apply(fn, data)


def shape_array(data):
    """Shape as a TRUE int64 array (ref tensor/matrix_op.cc shape_array) —
    created under a scoped x64 enable so dims past 2^31 don't truncate to
    int32 (jax's default without jax_enable_x64)."""
    with _base.enable_x64(True):
        return NDArray(jnp.asarray(data.shape, jnp.int64))


def size_array(data):
    """Element count as a (1,) TRUE int64 array (ref size_array; see
    shape_array for the x64 scoping)."""
    with _base.enable_x64(True):
        return NDArray(jnp.asarray([data.size], jnp.int64))


def argmax_channel(data):
    """argmax over axis 1 (ref broadcast_reduce_op_index.cc argmax_channel)."""
    return _apply(lambda x: jnp.argmax(x, axis=1).astype(x.dtype), data)


def cast_storage(data, stype):
    """dense <-> row_sparse/csr conversion (ref tensor/cast_storage.cc);
    delegates to the sparse storage classes (nd.sparse)."""
    return data.tostype(stype)


def IdentityAttachKLSparseReg(data, sparseness_target=0.1, penalty=0.001,
                              momentum=0.9, **kw):
    """ref plugin sparse-reg op: identity forward; the KL sparseness
    penalty contributed to the backward is not replicated (document-level
    parity — penalty scheduling belongs in the loss here)."""
    return _apply(lambda x: x, data)


__all__ += ["add_n", "batch_take", "depth_to_space", "space_to_depth",
            "shape_array", "size_array", "argmax_channel", "cast_storage",
            "IdentityAttachKLSparseReg"]


def Correlation(data1, data2, kernel_size=1, max_displacement=1, stride1=1,
                stride2=1, pad_size=0, is_multiply=True):
    """Cost volume between two feature maps (ref src/operator/correlation.cc
    CorrelationForward + correlation-inl.h shape inference; FlowNet).

    Output (N, D*D, top_h, top_w) with D = 2*(max_displacement//stride2)+1,
    top_h = ceil((H + 2*pad_size - 2*border)/stride1),
    border = max_displacement + (kernel_size-1)//2.  Channel
    tc = dy_idx*D + dx_idx holds, per output pixel, the sum over the
    kernel_size x kernel_size window and input channels of
    x1*x2_displaced (is_multiply) or |x1 - x2_displaced|, divided by
    kernel_size^2 * C — exactly the reference's sumelems normalization.
    The displacement/kernel loops are static and XLA-unrolled into fused
    strided-slice multiplies."""
    if kernel_size % 2 != 1:
        raise ValueError("kernel_size must be odd")
    gr = max_displacement // stride2
    kr = (kernel_size - 1) // 2
    border = max_displacement + kr
    md = max_displacement

    def fn(x1, x2):
        N, C, H, W = x1.shape
        p = pad_size
        ph, pw = H + 2 * p, W + 2 * p
        top_h = -((2 * border - ph) // stride1)
        top_w = -((2 * border - pw) // stride1)
        if top_h < 1 or top_w < 1:
            raise ValueError("Correlation: input too small for "
                             "max_displacement/kernel_size")
        x1p = jnp.pad(x1, ((0, 0), (0, 0), (p, p), (p, p)))
        x2p = jnp.pad(x2, ((0, 0), (0, 0), (p, p), (p, p)))

        def tap(src, y0, x0):
            return src[:, :, y0: y0 + (top_h - 1) * stride1 + 1: stride1,
                       x0: x0 + (top_w - 1) * stride1 + 1: stride1]

        outs = []
        for dy in range(-gr, gr + 1):
            for dx in range(-gr, gr + 1):
                s2p, s2o = dy * stride2, dx * stride2
                acc = None
                for h in range(kernel_size):
                    for w in range(kernel_size):
                        a = tap(x1p, md + h, md + w)
                        b = tap(x2p, md + s2p + h, md + s2o + w)
                        t = a * b if is_multiply else jnp.abs(a - b)
                        t = t.sum(axis=1)
                        acc = t if acc is None else acc + t
                outs.append(acc / (kernel_size * kernel_size * C))
        return jnp.stack(outs, axis=1)
    return _apply(fn, data1, data2)


def Crop(*data, offset=(0, 0), h_w=(0, 0), center_crop=False, num_args=None,
         **kw):
    """Legacy crop op (ref src/operator/crop.cc): crop data[0] to h_w, or
    to data[1]'s spatial shape when two inputs are given."""
    x = data[0]
    if len(data) == 2:
        th, tw = data[1].shape[2], data[1].shape[3]
    else:
        th, tw = h_w
    H, W = x.shape[2], x.shape[3]
    if center_crop:
        oy, ox = (H - th) // 2, (W - tw) // 2
    else:
        oy, ox = offset
    return _apply(lambda a: a[:, :, oy: oy + th, ox: ox + tw], x)


__all__ += ["Correlation", "Crop"]


def moveaxis(data, source, destination):
    """ref ndarray.py moveaxis."""
    return _apply(lambda x: jnp.moveaxis(x, source, destination), data)


def onehot_encode(indices, out):
    """ref ndarray.py onehot_encode (legacy): writes one-hot rows into out."""
    depth = out.shape[1]
    res = _apply(lambda i: jax.nn.one_hot(i.astype(jnp.int32), depth,
                                          dtype=out.dtype), indices)
    out._data = res._data
    return out


def true_divide(lhs, rhs):
    return divide(lhs, rhs)


def histogram(a, bins=10, range=None):
    """ref tensor/histogram.cc: returns (counts, bin_edges)."""
    import builtins
    rng = range if range is not None else (
        float(a.min().asscalar()), float(builtins.max(
            float(a.max().asscalar()),
            float(a.min().asscalar()) + 1e-6)))
    if isinstance(bins, NDArray):
        cnt, edges = jnp.histogram(a._data, bins=bins._data)
    else:
        cnt, edges = jnp.histogram(a._data, bins=bins, range=rng)
    return NDArray(cnt), NDArray(edges)


def split_v2(ary, indices_or_sections=1, axis=0, squeeze_axis=False):
    """ref matrix_op.cc split_v2: numpy-style sections OR index points."""
    sections = tuple(indices_or_sections) \
        if isinstance(indices_or_sections, (list, tuple)) \
        else indices_or_sections

    def go(x):
        parts = jnp.split(x, sections, axis=axis)
        if squeeze_axis:
            parts = [p.squeeze(axis) for p in parts]
        return parts
    return _apply(go, ary)


def from_numpy(ndarray_np, zero_copy=True):
    """ref ndarray.py from_numpy (dlpack family) — device_put is the copy."""
    return NDArray(jnp.asarray(ndarray_np))


def to_dlpack_for_read(data):
    """ref to_dlpack_for_read: export via the dlpack protocol. Returns the
    protocol-bearing object (modern consumers call __dlpack__ themselves —
    torch.from_dlpack / np.from_dlpack accept it directly)."""
    return data._data


def to_dlpack_for_write(data):
    """jax buffers are immutable; writable export is a host-copy contract."""
    return data._data


def from_dlpack(dlpack):
    import jax.dlpack as jdl
    return NDArray(jdl.from_dlpack(dlpack))


__all__ += ["moveaxis", "onehot_encode", "true_divide", "histogram",
            "split_v2", "from_numpy", "to_dlpack_for_read",
            "to_dlpack_for_write", "from_dlpack"]
