"""Random sampling ops (ref src/operator/random/sample_op.cc, python/mxnet/random.py).

TPU-native design: a global threefry PRNG key (jax.random) split per call —
the stateful-global-seed UX of MXNet over JAX's functional counter-based RNG,
which vectorises on the VPU with no sequential state.
"""
from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp
import numpy as onp

from .ndarray import NDArray, _apply, _ctx_put, _np_dtype, _to_nd

__all__ = ["seed", "uniform", "normal", "randn", "randint", "exponential", "gamma",
           "poisson", "negative_binomial", "generalized_negative_binomial",
           "multinomial", "bernoulli", "shuffle"]


class _RngState(threading.local):
    def __init__(self):
        self.key = jax.random.PRNGKey(0)


_t0 = time.perf_counter()
_RNG = _RngState()
#: seconds of the process's first device touch (the key above starts the
#: backend and runs a program): the package's import reports it as
#: mxtpu_import_seconds{part="backend"}
BACKEND_TOUCH_S = time.perf_counter() - _t0
del _t0


def seed(seed_state, ctx="all"):
    """ref python/mxnet/random.py:seed — reseed the global generator."""
    _RNG.key = jax.random.PRNGKey(int(seed_state))


def _next_key():
    # inside a compiled (hybridized/jitted) program, randomness must come from
    # the per-call key argument, not the global python-side state
    from ..gluon import _functional
    if _functional.in_functional_mode():
        return _functional.next_functional_key()
    _RNG.key, sub = jax.random.split(_RNG.key)
    return sub


def _copy_out(res, out=None):
    if out is not None:
        out._data = res._data
        return out
    return res


def _shape_of(shape, *arrs):
    if shape is None:
        for a in arrs:
            if isinstance(a, NDArray):
                return a.shape
        return (1,)
    if isinstance(shape, int):
        shape = (shape,)
    return tuple(shape)


def uniform(low=0.0, high=1.0, shape=None, dtype="float32", ctx=None, out=None, **kw):
    shp = _shape_of(shape, low, high)
    key = _next_key()
    if isinstance(low, NDArray) or isinstance(high, NDArray):
        low, high = _to_nd(low), _to_nd(high)
        def fn(lo, hi):
            u = jax.random.uniform(key, shp + lo.shape, _np_dtype(dtype))
            return lo + u * (hi - lo)
        return _apply(fn, low, high)
    data = jax.random.uniform(key, shp, _np_dtype(dtype), low, high)
    res = NDArray(_ctx_put(data, ctx), ctx=ctx)
    return _copy_out(res, out)


def normal(loc=0.0, scale=1.0, shape=None, dtype="float32", ctx=None, out=None, **kw):
    shp = _shape_of(shape, loc, scale)
    key = _next_key()
    data = loc + scale * jax.random.normal(key, shp, _np_dtype(dtype))
    res = NDArray(_ctx_put(data, ctx), ctx=ctx)
    return _copy_out(res, out)


def randn(*shape, loc=0.0, scale=1.0, dtype="float32", ctx=None, **kw):
    return normal(loc, scale, shape or (1,), dtype, ctx)


def randint(low, high=None, shape=(1,), dtype="int32", ctx=None, out=None, **kw):
    if high is None:
        low, high = 0, low
    key = _next_key()
    data = jax.random.randint(key, _shape_of(shape), int(low), int(high), _np_dtype(dtype))
    return _copy_out(NDArray(_ctx_put(data, ctx), ctx=ctx), out)


def exponential(scale=1.0, shape=None, dtype="float32", ctx=None, out=None, **kw):
    key = _next_key()
    data = scale * jax.random.exponential(key, _shape_of(shape, scale), _np_dtype(dtype))
    return _copy_out(NDArray(_ctx_put(data, ctx), ctx=ctx), out)


def gamma(alpha=1.0, beta=1.0, shape=None, dtype="float32", ctx=None, out=None, **kw):
    key = _next_key()
    data = beta * jax.random.gamma(key, alpha, _shape_of(shape, alpha, beta), _np_dtype(dtype))
    return _copy_out(NDArray(_ctx_put(data, ctx), ctx=ctx), out)


def poisson(lam=1.0, shape=None, dtype="float32", ctx=None, out=None, **kw):
    key = _next_key()
    data = jax.random.poisson(key, lam, _shape_of(shape, lam)).astype(_np_dtype(dtype))
    return _copy_out(NDArray(_ctx_put(data, ctx), ctx=ctx), out)


def negative_binomial(k=1, p=1.0, shape=None, dtype="float32", ctx=None, out=None, **kw):
    key1, key2 = jax.random.split(_next_key())
    g = jax.random.gamma(key1, k, _shape_of(shape)) * (1 - p) / p
    data = jax.random.poisson(key2, g).astype(_np_dtype(dtype))
    return _copy_out(NDArray(_ctx_put(data, ctx), ctx=ctx), out)


def generalized_negative_binomial(mu=1.0, alpha=1.0, shape=None, dtype="float32",
                                  ctx=None, out=None, **kw):
    k = 1.0 / alpha
    p = k / (k + mu)
    return negative_binomial(k, p, shape, dtype, ctx, out)


def bernoulli(prob=None, logit=None, shape=None, dtype="float32", ctx=None, **kw):
    key = _next_key()
    if prob is None:
        prob = jax.nn.sigmoid(logit._data if isinstance(logit, NDArray) else logit)
    if isinstance(prob, NDArray):
        prob = prob._data
    data = jax.random.bernoulli(key, prob, _shape_of(shape) if shape else None)
    return NDArray(_ctx_put(data.astype(_np_dtype(dtype)), ctx), ctx=ctx)


def multinomial(data, shape=(1,), get_prob=False, dtype="int32", **kw):
    """ref src/operator/random/sample_multinomial_op.cc — sample from pmf rows."""
    key = _next_key()
    if isinstance(shape, int):
        shape = (shape,)
    n = 1
    for s in shape:
        n *= s
    def fn(p):
        logits = jnp.log(jnp.maximum(p, 1e-37))
        if p.ndim == 1:
            out = jax.random.categorical(key, logits, shape=(n,))
            return out.reshape(shape).astype(_np_dtype(dtype)) if shape != (1,) else out[0].astype(_np_dtype(dtype)).reshape(())
        out = jax.random.categorical(key, logits[:, None, :], axis=-1, shape=(p.shape[0], n))
        return out.reshape((p.shape[0],) + shape).astype(_np_dtype(dtype))
    return _apply(fn, data)


def shuffle(data, **kw):
    key = _next_key()
    return _apply(lambda x: jax.random.permutation(key, x, axis=0), data)
