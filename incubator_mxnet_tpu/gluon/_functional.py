"""Functional-mode machinery: turn a stateful Block call into a pure JAX
function — the TPU-native analog of CachedOp/hybridize
(ref src/imperative/cached_op.cc:762 Forward, python/mxnet/gluon/block.py:923).

In functional mode:
- Parameter data are temporarily swapped for traced values (the pure inputs).
- BatchNorm-style aux-state updates are COLLECTED (not written) and returned
  as extra outputs, then written back after the compiled call.
- Random ops draw from a per-call PRNG key argument instead of the global
  stateful key, so compiled programs get fresh randomness per step.
- Step counters, small integer values a block computes anyway and a reader
  on the host wants once a step (a MoELayer's rows an expert), are COLLECTED
  and leave a compiled train step as one more output (jit.TrainStep); a
  compiled forward drops them.
"""
from __future__ import annotations

import threading

import jax
import jax.numpy as jnp

from .. import autograd
from ..ndarray import NDArray


class _FnState(threading.local):
    def __init__(self):
        self.active = False
        self.key = None           # traced PRNG key, split per use
        self.aux_updates = None   # list of (Parameter, traced_new_value)
        self.step_counters = None  # list of (name, traced ints, publish, static)


_STATE = _FnState()


def in_functional_mode():
    return _STATE.active


def next_functional_key():
    _STATE.key, sub = jax.random.split(_STATE.key)
    return sub


def collect_aux_update(param_arr, new_value):
    """Record 'param_arr should become new_value' instead of mutating (BatchNorm)."""
    _STATE.aux_updates.append((param_arr, new_value))


def collect_step_counter(name, value, publish=None, **static):
    """Record 'this step's `name` is `value`' (an integer array the block
    computes anyway) with the facts of its shapes the host reads it by
    (``static``: plain numbers and flags). A compiled train step hands the
    values out beside its loss and books them to the step once they are
    known (jit.TrainStep: the span ring's ``train:counters``), calling
    ``publish(name, values, **static)`` there (``values`` a list of ints)
    for the block's own series.
    Outside functional mode nothing is recorded: an eager call traces what
    it traced."""
    if _STATE.active:
        _STATE.step_counters.append((name, value, publish, static))


class FunctionalScope:
    def __init__(self, key):
        self._key = key

    def __enter__(self):
        self._prev = (_STATE.active, _STATE.key, _STATE.aux_updates,
                      _STATE.step_counters)
        _STATE.active = True
        _STATE.key = self._key
        _STATE.aux_updates = []
        _STATE.step_counters = []
        return _STATE

    def __exit__(self, *a):
        (_STATE.active, _STATE.key, _STATE.aux_updates,
         _STATE.step_counters) = self._prev


def make_pure_fn(block, train_mode):
    """Build fn(param_datas, input_datas, key) -> (out_datas, aux_new_values).

    ``aux_box`` (returned alongside) is filled at trace time with the live aux
    NDArrays, in the same order as aux_new_values — stable for a fixed graph.
    Step counters the blocks register are dropped with the scope: a forward
    has no step to book them to.
    """
    params = list(block.collect_params().values())
    param_arrs = [p.data() for p in params]
    aux_box = []  # filled during trace: which NDArrays the aux outputs belong to

    def pure_fn(param_datas, input_datas, key):
        # swap traced data into the live NDArray objects
        saved = [a._data for a in param_arrs]
        for a, d in zip(param_arrs, param_datas):
            a._data = d
        try:
            with FunctionalScope(key) as st:
                with autograd.pause(train_mode=train_mode):
                    # a direct forward call: name the root's scope here
                    # (Block.__call__ names every child's)
                    with jax.named_scope(block.name):
                        out = block.forward(
                            *[NDArray(d) for d in input_datas])
                outs = out if isinstance(out, (list, tuple)) else [out]
                out_datas = [o._data for o in outs]
                aux_pairs = list(st.aux_updates)
        finally:
            for a, s in zip(param_arrs, saved):
                a._data = s
        aux_box[:] = [a for (a, _v) in aux_pairs]
        return out_datas, [v for (_a, v) in aux_pairs]

    return params, param_arrs, pure_fn, aux_box
