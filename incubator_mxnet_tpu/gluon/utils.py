"""gluon.utils (ref python/mxnet/gluon/utils.py)."""
from __future__ import annotations

import math

from .. import ndarray as nd
from .. import telemetry
from ..ndarray import NDArray

__all__ = ["split_data", "split_and_load", "clip_global_norm", "check_sha1", "download",
           "recompute"]

_RECOMPUTES = telemetry.counter(
    "mxtpu_recompute_total",
    "gluon.utils.recompute calls traced, by what the backward keeps of the "
    "block's forward beside its inputs: none (everything is computed "
    "again, Pallas kernels included) or given (a jax.checkpoint policy "
    "names results to keep, e.g. a kernel's output and statistics).",
    ("policy",))


def split_data(data, num_slice, batch_axis=0, even_split=True):
    """ref utils.py split_data."""
    size = data.shape[batch_axis]
    if even_split and size % num_slice != 0:
        raise ValueError(
            "data with shape %s cannot be evenly split into %d slices along axis %d."
            % (str(data.shape), num_slice, batch_axis))
    if num_slice == 1:
        return [data]
    step = size // num_slice
    slices = [nd.slice_axis(data, batch_axis, i * step,
                            (i + 1) * step if i < num_slice - 1 else size)
              for i in range(num_slice)]
    return slices

def split_and_load(data, ctx_list, batch_axis=0, even_split=True):
    """ref utils.py split_and_load — slices land on each ctx."""
    if not isinstance(data, NDArray):
        data = nd.array(data, ctx=ctx_list[0])
    if len(ctx_list) == 1:
        return [data.as_in_context(ctx_list[0])]
    slices = split_data(data, len(ctx_list), batch_axis, even_split)
    return [i.as_in_context(ctx) for i, ctx in zip(slices, ctx_list)]


def clip_global_norm(arrays, max_norm, check_isfinite=True):
    """ref utils.py clip_global_norm."""
    assert len(arrays) > 0
    total_norm = math.sqrt(sum(float((x * x).sum().asscalar()) for x in arrays))
    if check_isfinite and not math.isfinite(total_norm):
        import warnings
        warnings.warn("nan or inf is detected. Clipping results will be undefined.")
    scale = max_norm / (total_norm + 1e-8)
    if scale < 1.0:
        for arr in arrays:
            arr._data = (arr * scale)._data
    return total_norm


def check_sha1(filename, sha1_hash):
    import hashlib
    sha1 = hashlib.sha1()
    with open(filename, "rb") as f:
        while True:
            data = f.read(1048576)
            if not data:
                break
            sha1.update(data)
    return sha1.hexdigest() == sha1_hash


def download(url, path=None, overwrite=False, sha1_hash=None, retries=5,
             verify_ssl=True):
    raise RuntimeError("network egress is unavailable in this environment; "
                       "place files locally instead (url=%s)" % url)



def recompute(block, *args, policy=None):
    """``block(*args)`` with its forward recomputed in the backward pass
    (``jax.checkpoint`` around this one block): a gradient through it keeps
    the block's inputs and none of what it computed (but what ``policy``, a
    ``jax.checkpoint_policies`` rule, says to keep: a named result that is
    dear to compute again: the Pallas kernels' forward rules name what they
    hand their backward, ``ops.attention.ATTENDED_NAME``,
    ``ops.selective_scan.SCANNED_NAME``, ``ops.delta_rule.RULED_NAME``,
    ``ops.sparse_attention.ATTENDED_NAME``, and a layer that saves a name
    runs that forward kernel once a step, not twice). Around each layer of a
    stack, the activations held for the backward are one input a layer plus
    one layer's working set, where ``TrainStep(remat=True)`` checkpoints the
    whole forward at once and lowers nothing at the peak. A block that
    returns several arrays (a layer that hands later layers more than the
    residual stream) returns them here too, as a tuple.

    The block's parameters go in as arguments, like the inputs, so the call
    is one pure function of arrays: one op on the eager tape, and inside a
    compiled step its scope names (``Block.__call__``'s) stay on every op,
    forward and recomputed. A block that updates auxiliary state or draws
    random numbers in its forward (BatchNorm, Dropout) would do so twice:
    refused. Step counters the block registers
    (``_functional.collect_step_counter``) are values of the checkpointed
    trace: they leave it as extra outputs (integers: nothing flows back into
    them, so they are the first forward's and no recomputation makes them
    again) and are registered again here, outside, under the same names."""
    from .. import autograd
    from ..ndarray import _apply
    from . import _functional
    _RECOMPUTES.inc(policy="none" if policy is None else "given")
    arrs = [p.data() for p in block.collect_params().values()]
    n = len(args)
    traced = {}       # what the trace found: the block's counters, a tuple?

    def pure(*datas):
        state = _functional._STATE
        saved = [a._data for a in arrs]
        for a, d in zip(arrs, datas[n:]):
            a._data = d
        key, n_aux = state.key, len(state.aux_updates or ())
        n_counters = len(state.step_counters or ())
        try:
            # one op on the eager tape: nothing inside is recorded
            with autograd.pause(train_mode=autograd.is_training()):
                out = block(*[NDArray(d) for d in datas[:n]])
        finally:
            for a, s in zip(arrs, saved):
                a._data = s
        if state.key is not key or len(state.aux_updates or ()) != n_aux:
            raise ValueError(
                "recompute(%s): the block updates auxiliary state or draws "
                "random numbers in its forward, which a recomputation would "
                "repeat" % block.name)
        inside = (state.step_counters or [])[n_counters:]
        if inside:
            del state.step_counters[n_counters:]
        traced["counters"] = [(name, publish, static)
                              for name, _, publish, static in inside]
        traced["tuple"] = isinstance(out, (tuple, list))
        outs = tuple(o._data for o in out) if traced["tuple"] \
            else (out._data,)
        outs += tuple(value for _, value, _, _ in inside)
        return outs if traced["tuple"] or inside else outs[0]

    import jax
    out = _apply(jax.checkpoint(pure, policy=policy), *args, *arrs)
    if not traced["counters"]:
        return out
    n_out = len(out) - len(traced["counters"])
    for (name, publish, static), value in zip(traced["counters"],
                                              out[n_out:]):
        _functional.collect_step_counter(name, value._data, publish,
                                         **static)
    return out[:n_out] if traced["tuple"] else out[0]
