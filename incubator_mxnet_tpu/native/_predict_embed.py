"""Helper module for the embedded-interpreter C predict API
(native/src/c_predict_api.cc — ref src/c_api/c_predict_api.cc).

The C side keeps each predictor as an opaque PyObject (a ``_PredState``)
and calls the module-level functions below through the CPython C API. All
array traffic crosses the ABI as raw bytes (C-contiguous, row-major) — the
same contract as the reference's MXPredSetInput/MXPredGetOutput float
buffers, generalized to any dtype the artifact declares.

Kept deliberately free of framework imports at module load: the heavy
import (jax via contrib.serving) happens inside ``create`` so that merely
loading libmxtpu_predict.so stays cheap.
"""
from __future__ import annotations

import numpy as np

__all__ = [
    "create", "num_inputs", "num_outputs", "input_shape", "input_dtype",
    "output_shape", "output_dtype", "set_input", "forward", "output_bytes",
]


class _PredState:
    __slots__ = ("model", "inputs", "outputs")

    def __init__(self, model):
        self.model = model
        self.inputs = [None] * len(model.input_shapes)
        self.outputs = None


def create(path):
    """Load a .mxtpu serving artifact → predictor state (≙ MXPredCreate)."""
    from incubator_mxnet_tpu.contrib import serving
    return _PredState(serving.load(path))


def num_inputs(st):
    return len(st.model.input_shapes)


def num_outputs(st):
    return len(st.model.output_shapes)


def input_shape(st, i):
    return tuple(int(d) for d in st.model.input_shapes[i])


def output_shape(st, i):
    return tuple(int(d) for d in st.model.output_shapes[i])


def input_dtype(st, i):
    return st.model._exp.in_avals[i].dtype.name


def output_dtype(st, i):
    return st.model._exp.out_avals[i].dtype.name


def set_input(st, i, view):
    """Stage input i from a C buffer (memoryview) — copies immediately."""
    shape = input_shape(st, i)
    dt = np.dtype(input_dtype(st, i))
    want = int(np.prod(shape, dtype=np.int64)) * dt.itemsize
    if view.nbytes != want:
        raise ValueError(
            "input %d: got %d bytes, want %d (shape %s dtype %s)"
            % (i, view.nbytes, want, shape, dt.name))
    st.inputs[i] = np.frombuffer(view, dtype=dt).reshape(shape).copy()


def forward(st):
    """Run the compiled program on the staged inputs (≙ MXPredForward)."""
    missing = [i for i, x in enumerate(st.inputs) if x is None]
    if missing:
        raise ValueError("inputs %s not set before forward" % missing)
    out = st.model._exp.call(*st.inputs)
    if not isinstance(out, (list, tuple)):
        out = (out,)
    st.outputs = [np.asarray(o) for o in out]


def output_bytes(st, i):
    """Output i as contiguous bytes (≙ MXPredGetOutput)."""
    if st.outputs is None:
        raise ValueError("forward has not been run")
    return np.ascontiguousarray(st.outputs[i]).tobytes()
