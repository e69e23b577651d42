"""Kernel traces counted where they happen.

Pallas traces a kernel's Python body synchronously inside the call that
``pl.pallas_call(...)`` returns, in every process, before any cached
executable loads (PERF.md section 6, PR 35: about a millisecond a traced
operation on the chip's host). ``pallas_call`` below is that call made
through one door: while an outer program is being traced, the host seconds
it takes go to ``mxtpu_kernel_trace_seconds_total{kernel}`` and one to
``mxtpu_kernel_traces_total{kernel}``, ``kernel`` the name the call carries.
The equation it binds is the one ``pl.pallas_call`` binds: no jit, no scope
and no argument of its own, so a caller under ``jax.jit(..., inline=True)``
(``traced_once`` below) still traces a shape once and
the counter reads one. (Mosaic's lowering of the kernel happens later,
inside the outer program's jaxpr -> MLIR: ``setup_phases`` books it there.)
"""
from __future__ import annotations

import functools
import time

import jax

from .. import telemetry

_SECONDS = telemetry.counter(
    "mxtpu_kernel_trace_seconds_total",
    "Host seconds spent tracing Pallas kernel bodies inside traced "
    "programs, by kernel name.", ("kernel",))
_TRACES = telemetry.counter(
    "mxtpu_kernel_traces_total",
    "Pallas kernel bodies traced inside traced programs, by kernel name.",
    ("kernel",))


def pallas_call(kernel, operands, *, name, **params):
    """``pl.pallas_call(kernel, name=name, **params)(*operands)``, its host
    time counted when the operands are tracers (an eager call also
    compiles and runs: that is not a kernel's trace)."""
    from jax.experimental import pallas as pl
    call = pl.pallas_call(kernel, name=name, **params)
    # (on what the operands ARE, not on a value: the program is the same)
    if not any(isinstance(x, jax.core.Tracer)  # mxtpulint: disable=R011
               for x in operands):
        return call(*operands)
    t0 = time.perf_counter()
    try:
        return call(*operands)
    finally:
        _SECONDS.inc(time.perf_counter() - t0, kernel=name)
        _TRACES.inc(kernel=name)


def traced_once(*static):
    """A kernel's Python body and its Mosaic lowering are paid by every
    process before its cached executable loads (about 1 s a backward kernel
    on the chip's host). Inlined jit: a call of shapes seen before re-binds
    the SAME kernel jaxpr under the caller's scopes, so a model's layers
    trace it once a process and lower it once a program (JAX caches an
    equation's lowering by its parameters)."""
    return functools.partial(jax.jit, static_argnames=static, inline=True)
