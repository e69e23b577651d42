"""Model serving / deployment export (ref src/c_api/c_predict_api.cc,
cpp-package inference, amalgamation).

The reference's deployment surface is a C predict API over its own graph
format. The TPU-native equivalent is a SERIALIZED COMPILED PROGRAM: the
whole forward pass (params baked in or passed as inputs) lowered to
StableHLO and serialized with jax.export — the portable artifact the XLA
ecosystem serves. The .mxtpu file this module writes is loadable:

- from Python anywhere JAX runs: ``load(path).predict(x)`` (round-trip
  tested in tests/test_serving.py)
- from C/C++ without Python: the payload is a standard jax.export
  serialization whose StableHLO module (``export_mlir`` extracts it) is
  consumable by any PJRT plugin through the PJRT C API — the same contract
  TF-Serving/IFRT production loaders use. ``tools/pjrt_serve.c`` (plain
  C, vendored ``pjrt_c_api.h``, dlopen only) is such a loader; it last
  ran on a chip in July 2026 and no test exercises it against the
  installed libtpu (ROADMAP D9). This replaces
  c_predict_api.cc's role; the operator registry needed by the
  reference's C loader does not exist here by design (programs are
  self-contained).

Format: 8-byte magic "MXTPU\\x00v1" + jax.export bytes.
"""
from __future__ import annotations

import hashlib
import time as _time

import jax
import jax.export  # jax>=0.4.30 does not re-export the submodule lazily

from .. import aot
from .. import config
from ..gluon import _functional
from ..ndarray import NDArray
from ..telemetry import devstats

__all__ = ["export_model", "load", "export_mlir", "export_pjrt_bundle",
           "ServedModel"]

_MAGIC = b"MXTPU\x00v1"


def export_model(net, example_inputs, path, train_mode=False):
    """Serialize net's forward (params baked as constants) to ``path``.

    net: an initialized Gluon block. example_inputs: NDArray(s) fixing the
    input signature. Returns the ServedModel for immediate use.
    """
    if isinstance(example_inputs, NDArray):
        example_inputs = [example_inputs]
    params, param_arrs, pure_fn, _aux = _functional.make_pure_fn(
        net, train_mode=train_mode)
    param_datas = [a._data for a in param_arrs]
    key = jax.random.PRNGKey(0)

    def fwd(*xs):
        outs, _ = pure_fn(param_datas, list(xs), key)
        return outs[0] if len(outs) == 1 else tuple(outs)

    exp = jax.export.export(jax.jit(fwd))(
        *[x._data for x in example_inputs])
    with open(path, "wb") as f:
        f.write(_MAGIC + exp.serialize())
    return ServedModel(exp)


def load(path):
    """Load a .mxtpu artifact → ServedModel (≙ MXPredCreate)."""
    with open(path, "rb") as f:
        buf = f.read()
    if not buf.startswith(_MAGIC):
        raise ValueError("%s is not an mxtpu serving artifact" % path)
    return ServedModel(jax.export.deserialize(buf[len(_MAGIC):]))


def export_mlir(path):
    """The artifact's StableHLO module text (feed to PJRT C API loaders)."""
    return load(path).mlir_module()


def export_pjrt_bundle(artifact_path, out_dir):
    """Materialize the Python-free serving bundle for tools/pjrt_serve.c:
    ``module.mlir`` (the artifact's StableHLO) + ``compile_options.pb``
    (a serialized single-replica CompileOptionsProto — the opaque options
    blob PJRT_Client_Compile requires). After this one-time export step, a
    plain-C loader runs the model against any PJRT plugin with no Python
    anywhere in the serving process (ref c_predict_api.cc deployment)."""
    import os

    from jax._src import compiler as _compiler

    os.makedirs(out_dir, exist_ok=True)
    mlir_path = os.path.join(out_dir, "module.mlir")
    with open(mlir_path, "w") as f:
        f.write(export_mlir(artifact_path))
    opts = _compiler.get_compile_options(num_replicas=1, num_partitions=1)
    opts_path = os.path.join(out_dir, "compile_options.pb")
    with open(opts_path, "wb") as f:
        f.write(opts.SerializeAsString())
    return mlir_path, opts_path


class ServedModel:
    """≙ the reference's PredictorHandle (c_predict_api.cc).

    Dispatch goes through the process-wide aot.CACHE: the exported program
    is AOT-compiled ONCE per input signature (``jit(exp.call).lower()
    .compile()``) instead of re-building an ``Exported.call`` wrapper —
    and re-tracing its calling convention — on every chunk. Two
    ServedModels loaded from the same artifact share executables (the
    cache id is a digest of the serialized module), so a hot-reload of an
    unchanged model never recompiles a bucket.
    """

    def __init__(self, exported, model_id=None):
        self._exp = exported
        if model_id is None:
            try:
                payload = exported.mlir_module_serialized
            except Exception:
                payload = exported.serialize()
            model_id = "x" + hashlib.sha256(payload).hexdigest()[:20]
        self._model_id = model_id

    def _replica_device(self, replica):
        """The device data-parallel replica ``replica`` executes on
        (round-robin over the local device list), or None for replica 0 —
        replica 0 keeps the classic uncommitted single-device path, so a
        replicas=1 deployment is byte-identical to the pre-replica one.
        More replicas than devices warns ONCE: the wrap double-subscribes
        chips and duplicates executables (distinct cache keys per replica
        index), which is oversubscription the operator should see."""
        if not replica:
            return None
        devices = jax.devices()
        if int(replica) >= len(devices) and not getattr(
                self, "_wrap_warned", False):
            self._wrap_warned = True
            import logging
            logging.getLogger(__name__).warning(
                "ServedModel %s: replica index %d wraps onto the %d local "
                "device(s) — more batcher replicas than chips "
                "double-subscribes devices and duplicates compiled "
                "executables; lower MXTPU_SERVE_REPLICAS",
                self._model_id, int(replica), len(devices))
        return devices[int(replica) % len(devices)]

    def _run(self, *datas, replica=0):
        """One compiled execution at the exact signature of ``datas``,
        through the shared executable cache. ``replica`` pins the
        executable (and the inputs) to that replica's device, so N
        batcher replicas drive N chips concurrently — each (signature,
        device) pair is its own cache entry, all prewarmed by the
        registry's (bucket x replica) warm loop."""
        dev = self._replica_device(replica)
        extra = () if dev is None else ("dev", dev.id)
        key = aot.cache_key(self._model_id, aot.input_signature(datas),
                            kind="serve", extra=extra)
        exp = self._exp

        def build():
            if dev is None:
                specs = [jax.ShapeDtypeStruct(d.shape, d.dtype)
                         for d in datas]
            else:
                from jax.sharding import SingleDeviceSharding
                sh = SingleDeviceSharding(dev)
                specs = [jax.ShapeDtypeStruct(d.shape, d.dtype, sharding=sh)
                         for d in datas]
            return (jax.jit(exp.call).lower(*specs).compile(),
                    None, None)       # the .mxtpu file IS the artifact

        if dev is not None:
            # the compiled program is committed to the replica's device;
            # inputs must arrive on it (host numpy from the batcher pays
            # the same one copy it paid to device 0 before)
            datas = [jax.device_put(d, dev) for d in datas]
        entry = aot.compile_cached(key, build)
        t0 = _time.perf_counter()
        out = entry.fn(*datas)
        # device-truth MFU needs a block-until-ready span. Under the
        # batcher (an ambient dispatch context, which also provides the
        # serving labels) the outputs are materialized host-side
        # immediately after, so the sync moves cost rather than adding
        # any — always observe there. A DIRECT predict() caller keeps
        # async dispatch unless MXTPU_DEVSTATS_EVAL_SYNC opts in (the
        # same overlap contract as jit.EvalStep).
        if entry.stats is not None and (
                devstats.in_dispatch_context()
                or config.get_env("MXTPU_DEVSTATS_EVAL_SYNC")):
            try:
                jax.block_until_ready(out)
            except Exception:
                pass
            devstats.observe_dispatch("serve", entry.stats,
                                      _time.perf_counter() - t0,
                                      model=self._model_id,
                                      replica=int(replica))
        return out

    @property
    def input_shapes(self):
        return [tuple(a.shape) for a in self._exp.in_avals]

    @property
    def output_shapes(self):
        return [tuple(a.shape) for a in self._exp.out_avals]

    def mlir_module(self):
        """StableHLO module text of the compiled program."""
        return self._exp.mlir_module()

    def predict(self, *inputs):
        """≙ MXPredSetInput + MXPredForward + MXPredGetOutput."""
        import numpy as onp
        # array-likes (jax device arrays included) pass through untouched
        # — asarray would force a device->host copy; only list/scalar
        # payloads need materializing (the cache key wants .shape/.dtype)
        datas = [x._data if isinstance(x, NDArray)
                 else x if hasattr(x, "shape") and hasattr(x, "dtype")
                 else onp.asarray(x)
                 for x in inputs]
        out = self._run(*datas)
        if isinstance(out, (list, tuple)):
            return tuple(NDArray(o) for o in out)
        return NDArray(out)

    @property
    def batch_size(self):
        """The exported batch-axis extent (dim 0 of the first input)."""
        shp = self.input_shapes[0]
        if not shp:
            raise ValueError("exported model has a rank-0 input — no "
                             "batch axis to serve over")
        return int(shp[0])

    def predict_batch(self, *stacked_inputs, replica=0):
        """Serving-batcher entry point: run ``n`` stacked items (dim 0)
        through the FIXED exported batch shape by re-chunking.

        The artifact compiled exactly one batch size ``B``; a dynamic
        batcher produces buckets of any size. Inputs are split into
        ceil(n/B) chunks, the last chunk padded to ``B`` by repeating its
        final row (shape/dtype-exact, values in-distribution), and outputs
        are concatenated with the padding rows dropped — so callers see a
        true dim-0 batch axis whatever ``B`` was. Returns a tuple of
        numpy arrays (host-side: results go straight onto the wire).

        ``replica`` (declared, so the batcher and registry forward it)
        pins this dispatch to the replica's device — N data-parallel
        batcher workers drive N chips concurrently (docs/SERVING.md).
        """
        import numpy as onp

        B = self.batch_size
        ins = [onp.asarray(x._data if isinstance(x, NDArray) else x)
               for x in stacked_inputs]
        avals = self._exp.in_avals
        ins = [x.astype(a.dtype, copy=False) for x, a in zip(ins, avals)]
        n = ins[0].shape[0]
        out_chunks = []
        for lo in range(0, n, B):
            chunk = [x[lo:lo + B] for x in ins]
            pad = B - chunk[0].shape[0]
            if pad:
                chunk = [onp.concatenate([c, onp.repeat(c[-1:], pad, axis=0)])
                         for c in chunk]
            out = self._run(*chunk, replica=replica)
            outs = out if isinstance(out, (list, tuple)) else (out,)
            out_chunks.append([onp.asarray(o)[:B - pad] for o in outs])
        return tuple(onp.concatenate([ch[i] for ch in out_chunks])
                     for i in range(len(out_chunks[0])))
