"""Block / HybridBlock — the imperative NN API (ref python/mxnet/gluon/block.py:229,827).

TPU-native design: ``hybridize()`` does NOT build an NNVM graph — it wraps the
whole forward into ONE pure JAX function compiled by jax.jit (the CachedOp and
GraphExecutor of the reference collapse into this single compile-and-cache
component, SURVEY §7 table). Under autograd.record the compiled call is taped
as a single entry whose VJP is the XLA-differentiated whole graph.
"""
from __future__ import annotations

import os
import re
import threading

import jax
import numpy as onp

from .. import autograd
from .. import ndarray as nd
from ..ndarray import NDArray
from ..telemetry import spans
from .parameter import Parameter, ParameterDict, DeferredInitializationError
from . import _functional

__all__ = ["Block", "HybridBlock", "SymbolBlock"]


class _BlockScope:
    """Name scoping for parameter prefixes (ref block.py:35 _BlockScope)."""

    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None

    @staticmethod
    def create(prefix, params, hint):
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                prefix = _name_counter(hint) + "_"
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params, None
        if prefix is None:
            count = current._counter.get(hint, 0)
            prefix = "%s%d_" % (hint, count)
            current._counter[hint] = count + 1
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params, current._block._scope

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        return self

    def __exit__(self, *a):
        if self._block._empty_prefix:
            return
        _BlockScope._current.value = self._old_scope


_NAME_COUNTERS = {}


def _name_counter(hint):
    count = _NAME_COUNTERS.get(hint, 0)
    _NAME_COUNTERS[hint] = count + 1
    return "%s%d" % (hint, count)


class HookHandle:
    """Detachable hook registration (ref python/mxnet/gluon/utils.py HookHandle)."""

    def __init__(self, hooks_list, hook):
        self._list = hooks_list
        self._hook = hook

    def detach(self):
        if self._hook in self._list:
            self._list.remove(self._hook)

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.detach()


class Block:
    """Base building block (ref gluon/block.py:229)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params, self._scope_parent = _BlockScope.create(
            prefix, params, self._alias())
        self._scope = _BlockScope(self)
        self._children = {}
        self._reg_params = {}
        self._forward_hooks = []
        self._forward_pre_hooks = []

    def _alias(self):
        return self.__class__.__name__.lower()

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._prefix[:-1] if self._prefix.endswith("_") else self._prefix

    def name_scope(self):
        return self._scope

    @property
    def params(self):
        return self._params

    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
        elif isinstance(value, Parameter):
            reg = self.__dict__.get("_reg_params")
            if reg is not None:
                reg[name] = value
        super().__setattr__(name, value)

    def register_child(self, block, name=None):
        self._children[name or str(len(self._children))] = block

    def register_forward_hook(self, hook):
        """Returns a detachable handle (ref block.py HookHandle)."""
        self._forward_hooks.append(hook)
        return HookHandle(self._forward_hooks, hook)

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)
        return HookHandle(self._forward_pre_hooks, hook)

    def collect_params(self, select=None):
        ret = ParameterDict(self._params.prefix)
        if not select:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret.update({n: p for n, p in self.params.items() if pattern.match(n)})
        for child in self._children.values():
            ret.update(child.collect_params(select))
        return ret

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        # set-up work of the program's own (a small program or two a
        # parameter): the span owns their compile-pipeline events
        # (telemetry/setup_phases.py)
        params = self.collect_params()
        with spans.span("gluon:initialize", params=len(params)):
            params.initialize(init, ctx, verbose, force_reinit)

    def cast(self, dtype):
        # ONE span for the tree: the children's casts run inside the root's
        outer = spans.current_span()
        if outer is not None and outer.name == "gluon:cast":
            return self._cast_tree(dtype)
        with spans.span("gluon:cast", dtype=str(dtype),
                        params=len(self.collect_params())):
            self._cast_tree(dtype)

    def _cast_tree(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for _, p in self.params.items():
            p.cast(dtype)

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    # -- persistence ---------------------------------------------------
    def save_parameters(self, filename, deduplicate=False):
        """ref gluon/block.py:417."""
        params = self._collect_params_with_prefix()
        nd.save(filename, {k: v.data() for k, v in params.items()})

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False, dtype_source="current"):
        """ref gluon/block.py:473."""
        loaded = nd.load(filename)
        params = self._collect_params_with_prefix()
        if not allow_missing:
            for name in params:
                if name not in loaded:
                    raise AssertionError("Parameter %s missing in %s" % (name, filename))
        for name, data in loaded.items():
            if name not in params:
                if not ignore_extra:
                    raise AssertionError("Parameter %s in file not found in Block" % name)
                continue
            p = params[name]
            if p._data is None:
                p.shape = data.shape
                if p._deferred_init is not None:
                    p._finish_deferred_init()
                else:
                    p.initialize(ctx=ctx)
            p.set_data(data)

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + k: v for k, v in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    # -- call ----------------------------------------------------------
    def __call__(self, *args):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        # every op traced in here carries the block's name (the one its
        # parameters carry) in its HLO metadata; costs at trace time only
        with jax.named_scope(self.name):
            out = self.forward(*args)
        for hook in self._forward_hooks:
            hook(self, args, out)
        if args and all(isinstance(a, NDArray) for a in args):
            # remember the input SIGNATURE (shape/dtype only — keeping the
            # live arrays would pin the batch's device buffers in HBM) so
            # export() can emit the serving artifact without an explicit
            # example (see HybridBlock.export)
            self._last_input_avals = [(a.shape, a.dtype) for a in args]
        return out

    def forward(self, *args):
        raise NotImplementedError

    def summary(self, *inputs):
        summary_lines = ["-" * 64, "%-30s %20s" % ("Layer (type)", "Output Shape"),
                        "=" * 64]
        def walk(block, x, depth=0):
            out = block(x)
            return out
        out = self(*inputs)
        summary_lines.append("%-30s %20s" % (self.name, getattr(out, "shape", "?")))
        print("\n".join(summary_lines))

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join("  ({key}): {block}".format(
            key=key, block=_indent(repr(block), 2))
            for key, block in self._children.items())
        return s.format(name=self.__class__.__name__, modstr=modstr)


def _indent(s_, num_spaces):
    lines = s_.split("\n")
    if len(lines) == 1:
        return s_
    first = lines.pop(0)
    return first + "\n" + "\n".join(" " * num_spaces + line for line in lines)


class HybridBlock(Block):
    """Block that can be compiled to one XLA program (ref block.py:827).

    Subclasses implement ``hybrid_forward(F, x, **params)`` (MXNet idiom) or
    plain ``forward(x)``.
    """

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix, params)
        self._active = False
        self._cached_fn = None
        self._cached_meta = None
        self._flags = {}

    def hybridize(self, active=True, **kwargs):
        """Compile forward with jax.jit (≙ CachedOp, cached_op.cc:762)."""
        self._active = active
        self._flags = kwargs
        self._cached_fn = None
        for child in self._children.values():
            if isinstance(child, HybridBlock):
                # only the outermost compiled scope matters; children run traced
                child._flags = kwargs

    def infer_shape(self, *args):
        self._deferred_infer_shape(*args)

    def _deferred_infer_shape(self, *args):
        # run one eager forward on zeros to trigger deferred param init
        with autograd.pause():
            self.forward(*args)

    def cast(self, dtype):
        self._cached_fn = None
        super().cast(dtype)

    # -- hybrid_forward adapter ---------------------------------------
    def forward(self, *args):
        """Default: adapt MXNet's hybrid_forward(F, x, **params) signature."""
        if type(self).hybrid_forward is not HybridBlock.hybrid_forward:
            kwargs = {}
            for name, param in self._reg_params.items():
                try:
                    kwargs[name] = param.data()
                except DeferredInitializationError:
                    self._infer_param_shapes(*args)
                    kwargs[name] = param.data()
            return self.hybrid_forward(nd, *args, **kwargs)
        raise NotImplementedError(
            "%s must implement forward or hybrid_forward" % type(self).__name__)

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    def _infer_param_shapes(self, *args):
        """Infer deferred shapes from inputs (layer-specific override)."""
        raise DeferredInitializationError(
            "%s has uninitialized parameters and no shape inference; "
            "initialize with known in_units/in_channels" % type(self).__name__)

    # -- compiled call -------------------------------------------------
    def __call__(self, *args):
        if not self._active:
            return super().__call__(*args)
        return self._call_cached(*args)

    def _call_cached(self, *args):
        train_mode = autograd.is_training()
        arg_arrays = [a if isinstance(a, NDArray) else nd.array(a) for a in args]

        # deferred init: run shapes through eager path once
        try:
            params = list(self.collect_params().values())
            for p in params:
                if p._data is None and p._deferred_init is not None:
                    with autograd.pause(train_mode=train_mode):
                        Block.__call__(self, *arg_arrays)
                    break
        except DeferredInitializationError:
            pass

        meta = (train_mode, tuple((a.shape, str(a.dtype)) for a in arg_arrays))
        # per-net trace-lock discipline (jit._net_trace_lock): this path's
        # lazy first call TRACES pure_fn inside nd._apply — swapping
        # tracers into the live param NDArrays — and its hit path reads
        # a._data; either concurrent with an EvalStep/TrainStep/prewarm
        # trace of the same net would capture tracers mid-swap. Held for
        # the whole lookup+apply (dispatch is async; sub-µs uncontended).
        from .. import jit as _jit
        with _jit._net_trace_lock(self):
            return self._call_cached_locked(meta, train_mode, arg_arrays)

    def _call_cached_locked(self, meta, train_mode, arg_arrays):
        if self._cached_fn is None:
            self._cached_fn = {}
        if meta in self._cached_fn:
            # LRU touch (evict_to_bound contract): move-to-end so the
            # bound drops the coldest shape, never the one dispatching now
            self._cached_fn[meta] = self._cached_fn.pop(meta)
        else:
            params, param_arrs, pure_fn, aux_box = _functional.make_pure_fn(
                self, train_mode)
            jitted = jax.jit(lambda pd, xd, key: pure_fn(pd, xd, key))
            self._cached_fn[meta] = (jitted, param_arrs, aux_box)
            from ..config import evict_to_bound
            evict_to_bound(self._cached_fn)
        jitted, param_arrs, aux_box = self._cached_fn[meta]

        key = jax.random.PRNGKey(0) if not train_mode else _split_global_key()

        def taped_fn(*flat):
            n = len(param_arrs)
            pd, xd = list(flat[:n]), list(flat[n:])
            out_datas, aux_vals = jitted(pd, xd, key)
            return tuple(out_datas) + tuple(aux_vals)

        all_inputs = param_arrs + arg_arrays
        results = nd._apply(taped_fn, *all_inputs)
        if not isinstance(results, (tuple, list)):
            results = (results,)
        n_aux = len(aux_box)
        outs = list(results[: len(results) - n_aux])
        aux_new = results[len(results) - n_aux:]
        with autograd.pause():
            for arr, new in zip(aux_box, aux_new):
                arr._data = new._data
        return outs[0] if len(outs) == 1 else tuple(outs)

    def export(self, path, epoch=0, example_inputs=None):
        """Export for deployment (ref block.py:1106 HybridBlock.export).

        TPU-native: saves parameters + a manifest JSON, and — when the
        input signature is known (``example_inputs`` given, or the block
        has been called) — a ``<path>.mxtpu`` serving artifact (serialized
        compiled StableHLO, contrib/serving.py). ``SymbolBlock.imports``
        on the manifest loads that artifact back as an inference block, so
        export → imports round-trips like the reference's symbol.json +
        params contract.
        """
        import json
        params = self._collect_params_with_prefix()
        nd.save("%s-%04d.params" % (path, epoch),
                {("arg:" + k): v.data() for k, v in params.items()})
        artifact = None
        inputs = example_inputs
        if inputs is None:
            avals = getattr(self, "_last_input_avals", None)
            if avals is not None:
                inputs = [nd.zeros(shape, dtype=dtype)
                          for shape, dtype in avals]
        if inputs is not None:
            from ..contrib import serving
            artifact = "%s.mxtpu" % path
            serving.export_model(self, inputs, artifact)
        with open("%s-symbol.json" % path, "w") as f:
            json.dump({"format": "incubator_mxnet_tpu.hybrid",
                       "class": type(self).__name__,
                       "artifact": artifact and os.path.basename(artifact)},
                      f)


def _split_global_key():
    from ..ndarray import random as _rnd
    return _rnd._next_key()


class SymbolBlock(HybridBlock):
    """Wrap a Symbol graph as a Block (ref block.py:1218)."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="", params=params)
        from ..symbol import Symbol
        self._outputs = outputs
        self._inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        out = outputs if isinstance(outputs, Symbol) else outputs[0]
        self._sym = out
        input_names = {i.name for i in self._inputs}
        for name in out.list_inputs():
            if name not in input_names:
                self.params.get(name, allow_deferred_init=True)

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        """Load a serialized graph (+.params) as a Block
        (ref block.py:1311 SymbolBlock.imports). Accepts either a real
        Symbol graph JSON or a HybridBlock.export manifest — the latter
        loads the exported ``.mxtpu`` serving artifact as an
        inference-only block (params are baked into the program)."""
        import json as _json
        from .. import symbol as mxsym
        with open(symbol_file) as f:
            head = f.read(4096)
        try:
            meta = _json.loads(head)
        except ValueError:
            meta = None
        if isinstance(meta, dict) and \
                meta.get("format") == "incubator_mxnet_tpu.hybrid":
            artifact = meta.get("artifact")
            if not artifact:
                raise ValueError(
                    "%s is a hybrid-export manifest without a serving "
                    "artifact; re-export after a forward pass (or with "
                    "example_inputs) so the .mxtpu program is written"
                    % symbol_file)
            apath = os.path.join(os.path.dirname(os.path.abspath(symbol_file)),
                                 artifact)
            return _ServedBlock(apath)
        sym = mxsym.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        name_to_var = {v.name: v for v in sym.get_internals() if v.is_var}
        inputs = [name_to_var[n] for n in input_names]
        block = SymbolBlock(sym, inputs)
        if param_file is not None:
            loaded = {k.split(":", 1)[-1]: v  # strip arg:/aux: prefixes
                      for k, v in nd.load(param_file).items()}
            extra = set(loaded) - set(block.params.keys())
            missing = set(block.params.keys()) - set(loaded)
            if extra or missing:
                raise AssertionError(
                    "params file does not match the graph: missing %s, "
                    "extra %s" % (sorted(missing), sorted(extra)))
            for name, v in loaded.items():
                p = block.params.get(name)
                p.shape = tuple(v.shape)
                p.initialize(init="zeros", ctx=ctx, force_reinit=True)
                p.set_data(v if ctx is None else v.as_in_context(ctx))
        return block

    def forward(self, *args):
        bindings = {i.name: a for i, a in zip(self._inputs, args)}
        for name, p in self.params.items():
            bindings[name] = p.data()
        return self._sym.eval_imperative(bindings)


class _ServedBlock(Block):
    """SymbolBlock.imports result for hybrid-export manifests: wraps the
    .mxtpu serving artifact (compiled program, params baked in) as an
    inference-only Block."""

    def __init__(self, artifact_path):
        super().__init__(prefix="", params=None)
        from ..contrib import serving
        self._served = serving.load(artifact_path)
        self._artifact_path = artifact_path

    def forward(self, *args):
        return self._served.predict(*args)
