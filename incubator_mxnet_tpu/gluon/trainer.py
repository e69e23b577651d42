"""Gluon Trainer (ref python/mxnet/gluon/trainer.py:28).

Reference parity: kvstore wiring (:182-270), ``step`` (:328),
``_allreduce_grads`` (:379), ``_update`` (:438), save/load_states (:471,500).

TPU-native design: with a single logical parameter copy, ``_allreduce_grads``
is a no-op locally (SPMD data-parallel gradients are psum'd *inside* the
compiled step by parallel.DataParallelTrainer); the kvstore facade is kept for
API compatibility and server-style update_on_kvstore flows.
"""
from __future__ import annotations

from .. import optimizer as opt
from .. import kvstore as kvs_mod
from ..ndarray import NDArray
from .parameter import ParameterDict, Parameter

__all__ = ["Trainer"]


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None, kvstore="device",
                 compression_params=None, update_on_kvstore=None):
        if isinstance(params, (dict, ParameterDict)):
            params = list(params.values()) if hasattr(params, "values") else list(params)
        if not isinstance(params, (list, tuple)):
            raise ValueError("First argument must be a list or dict of Parameters")
        self._params = []
        self._param2idx = {}
        for i, param in enumerate(params):
            if not isinstance(param, Parameter):
                raise ValueError("First argument must contain Parameters, got %s" % type(param))
            self._params.append(param)
            self._param2idx[param.name] = i
        self._compression_params = compression_params
        self._contexts = self._check_contexts()
        optimizer_params = optimizer_params or {}
        self._init_optimizer(optimizer, optimizer_params)
        self._scale = self._optimizer.rescale_grad
        self._kvstore_params = {"kvstore": kvstore,
                                "update_on_kvstore": update_on_kvstore}
        self._kv_initialized = False
        self._kvstore = None
        self._update_on_kvstore = None
        self._states = [None] * len(self._params)
        self._states_initialized = False

    def _check_contexts(self):
        contexts = None
        for param in self._params:
            ctx = param.list_ctx() if param._data is not None or param._ctx else None
            contexts = contexts or ctx
        return contexts or []

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: param for i, param in enumerate(self._params)}
        if isinstance(optimizer, opt.Optimizer):
            assert not optimizer_params, \
                "optimizer_params must be None if optimizer is an Optimizer instance"
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt.create(optimizer, param_dict=param_dict,
                                         **optimizer_params)

    def _init_kvstore(self):
        config = self._kvstore_params
        kvstore = config["kvstore"]
        if kvstore is None:
            self._kvstore = None
            self._update_on_kvstore = False
        else:
            kv = kvs_mod.create(kvstore) if isinstance(kvstore, str) else kvstore
            self._kvstore = kv
            if self._compression_params:
                kv.set_gradient_compression(self._compression_params)
            update_on_kvstore = config["update_on_kvstore"]
            if update_on_kvstore is None:
                update_on_kvstore = kv.type.startswith("dist")
            self._update_on_kvstore = update_on_kvstore
            if update_on_kvstore:
                kv.set_optimizer(self._optimizer)
                # the store's copy of a parameter is what a server-style
                # update writes and `_update` pulls; every other flow
                # (the local update, a compiled `jit.TrainStep`) never reads
                # the store, and a copy there is 2 bytes a bfloat16
                # parameter of device memory for nothing: 1.53 GiB beside
                # EvaByte's 821 M, with which its step did not LOAD
                # (PERF.md section 6, PR 45)
                for i, param in enumerate(self._params):
                    if param.grad_req != "null":
                        kv.init(i, param.data())
        self._kv_initialized = True

    # ------------------------------------------------------------------
    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    @property
    def optimizer(self):
        return self._optimizer

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    def _init_states(self):
        for i, param in enumerate(self._params):
            if param.grad_req != "null" and self._states[i] is None:
                self._states[i] = self._optimizer.create_state_multi_precision(
                    i, param.data())
        self._states_initialized = True

    # ------------------------------------------------------------------
    def step(self, batch_size, ignore_stale_grad=False):
        """rescale, allreduce, update (ref trainer.py:328)."""
        if not self._kv_initialized:
            self._init_kvstore()
        if not self._states_initialized and not self._update_on_kvstore:
            self._init_states()
        self._optimizer.rescale_grad = self._scale / batch_size
        self._allreduce_grads()
        self._update(ignore_stale_grad)

    def allreduce_grads(self):
        if not self._kv_initialized:
            self._init_kvstore()
        self._allreduce_grads()

    def _allreduce_grads(self):
        """ref trainer.py:379. Single-logical-copy: kvstore push/pull only
        matters for update_on_kvstore (server-style) flows."""
        if self._kvstore is None or not self._update_on_kvstore:
            return
        for i, param in enumerate(self._params):
            if param.grad_req != "null":
                self._kvstore.push(i, param.grad(), priority=-i)

    def update(self, batch_size, ignore_stale_grad=False):
        if not self._kv_initialized:
            self._init_kvstore()
        if not self._states_initialized and not self._update_on_kvstore:
            self._init_states()
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad=False):
        for i, param in enumerate(self._params):
            if param.grad_req == "null":
                continue
            if self._update_on_kvstore and self._kvstore is not None:
                self._kvstore.pull(i, param.data(), priority=-i)
                continue
            new_state = self._optimizer.update_multi_precision(
                i, param.data(), param.grad(), self._states[i])
            if new_state is not None:
                self._states[i] = new_state

    # ------------------------------------------------------------------
    def save_states(self, fname):
        """ref trainer.py:471."""
        assert self._optimizer is not None
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname, dump_optimizer=False)
            return
        if not self._states_initialized:
            self._init_states()
        updater = opt.Updater(self._optimizer)
        updater.states = {i: s for i, s in enumerate(self._states) if s is not None}
        with open(fname, "wb") as f:
            f.write(updater.get_states(dump_optimizer=False))

    def load_states(self, fname):
        """ref trainer.py:500."""
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
            return
        updater = opt.Updater(self._optimizer)
        with open(fname, "rb") as f:
            updater.set_states(f.read())
        for i, s in updater.states.items():
            self._states[int(i)] = s
        self._states_initialized = True
