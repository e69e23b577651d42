"""Basic neural-net layers (ref python/mxnet/gluon/nn/basic_layers.py)."""
from __future__ import annotations

import numpy as onp

from ... import ndarray as nd
from ...ndarray import NDArray, _apply
from ..block import Block, HybridBlock
from ..parameter import Parameter

__all__ = ["Sequential", "HybridSequential", "Dense", "Dropout", "Embedding",
           "BatchNorm", "SyncBatchNorm", "InstanceNorm", "LayerNorm", "RMSNorm", "GroupNorm", "Flatten",
           "Lambda", "HybridLambda", "Activation", "LeakyReLU", "PReLU", "ELU",
           "SELU", "Swish", "GELU", "Identity"]


class Sequential(Block):
    """ref basic_layers.py Sequential."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix, params)

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)

    def forward(self, x):
        for block in self._children.values():
            x = block(x)
        return x

    def __len__(self):
        return len(self._children)

    def __getitem__(self, key):
        layers = list(self._children.values())[key]
        if isinstance(layers, list):
            net = type(self)(prefix=self._prefix)
            with net.name_scope():
                net.add(*layers)
            return net
        return layers

    def __iter__(self):
        return iter(self._children.values())

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)


class HybridSequential(HybridBlock):
    """ref basic_layers.py HybridSequential — one fused XLA program when hybridized."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix, params)

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)

    def forward(self, x):
        for block in self._children.values():
            x = block(x)
        return x

    def __len__(self):
        return len(self._children)

    def __getitem__(self, key):
        layers = list(self._children.values())[key]
        if isinstance(layers, list):
            net = type(self)(prefix=self._prefix)
            with net.name_scope():
                net.add(*layers)
            return net
        return layers

    def __iter__(self):
        return iter(self._children.values())


class Dense(HybridBlock):
    """Fully-connected layer (ref basic_layers.py Dense → nn/fully_connected.cc).

    ``flatten=False`` applies the layer to the last axis of an input of
    any rank. On the TPU a (B, S, U) input at B > 1 makes the matmul, and
    above all the weight gradient of a (U, 4 U) layer, a convolution with
    the batch as a window dimension, at up to twice the time of the same
    matmul over (B x S, U). A reshape in here alone does not cure it (XLA
    moves it through the neighbouring element-wise ops until the pair
    meets): the block that cares carries (tokens, channels) across its
    whole MLP half, `models.bert.mlp_tokens` (PERF.md section 6, PR 42)."""

    def __init__(self, units, activation=None, use_bias=True, flatten=True,
                 dtype="float32", weight_initializer=None, bias_initializer="zeros",
                 in_units=0, prefix=None, params=None):
        super().__init__(prefix, params)
        self._units = units
        self._flatten = flatten
        self.act_type = activation
        with self.name_scope():
            self.weight = self.params.get("weight", shape=(units, in_units),
                                          init=weight_initializer, dtype=dtype,
                                          allow_deferred_init=True)
            if use_bias:
                self.bias = self.params.get("bias", shape=(units,),
                                            init=bias_initializer, dtype=dtype,
                                            allow_deferred_init=True)
            else:
                self.bias = None

    def forward(self, x):
        if self.weight._data is None:
            in_units = int(onp.prod(x.shape[1:])) if self._flatten else x.shape[-1]
            self.weight.shape = (self._units, in_units)
            self.weight._finish_deferred_init()
            if self.bias is not None:
                self.bias._finish_deferred_init()
        out = nd.FullyConnected(x, self.weight.data(),
                                self.bias.data() if self.bias is not None else None,
                                num_hidden=self._units, flatten=self._flatten,
                                no_bias=self.bias is None)
        if self.act_type:
            out = nd.Activation(out, act_type=self.act_type)
        return out

    def __repr__(self):
        return "Dense(%s -> %s)" % (self.weight.shape[1] or None, self._units)


class Dropout(HybridBlock):
    def __init__(self, rate, axes=(), **kwargs):
        super().__init__(**kwargs)
        self._rate = rate
        self._axes = axes

    def forward(self, x):
        return nd.Dropout(x, p=self._rate, axes=self._axes)

    def __repr__(self):
        return "Dropout(p=%s)" % self._rate


class Embedding(HybridBlock):
    """ref basic_layers.py Embedding → tensor/indexing_op.cc."""

    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, sparse_grad=False, **kwargs):
        super().__init__(**kwargs)
        self._input_dim = input_dim
        self._output_dim = output_dim
        with self.name_scope():
            self.weight = self.params.get("weight", shape=(input_dim, output_dim),
                                          init=weight_initializer, dtype=dtype)

    def forward(self, x):
        return nd.Embedding(x, self.weight.data(), input_dim=self._input_dim,
                            output_dim=self._output_dim)

    def __repr__(self):
        return "Embedding(%d -> %d)" % (self._input_dim, self._output_dim)


class BatchNorm(HybridBlock):
    """ref basic_layers.py BatchNorm → nn/batch_norm.cc."""

    def __init__(self, axis=1, momentum=0.9, epsilon=1e-5, center=True, scale=True,
                 use_global_stats=False, beta_initializer="zeros",
                 gamma_initializer="ones", running_mean_initializer="zeros",
                 running_variance_initializer="ones", in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._axis = axis
        self._momentum = momentum
        self._epsilon = epsilon
        self._center = center
        self._scale = scale
        self._use_global_stats = use_global_stats
        self.in_channels = in_channels
        with self.name_scope():
            self.gamma = self.params.get("gamma", grad_req="write" if scale else "null",
                                         shape=(in_channels,), init=gamma_initializer,
                                         allow_deferred_init=True)
            self.beta = self.params.get("beta", grad_req="write" if center else "null",
                                        shape=(in_channels,), init=beta_initializer,
                                        allow_deferred_init=True)
            self.running_mean = self.params.get("running_mean", grad_req="null",
                                                shape=(in_channels,),
                                                init=running_mean_initializer,
                                                allow_deferred_init=True,
                                                differentiable=False)
            self.running_var = self.params.get("running_var", grad_req="null",
                                               shape=(in_channels,),
                                               init=running_variance_initializer,
                                               allow_deferred_init=True,
                                               differentiable=False)

    def _ensure_init(self, x):
        if self.gamma._data is None:
            c = x.shape[self._axis]
            for p in (self.gamma, self.beta, self.running_mean, self.running_var):
                p.shape = (c,)
                p._finish_deferred_init()

    def forward(self, x):
        self._ensure_init(x)
        return nd.BatchNorm(x, self.gamma.data(), self.beta.data(),
                            self.running_mean.data(), self.running_var.data(),
                            eps=self._epsilon, momentum=self._momentum,
                            fix_gamma=not self._scale,
                            use_global_stats=self._use_global_stats, axis=self._axis)

    def cast(self, dtype):
        if str(dtype) in ("float16", "bfloat16"):
            dtype = "float32"  # BN statistics stay fp32 (AMP semantics)
        super().cast(dtype)


class InstanceNorm(HybridBlock):
    def __init__(self, axis=1, epsilon=1e-5, center=True, scale=False,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._epsilon = epsilon
        self._axis = axis
        with self.name_scope():
            self.gamma = self.params.get("gamma", grad_req="write" if scale else "null",
                                         shape=(in_channels,), init=gamma_initializer,
                                         allow_deferred_init=True)
            self.beta = self.params.get("beta", grad_req="write" if center else "null",
                                        shape=(in_channels,), init=beta_initializer,
                                        allow_deferred_init=True)

    def forward(self, x):
        if self.gamma._data is None:
            c = x.shape[self._axis]
            for p in (self.gamma, self.beta):
                p.shape = (c,)
                p._finish_deferred_init()
        return nd.InstanceNorm(x, self.gamma.data(), self.beta.data(), eps=self._epsilon)


class LayerNorm(HybridBlock):
    """ref basic_layers.py LayerNorm → nn/layer_norm.cc."""

    def __init__(self, axis=-1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._axis = axis
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get("gamma", grad_req="write" if scale else "null",
                                         shape=(in_channels,), init=gamma_initializer,
                                         allow_deferred_init=True)
            self.beta = self.params.get("beta", grad_req="write" if center else "null",
                                        shape=(in_channels,), init=beta_initializer,
                                        allow_deferred_init=True)

    def forward(self, x):
        if self.gamma._data is None:
            c = x.shape[self._axis]
            for p in (self.gamma, self.beta):
                p.shape = (c,)
                p._finish_deferred_init()
        return nd.LayerNorm(x, self.gamma.data(), self.beta.data(),
                            axis=self._axis, eps=self._epsilon)


class RMSNorm(HybridBlock):
    """y = x / sqrt(mean(x^2, axis) + epsilon) * gamma (Zhang & Sennrich
    2019): LayerNorm without the mean and the shift. The statistics are
    float32 whatever the input's type, as nd.LayerNorm's are."""

    def __init__(self, axis=-1, epsilon=1e-5, gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._axis = axis
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(in_channels,),
                                         init=gamma_initializer,
                                         allow_deferred_init=True)

    def forward(self, x):
        if self.gamma._data is None:
            self.gamma.shape = (x.shape[self._axis],)
            self.gamma._finish_deferred_init()
        import jax.numpy as jnp
        from jax import lax
        axis, eps = self._axis, self._epsilon

        def fn(x, g):
            xf = x.astype(jnp.float32)
            ms = jnp.mean(xf * xf, axis=axis, keepdims=True)
            shape = [1] * x.ndim
            shape[axis] = x.shape[axis]
            return (xf * lax.rsqrt(ms + eps)
                    * g.astype(jnp.float32).reshape(shape)).astype(x.dtype)

        return _apply(fn, x, self.gamma.data())


class GroupNorm(HybridBlock):
    def __init__(self, num_groups=1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._num_groups = num_groups
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get("gamma", grad_req="write" if scale else "null",
                                         shape=(in_channels,), init=gamma_initializer,
                                         allow_deferred_init=True)
            self.beta = self.params.get("beta", grad_req="write" if center else "null",
                                        shape=(in_channels,), init=beta_initializer,
                                        allow_deferred_init=True)

    def forward(self, x):
        if self.gamma._data is None:
            c = x.shape[1]
            for p in (self.gamma, self.beta):
                p.shape = (c,)
                p._finish_deferred_init()
        return nd.GroupNorm(x, self.gamma.data(), self.beta.data(),
                            num_groups=self._num_groups, eps=self._epsilon)


class Flatten(HybridBlock):
    def forward(self, x):
        return x.flatten()

    def __repr__(self):
        return "Flatten"


class Identity(HybridBlock):
    def forward(self, x):
        return x


class Lambda(Block):
    def __init__(self, function, prefix=None):
        super().__init__(prefix=prefix)
        if isinstance(function, str):
            self._func_impl = getattr(nd, function)
        else:
            self._func_impl = function

    def forward(self, *args):
        return self._func_impl(*args)


class HybridLambda(HybridBlock):
    def __init__(self, function, prefix=None):
        super().__init__(prefix=prefix)
        if isinstance(function, str):
            self._func_impl = getattr(nd, function)
        else:
            self._func_impl = function

    def forward(self, *args):
        return self._func_impl(*args)


class Activation(HybridBlock):
    def __init__(self, activation, **kwargs):
        self._act_type = activation
        super().__init__(**kwargs)

    def _alias(self):
        return self._act_type

    def forward(self, x):
        return nd.Activation(x, act_type=self._act_type)

    def __repr__(self):
        return "Activation(%s)" % self._act_type


class LeakyReLU(HybridBlock):
    def __init__(self, alpha, **kwargs):
        super().__init__(**kwargs)
        self._alpha = alpha

    def forward(self, x):
        return nd.LeakyReLU(x, act_type="leaky", slope=self._alpha)


class PReLU(HybridBlock):
    def __init__(self, alpha_initializer=None, in_channels=1, **kwargs):
        super().__init__(**kwargs)
        from ... import initializer
        with self.name_scope():
            self.alpha = self.params.get("alpha", shape=(in_channels,),
                                         init=alpha_initializer or initializer.Constant(0.25))

    def forward(self, x):
        return nd.LeakyReLU(x, gamma=self.alpha.data(), act_type="prelu")


class ELU(HybridBlock):
    def __init__(self, alpha=1.0, **kwargs):
        super().__init__(**kwargs)
        self._alpha = alpha

    def forward(self, x):
        return nd.LeakyReLU(x, act_type="elu", slope=self._alpha)


class SELU(HybridBlock):
    def forward(self, x):
        return nd.LeakyReLU(x, act_type="selu")


class GELU(HybridBlock):
    def forward(self, x):
        return nd.LeakyReLU(x, act_type="gelu")


class Swish(HybridBlock):
    def __init__(self, beta=1.0, **kwargs):
        super().__init__(**kwargs)
        self._beta = beta

    def forward(self, x):
        return x * nd.sigmoid(self._beta * x)


class SyncBatchNorm(BatchNorm):
    """Cross-device synchronized BatchNorm (ref gluon/contrib/nn/basic_layers.py
    SyncBatchNorm, src/operator/contrib/sync_batch_norm.cc).

    TPU-native: under SPMD data parallelism the fused train step computes
    batch statistics over the GLOBAL batch — ``jnp.mean`` along a dp-sharded
    axis lowers to a cross-device all-reduce — so BatchNorm is already
    synchronized; this subclass exists for API parity. ``num_devices`` is
    accepted and ignored (the mesh defines the sync group).
    """

    def __init__(self, in_channels=0, num_devices=None, **kwargs):
        super().__init__(in_channels=in_channels, **kwargs)
