"""Mixture-of-Experts with a sort-based DROPLESS dispatch — NEW capability
(SURVEY §2.5: no MoE ops in the reference).

One dispatch, whatever the expert count: the router's softmax and top-k run
in float32; the T x k (token, slot) assignments are sorted by expert, the
token rows gathered in that order, and every expert's matmul runs as ONE
grouped matmul over the stacked weights with the per-expert row counts as
data (``jax.lax.ragged_dot``: on a TPU XLA lowers it to a Mosaic grouped
matmul, on the CPU to plain ops); the rows are then un-sorted and summed
per token with the router's weights. Shapes are static — always exactly
T x k rows — and nothing is dropped, however uneven the routing: an expert
with no token costs nothing, one with all of them gets all of them. No
(T, E, C) or (E, T, H) tensor exists.

The stacked expert weights carry ``PartitionSpec(ep_axis, None, None)``,
so on a mesh with an ``ep`` axis GSPMD shards the experts' state; the
token exchange between chips (an all-to-all of the sorted rows) is not
written yet (ROADMAP R2), and GSPMD gathers what the grouped matmul needs.

An auxiliary load-balancing loss (Switch-Transformer form,
``E * sum_e fraction_routed_e * mean_gate_e``) and the ST-MoE router z-loss
are returned by ``forward_with_aux`` for the trainer to add to the task
loss. The dense O(T*E) form lives on in the tests as their reference
(tests/test_moe_dispatch.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import initializer, telemetry
from .. import ndarray as nd
from ..gluon.block import HybridBlock
from ..ndarray import _apply

__all__ = ["MoELayer", "dropless_moe", "load_balancing_loss",
           "router_z_loss"]

_DISPATCHES = telemetry.counter(
    "mxtpu_moe_dispatch_total",
    "MoE expert dispatches traced, by path (one is left: the sort-based "
    "dropless grouped matmul).", ("path",))


def load_balancing_loss(gates, top_idx, num_experts):
    """Switch-Transformer aux loss: E * sum_e f_e * p_e.

    gates: (T, E) softmax router probabilities; top_idx: (T, k) chosen experts.
    f_e = fraction of tokens whose FIRST choice is e; p_e = mean gate prob.
    """
    p = jnp.mean(gates, axis=0)                                   # (E,)
    f = jnp.mean(jax.nn.one_hot(top_idx[:, 0], num_experts,
                                dtype=gates.dtype), axis=0)       # (E,)
    return num_experts * jnp.sum(f * p)


def router_z_loss(logits):
    """ST-MoE router z-loss: mean(logsumexp(logits)^2) — keeps router
    logits small so the softmax stays out of its saturated/overflow-prone
    region (bf16 routers drift without it)."""
    z = jax.nn.logsumexp(logits, axis=-1)
    return jnp.mean(jnp.square(z))


# Both permutations move whole rows and are each other's inverse, so each
# one's gradient is a GATHER by the other; JAX's own rule for x[idx] is a
# scatter-add, which the TPU runs an order of magnitude slower.
@jax.custom_vjp
def _gather_sorted(tokens, order, inverse):
    """tokens (T, D) -> rows (T*k, D): row i is the token of the i-th
    assignment in expert order, tokens[order[i] // k]."""
    return tokens[order // (order.shape[0] // tokens.shape[0])]


def _gather_sorted_fwd(tokens, order, inverse):
    return _gather_sorted(tokens, order, inverse), (tokens.shape[0], inverse)


def _gather_sorted_bwd(res, g):
    n_tokens, inverse = res
    per_slot = g[inverse].reshape(n_tokens, -1, g.shape[-1])
    return per_slot.astype(jnp.float32).sum(1).astype(g.dtype), None, None


_gather_sorted.defvjp(_gather_sorted_fwd, _gather_sorted_bwd)


@jax.custom_vjp
def _unsort(rows, order, inverse):
    """rows in expert order -> rows in (token, slot) order."""
    return rows[inverse]


def _unsort_fwd(rows, order, inverse):
    return rows[inverse], order


def _unsort_bwd(order, g):
    return g[order], None, None


_unsort.defvjp(_unsort_fwd, _unsort_bwd)


def dropless_moe(tokens, top_vals, top_idx, w_up, w_down, act, w_gate=None):
    """y_t = sum_j top_vals[t, j] * FFN_{top_idx[t, j]}(tokens[t]).

    tokens (T, D); top_vals (T, k) float32; top_idx (T, k) int;
    w_up (E, D, H), w_down (E, H, D) stacked expert weights;
    FFN_e(x) = act(x w_up[e]) w_down[e], or with ``w_gate`` (E, D, H) the
    gated form (act(x w_gate[e]) * (x w_up[e])) w_down[e] (SwiGLU when
    ``act`` is silu). Every op is under one of the scopes `moe_dispatch`,
    `moe_experts`, `moe_combine`.
    """
    n_tokens, k = top_idx.shape
    num_experts = w_up.shape[0]
    _DISPATCHES.inc(path="dropless")
    with jax.named_scope("moe_dispatch"):
        flat = top_idx.reshape(-1).astype(jnp.int32)              # (T*k,)
        slots = jnp.arange(flat.shape[0], dtype=jnp.int32)
        # two stable sorts: assignments by expert, and the way back
        _, order = jax.lax.sort_key_val(flat, slots)
        _, inverse = jax.lax.sort_key_val(order, slots)
        group_sizes = jnp.sum(
            flat[:, None] == jnp.arange(num_experts, dtype=jnp.int32),
            axis=0, dtype=jnp.int32)                              # (E,)
        rows = _gather_sorted(tokens, order, inverse)             # (T*k, D)
    with jax.named_scope("moe_experts"):
        grouped = functools.partial(jax.lax.ragged_dot,
                                    group_sizes=group_sizes)
        h = act(grouped(rows, w_up)) if w_gate is None \
            else act(grouped(rows, w_gate)) * grouped(rows, w_up)
        y = grouped(h, w_down)                                    # (T*k, D)
    with jax.named_scope("moe_combine"):
        y = _unsort(y, order, inverse).reshape(n_tokens, k, -1)
        out = jnp.sum(y.astype(jnp.float32) * top_vals[..., None], axis=1)
    return out.astype(tokens.dtype)


class _StackedXavier(initializer.Initializer):
    """Xavier (uniform, avg) for E stacked (fan_in, fan_out) matrices:
    every expert gets the scale its own matrix would. Xavier itself reads a
    3-D shape as a convolution kernel's and would scale 64 experts of
    2048 x 1024 down 26-fold."""

    def _init_weight(self, name, arr):
        _, fan_in, fan_out = arr.shape
        scale = math.sqrt(3.0 / ((fan_in + fan_out) / 2.0))
        arr._data = nd.random.uniform(-scale, scale, arr.shape).astype(
            arr.dtype)._data


_ACTIVATIONS = {"relu": jax.nn.relu, "gelu": jax.nn.gelu,
                "silu": jax.nn.silu}


class MoELayer(HybridBlock):
    """Top-k routed expert FFN, dropless: y = sum_k g_k * FFN_{e_k}(x).

    The router is softmax(x Wr) over all experts in float32; the k largest
    probabilities weigh the chosen experts' outputs, renormalised to sum
    to one when ``norm_topk_prob`` (the Switch/GShard convention and the
    default) and used as they are otherwise (OLMoE, Mixtral-style
    configurations with ``norm_topk_prob: false``).

    Weights, stacked over experts with E sharded over ``ep_axis``:
    ``w1`` (E, D, H) and ``w2`` (E, H, D); ``gated=True`` adds ``w3``
    (E, D, H) and the experts become act(x w1) * (x w3) -> w2 (SwiGLU with
    activation='silu'). ``forward`` returns the output only;
    ``forward_with_aux`` also the load-balancing + z loss for the trainer
    to add to the task loss. Every token reaches all its k experts
    (`dropless_moe`): there is no capacity and no second dispatch.
    """

    def __init__(self, num_experts, hidden_size, ffn_hidden, top_k=2,
                 ep_axis="ep", activation="relu", gated=False,
                 norm_topk_prob=True, z_loss_coef=1e-3,
                 capacity_factor=None, **kwargs):
        super().__init__(**kwargs)
        if capacity_factor is not None:
            import warnings
            warnings.warn(
                "MoELayer(capacity_factor=%r) is ignored: the one dispatch "
                "left is dropless — every token reaches its top-%d experts "
                "in T*k static rows, and no capacity can drop one"
                % (capacity_factor, top_k), stacklevel=2)
        if not 1 <= top_k <= num_experts:
            raise ValueError("top_k=%d of %d experts" % (top_k, num_experts))
        self.num_experts = num_experts
        self.top_k = top_k
        self.norm_topk_prob = norm_topk_prob
        self.z_loss_coef = z_loss_coef
        self._act = activation
        self._gated = gated
        stacked = {"w1": (num_experts, hidden_size, ffn_hidden),
                   "w2": (num_experts, ffn_hidden, hidden_size)}
        if gated:
            stacked["w3"] = stacked["w1"]
        with self.name_scope():
            self.gate_weight = self.params.get(
                "gate_weight", shape=(num_experts, hidden_size), init="xavier")
            for name, shape in stacked.items():
                param = self.params.get(name, shape=shape,
                                        init=_StackedXavier())
                param.sharding = P(ep_axis, None, None)
                setattr(self, name, param)

    def route(self, tokens, gw):
        """tokens (T, D), gw (E, D) -> (logits, gates, top_vals, top_idx),
        all float32 but the indices: the matmul accumulates in float32 and
        the softmax and top-k never see a narrower type."""
        logits = jnp.einsum("td,ed->te", tokens, gw,
                            preferred_element_type=jnp.float32)
        gates = jax.nn.softmax(logits, axis=-1)
        top_vals, top_idx = jax.lax.top_k(gates, self.top_k)
        if self.norm_topk_prob:
            top_vals = top_vals / jnp.sum(top_vals, -1, keepdims=True)
        return logits, gates, top_vals, top_idx

    def _fn(self, xd, gw, w1, w2, *w3, compute_aux):
        shape = xd.shape
        tokens = xd.reshape(-1, shape[-1])                        # (T, D)
        with jax.named_scope("router"):
            logits, gates, top_vals, top_idx = self.route(tokens, gw)
        # gated: w1 is the activated (gate) projection, w3 the linear one
        w_gate, w_up = (w1, w3[0]) if w3 else (None, w1)
        out = dropless_moe(tokens, top_vals, top_idx, w_up, w2,
                           _ACTIVATIONS[self._act], w_gate).reshape(shape)
        if compute_aux:
            with jax.named_scope("router"):
                aux = load_balancing_loss(gates, top_idx, self.num_experts) \
                    + self.z_loss_coef * router_z_loss(logits)
            return out, aux
        return out

    def _weights(self):
        names = ("gate_weight", "w1", "w2") + (("w3",) if self._gated else ())
        return [getattr(self, n).data() for n in names]

    def forward(self, x):
        """x: (..., D) → (..., D)."""
        return _apply(functools.partial(self._fn, compute_aux=False), x,
                      *self._weights())

    def forward_with_aux(self, x):
        """Returns (y, aux) where aux = Switch load-balancing loss +
        z_loss_coef * ST-MoE router z-loss (add to the task loss)."""
        return _apply(functools.partial(self._fn, compute_aux=True), x,
                      *self._weights())
