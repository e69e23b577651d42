"""BERT / Transformer encoder (BASELINE config 3 — GluonNLP BERT-base analog).

TPU-native design points:
- MXU-friendly: all projections are batched matmuls; bf16-ready (cast()).
- Tensor parallelism: ``tp_axis`` shards attention heads and FFN hidden over
  the mesh (Megatron pattern via GSPMD sharding annotations on the params).
- Sequence parallelism: ``attention='ring'`` computes attention with the
  ring-attention kernel over the ``sp`` mesh axis (parallel/ring_attention.py)
  — the long-context capability absent in the reference (SURVEY §5).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import PartitionSpec as P

from .. import ndarray as nd
from .. import telemetry
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ndarray import NDArray, _apply
from .lm_head import ChunkedHeadLossBase


_TRUNK_BLOCKS = telemetry.counter(
    "mxtpu_trunk_block_total",
    "Transformer blocks traced, by the form their MLP half's activations "
    "have (tokens_2d: B > 1 sequences carried as (B x S, U) from the half's "
    "entry to its exit, so its matmuls are the two-dimensional programs XLA "
    "makes at B = 1; batch_1: one sequence, nothing to flatten; "
    "batch_seq_3d: kept (B, S, U) because a mesh train step is tracing the "
    "block. That is a rule on the MESH, whatever the step does with its "
    "optimizer state: the one mesh step measured, dp 4 under ZeRO-1 on "
    "four v5e chips, ran 1.75 % faster so; a mesh step without ZeRO is "
    "held to it unmeasured).", ("form",))


def mlp_tokens(x):
    """The entry of a transformer block's MLP half: x (B, S, U) -> x as
    the half carries it, (B x S, U) at B > 1. The caller reshapes the
    half's output back to ``x.shape``.

    At B > 1 XLA's TPU compiler makes a (B, S, U) matmul a convolution
    with the batch as a window dimension and lays activations out with
    the SEQUENCE minor-most; the (U, 4 U) weight gradients with their
    Adam update fused behind, the forward matmuls with GELU, bias and the
    norm's statistics inside, and the input gradients then take up to
    twice the time of the same matmuls over (B x S, U) (PERF.md section 6,
    PR 42). A reshape inside `nn.Dense` alone is undone (XLA moves it
    through the element-wise ops until the pair meets), so the rank-2
    form spans the half: fc1, the activation, fc2, their biases and all
    their gradients see (tokens, channels). Rows keep their order and no
    value changes. The ATTENTION half stays (B, S, U): its four (U, U)
    weight gradients are the faster as they are (measured, same section),
    and its heads need (B, S) apart anyway.

    One thing a block can observe keeps the 3-D form at B > 1: a mesh
    train step tracing it (`parallel.mesh.step_mesh()`). The rule is on
    the mesh and not on what caused the reading: on four chips under
    ZeRO-1, where the Adam update is not fused behind the weight
    gradient, the 2-D form measured 1.75 % SLOWER; a mesh step without
    ZeRO keeps the update in the fusion and may be the faster 2-D, and
    is held to 3-D until someone measures it (ROADMAP, S9). Counts the
    form, once a traced block (`mxtpu_trunk_block_total{form}`)."""
    from ..parallel.mesh import step_mesh
    if x.shape[0] == 1:
        form = "batch_1"
    elif step_mesh() is not None:
        form = "batch_seq_3d"
    else:
        form = "tokens_2d"
    _TRUNK_BLOCKS.inc(form=form)
    return x.reshape((-1, x.shape[-1])) if form == "tokens_2d" else x


class MultiHeadAttention(HybridBlock):
    """``num_kv_heads`` < ``num_heads`` is grouped-query attention (each
    key-value head serves num_heads / num_kv_heads query heads);
    ``head_dim`` is the heads' size where it is not units / num_heads, so
    the q, k, v widths may differ from the input's. Both default to the
    multi-head layout.

    Ranks: ``forward``, ``heads_output`` and ``project`` take x as
    (B, S, U); ``forward`` returns (B, S, U), ``heads_output``
    (B, S, H * D), ``project`` and ``split_heads`` (B, H, S, D). The
    block around it flattens its MLP half to (B x S, U) (`mlp_tokens`)
    and NOT this half: the heads need B and S apart, and the four
    projections' weight gradients are (U, U), where XLA's batched form is
    the faster one on a v5e (PERF.md section 6, PR 42)."""

    def __init__(self, units, num_heads, dropout=0.0, attention="dense",
                 sp_axis="sp", tp_axis=None, causal=False, use_bias=True,
                 num_kv_heads=None, head_dim=None, **kwargs):
        super().__init__(**kwargs)
        if head_dim is None:
            assert units % num_heads == 0
            head_dim = units // num_heads
        num_kv_heads = num_kv_heads or num_heads
        assert num_heads % num_kv_heads == 0
        self._units = units
        self._num_heads = num_heads
        self._num_kv_heads = num_kv_heads
        self._dropout = dropout
        self._attention = attention
        self._sp_axis = sp_axis
        self._tp_axis = tp_axis
        self._causal = causal
        q_units, kv_units = num_heads * head_dim, num_kv_heads * head_dim
        with self.name_scope():
            self.query = nn.Dense(q_units, flatten=False, in_units=units, use_bias=use_bias)
            self.key = nn.Dense(kv_units, flatten=False, in_units=units, use_bias=use_bias)
            self.value = nn.Dense(kv_units, flatten=False, in_units=units, use_bias=use_bias)
            self.proj = nn.Dense(units, flatten=False, in_units=q_units, use_bias=use_bias)
        if tp_axis:
            # shard heads over tp: qkv col-parallel, out proj row-parallel
            for lyr in (self.query, self.key, self.value):
                lyr.weight.sharding = P(tp_axis, None)
                if use_bias:
                    lyr.bias.sharding = P(tp_axis)
            self.proj.weight.sharding = P(None, tp_axis)

    def forward(self, x, mask=None):
        return self.proj(self.heads_output(x, mask))

    def heads_output(self, x, mask=None):
        """x (B, S, U) -> the heads' outputs side by side (B, S, H * D),
        before the out-projection: what a subclass gates or norms."""
        B, S, _ = x.shape
        H = self._num_heads
        # (B, H, S, D) each; a subclass's QK-norm and RoPE live in project()
        q, k, v = self.project(x)
        D = q.shape[-1]

        causal = self._causal
        if self._attention == "ring":
            from ..parallel.ring_attention import ring_attention
            from ..parallel.mesh import current_mesh
            mesh = current_mesh()
            out = _apply(lambda qd, kd, vd: ring_attention(
                qd, kd, vd, mesh=mesh, axis=self._sp_axis, causal=causal),
                q, k, v)
        elif self._attention == "ulysses":
            from ..parallel.ulysses import ulysses_attention
            from ..parallel.mesh import current_mesh
            mesh = current_mesh()
            out = _apply(lambda qd, kd, vd: ulysses_attention(
                qd, kd, vd, mesh=mesh, axis=self._sp_axis, causal=causal),
                q, k, v)
        elif self._attention == "flash":
            from ..ops.attention import (flash_attention,
                                         flash_attention_on_mesh)
            from ..parallel.mesh import step_mesh
            step = step_mesh()
            if step is not None:
                # a mesh train step is tracing us: GSPMD cannot partition
                # the Mosaic kernels, so they run under shard_map on each
                # device's slice of the batch (and of the heads, under tp)
                mesh, data_axis = step
                out = _apply(lambda qd, kd, vd: flash_attention_on_mesh(
                    qd, kd, vd, mesh, batch_axis=data_axis,
                    head_axis=self._tp_axis, causal=causal), q, k, v)
            else:
                out = _apply(lambda qd, kd, vd: flash_attention(
                    qd, kd, vd, causal), q, k, v)
        else:
            scale = 1.0 / math.sqrt(D)
            scores = nd.batch_dot(q.reshape((B * H, S, D)),
                                  k.reshape((B * H, S, D)), transpose_b=True) * scale
            if causal:
                def causal_mask(sc):
                    import jax.numpy as jnp
                    qi = jnp.arange(S)[:, None]
                    ki = jnp.arange(S)[None, :]
                    return jnp.where(qi >= ki, sc, -1e9)
                scores = _apply(causal_mask, scores)
            if mask is not None:
                scores = scores.reshape((B, H, S, S)) + (1.0 - mask) * -1e9
                scores = scores.reshape((B * H, S, S))
            attn = nd.softmax(scores, axis=-1)
            if self._dropout:
                attn = nd.Dropout(attn, p=self._dropout)
            out = nd.batch_dot(attn, v.reshape((B * H, S, D))).reshape((B, H, S, D))
        return out.transpose((0, 2, 1, 3)).reshape((B, S, H * D))

    def split_heads(self, t, kv=False):
        """(B, S, heads * D) -> (B, H, S, D). ``kv``: t holds the
        key-value heads, which grouped-query attention has fewer of; they
        are repeated to the query heads here, so every attention path sees
        three tensors of one shape."""
        B, S, U = t.shape
        H = self._num_heads
        heads = self._num_kv_heads if kv else H
        t = t.reshape((B, S, heads, U // heads)).transpose((0, 2, 1, 3))
        return t if heads == H else nd.repeat(t, H // heads, axis=1)

    def project(self, x):
        """x (B, S, U) -> q, k, v, each (B, H, S, D). What a subclass
        changes between the projections and the scores (QK-norm, RoPE)
        goes here; the attention itself is shared."""
        return (self.split_heads(self.query(x)),
                self.split_heads(self.key(x), kv=True),
                self.split_heads(self.value(x), kv=True))


class TransformerEncoderLayer(HybridBlock):
    """Post-norm encoder block: ln(x + attn(x)); ln(x + ffn(x)).
    (B, S, U) in, (B, S, U) out, ``mask`` as `MultiHeadAttention` takes
    it. The attention half and both norms are (B, S, U); the MLP half
    (ffn1, GELU, ffn2) is (B x S, U) inside at B > 1 (`mlp_tokens`, which
    says why), reshaped back before the residual add."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.1,
                 attention="dense", tp_axis=None, sp_axis="sp", **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.attention_cell = MultiHeadAttention(units, num_heads, dropout,
                                                     attention, sp_axis, tp_axis)
            self.ln1 = nn.LayerNorm(in_channels=units)
            self.ffn1 = nn.Dense(hidden_size, flatten=False, in_units=units)
            self.ffn2 = nn.Dense(units, flatten=False, in_units=hidden_size)
            self.ln2 = nn.LayerNorm(in_channels=units)
            self.dropout_layer = nn.Dropout(dropout) if dropout else None
        if tp_axis:
            self.ffn1.weight.sharding = P(tp_axis, None)
            self.ffn1.bias.sharding = P(tp_axis)
            self.ffn2.weight.sharding = P(None, tp_axis)

    def forward(self, x, mask=None):
        h = self.attention_cell(x, mask)
        if self.dropout_layer:
            h = self.dropout_layer(h)
        x = self.ln1(x + h)
        with jax.named_scope("ffn"):
            h = mlp_tokens(x)
            h = self.ffn2(nd.LeakyReLU(self.ffn1(h), act_type="gelu"))
            h = h.reshape(x.shape)
        if self.dropout_layer:
            h = self.dropout_layer(h)
        return self.ln2(x + h)


class BERTEncoder(HybridBlock):
    """ref GluonNLP bert.BERTEncoder (structure parity)."""

    def __init__(self, units=768, hidden_size=3072, num_layers=12, num_heads=12,
                 max_length=512, dropout=0.1, attention="dense", tp_axis=None,
                 sp_axis="sp", **kwargs):
        super().__init__(**kwargs)
        self._max_length = max_length
        self._units = units
        with self.name_scope():
            self.position_weight = self.params.get("position_weight",
                                                   shape=(max_length, units),
                                                   init="normal")
            self.layers = []
            for i in range(num_layers):
                layer = TransformerEncoderLayer(units, hidden_size, num_heads,
                                                dropout, attention, tp_axis, sp_axis)
                self.register_child(layer, "layer%d" % i)
                self.layers.append(layer)

    def forward(self, x, mask=None):
        S = x.shape[1]
        pos = nd.slice_axis(self.position_weight.data(), 0, 0, S)
        x = x + pos.expand_dims(0)
        for layer in self.layers:
            x = layer(x, mask)
        return x


class BERTModel(HybridBlock):
    """BERT with embeddings + MLM head (pretraining objective)."""

    def __init__(self, vocab_size=30522, units=768, hidden_size=3072, num_layers=12,
                 num_heads=12, max_length=512, dropout=0.1, attention="dense",
                 tp_axis=None, sp_axis="sp", **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.word_embed = nn.Embedding(vocab_size, units)
            self.token_type_embed = nn.Embedding(2, units)
            self.embed_ln = nn.LayerNorm(in_channels=units)
            self.embed_dropout = nn.Dropout(dropout) if dropout else None
            self.encoder = BERTEncoder(units, hidden_size, num_layers, num_heads,
                                       max_length, dropout, attention, tp_axis,
                                       sp_axis)
            self.mlm_dense = nn.Dense(units, flatten=False, activation="relu",
                                      in_units=units)
            self.mlm_ln = nn.LayerNorm(in_channels=units)
            self.mlm_decoder = nn.Dense(vocab_size, flatten=False, in_units=units)

    def forward(self, token_ids, token_types=None, mask=None):
        mlm = self.mlm_decoder(self.features(token_ids, token_types, mask))
        return mlm

    def features(self, token_ids, token_types=None, mask=None):
        """Pre-decoder MLM activations (B, S, U) — pair with
        ``ChunkedMLMLoss`` so the (B*S, V) logits never materialize (the
        vocab-CE HBM lever, docs/PERF_BERT.md)."""
        x = self.word_embed(token_ids)
        if token_types is not None:
            x = x + self.token_type_embed(token_types)
        x = self.embed_ln(x)
        if self.embed_dropout:
            x = self.embed_dropout(x)
        h = self.encoder(x, mask)
        return self.mlm_ln(self.mlm_dense(h))


class ChunkedMLMLoss(ChunkedHeadLossBase):
    """BERT counterpart of models.gpt.ChunkedLMLoss — same chunked
    softmax-CE forward, but the head is the UNTIED, BIASED mlm_decoder.
    Use with ``FeaturesView(bert)`` (variadic: token_types/mask pass
    through to ``features``):

        bert = BERTModel(...)
        step = jit.TrainStep(FeaturesView(bert), ChunkedMLMLoss(bert), tr)
    """

    def _head_params(self):
        return (self._model.mlm_decoder.weight.data(),
                self._model.mlm_decoder.bias.data())
