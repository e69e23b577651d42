"""chip_smoke.py — the quickest proof that HEAD still starts on the chip.

One process, no arguments: drives the system's main paths once, through the
public package, at full width (the sizes in FULL below) — the Pallas
flash-attention kernels against a reference, the dropless expert dispatch
against its dense form, the held dispatch's sum of a window's rows into
their tokens alone in both its forms, BERT and a long-context GPT
through gluon.Trainer -> jit.TrainStep with those kernels, one tiny
Nemotron-H share (chunked Mamba-2 scan, held expert dispatch, grouped-query
attention, each layer recomputed), ResNet-50 training, ResNet-50 behind the HTTP
server, the generative engine, and (on a host with >= 4 chips) the dp and
dp x sp mesh steps — and checks what comes out. Weights are random from a
seed. It measures nothing: the compile and step
seconds it prints are for information.

It fails (non-zero exit, no result line) when JAX's default backend is not
a TPU, when the device_kind has no row in the peak table, when
MXTPU_FLASH_INTERPRET is set, or when any phase fails; nothing here catches
an exception to carry on. The last line of stdout on success is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

`--rehearse` runs the same phases at toy sizes on whatever backend JAX has
(kernels interpreted) so that a change can be debugged on the CPU before
chip time is spent; `--phases a,b` runs a subset. Neither prints the result
line: they are not a pass.
"""
import argparse
import functools
import gc
import json
import math
import os
import re
import statistics
import sys
import threading
import time
import urllib.request
from unittest import mock

PHASES = ("kernels", "moe", "combine", "bert", "gpt", "hybrid", "streams", "resnet",
          "serve", "generate", "multichip")

# the sizes a run on the chip drives (TOY below: what --rehearse drives)
FULL = {
    # (B, H, S, D), causal: S picks the block (512 as BERT, 1024 as GPT);
    # D = 64 below S = 2048 takes the short family (BERT-large's shape, and
    # its longest tile with the causal mask)
    "kernels": [((2, 4, 512, 128), False), ((1, 4, 2048, 128), True),
                ((16, 16, 512, 64), False), ((8, 16, 768, 64), True)],
    # OLMoE's expert layer: 64 experts of 2048 -> 1024, 8 per token
    "moe": dict(T=4096, U=2048, I=1024, E=64, K=8),
    # the held combine alone, a window's rows into their tokens, at the five
    # held cells' (W rows, T tokens, D wide, at most `most` rows a token)
    "combine": dict(shapes={"ling": (2048, 8192, 2560, 8),
                            "xing": (8192, 8192, 3584, 4),
                            "keye": (32768, 16384, 2048, 8),
                            "solar": (4096, 8192, 4096, 8),
                            "nemotron": (6144, 8192, 1024, 8)},
                    live=(0.25, 0.5, 1.0), calls=20),
    "bert": dict(B=64, S=512, V=32768, U=1024, L=12, H=8),
    "gpt": dict(S=8192, V=32768, U=1024, L=4, H=8),
    # one Mamba-2, one expert and one attention layer of a Nemotron-H share:
    # 8 of 64 experts held, 4 a token, one of two mixer shards
    "hybrid": dict(S=2048, V=8192, U=1024, P="ME*", MH=32, MD=64, G=2, N=128,
                   Q=128, QH=4, KV=1, L=512, I=1024, SH=2048, E=64, HELD=8,
                   K=4, SHARDS=2, SCAN_S=8192,
                   # the Mamba-1 scan alone at the SambaY cell's shape
                   SEL_S=16384, SEL_C=5120, SEL_N=16, SEL_R=160,
                   # a differential layer's call there: (1, H, S, D | 2 D)
                   WIDE_H=20, WIDE_S=16384, WIDE_D=64, WIDE_WINDOW=512,
                   # the gated delta rule alone at the Solar cell's shape
                   DR_S=8192, DR_H=8, DR_D=128, DR_Q=64,
                   # sparse attention alone at the Keye cell's shape
                   SA_S=16384, SA_H=32, SA_G=4, SA_D=128, SA_J=16, SA_DI=64,
                   SA_K=2048,
                   # EVA attention alone at the EvaByte cell's shape
                   EV_S=16384, EV_H=32, EV_D=128, EV_W=2048, EV_C=16),
    # one hyper-connected sublayer and one latent-attention block with a
    # low-rank query at the Xing cell's widths (4 streams of 3584; 32 heads,
    # 768 | 512 | 128 + 64 | 128, YaRN x 64 over 4096)
    "streams": dict(S=8192, U=3584, N=4, H=32, QL=768, KL=512, DN=128, DR=64,
                    DV=128, YARN=64, YARN_FROM=4096),
    "resnet": dict(B=256, HW=224),
    "serve": dict(HW=224, requests=16, clients=4, max_batch=8),
    "ring": dict(B=4, S=2048, V=32768, U=1024, L=2, H=8),
}
# --rehearse: same code paths, toy shapes (D stays 128 in the models so the
# streamed kernels are legal and run interpreted)
TOY = {
    "kernels": [((1, 2, 128, 128), False), ((1, 2, 256, 128), True),
                ((1, 2, 256, 64), False)],
    "moe": dict(T=64, U=32, I=16, E=8, K=2),
    "combine": dict(shapes={"toy": (64, 48, 16, 4), "wide": (96, 32, 20, 3)},
                    live=(0.25, 0.5, 1.0), calls=2),
    "bert": dict(B=4, S=128, V=512, U=256, L=1, H=2),
    "gpt": dict(S=256, V=512, U=256, L=1, H=2),
    "hybrid": dict(S=256, V=512, U=128, P="ME*", MH=16, MD=8, G=2, N=16,
                   Q=16, QH=2, KV=1, L=64, I=48, SH=96, E=16, HELD=4, K=4,
                   # (1024 channels: the narrowest the scan's kernels take)
                   SHARDS=2, SCAN_S=256, SEL_S=160, SEL_C=1024, SEL_N=16,
                   SEL_R=4,
                   # (narrow heads ride the streamed kernels from 2048 on)
                   WIDE_H=1, WIDE_S=2048, WIDE_D=64, WIDE_WINDOW=512,
                   # (heads of 128: the narrowest the rule's kernels take)
                   DR_S=160, DR_H=2, DR_D=128, DR_Q=16,
                   SA_S=256, SA_H=4, SA_G=2, SA_D=128, SA_J=2, SA_DI=64,
                   SA_K=48,
                   # (windows of 128 rows of 128: the streamed kernels' least)
                   EV_S=512, EV_H=2, EV_D=128, EV_W=128, EV_C=16),
    "streams": dict(S=256, U=128, N=4, H=2, QL=48, KL=32, DN=16, DR=8,
                    DV=16, YARN=64, YARN_FROM=64),
    "resnet": dict(B=16, HW=64),
    "serve": dict(HW=32, requests=16, clients=4, max_batch=8),
    "ring": dict(B=4, S=256, V=512, U=256, L=1, H=2),
}


def log(msg):
    print(msg, flush=True)


# ------------------------------------------------------------------- kernels
def phase_kernels(cases, on_chip, shared):
    """flash_attention's output and its three gradients against a float32
    jax.numpy reference, on the device, at the block sizes the BERT and
    GPT phases use (the 1024-block causal backward is the streamed kernel
    with the largest VMEM footprint) and at the short family's shapes."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops.attention import flash_attention

    def reference(q, k, v, causal):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") \
            / math.sqrt(q.shape[-1])
        if causal:
            n = q.shape[2]
            s = jnp.where(jnp.arange(n)[:, None] >= jnp.arange(n)[None, :],
                          s, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v,
                          precision="highest")

    compile_s, steady, worst = 0.0, [], 0.0
    for shape, causal in cases:
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        q, k, v, w = (jax.random.normal(kk, shape, jnp.float32)
                      .astype(jnp.bfloat16) for kk in keys)

        def both(attn):
            def f(q, k, v):
                out = attn(q, k, v, causal)
                return (out.astype(jnp.float32)
                        * w.astype(jnp.float32)).sum(), out
            return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                              has_aux=True))

        flash = both(flash_attention)
        if on_chip and "tpu_custom_call" not in \
                flash.lower(q, k, v).as_text():
            raise RuntimeError("flash_attention%s lowered without the "
                               "Pallas kernels" % (shape,))
        t0 = time.perf_counter()
        (_, out), grads = jax.block_until_ready(flash(q, k, v))
        compile_s += time.perf_counter() - t0
        t0 = time.perf_counter()
        jax.block_until_ready(flash(q, k, v))
        steady.append(time.perf_counter() - t0)
        (_, ref_out), ref_grads = both(reference)(q, k, v)
        for name, got, want in zip(("out", "dq", "dk", "dv"),
                                   (out,) + grads, (ref_out,) + ref_grads):
            got, want = (jnp.asarray(x, jnp.float32) for x in (got, want))
            err = float(jnp.abs(got - want).max() / jnp.abs(want).max())
            worst = max(worst, err)
            # bf16 in and out: one rounding is 2^-8 of the value
            if not err <= 2e-2:
                raise RuntimeError("flash %s%s causal=%s: max error %.3g of "
                                   "the reference's max" % (
                                       name, shape, causal, err))
    return compile_s, statistics.median(steady), \
        "out/dq/dk/dv vs float32 reference at %s, worst error %.2g of max" \
        % ([c[0] for c in cases], worst)


# ----------------------------------------------------------------------- moe
def phase_moe(cfg, on_chip, shared):
    """parallel.moe.dropless_moe (sort, gather, the grouped matmuls, un-sort
    and combine) and its gradients against the dense form — every expert on
    every token in float32, a (T, E) matrix of weights picking what counts
    — on the device, at OLMoE's expert layer. A new libtpu that lowers
    jax.lax.ragged_dot differently fails here first."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.parallel.moe import dropless_moe
    T, U, I, E, K = (cfg[k] for k in "TUIEK")
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    x, probe = (jax.random.normal(k, (T, U), jnp.float32)
                .astype(jnp.bfloat16) for k in keys[:2])
    gate, up = (jax.random.normal(k, (E, U, I), jnp.float32)
                .astype(jnp.bfloat16) / math.sqrt(U) for k in keys[2:4])
    down = (jax.random.normal(keys[4], (E, I, U), jnp.float32)
            / math.sqrt(I)).astype(jnp.bfloat16)
    gates = jax.nn.softmax(
        1.4 * jax.random.normal(keys[5], (T, E), jnp.float32), -1)
    top_vals, top_idx = jax.lax.top_k(gates, K)

    def dense(x, top_vals, gate, up, down):
        x, gate, up, down = (a.astype(jnp.float32)
                             for a in (x, gate, up, down))
        weight = jnp.zeros((T, E), jnp.float32) \
            .at[jnp.arange(T)[:, None], top_idx].set(top_vals)

        def one(out, expert):
            g, u, d, w_e = expert
            h = jax.nn.silu(jnp.dot(x, g, precision="highest")) \
                * jnp.dot(x, u, precision="highest")
            return out + w_e[:, None] * jnp.dot(h, d, precision="highest"), \
                None

        return jax.lax.scan(jax.checkpoint(one), jnp.zeros((T, U)),
                            (gate, up, down, weight.T))[0]

    def dropless(x, top_vals, gate, up, down):
        return dropless_moe(x, top_vals, top_idx, up, down, jax.nn.silu,
                            gate)[0]

    def both(fn):
        def f(*args):
            out = fn(*args)
            return (out.astype(jnp.float32)
                    * probe.astype(jnp.float32)).sum(), out
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4),
                                          has_aux=True))

    args = (x, top_vals, gate, up, down)
    system = both(dropless)
    if on_chip and "ragged" not in system.lower(*args).compile().as_text():
        raise RuntimeError("dropless_moe compiled without a grouped matmul")
    t0 = time.perf_counter()
    (_, out), grads = jax.block_until_ready(system(*args))
    compile_s = time.perf_counter() - t0
    steady = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(system(*args))
        steady.append(time.perf_counter() - t0)
    (_, ref_out), ref_grads = both(dense)(*args)
    worst = 0.0
    for name, got, want in zip(("out", "dx", "dp", "dgate", "dup", "ddown"),
                               (out,) + grads, (ref_out,) + ref_grads):
        got, want = (jnp.asarray(a, jnp.float32) for a in (got, want))
        err = float(jnp.abs(got - want).max() / jnp.abs(want).max())
        worst = max(worst, err)
        # bf16 rows and hidden layer, float32 sums: a few roundings of 2^-8
        if not err <= 3e-2:
            raise RuntimeError("dropless_moe %s: max error %.3g of the "
                               "dense form's max" % (name, err))
    counts = jnp.bincount(top_idx.reshape(-1), length=E)
    flops = 6 * K * 3 * U * I * T
    return compile_s, statistics.median(steady), \
        "out and 5 gradients vs the dense float32 form at %d tokens, %d " \
        "experts top-%d of %d -> %d (rows per expert %d..%d), worst error " \
        "%.2g of max%s" % (
            T, E, K, U, I, int(counts.min()), int(counts.max()), worst,
            "; %.1f TFLOP/s of required expert FLOPs over the whole call"
            % (flops / statistics.median(steady) / 1e12) if on_chip else "")


def phase_combine(cfg, on_chip, shared):
    """The held dispatch's sum of a window's rows into their tokens
    (parallel.moe._rows_to_tokens) ALONE, at the held cells' shapes and at
    a quarter, a half and all of the window live: both of its forms
    whatever the rows' width would choose (XLA's float32 row scatter-add;
    a sort of the window's token ids, two row gathers and shifted adds:
    the same sum, the order of a token's at most `most` additions is all
    that may differ), beside two forms that were not built: the runs'
    first rows scattered by unique indices, and for windows up to 4096
    rows a one-hot matmul. Each form runs `calls` times in one loop of one
    program, on token ids that move with the iteration so that nothing
    leaves the loop, and a line gives each form's milliseconds a call
    (docs/PERF_NOTES.md, PR 54 has the chip's; `_sorts_the_window` quotes
    them). bfloat16 rows are the forward's, float32 the backward's."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from incubator_mxnet_tpu.parallel import moe
    calls = cfg["calls"]
    f32 = jnp.float32

    def forms(n_tokens, most):
        def systems(sorts):
            def form(total, rows, token, live):
                with mock.patch.object(moe, "_sorts_the_window",
                                       lambda width: sorts):
                    return moe._rows_to_tokens(total, rows, token, live,
                                               most)
            return form

        def first_rows(total, rows, token, live):
            # the sorted form's runs, and every row but a run's first sent
            # past the end, each to a place of its own, and dropped
            window = rows.shape[0]
            key, at = jax.lax.sort_key_val(
                jnp.where(live, token, n_tokens),
                jnp.arange(window, dtype=jnp.int32))
            key = jnp.pad(key, (0, most - 1), constant_values=-1)
            rows = rows[jnp.pad(at, (0, most - 1))]
            run = rows[:window].astype(f32)
            for j in range(1, most):
                run = run + jnp.where(
                    (key[j:j + window] == key[:window])[:, None],
                    rows[j:j + window].astype(f32), 0)
            first = key[:window] != jnp.pad(key[:window - 1], (1, 0),
                                            constant_values=-1)
            return total.at[jnp.where(
                first, key[:window],
                n_tokens + jnp.arange(window, dtype=jnp.int32))].add(
                    run, unique_indices=True, mode="drop")

        def one_hot(total, rows, token, live):
            hot = jnp.where(live, token, n_tokens)[None, :] \
                == jnp.arange(n_tokens, dtype=jnp.int32)[:, None]
            return total + jnp.dot(hot.astype(rows.dtype), rows,
                                   preferred_element_type=f32)

        return {"scatter-add": systems(False), "sorted": systems(True),
                "first rows scattered": first_rows, "one-hot": one_hot}

    def looped(form, n_tokens, width):
        def many(rows, token, live):
            return jax.lax.fori_loop(
                0, calls, lambda i, total: form(
                    total, rows, (token + i) % n_tokens, live),
                jnp.zeros((n_tokens, width), f32))
        return jax.jit(many)

    rng = np.random.default_rng(54)
    compile_s, steady, lines, worst = 0.0, [], [], 0.0
    for cell, (window, n_tokens, width, most) in cfg["shapes"].items():
        chosen = "sorted" if moe._sorts_the_window(width) else "scatter-add"
        for share in cfg["live"]:
            # `most` experts' rows, each expert's tokens distinct and in
            # order, as a window of the sorted assignments holds them
            each = int(window * share) // most
            token = np.arange(window, dtype=np.int32) % n_tokens
            token[:each * most] = np.concatenate([np.sort(rng.choice(
                n_tokens, each, replace=False)) for _ in range(most)])
            live = np.arange(window) < each * most
            for dtype in (jnp.bfloat16, f32) if share == 0.5 \
                    else (jnp.bfloat16,):
                rows = jnp.asarray(
                    rng.standard_normal((window, width)) * live[:, None],
                    dtype)
                args = (rows, jnp.asarray(token), jnp.asarray(live))
                ms, want = {}, None
                for name, form in forms(n_tokens, most).items():
                    if name == "one-hot" and (
                            window > 4096 or dtype != jnp.bfloat16):
                        continue    # 2 W T D FLOPs; exact for bf16 rows
                    fn = looped(form, n_tokens, width)
                    t0 = time.perf_counter()
                    got = jax.block_until_ready(fn(*args))
                    compile_s += time.perf_counter() - t0
                    if want is None:
                        want = got
                    err = float(jnp.abs(got - want).max()
                                / jnp.abs(want).max())
                    # the same float32 sum in another order
                    if err <= 1e-5:
                        ms[name] = "%.3f" % (median_ms(fn, args) / calls)
                        worst = max(worst, err)
                    elif name in ("scatter-add", "sorted"):
                        raise RuntimeError(
                            "combine %s, %s: %.3g of the scatter-add's max"
                            % (cell, name, err))
                    else:       # a form that was not built, and why not
                        ms[name] = "WRONG by %.2g of the max" % err
                steady.append(float(ms[chosen]) / 1e3)
                lines.append("%s %s %d %% live: %s" % (
                    cell, jnp.dtype(dtype).name, 100 * share, ", ".join(
                        "%s %s" % item for item in ms.items())))
                log("combine: (W %d, T %d, D %d, most %d; the width "
                    "chooses %s) %s ms a call" % (
                        window, n_tokens, width, most, chosen, lines[-1]))
    return compile_s, statistics.median(steady), \
        "%d shapes x loads of a window's rows into their tokens, every " \
        "form within %.2g of the scatter-add's max%s" % (
            len(lines), worst,
            "; ms a call in the lines above" if on_chip else "")


# ------------------------------------------------------------------ training
def run_steps(step, inputs, warm, steps):
    """warm + steps calls on one fixed batch, every loss read back (the
    readback is the sync). -> (losses, compile seconds = first call,
    median seconds of the steady calls)."""
    losses, times = [], []
    for _ in range(warm + steps):
        t0 = time.perf_counter()
        loss = float(step(*inputs).mean().asscalar())
        times.append(time.perf_counter() - t0)
        losses.append(loss)
    if not all(math.isfinite(v) for v in losses):
        raise RuntimeError("non-finite loss: %r" % (losses,))
    return losses, times[0], statistics.median(times[warm:])


def mosaic_kernels_in_train_program(expect_at_least):
    """Count tpu_custom_call (Mosaic) instructions in the ONE compiled
    train program the AOT cache holds — the proof that attention did not
    take the XLA composite (or the interpreter)."""
    from incubator_mxnet_tpu import aot
    entries = [aot.CACHE.peek(k) for k in aot.CACHE.keys()
               if k.kind == "train"]
    if len(entries) != 1:
        raise RuntimeError("expected one live train program, found %d"
                           % len(entries))
    n = entries[0].fn.as_text().count("tpu_custom_call")
    if n < expect_at_least:
        raise RuntimeError(
            "compiled train step holds %d tpu_custom_call(s), expected >= %d"
            " (2 per attention layer: forward, backward) — attention did not "
            "run as the Pallas kernels" % (n, expect_at_least))
    return n


def build_bert(cfg, attention="flash"):
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import models
    mx.random.seed(0)
    net = models.BERTModel(vocab_size=cfg["V"], units=cfg["U"],
                           hidden_size=4 * cfg["U"], num_layers=cfg["L"],
                           num_heads=cfg["H"], max_length=cfg["S"],
                           dropout=0.0, attention=attention)
    net.initialize(mx.init.Xavier())
    net.cast("bfloat16")
    return net


def adam_trainer(net):
    from incubator_mxnet_tpu import gluon
    return gluon.Trainer(net.collect_params(), "adam",
                         {"learning_rate": 1e-4, "multi_precision": True})


def fixed_tokens(cfg, batch):
    import numpy as onp
    from incubator_mxnet_tpu import nd
    rng = onp.random.RandomState(0)
    return nd.array(rng.randint(0, cfg["V"], (batch, cfg["S"]))
                    .astype("int32"))


def phase_bert(cfg, on_chip, shared):
    from incubator_mxnet_tpu import gluon, jit
    net = build_bert(cfg)
    tokens = fixed_tokens(cfg, cfg["B"])
    trainer = adam_trainer(net)
    step = jit.TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), trainer)
    losses, compile_s, steady_s = run_steps(step, (tokens, tokens), 2, 3)
    if not losses[-1] < losses[0]:
        raise RuntimeError("loss did not fall: %r" % (losses,))
    kernels = (mosaic_kernels_in_train_program(2 * cfg["L"]) if on_chip
               else "skipped (interpreted)")
    shared["bert_first_loss"] = losses[0]
    del step, trainer, net, tokens
    gc.collect()
    return compile_s, steady_s, "loss %.4f -> %.4f, mosaic kernels: %s" % (
        losses[0], losses[-1], kernels)


def phase_gpt(cfg, on_chip, shared):
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import jit, models
    mx.random.seed(0)
    net = models.GPTModel(vocab_size=cfg["V"], units=cfg["U"],
                          num_layers=cfg["L"], num_heads=cfg["H"],
                          max_length=cfg["S"], attention="flash")
    net.initialize(mx.init.Xavier())
    net.cast("bfloat16")
    tokens = fixed_tokens(cfg, 1)
    view = models.FeaturesView(net)
    trainer = adam_trainer(view)
    step = jit.TrainStep(view, models.ChunkedLMLoss(net), trainer)
    losses, compile_s, steady_s = run_steps(step, (tokens, tokens), 2, 2)
    if not losses[-1] < losses[0]:
        raise RuntimeError("loss did not fall: %r" % (losses,))
    kernels = (mosaic_kernels_in_train_program(2 * cfg["L"]) if on_chip
               else "skipped (interpreted)")
    del step, trainer, view, net, tokens
    gc.collect()
    return compile_s, steady_s, "S=%d causal, loss %.4f -> %.4f, mosaic " \
        "kernels: %s" % (cfg["S"], losses[0], losses[-1], kernels)


#: the chunked scan alone, float32 at "highest", may be this far (of its
#: largest output) from the recurrence run a position at a time; a state
#: rounded to bfloat16 once a chunk must be further. Between the two
#: readings of one v5e (PERF.md section 6, PR 31)
SCAN_ALONE_LIMIT = 1e-4
#: the same for the gated delta rule alone (`delta_rule_alone`)
DELTA_RULE_ALONE_LIMIT = 1e-4


def scan_alone(cfg):
    """`ops.ssd.ssd_chunked` alone at one shard's shape (half the heads, one
    group, SCAN_S positions), float32 inputs at "highest" precision,
    against the recurrence a position at a time: the one comparison in
    which the state's float32 shows (against bfloat16 activations a
    bfloat16 state is inside the rounding). -> (its distance, the distance
    of the recurrence with its state rounded to bfloat16 once a chunk)."""
    import jax
    import jax.numpy as jnp
    import numpy as onp
    from incubator_mxnet_tpu.ops.ssd import ssd_chunked
    s, h, p, n, q = cfg["SCAN_S"], cfg["MH"] // 2, cfg["MD"], cfg["N"], \
        cfg["Q"]
    rng = onp.random.default_rng(0)
    x, bm, cm = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
                 for shape in ((1, s, h, p), (1, s, 1, n), (1, s, 1, n)))
    dt = jnp.asarray(onp.exp(rng.uniform(onp.log(1e-3), onp.log(0.1),
                                         (1, s, h))), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, (h,)), jnp.float32)

    def recurrence(round_state):
        def step(state, at):
            x_t, dt_t, b_t, c_t, rounds = at
            state = jnp.exp(dt_t * a)[..., None, None] * state \
                + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
            if round_state:
                state = jnp.where(
                    rounds, jax.lax.reduce_precision(state, 8, 7), state)
            return state, (state * c_t[:, :, None, :]).sum(-1)
        by_time = tuple(t.swapaxes(0, 1) for t in (x, dt, bm, cm)) \
            + (jnp.arange(s) % q == q - 1,)
        return jax.lax.scan(step, jnp.zeros((1, h, p, n), jnp.float32),
                            by_time)[1].swapaxes(0, 1)

    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda: recurrence(False))()
        rounded = jax.jit(lambda: recurrence(True))()
        got = jax.jit(lambda: ssd_chunked(
            x, dt, a, bm, cm, jnp.zeros((h,), jnp.float32), q))()
    top = float(jnp.abs(want).max())
    return float(jnp.abs(got - want).max()) / top, \
        float(jnp.abs(rounded - want).max()) / top


def delta_rule_alone(cfg, on_chip):
    """`ops.delta_rule.gated_delta_rule` alone at the Solar cell's shape
    (DR_S positions, DR_H heads of DR_D x DR_D, chunks of DR_Q), float32
    inputs (the op runs every matmul at full precision itself), through the
    one entry the cell runs and on the schedule it runs there (the Pallas
    kernel pair wherever kernels run: compiled for the chip, one Mosaic
    call forward and two for a gradient, BEFORE the first call; interpreted
    in a rehearsal), against the recurrence a position at a time; as
    `scan_alone`, the one comparison in which the state's float32 (and a
    matmul's: Mosaic's default rounds float32 operands to bfloat16) shows.
    q and k have unit length (q scaled), the decay is A in [1, 16] a head
    times a step about log-normal around 0.011 a CHANNEL, b = 2 sigmoid(.)
    reaches both its ends.
    -> {"path": the schedule the calls took, "sound": the output's
    distance, "rounded": the recurrence's with its state rounded to
    bfloat16 once a chunk, "gradients": the worst of the five (q, k, v, g,
    b), each over its own largest entry, "forward_ms", "both_ms": the op
    alone with a bfloat16 v, "xla_ms": (forward, forward + backward) of the
    XLA form at the same operands (None off the chip: a CPU time is no
    device number)}."""
    import jax
    import jax.numpy as jnp
    import numpy as onp
    from incubator_mxnet_tpu.ops import delta_rule as op
    s, h, d, q_ = cfg["DR_S"], cfg["DR_H"], cfg["DR_D"], cfg["DR_Q"]
    rng = onp.random.default_rng(0)

    def unit(x):
        return x / onp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.standard_normal((1, s, h, d))) * d ** -0.5
    k = unit(rng.standard_normal((1, s, h, d)))
    v = rng.standard_normal((1, s, h, d))
    g = -rng.uniform(1, 16, (h, 1)) * onp.log1p(onp.exp(
        rng.standard_normal((1, s, h, d)) - 4.5))
    beta = 2 / (1 + onp.exp(-2 * rng.standard_normal((1, s, h))))
    args = tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))

    def system(*a):
        return op.gated_delta_rule(*a, chunk=q_)

    def both(fn):
        return jax.jit(jax.grad(
            lambda *t: jnp.sum(fn(*t).astype(jnp.float32)), (0, 1, 2, 3, 4)))

    path = "pallas" if op._kernels_run_here() and op._kernel_takes(d, d, q_) \
        else "xla"
    calls = op._CALLS.value(path=path)
    operands = args[:2] + (args[2].astype(jnp.bfloat16),) + args[3:]
    if on_chip:
        if path != "pallas":
            raise RuntimeError("the delta rule's kernels refuse the cell's "
                               "shape (%d x %d, chunks of %d)" % (d, d, q_))
        for fn, kernels in ((jax.jit(system), ("delta_rule_fwd",)),
                            (both(system), ("delta_rule_fwd",
                                            "delta_rule_bwd"))):
            lowered = fn.lower(*operands)
            text = lowered.as_text()
            if text.count("tpu_custom_call") != len(kernels) \
                    or not all(k in text for k in kernels):
                raise RuntimeError("the delta rule is not the Mosaic calls "
                                   "%s" % (kernels,))
            lowered.compile()

    def recurrence(q, k, v, g, beta, round_state=False):
        def step(state, at):
            q_t, k_t, v_t, g_t, b_t, rounds = at
            state = jnp.exp(g_t)[..., None] * state
            u = b_t[..., None] * (v_t - (state * k_t[..., None]).sum(-2))
            state = state + k_t[..., None] * u[..., None, :]
            if round_state:
                state = jnp.where(
                    rounds, jax.lax.reduce_precision(state, 8, 7), state)
            return state, (state * q_t[..., None]).sum(-2)
        by_time = tuple(t.swapaxes(0, 1) for t in (q, k, v, g, beta)) \
            + (jnp.arange(q.shape[1]) % q_ == q_ - 1,)
        return jax.lax.scan(step, jnp.zeros((1, h, d, d), jnp.float32),
                            by_time)[1].swapaxes(0, 1)

    def distance(got, want):
        return float(jnp.abs(got - want).max() / jnp.abs(want).max())

    def gradients(fn, *a):
        """The five gradients of sum(o cot), the recurrence's in
        checkpointed segments (autodiff keeps a state a position)."""
        return jax.jit(jax.grad(lambda cot, *t: jnp.sum(fn(*t) * cot),
                                (1, 2, 3, 4, 5)))(*a)

    with jax.default_matmul_precision("highest"):
        want = jax.jit(recurrence)(*args)
        out = {"sound": distance(jax.jit(system)(*args), want),
               "rounded": distance(jax.jit(functools.partial(
                   recurrence, round_state=True))(*args), want)}
        del want
        # the gradients on the first eighth of the positions: autodiff of
        # the recurrence keeps every position's state (64 KB a head)
        short = tuple(t[:, :max(s // 8, 2 * q_)] for t in args)
        cot = jnp.asarray(rng.standard_normal(short[2].shape), jnp.float32)
        out["gradients"] = max(
            distance(a, b) for a, b in zip(gradients(system, cot, *short),
                                           gradients(recurrence, cot, *short)))
    out["path"] = path
    out["forward_ms"] = out["both_ms"] = out["xla_ms"] = None
    if on_chip:
        out["forward_ms"] = median_ms(jax.jit(system), operands)
        out["both_ms"] = median_ms(both(system), operands)
        # the other schedule at the same operands: steered from here, the
        # op has no argument that chooses
        run_here, op._kernels_run_here = op._kernels_run_here, lambda: False
        try:
            out["xla_ms"] = (
                median_ms(jax.jit(lambda *a: system(*a)), operands),
                median_ms(both(lambda *a: system(*a)), operands))
        finally:
            op._kernels_run_here = run_here
    if op._CALLS.value(path=path) == calls:
        raise RuntimeError("mxtpu_delta_rule_total{path=%r} did not count "
                           "the calls" % path)
    return out


#: sparse attention alone, float32 at "highest": its output may be this far
#: (of the largest entry) from a masked softmax over `lax.top_k`'s keys, its
#: six gradients `gradients` (the indexer's are differences of two
#: probabilities, summed in another order); the same reference choosing on
#: index scores rounded to bfloat16 must be further than `outputs`.
#: Between the readings of one v5e (docs/PERF_KEYE_VL2.md section 2)
SPARSE_ALONE_LIMITS = {"outputs": 1e-4, "gradients": 2e-3}


def sparse_attention_alone(cfg, on_chip):
    """`ops.sparse_attention.sparse_attention` alone at the Keye cell's shape
    (SA_S positions, SA_H query heads on SA_G key-value heads of SA_D, SA_J
    index heads of SA_DI, SA_K keys a query), float32 operands, through the
    one entry the cell runs and on the schedule it runs there (the five
    Pallas kernels wherever kernels run: in the lowered forward and
    gradient BEFORE the first call; interpreted in a rehearsal), against a masked softmax over
    the keys `lax.top_k` takes a row, in blocks of 128 queries: two
    algorithms of selection, one set. The gradients on the first quarter of
    the positions (still more than SA_K).
    -> {"path", "sound": the output's distance, "kl": the KL's relative
    distance, "rounded": the output's distance when the reference chooses
    on scores rounded to bfloat16, "gradients": the worst of the six,
    "forward_ms", "both_ms": the op alone in bfloat16 (None off the
    chip)}."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops import sparse_attention as op
    s, h, g, d = cfg["SA_S"], cfg["SA_H"], cfg["SA_G"], cfg["SA_D"]
    j, di, topk = cfg["SA_J"], cfg["SA_DI"], cfg["SA_K"]
    keys = jax.random.split(jax.random.PRNGKey(0), 7)
    args = tuple(
        jax.random.normal(key, (1, s) + tail, jnp.float32) * scale
        for key, tail, scale in zip(keys, (
            (h, d), (g, d), (g, d), (j, di), (di,), (j,)),
            (1, 1, 1, 1, 1, (j * di) ** -0.5)))

    def system(*a):
        return op.sparse_attention(*a, topk)

    def plain(q, k, v, qi, ki, w, round_scores=False):
        n, block = q.shape[1], 128

        @jax.checkpoint
        def one(at):
            q_b, qi_b, w_b, start = at
            scores = jnp.einsum("qj,qjs->qs", w_b, jax.nn.relu(
                jnp.einsum("qjd,sd->qjs", qi_b, ki[0])))
            if round_scores:
                scores = jax.lax.reduce_precision(scores, 8, 7)
            seen = jnp.arange(n)[None] <= (start + jnp.arange(block))[:, None]
            scores = jnp.where(seen, jnp.where(scores == 0, 0.0, scores),
                               -jnp.inf)
            vals, idx = jax.lax.top_k(jax.lax.stop_gradient(scores),
                                      min(topk, n))
            kth = vals[:, -1:]
            last = jnp.max(jnp.where(vals == kth, idx, -1), -1, keepdims=True)
            mask = seen & ((scores > kth) | (
                (scores == kth) & (jnp.arange(n)[None] <= last)))
            att = jnp.einsum("qgrd,sgd->grqs", q_b.reshape(
                block, g, h // g, d), k[0]) / math.sqrt(d)
            a = jax.nn.softmax(jnp.where(mask, att, -jnp.inf), -1)
            o = jnp.einsum("grqs,sgd->qgrd", a, v[0]).reshape(block, h, d)
            p = jax.lax.stop_gradient(a.mean((0, 1)))
            log_pi = jax.nn.log_softmax(jnp.where(mask, scores, -jnp.inf), -1)
            kl = jnp.where(p > 0, p * (jnp.log(jnp.where(p > 0, p, 1.0))
                                       - jnp.where(mask, log_pi, 0.0)), 0.0)
            return o, kl.sum()

        o, kl = jax.lax.map(one, (
            q[0].reshape(n // block, block, h, d),
            qi[0].reshape(n // block, block, j, di),
            w[0].reshape(n // block, block, j), jnp.arange(0, n, block)))
        return o.reshape(1, n, h, d), kl.sum(keepdims=True) / n

    def both(fn):
        return jax.jit(jax.grad(lambda cot, *t: jnp.sum(
            fn(*t)[0].astype(jnp.float32) * cot) + jnp.sum(fn(*t)[1]),
            (1, 2, 3, 4, 5, 6)))

    path = op._strips_of()[0]
    calls = op._ATTENTIONS.value(path=path)
    low = tuple(x.astype(jnp.bfloat16) for x in args[:5]) + args[5:]
    cot = jax.random.normal(keys[6], args[0].shape, jnp.float32)
    if on_chip:
        if path != "pallas_masked_strips":
            raise RuntimeError("sparse attention took %r on the chip" % path)
        # (a gradient alone never needs the KL's VALUE: JAX drops the
        # forward's head-mean from it, and the backward kernel has its own)
        for fn, operands, kernels in (
                (jax.jit(system), low, ("sparse_index_fwd", "sparse_flash_fwd",
                                        "sparse_head_mean")),
                (both(system), (cot.astype(jnp.bfloat16),) + low,
                 ("sparse_index_fwd", "sparse_index_bwd", "sparse_flash_fwd",
                  "sparse_flash_bwd"))):
            text = fn.lower(*operands).as_text()
            if not all(k in text for k in kernels):
                raise RuntimeError("sparse attention is not the Mosaic "
                                   "calls %s" % (kernels,))

    def distance(got, want):
        return float(jnp.abs(got - want).max() / jnp.abs(want).max())

    with jax.default_matmul_precision("highest"):
        o_want, kl_want = jax.jit(plain)(*args)
        o_got, kl_got = jax.jit(system)(*args)
        out = {"sound": distance(o_got, o_want),
               "kl": distance(kl_got, kl_want),
               "rounded": distance(jax.jit(functools.partial(
                   plain, round_scores=True))(*args)[0], o_want)}
        del o_want, o_got
        some = min(s, -(-max(s // 4, 2 * topk) // 128) * 128)
        short = tuple(t[:, :some] for t in args)
        cot_s = cot[:, :some]
        out["gradients"] = max(
            distance(a, b) for a, b in zip(both(system)(cot_s, *short),
                                           both(plain)(cot_s, *short)))
    out["path"] = path
    out["forward_ms"] = out["both_ms"] = None
    if on_chip:
        out["forward_ms"] = median_ms(jax.jit(system), low)
        out["both_ms"] = median_ms(both(system),
                                   (cot.astype(jnp.bfloat16),) + low)
    if op._ATTENTIONS.value(path=path) == calls:
        raise RuntimeError("mxtpu_sparse_attention_total{path=%r} did not "
                           "count the calls" % path)
    return out

EVA_ALONE_LIMITS = {"outputs": 1e-4, "gradients": 2e-3}


def eva_attention_alone(cfg, on_chip):
    """`ops.eva_attention.eva_attention` alone at the EvaByte cell's shape
    (EV_S positions, EV_H heads of EV_D, windows of EV_W, chunks of EV_C),
    float32 operands, through the one entry the cell runs (the exact part
    on the streamed kernels wherever kernels run: in the lowered forward
    and gradient BEFORE the first call; interpreted in a rehearsal), against
    ONE masked softmax a block of 256 queries over [every key; every
    summary]: the op splits the row and joins the parts by their
    log-sum-exps, this does not. The gradients on the first two windows.
    -> {"local": the exact part's path, "sound": the output's distance,
    "no_remote": the output's distance when the plain form leaves the
    summaries out, "gradients": the worst of the five (q, k, v, phi, mu),
    "forward_ms", "both_ms": the op alone in bfloat16 (None off the
    chip)}."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops import eva_attention as op
    s, h, d = cfg["EV_S"], cfg["EV_H"], cfg["EV_D"]
    window, chunk = cfg["EV_W"], cfg["EV_C"]
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    args = tuple(jax.random.normal(key, shape, jnp.float32) * scale
                 for key, shape, scale in zip(
                     keys, ((1, h, s, d),) * 3 + ((h, d),) * 2,
                     (1, 1, 1, 1, 0.25)))
    cot = jax.random.normal(keys[5], args[0].shape, jnp.float32)

    def system(*a):
        return op.eva_attention(*a, window, chunk)

    def plain(q, k, v, phi, mu, remote=True):
        n, block, scale = q.shape[2], 256, 1.0 / math.sqrt(d)
        kc = k.reshape(1, h, n // chunk, chunk, d)
        vc = v.reshape(1, h, n // chunk, chunk, d)
        a = jax.nn.softmax(scale * (kc * phi[None, :, None, None]).sum(-1),
                           -1)[..., None]
        kt, vt = (a * kc).sum(-2) + mu[None, :, None], (a * vc).sum(-2)
        keys_all = jnp.concatenate([k, kt], 2)
        values_all = jnp.concatenate([v, vt], 2)

        @jax.checkpoint
        def one(at):
            q_b, start = at                                 # (1, h, block, d)
            t = (start + jnp.arange(block))[:, None]
            u, j = jnp.arange(n)[None], jnp.arange(n // chunk)[None]
            seen = jnp.concatenate([
                (u // window == t // window) & (u <= t),
                (j < (window // chunk) * (t // window)) & remote], 1)
            sc = jnp.einsum("bhqd,bhkd->bhqk", q_b, keys_all) * scale
            return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(
                jnp.where(seen, sc, -jnp.inf), -1), values_all)

        o = jax.lax.map(one, (jnp.moveaxis(q.reshape(
            1, h, n // block, block, d), 2, 0), jnp.arange(0, n, block)))
        return jnp.moveaxis(o, 0, 2).reshape(1, h, n, d)

    def both(fn):
        return jax.jit(jax.grad(lambda cot, *t: jnp.sum(
            fn(*t).astype(jnp.float32) * cot), (1, 2, 3, 4, 5)))

    local = "streamed" if op.flash_attention_supported(
        (1, h * (s // window), window, d)) else "dense"
    calls = op._CALLS.value(local=local, remote="strips")
    low = tuple(x.astype(jnp.bfloat16) for x in args[:3]) + args[3:]
    if on_chip:
        if local != "streamed":
            raise RuntimeError("EVA's exact part took %r on the chip" % local)
        for fn, operands, kernels in (
                (jax.jit(system), low, ("flash_fwd",)),
                (both(system), (cot.astype(jnp.bfloat16),) + low,
                 ("flash_fwd", "flash_bwd_dkvq"))):
            text = fn.lower(*operands).as_text()
            if not all(k in text for k in kernels):
                raise RuntimeError("EVA's exact part is not the Mosaic "
                                   "calls %s" % (kernels,))

    def distance(got, want):
        return float(jnp.abs(got - want).max() / jnp.abs(want).max())

    with jax.default_matmul_precision("highest"):
        o_want = jax.jit(plain)(*args)
        out = {"sound": distance(jax.jit(system)(*args), o_want),
               "no_remote": distance(jax.jit(functools.partial(
                   plain, remote=False))(*args), o_want)}
        del o_want
        short = tuple(t[:, :, :2 * window] for t in args[:3]) + args[3:]
        cot_s = cot[:, :, :2 * window]
        # float32 operands at full matmul precision: the streamed
        # backward's tiles at blocks of 1024 exceed its scoped VMEM (30.5 MiB
        # of 26 on a v5e, PR 45); blocks of 512 for this gradient alone
        with mock.patch.dict(os.environ, {"MXTPU_FLASH_BLOCK_Q": "512",
                                          "MXTPU_FLASH_BLOCK_K": "512"}):
            got = both(system)(cot_s, *short)
        out["gradients"] = max(distance(a, b) for a, b in zip(
            got, both(plain)(cot_s, *short)))
    out["local"] = local
    out["forward_ms"] = out["both_ms"] = None
    if on_chip:
        out["forward_ms"] = median_ms(jax.jit(system), low)
        out["both_ms"] = median_ms(both(system),
                                   (cot.astype(jnp.bfloat16),) + low)
    if op._CALLS.value(local=local, remote="strips") == calls:
        raise RuntimeError("mxtpu_eva_attention_total{local=%r} did not "
                           "count the calls" % local)
    return out


def median_ms(fn, operands):
    """Milliseconds of a jitted call, the median of five after the first."""
    import jax
    jax.block_until_ready(fn(*operands))
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*operands))
        times.append(time.perf_counter() - t0)
    return 1e3 * statistics.median(times)


def selective_scan_alone(cfg, on_chip):
    """`ops.selective_scan.selective_scan` alone at the SambaY cell's shape
    (SEL_S positions, SEL_C channels, SEL_N states, the step sizes'
    projection SEL_R wide, the op's own chunk), float32 inputs at "highest"
    precision, through the one entry the cell runs and on the route it runs
    there (the Pallas kernel pair: a Mosaic call in the lowered text on the
    chip, interpreted in a rehearsal), against the Mamba-1 recurrence a
    position at a time fed softplus(low W^T + b): as `scan_alone`, the one
    comparison in which the state's float32 shows. The eight gradients are
    compared on ONE channel block (the first 1024 channels: autodiff of the
    recurrence keeps every position's state, 1 GB a block in float32).
    -> {"sound": the output's distance, "rounded": the recurrence's with
    its state rounded to bfloat16 once a chunk, "gradients": the worst of
    the eight, each over its own largest entry, "forward_ms", "both_ms":
    the op alone in bfloat16 at the whole shape (None off the chip: a CPU
    time is no device number)}."""
    import jax
    import jax.numpy as jnp
    import numpy as onp
    from incubator_mxnet_tpu.ops import selective_scan as op
    s, c, n, r = cfg["SEL_S"], cfg["SEL_C"], cfg["SEL_N"], cfg["SEL_R"]
    block = op._KERNEL_CHANNELS
    rng = onp.random.default_rng(0)
    x, low, bm, cm = (
        jnp.asarray(rng.standard_normal(shape), jnp.float32)
        for shape in ((1, s, c), (1, s, r), (1, s, n), (1, s, n)))
    # step sizes about log-normal around 0.011, 0.001 to 0.1
    w = jnp.asarray(rng.standard_normal((c, r)) / onp.sqrt(r), jnp.float32)
    bias = jnp.full((c,), -4.5, jnp.float32)
    a = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32), (c, n))
    # (no skip in the outputs' comparison, as PR 34 read it; the gradients'
    # has one)
    args = (x, low, a, bm, cm, jnp.zeros((c,), jnp.float32), w, bias)

    def system(x, low, a, bm, cm, skip, w, bias):
        return op.selective_scan(x, low, a, bm, cm, skip, (w, bias))

    def recurrence(x, low, a, bm, cm, skip, w, bias, round_state=False):
        dt = jax.nn.softplus(jnp.einsum("bsr,cr->bsc", low, w) + bias)

        def step(state, at):
            x_t, dt_t, b_t, c_t, rounds = at
            state = jnp.exp(dt_t[..., None] * a) * state \
                + (dt_t * x_t)[..., None] * b_t[:, None, :]
            if round_state:
                state = jnp.where(
                    rounds, jax.lax.reduce_precision(state, 8, 7), state)
            return state, (state * c_t[:, None, :]).sum(-1)
        by_time = tuple(t.swapaxes(0, 1) for t in (x, dt, bm, cm)) \
            + (jnp.arange(s) % op._CHUNK == op._CHUNK - 1,)
        return jax.lax.scan(step, jnp.zeros(a.shape, jnp.float32)[None],
                            by_time)[1].swapaxes(0, 1) + skip * x

    def distance(got, want):
        return float(jnp.abs(got - want).max() / jnp.abs(want).max())

    def one_block(t, channel_axis):
        return t if channel_axis is None else \
            jax.lax.slice_in_dim(t, 0, block, axis=channel_axis)

    def gradients(fn):
        """(cotangent, *inputs) -> the eight gradients of sum(y cot)."""
        return jax.jit(jax.grad(lambda cot, *t: jnp.sum(fn(*t) * cot),
                                tuple(range(1, 1 + len(args)))))

    kernels = op._SCANS.value(path="pallas")
    with jax.default_matmul_precision("highest"):
        if on_chip and "tpu_custom_call" not in \
                jax.jit(system).lower(*args).as_text():
            raise RuntimeError("the selective scan lowered to no Mosaic "
                               "kernel at %d x %d x %d" % (s, c, n))
        want = jax.jit(recurrence)(*args)
        out = {"sound": distance(jax.jit(system)(*args), want),
               "rounded": distance(jax.jit(functools.partial(
                   recurrence, round_state=True))(*args), want)}
        del want
        part = tuple(one_block(t, axis) for t, axis in zip(
            args, (2, None, 0, None, None, 0, 0, 0)))
        part = part[:5] + (jnp.asarray(
            rng.standard_normal((block,)), jnp.float32),) + part[6:]
        cot = jnp.asarray(rng.standard_normal(part[0].shape), jnp.float32)
        out["gradients"] = max(
            distance(got, want) for got, want in zip(
                gradients(system)(cot, *part),
                gradients(recurrence)(cot, *part)))
    if op._SCANS.value(path="pallas") == kernels:
        raise RuntimeError("the selective scan took the XLA form")
    out["forward_ms"] = out["both_ms"] = None
    if on_chip:
        lowp = tuple(t.astype(jnp.bfloat16) if i in (0, 1, 3, 4, 6) else t
                     for i, t in enumerate(args))
        out["forward_ms"] = median_ms(jax.jit(system), lowp)
        out["both_ms"] = median_ms(
            gradients(system), (jnp.ones(x.shape, jnp.bfloat16),) + lowp)
    return out


#: a wide-value call against the two narrow calls it replaces, bfloat16, as
#: a share of each tensor's largest entry: an output column is the same
#: float32 sum rounded once on both sides; a narrow pair's dQ and dK are two
#: bfloat16 gradients added where the wide call sums in float32 and rounds
#: once (readings of one v5e: PERF.md section 6, PR 37)
WIDE_VALUE_LIMITS = {"outputs": 2.0 ** -7, "gradients": 2.0 ** -6}


def wide_value_alone(cfg, on_chip):
    """`flash_attention` with a value twice as wide as its keys, at a
    differential layer's shape in the SambaY cell ((1, WIDE_H, WIDE_S,
    WIDE_D | 2 WIDE_D), bfloat16), full causal and under WIDE_WINDOW:
    forward and the three gradients of ONE call against `[v_1; v_2]`
    against the TWO calls of one width it replaces (a_i v_1, a_i v_2: the
    same map twice), on the streamed kernels both (a Mosaic call in the
    lowered text on the chip: a refusal or a VMEM limit at the wide tiles
    is met here, not in the cell). -> {"causal" | "window": {"outputs",
    "gradients": the worst distance over the tensor's largest entry,
    "wide_ms", "narrow_ms": (forward, forward + backward) of the one call
    and of the pair, None off the chip}}."""
    import jax
    import jax.numpy as jnp
    import numpy as onp
    from incubator_mxnet_tpu.ops import attention as op
    h, s, d = cfg["WIDE_H"], cfg["WIDE_S"], cfg["WIDE_D"]
    rng = onp.random.default_rng(0)
    q, k, vv, cot = (
        jnp.asarray(rng.standard_normal((1, h, s, w)), jnp.bfloat16)
        for w in (d, d, 2 * d, 2 * d))

    def distance(got, want):
        got, want = (t.astype(jnp.float32) for t in (got, want))
        return float(jnp.abs(got - want).max() / jnp.abs(want).max())

    out = {}
    for name, window in (("causal", None), ("window", cfg["WIDE_WINDOW"])):
        def wide(q, k, vv):
            return op.flash_attention(q, k, vv, True, window=window)

        def narrow(q, k, vv):
            return jnp.concatenate(
                [op.flash_attention(q, k, vv[..., :d], True, window=window),
                 op.flash_attention(q, k, vv[..., d:], True, window=window)],
                -1)

        def both(fn):
            """-> (output, dq, dk, dv) of sum(out * cot)."""
            def run(q, k, vv):
                y, back = jax.vjp(fn, q, k, vv)
                return (y,) + back(cot)
            return jax.jit(run)

        if op.attention_route(q.shape, k.shape, vv.shape,
                              window=window) != "streamed":
            raise RuntimeError("a value %d wide on keys of %d at S=%d left "
                               "the streamed kernels" % (2 * d, d, s))
        calls = op._WIDE_VALUES.value(route="streamed")
        if on_chip and jax.jit(wide).lower(q, k, vv).as_text() \
                .count("tpu_custom_call") != 1:
            raise RuntimeError("the wide-value forward is not ONE Mosaic "
                               "call (%s)" % name)
        got, want = both(wide)(q, k, vv), both(narrow)(q, k, vv)
        if op._WIDE_VALUES.value(route="streamed") == calls:
            raise RuntimeError("mxtpu_attention_wide_value_total did not "
                               "count the wide call")
        out[name] = {
            "outputs": distance(got[0], want[0]),
            "gradients": max(distance(g, w)
                             for g, w in zip(got[1:], want[1:])),
            "wide_ms": None, "narrow_ms": None}
        if on_chip:
            for key, fn in (("wide_ms", wide), ("narrow_ms", narrow)):
                out[name][key] = (median_ms(jax.jit(fn), (q, k, vv)),
                                  median_ms(both(fn), (q, k, vv)))
    return out


def watch_choices(net):
    """Every MoELayer of `net` also hands its router's `top_idx` out of the
    step, as a step counter beside the rows an expert it already publishes:
    what the host counts the published rows against. -> the layers."""
    from incubator_mxnet_tpu.gluon import _functional
    from incubator_mxnet_tpu.parallel import MoELayer
    layers = []

    def watch(block):
        if isinstance(block, MoELayer):
            route = block.route

            def watched(*args):
                out = route(*args)
                _functional.collect_step_counter(block.name + ":top_idx",
                                                 out[3])
                return out
            block.route = watched
            layers.append(block)
    net.apply(watch)
    return layers


def published_rows_are_counts(layers):
    """Each resolved step's published rows of each layer against a NumPy
    count of the same step's `top_idx` over the layer's held experts. ->
    (steps compared, the live rows a layer a step, smallest and largest)."""
    import numpy as onp
    from incubator_mxnet_tpu import jit
    from incubator_mxnet_tpu.telemetry import spans
    jit.flush_step_counters()
    held = {layer.name: layer.held or (0, layer.num_experts)
            for layer in layers}
    records = [r for r in spans.snapshot() if r["name"] == "train:counters"]
    live = []
    for record in records:
        by_name = {c["name"]: c for c in record["args"]["counters"]}
        for name, (first, count) in held.items():
            want = onp.bincount(by_name[name + ":top_idx"]["values"],
                                minlength=first + count)[first:first + count]
            if by_name[name]["values"] != want.tolist():
                raise RuntimeError(
                    "step %d, %s: the dispatch published %r rows an expert, "
                    "a count of the step's top_idx gives %r" % (
                        record["args"]["step"], name,
                        by_name[name]["values"], want.tolist()))
            live.append(int(want.sum()))
    if not records or not live:
        raise RuntimeError("no step published its experts' rows")
    return len(records), min(live), max(live)


def phase_hybrid(cfg, on_chip, shared):
    """One tiny Nemotron-H share through TrainStep: the chunked Mamba-2 scan
    with its backward, the held dispatch (experts 8..15 of 64) and the
    grouped-query kernels meet the device outside the benchmark, each layer
    recomputed in the backward."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import jit, models
    mx.random.seed(0)
    net = models.NemotronHModel(
        cfg["V"], cfg["U"], cfg["P"],
        mamba=dict(num_heads=cfg["MH"], head_dim=cfg["MD"], n_groups=cfg["G"],
                   state=cfg["N"], chunk=cfg["Q"], shards=cfg["SHARDS"]),
        attention=dict(num_heads=cfg["QH"], num_kv_heads=cfg["KV"],
                       head_dim=128, attention="flash"),
        moe=dict(latent=cfg["L"], num_experts=cfg["E"], ffn_hidden=cfg["I"],
                 top_k=cfg["K"], shared_hidden=cfg["SH"], scale=2.5,
                 held=(cfg["HELD"], cfg["HELD"])),
        remat_layers=True)
    net.initialize(mx.init.Xavier())
    net.cast("bfloat16")
    tokens = fixed_tokens(cfg, 1)
    view = models.FeaturesView(net)
    trainer = adam_trainer(view)
    routed = watch_choices(net)
    step = jit.TrainStep(view, models.ChunkedUntiedLMLoss(net), trainer)
    losses, compile_s, steady_s = run_steps(step, (tokens, tokens), 2, 2)
    if not losses[-1] < losses[0]:
        raise RuntimeError("loss did not fall: %r" % (losses,))
    counted = published_rows_are_counts(routed)
    log("held dispatch: the rows an expert that %d steps published (%d "
        "layer(s), %d..%d live rows a layer a step) equal a count of the "
        "same step's top_idx" % ((counted[0], len(routed)) + counted[1:]))
    (text,) = [t for model_id, t in jit.compiled_train_programs()
               if model_id == step._model_id]
    if "ssd_scan" not in text or "moe_dispatch" not in text:
        raise RuntimeError("the step lost the scan's or the dispatch's name")
    if on_chip and "ragged" not in text:
        raise RuntimeError("held dispatch compiled without a grouped matmul")
    kernels = (mosaic_kernels_in_train_program(2) if on_chip
               else "skipped (interpreted)")
    del step, trainer, view, net, tokens
    gc.collect()
    sound, rounded = scan_alone(cfg)
    if not sound < SCAN_ALONE_LIMIT < rounded:
        raise RuntimeError(
            "scan alone, float32: %.3g of the largest output from the "
            "recurrence (limit %g), a bfloat16 state a chunk %.3g"
            % (sound, SCAN_ALONE_LIMIT, rounded))
    sel = selective_scan_alone(cfg, on_chip)
    if not (sel["sound"] < SCAN_ALONE_LIMIT < sel["rounded"]
            and sel["gradients"] < SCAN_ALONE_LIMIT):
        raise RuntimeError(
            "selective scan alone, float32: %.3g of the largest output from "
            "the recurrence and %.3g of a gradient's largest entry (limit "
            "%g), a bfloat16 state a chunk %.3g" % (
                sel["sound"], sel["gradients"], SCAN_ALONE_LIMIT,
                sel["rounded"]))
    wide = wide_value_alone(cfg, on_chip)
    for name, read in wide.items():
        if not (read["outputs"] <= WIDE_VALUE_LIMITS["outputs"]
                and read["gradients"] <= WIDE_VALUE_LIMITS["gradients"]):
            raise RuntimeError(
                "a wide-value call (%s) against the two narrow calls it "
                "replaces: outputs %.3g (limit %.3g), gradients %.3g (limit "
                "%.3g) of the largest entry" % (
                    name, read["outputs"], WIDE_VALUE_LIMITS["outputs"],
                    read["gradients"], WIDE_VALUE_LIMITS["gradients"]))
    rule = delta_rule_alone(cfg, on_chip)
    if not (rule["sound"] < DELTA_RULE_ALONE_LIMIT < rule["rounded"]
            and rule["gradients"] < DELTA_RULE_ALONE_LIMIT):
        raise RuntimeError(
            "gated delta rule alone, float32: %.3g of the largest output "
            "from the recurrence and %.3g of a gradient's largest entry "
            "(limit %g), a bfloat16 state a chunk %.3g" % (
                rule["sound"], rule["gradients"], DELTA_RULE_ALONE_LIMIT,
                rule["rounded"]))
    sparse = sparse_attention_alone(cfg, on_chip)
    if not (sparse["sound"] < SPARSE_ALONE_LIMITS["outputs"]
            < sparse["rounded"]
            and sparse["kl"] < SPARSE_ALONE_LIMITS["outputs"]
            and sparse["gradients"] < SPARSE_ALONE_LIMITS["gradients"]):
        raise RuntimeError(
            "sparse attention alone, float32: outputs %.3g of the largest "
            "entry from a masked softmax over top_k's keys and the KL %.3g "
            "(limit %g), the six gradients %.3g (limit %g); choosing on "
            "bfloat16 scores %.3g" % (
                sparse["sound"], sparse["kl"], SPARSE_ALONE_LIMITS["outputs"],
                sparse["gradients"], SPARSE_ALONE_LIMITS["gradients"],
                sparse["rounded"]))
    eva = eva_attention_alone(cfg, on_chip)
    if not (eva["sound"] < EVA_ALONE_LIMITS["outputs"] < eva["no_remote"]
            and eva["gradients"] < EVA_ALONE_LIMITS["gradients"]):
        raise RuntimeError(
            "EVA attention alone, float32: outputs %.3g of the largest "
            "entry from one masked softmax over keys and summaries (limit "
            "%g), the five gradients %.3g (limit %g); without the summaries "
            "%.3g" % (eva["sound"], EVA_ALONE_LIMITS["outputs"],
                      eva["gradients"], EVA_ALONE_LIMITS["gradients"],
                      eva["no_remote"]))
    alone = "" if sel["forward_ms"] is None else \
        "; forward %.1f ms, forward + backward %.1f ms in bfloat16 (the XLA " \
        "form 36-38 / 77-80, PR 34)" % (sel["forward_ms"], sel["both_ms"])
    return compile_s, steady_s, "pattern %s, S=%d, loss %.4f -> %.4f, " \
        "mosaic kernels: %s; scan alone at S=%d in float32 %.2g of its " \
        "largest output from the recurrence (a bfloat16 state a chunk " \
        "%.2g); selective scan alone at %d x %d x %d, the kernel pair: " \
        "%.2g (%.2g), the eight gradients of one channel block %.2g%s; " \
        "attention with a value of its own width at (1, %d, %d, %d | %d) " \
        "against the two narrow calls it replaces: %s; gated delta rule " \
        "alone at %d x %d x %d x %d (%s) in float32 %.2g of its largest " \
        "output from the recurrence (a bfloat16 state a chunk %.2g), the " \
        "five gradients %.2g%s; sparse attention alone at %d x %d | %d x " \
        "%d, %d x %d index heads, %d keys a query (%s) in float32 %.2g of " \
        "its largest output from a masked softmax over top_k's keys " \
        "(choosing on bfloat16 scores %.2g), the KL %.2g, the six " \
        "gradients %.2g%s; EVA attention alone at %d x %d x %d, windows of " \
        "%d, chunks of %d (exact part: %s) in float32 %.2g of its largest " \
        "output from one masked softmax over keys and summaries (without " \
        "the summaries %.2g), the five gradients %.2g%s" % (
            cfg["P"], cfg["S"], losses[0], losses[-1], kernels,
            cfg["SCAN_S"], sound, rounded, cfg["SEL_S"], cfg["SEL_C"],
            cfg["SEL_N"], sel["sound"], sel["rounded"], sel["gradients"],
            alone, cfg["WIDE_H"], cfg["WIDE_S"], cfg["WIDE_D"],
            2 * cfg["WIDE_D"], "; ".join(
                "%s outputs %.2g, gradients %.2g%s" % (
                    name, read["outputs"], read["gradients"],
                    "" if read["wide_ms"] is None else
                    ", forward %.1f ms and forward + backward %.1f ms for "
                    "%.1f and %.1f" % (read["wide_ms"] + read["narrow_ms"]))
                for name, read in wide.items()),
            cfg["DR_S"], cfg["DR_H"], cfg["DR_D"], cfg["DR_D"], rule["path"],
            rule["sound"], rule["rounded"], rule["gradients"],
            "" if rule["forward_ms"] is None else
            ", forward %.1f ms, forward + backward %.1f ms (the XLA form "
            "%.1f / %.1f)" % ((rule["forward_ms"], rule["both_ms"])
                              + rule["xla_ms"]),
            cfg["SA_S"], cfg["SA_H"], cfg["SA_G"], cfg["SA_D"], cfg["SA_J"],
            cfg["SA_DI"], cfg["SA_K"], sparse["path"], sparse["sound"],
            sparse["rounded"], sparse["kl"], sparse["gradients"],
            "" if sparse["forward_ms"] is None else
            ", forward %.1f ms, forward + backward %.1f ms in bfloat16" % (
                sparse["forward_ms"], sparse["both_ms"]),
            cfg["EV_S"], cfg["EV_H"], cfg["EV_D"], cfg["EV_W"], cfg["EV_C"],
            eva["local"], eva["sound"], eva["no_remote"], eva["gradients"],
            "" if eva["forward_ms"] is None else
            ", forward %.1f ms, forward + backward %.1f ms in bfloat16" % (
                eva["forward_ms"], eva["both_ms"]))


#: the hyper-connection's maps in float32 against the plain form on the SAME
#: bfloat16 stream (x^ P is a float32 matmul at full precision: a bfloat16
#: pass of it reads 2e-3); its mixed streams and the latent block in bfloat16
#: against themselves in float32 (bfloat16 rounding: 2^-8 an entry)
STREAMS_LIMITS = {"maps": 1e-4, "bfloat16": 2.0 ** -5}


def hyper_connection_alone(cfg, on_chip):
    """One `HyperConnection` sublayer, X' = Hres X + Hpost^T silu(Hpre X),
    at (1, S, N U) in bfloat16: the three maps against the plain form a
    position at a time (20 Sinkhorn rounds written out) on the same
    stream, X' against the plain form in float32 -> {"maps", "mixed":
    the worst distance over the tensor's largest entry, "forward_ms",
    "both_ms": None off the chip}."""
    import jax
    import jax.numpy as jnp
    import numpy as onp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import models, nd
    n, u, s = cfg["N"], cfg["U"], cfg["S"]
    mx.random.seed(0)
    hc = models.HyperConnection(u, n)
    hc.initialize(mx.init.Xavier())
    rng = onp.random.default_rng(0)
    hc.weight.set_data(nd.array(rng.standard_normal(
        hc.weight.shape, onp.float32) / onp.sqrt(n * u)))
    bias = onp.zeros(2 * n + n * n, onp.float32)
    bias[2 * n:] = 2.0 * onp.eye(n, dtype=onp.float32).reshape(-1)
    hc.bias.set_data(nd.array(bias))
    x = jnp.asarray(rng.standard_normal((1, s, n * u)), jnp.bfloat16)
    cot = jnp.asarray(rng.standard_normal((1, s, n * u)), jnp.bfloat16)
    params = tuple(p.data()._data for p in (hc.weight, hc.bias, hc.scale))

    def plain_maps(one, w, b, a):
        vec = one.reshape(-1)
        raw = w @ (vec / jnp.sqrt((vec * vec).mean() + 1e-6))
        h_pre = jax.nn.sigmoid(a[0] * raw[:n] + b[:n])
        h_post = 2.0 * jax.nn.sigmoid(a[1] * raw[n:2 * n] + b[n:2 * n])
        m = jnp.exp(jnp.clip((a[2] * raw[2 * n:] + b[2 * n:]).reshape(n, n),
                             -30.0, 30.0))
        for _ in range(20):
            m = m / (m.sum(1, keepdims=True) + 1e-6)
            m = m / (m.sum(0, keepdims=True) + 1e-6)
        return h_pre, h_post, m

    def plain(x, w, b, a):
        with jax.default_matmul_precision("highest"):
            xs = x.astype(jnp.float32).reshape(s, n, u)
            h_pre, h_post, h_res = jax.vmap(
                lambda one: plain_maps(one, w, b, a))(xs)
            y = jax.nn.silu(jnp.einsum("sn,snc->sc", h_pre, xs))
            mixed = jnp.einsum("sij,sjc->sic", h_res, xs) \
                + h_post[..., None] * y[:, None]
            return mixed.reshape(1, s, n * u), (h_pre.T, h_post.T,
                                                h_res.transpose(1, 2, 0))

    def system(x, w, b, a):
        u_, h_post, h_res = hc._read(x, w, b, a)
        y = jax.nn.silu(u_.astype(jnp.float32)).astype(x.dtype)
        return hc._write(x, y, h_post, h_res)

    def distance(got, want):
        got, want = (t.astype(jnp.float32) for t in (got, want))
        return float(jnp.abs(got - want).max() / jnp.abs(want).max())

    want, want_maps = jax.jit(plain)(x, *params)
    got_maps = jax.jit(hc.maps)(x, *params)
    out = {"maps": max(distance(g, w) for g, w in zip(got_maps, want_maps)),
           "mixed": distance(jax.jit(system)(x, *params), want),
           "forward_ms": None, "both_ms": None}
    if on_chip:
        def both(x, w, b, a):
            y, back = jax.vjp(system, x, w, b, a)
            return (y,) + back(cot)
        out["forward_ms"] = median_ms(jax.jit(system), (x,) + params)
        out["both_ms"] = median_ms(jax.jit(both), (x,) + params)
    return out


def latent_block_alone(cfg, on_chip):
    """One `MultiHeadLatentAttention` block in the DeepSeek-V3 form (a
    low-rank query with its norm, no QK-norm, no gate, a YaRN table, the
    scale times m^2) at (1, S, U): bfloat16 against the same weights in
    float32 at full matmul precision (the streamed kernels at blocks of
    512 there: ops/attention.py), on the streamed kernels on the chip ->
    {"outputs", "route", "forward_ms", "both_ms"}."""
    import jax
    import jax.numpy as jnp
    import numpy as onp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import models, nd
    from incubator_mxnet_tpu.models.ling3 import yarn_inv_freq, yarn_mscale
    from incubator_mxnet_tpu.ops import attention as op
    mx.random.seed(0)
    block = models.MultiHeadLatentAttention(
        cfg["U"], cfg["H"], cfg["KL"], cfg["DN"], cfg["DR"], cfg["DV"],
        q_latent=cfg["QL"], qk_norm=False, head_gate=False,
        inv_freq=yarn_inv_freq(cfg["DR"], 1e4, cfg["YARN"],
                               cfg["YARN_FROM"]),
        scale=yarn_mscale(cfg["YARN"]) ** 2
        / (cfg["DN"] + cfg["DR"]) ** 0.5)
    block.initialize(mx.init.Xavier())
    x = onp.random.default_rng(1).standard_normal(
        (1, cfg["S"], cfg["U"])).astype("float32")
    routes = {r: op._ROUTES.value(route=r) for r in ("streamed", "composite")}
    with mock.patch.dict(os.environ, {"MXTPU_FLASH_BLOCK_Q": "512",
                                      "MXTPU_FLASH_BLOCK_K": "512"}), \
            jax.default_matmul_precision("highest"):
        want = block(nd.array(x))._data
        jax.block_until_ready(want)
    block.cast("bfloat16")
    xb = jnp.asarray(x, jnp.bfloat16)

    def system(xb):
        return block(nd.NDArray(xb))._data

    got = jax.jit(system)(xb).astype(jnp.float32)
    route = [r for r, was in routes.items() if op._ROUTES.value(route=r) > was]
    out = {"outputs": float(jnp.abs(got - want).max() / jnp.abs(want).max()),
           "route": "+".join(route), "forward_ms": None, "both_ms": None}
    if on_chip:
        if route != ["streamed"]:
            raise RuntimeError("the latent block's attention took %s at "
                               "S=%d" % (route, cfg["S"]))
        cot = jnp.asarray(x[::-1], jnp.bfloat16)
        out["forward_ms"] = median_ms(jax.jit(system), (xb,))
        out["both_ms"] = median_ms(jax.jit(
            lambda xb: jax.vjp(system, xb)[1](cot)), (xb,))
    return out


def phase_streams(cfg, on_chip, shared):
    """A residual path of several streams meets the device outside the
    benchmark: one hyper-connected sublayer and one latent-attention block
    with a low-rank query and YaRN frequencies, at the Xing cell's widths,
    each against its float32 form."""
    t0 = time.perf_counter()
    hyper = hyper_connection_alone(cfg, on_chip)
    latent = latent_block_alone(cfg, on_chip)
    spent = time.perf_counter() - t0
    if not (hyper["maps"] < STREAMS_LIMITS["maps"]
            and hyper["mixed"] < STREAMS_LIMITS["bfloat16"]
            and latent["outputs"] < STREAMS_LIMITS["bfloat16"]):
        raise RuntimeError(
            "streams: the maps read %.3g of their largest entry from the "
            "plain form (limit %g), the mixed streams %.3g and the latent "
            "block %.3g from their float32 forms (limit %g)" % (
                hyper["maps"], STREAMS_LIMITS["maps"], hyper["mixed"],
                latent["outputs"], STREAMS_LIMITS["bfloat16"]))
    ms = "" if hyper["forward_ms"] is None else \
        "; the sublayer's two mixes and maps forward %.2f ms, forward + " \
        "backward %.2f ms; the block forward %.1f ms, forward + backward " \
        "%.1f ms in bfloat16" % (hyper["forward_ms"], hyper["both_ms"],
                                 latent["forward_ms"], latent["both_ms"])
    return spent, 0.0, "a hyper-connection of %d streams of %d at S=%d: the " \
        "maps %.2g of their largest entry from the plain form a position " \
        "at a time, the mixed streams in bfloat16 %.2g from float32; a " \
        "latent block of %d heads, query latent %d (%s) in bfloat16 %.2g " \
        "from float32%s" % (cfg["N"], cfg["U"], cfg["S"], hyper["maps"],
                            hyper["mixed"], cfg["H"], cfg["QL"],
                            latent["route"], latent["outputs"], ms)


def build_resnet():
    import incubator_mxnet_tpu as mx
    mx.random.seed(0)
    net = mx.gluon.model_zoo.vision.resnet50_v1(classes=1000)
    net.initialize(mx.init.Xavier())
    net.cast("bfloat16")
    return net


def phase_resnet(cfg, on_chip, shared):
    import numpy as onp
    from incubator_mxnet_tpu import gluon, jit, nd
    net = build_resnet()
    rng = onp.random.RandomState(0)
    x = nd.array(rng.standard_normal((cfg["B"], 3, cfg["HW"], cfg["HW"]))
                 .astype("float32")).astype("bfloat16")
    y = nd.array(rng.randint(0, 1000, cfg["B"]).astype("float32"))
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9,
                             "multi_precision": True})
    step = jit.TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), trainer)
    losses, compile_s, steady_s = run_steps(step, (x, y), 2, 3)
    del step, trainer, net, x, y
    gc.collect()
    return compile_s, steady_s, "b%d, losses finite (%.3f .. %.3f)" % (
        cfg["B"], losses[0], losses[-1])


# ------------------------------------------------------------------- serving
def http_json(url, payload=None, timeout=300.0):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(url, data=data)
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, resp.read()


def phase_serve(cfg, on_chip, shared):
    import numpy as onp
    from incubator_mxnet_tpu import jit, nd, serving
    net = build_resnet()
    hw, n = cfg["HW"], cfg["requests"]
    rng = onp.random.RandomState(1)
    # bf16-representable inputs, so the JSON round trip is exact
    items = nd.array(rng.standard_normal((n, 3, hw, hw)).astype("float32")) \
        .astype("bfloat16").asnumpy()
    net(nd.array(items[:1]))        # finalize deferred parameter shapes
    buckets = serving.default_buckets(cfg["max_batch"])
    t0 = time.perf_counter()
    server = serving.serve({"resnet50": net}, port=0,
                           max_batch_size=cfg["max_batch"],
                           warm_spec=[((3, hw, hw), "bfloat16")],
                           prewarm=True)
    compile_s = time.perf_counter() - t0       # the whole bucket ladder
    base = "http://127.0.0.1:%d" % server.port
    replies, lat, errors = [None] * n, [None] * n, []

    def client(ids):
        for i in ids:
            t = time.perf_counter()
            try:
                status, body = http_json(
                    base + "/v1/models/resnet50:predict",
                    {"inputs": [items[i].astype("float32").tolist()],
                     "dtype": "bfloat16"})
            except Exception as e:  # noqa: BLE001 — reported below, fatal
                errors.append("request %d: %r" % (i, e))
                return
            lat[i] = time.perf_counter() - t
            replies[i] = (status, body)

    threads = [threading.Thread(target=client, daemon=True,
                                args=(range(c, n, cfg["clients"]),))
               for c in range(cfg["clients"])]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(600.0)
        if errors or any(t.is_alive() for t in threads):
            raise RuntimeError("client failure: %s" % (errors or "hung",))
        health, _ = http_json(base + "/healthz")
        _, metrics = http_json(base + "/metrics")
    finally:
        server.stop()
    if health != 200:
        raise RuntimeError("/healthz answered %d" % health)
    for metric, want in (("mxtpu_serving_ok_total", n),
                         ("mxtpu_aot_prewarms_total", len(buckets))):
        m = re.search(r'^%s\{model="resnet50"\} (\S+)$' % metric,
                      metrics.decode(), re.M)
        if m is None or float(m.group(1)) != want:
            raise RuntimeError("/metrics %s is %s, expected %d"
                               % (metric, m and m.group(1), want))

    # reference: the same inputs straight through EvalStep (the bucket-8
    # program the server just compiled, found again in the AOT cache)
    direct = jit.EvalStep(net)
    mb = cfg["max_batch"]
    ref = onp.concatenate([
        direct(nd.array(items[i:i + mb])).asnumpy().astype("float32")
        for i in range(0, n, mb)])
    exact = 0
    for i, (status, body) in enumerate(replies):
        if status != 200:
            raise RuntimeError("request %d answered %d" % (i, status))
        out = onp.asarray(json.loads(body)["outputs"][0], "float32").ravel()
        if out.shape != (1000,) or not onp.isfinite(out).all():
            raise RuntimeError("request %d: bad logits %r" % (i, out.shape))
        # buckets 1/2/4/8 are four compiled programs; bf16 sums may differ
        # in the last bits between them, so agree within bf16 noise and
        # accept an arg-max swap only between logits that tie within it
        tol = 0.05 * max(1.0, float(onp.abs(ref[i]).max()))
        if onp.abs(out - ref[i]).max() > tol:
            raise RuntimeError("request %d: logits differ from EvalStep by "
                               "%.4g (> %.4g)" % (
                                   i, onp.abs(out - ref[i]).max(), tol))
        exact += int(out.argmax() == ref[i].argmax())
        if ref[i][out.argmax()] < ref[i].max() - tol:
            raise RuntimeError("request %d: arg-max %d vs EvalStep %d"
                               % (i, out.argmax(), ref[i].argmax()))
    del direct, net
    gc.collect()
    return compile_s, statistics.median(lat), \
        "%d/%d HTTP 200 from %d threads, arg-max equal to EvalStep %d/%d " \
        "(rest tie within bf16 noise), healthz 200, /metrics counts %d " \
        "ok and buckets %s prewarmed" % (
            n, n, cfg["clients"], exact, n, n, buckets)


def phase_generate(cfg, on_chip, shared):
    from incubator_mxnet_tpu.serving.generate import GenerativeEngine
    engine = GenerativeEngine(prewarm=False)
    try:
        t0 = time.perf_counter()
        engine.warm()
        compile_s = time.perf_counter() - t0
        prompts = [list(range(1, 1 + k)) for k in (3, 9, 17, 33, 5, 48)]
        results, errors = [None] * len(prompts), []

        def one(i):
            t = time.perf_counter()
            try:
                toks, reason = engine.submit(
                    prompts[i], max_new_tokens=24, seed=i).tokens(300.0)
            except Exception as e:  # noqa: BLE001 — reported below, fatal
                errors.append("stream %d: %r" % (i, e))
                return
            results[i] = (toks, reason, time.perf_counter() - t)

        threads = [threading.Thread(target=one, args=(i,), daemon=True)
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(600.0)
        if errors or any(t.is_alive() for t in threads):
            raise RuntimeError("stream failure: %s" % (errors or "hung",))
    finally:
        engine.close()
    if engine.alive:
        raise RuntimeError("decode loop still alive after close()")
    vocab = engine.model.VOCAB
    for i, (toks, reason, _dt) in enumerate(results):
        if reason not in ("eos", "max_tokens"):
            raise RuntimeError("stream %d ended with %r" % (i, reason))
        if not toks or not all(0 <= t < vocab for t in toks):
            raise RuntimeError("stream %d: tokens out of range" % i)
    n_tok = sum(len(r[0]) for r in results)
    return compile_s, statistics.median(r[2] for r in results), \
        "%d concurrent streams, %d tokens, all ended eos/max_tokens; at " \
        "TinyLM's 64 wide this shows only that the prefill/decode/write " \
        "programs compile on the device and the loop terminates" % (
            len(prompts), n_tok)


# ----------------------------------------------------------------- multichip
def spread(arr, n_dev, what):
    """Assert a jax.Array is partitioned on dim 0 over n_dev devices (not
    sitting on one, not replicated)."""
    if len(arr.sharding.device_set) != n_dev:
        raise RuntimeError("%s lives on %d device(s), expected %d"
                           % (what, len(arr.sharding.device_set), n_dev))
    rows = {s.data.shape[0] for s in arr.addressable_shards}
    if rows != {arr.shape[0] // n_dev}:
        raise RuntimeError("%s: per-device rows %s, expected %d (dim 0 of "
                           "%d over %d devices)" % (
                               what, sorted(rows), arr.shape[0] // n_dev,
                               arr.shape[0], n_dev))


def phase_multichip(cfgs, on_chip, shared):
    import jax
    from incubator_mxnet_tpu import gluon, parallel
    cfg, ring = cfgs["bert"], cfgs["ring"]
    devices = jax.devices()[:4]

    # -- dp=4, ZeRO-1: the BERT phase again, global batch unchanged
    mesh = parallel.make_mesh({"dp": 4}, devices=devices)
    net = build_bert(cfg)
    tokens = fixed_tokens(cfg, cfg["B"])
    trainer = adam_trainer(net)
    step = parallel.DataParallelTrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), trainer, mesh=mesh,
        zero=True)
    t0 = time.perf_counter()
    loss = step(tokens, tokens)
    first = float(loss.mean().asscalar())
    compile_s = time.perf_counter() - t0
    # the per-example loss is computed from the batch inside the program:
    # rows spread over four devices means the batch was
    spread(loss._data, 4, "per-example loss (the batch dimension)")
    n_state = 0
    for state in trainer._states:
        for leaf in jax.tree_util.tree_leaves(jax.tree_util.tree_map(
                lambda s: s._data, state)):
            if leaf.ndim and leaf.shape[0] % 4 == 0:
                spread(leaf, 4, "ZeRO optimizer state %s" % (leaf.shape,))
                n_state += 1
    if not n_state:
        raise RuntimeError("no dp-sharded optimizer state found")
    ref = shared.get("bert_first_loss")
    if ref is None:
        raise RuntimeError("the multichip phase needs the bert phase's "
                           "first loss (run both)")
    if abs(first - ref) > 2e-2 * abs(ref):
        raise RuntimeError("dp=4 first loss %.5f vs single-chip %.5f"
                           % (first, ref))
    t0 = time.perf_counter()
    second = float(step(tokens, tokens).mean().asscalar())
    steady_s = time.perf_counter() - t0
    del step, trainer, net, tokens, loss
    gc.collect()

    # -- dp=2 x sp=2: ring attention, the Pallas kernel inside shard_map
    mesh = parallel.make_mesh({"dp": 2, "sp": 2}, devices=devices)
    if on_chip:
        import jax.numpy as jnp
        q = jax.ShapeDtypeStruct(
            (ring["B"], ring["H"], ring["S"], ring["U"] // ring["H"]),
            jnp.bfloat16)
        lowered = jax.jit(lambda q, k, v: parallel.ring_attention(
            q, k, v, mesh=mesh, axis="sp")).lower(q, q, q).as_text()
        if "tpu_custom_call" not in lowered:
            raise RuntimeError("ring attention lowered without the Pallas "
                               "kernel at local S=%d" % (ring["S"] // 2))
    net = build_bert(ring, attention="ring")
    tokens = fixed_tokens(ring, ring["B"])
    trainer = adam_trainer(net)
    step = parallel.DataParallelTrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), trainer, mesh=mesh)
    ring_loss = float(step(tokens, tokens).mean().asscalar())
    if not math.isfinite(ring_loss):
        raise RuntimeError("ring step loss %r" % ring_loss)
    del step, trainer, net, tokens
    gc.collect()
    parallel.set_current_mesh(None)
    return compile_s, steady_s, \
        "dp=4 zero=True first loss %.4f (single chip %.4f) then %.4f, " \
        "batch and %d optimizer-state leaves spread over 4 devices; " \
        "dp=2 x sp=2 ring step at S=%d loss %.4f%s" % (
            first, ref, second, n_state, ring["S"], ring_loss,
            ", Pallas kernel inside shard_map" if on_chip else "")


# ---------------------------------------------------------------------- main
def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes, interpreted kernels, any backend: a "
                         "debugging aid, NOT a pass")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma-separated subset of %s (a subset is NOT a "
                         "pass)" % (PHASES,))
    args = ap.parse_args()
    phases = [p for p in args.phases.split(",") if p]
    unknown = [p for p in phases if p not in PHASES]
    if unknown:
        ap.error("unknown phase(s) %s" % unknown)

    if args.rehearse:
        os.environ["MXTPU_FLASH_INTERPRET"] = "1"
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                "--xla_force_host_platform_device_count=4 "
                + os.environ.get("XLA_FLAGS", ""))

    import jax
    import jaxlib
    from incubator_mxnet_tpu import runtime  # the import places the cache

    # ---- device gate ----------------------------------------------------
    if args.rehearse:
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": len(jax.devices())}
        log("REHEARSAL on %s — toy sizes, kernels interpreted; this is NOT "
            "a chip pass" % device)
    else:
        device, (peak_bf16, _peak_int8, peak_bw) = runtime.require_tpu()
        log("peaks for %r (devstats.PEAK_TABLE): %.0f TFLOP/s bf16, "
            "%.0f GB/s HBM" % (device["kind"], peak_bf16 / 1e12,
                               peak_bw / 1e9))
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "not installed"
    log("device: platform=%s device_kind=%r count=%d" % (
        device["platform"], device["kind"], device["count"]))
    log("versions: python %s, jax %s, jaxlib %s, libtpu %s" % (
        sys.version.split()[0], jax.__version__, jaxlib.__version__,
        libtpu_version))
    cache_dir = jax.config.jax_compilation_cache_dir
    if cache_dir is None:
        log("compile cache: none (process pinned to the CPU backend)")
    else:
        log("compile cache: %s (%d entries at start%s)" % (
            cache_dir,
            len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0,
            ", placed by JAX_COMPILATION_CACHE_DIR"
            if os.environ.get("JAX_COMPILATION_CACHE_DIR") else ""))

    cfgs = TOY if args.rehearse else FULL
    on_chip = not args.rehearse
    shared = {}
    table = {"kernels": (phase_kernels, cfgs["kernels"]),
             "moe": (phase_moe, cfgs["moe"]),
             "combine": (phase_combine, cfgs["combine"]),
             "bert": (phase_bert, cfgs["bert"]),
             "gpt": (phase_gpt, cfgs["gpt"]),
             "hybrid": (phase_hybrid, cfgs["hybrid"]),
             "streams": (phase_streams, cfgs["streams"]),
             "resnet": (phase_resnet, cfgs["resnet"]),
             "serve": (phase_serve, cfgs["serve"]),
             "generate": (phase_generate, None),
             "multichip": (phase_multichip, cfgs)}
    t_all = time.perf_counter()
    for name in phases:
        if name == "multichip" and device["count"] < 4:
            log("multichip: skipped, %d device(s)" % device["count"])
            continue
        fn, cfg = table[name]
        try:
            compile_s, steady_s, detail = fn(cfg, on_chip, shared)
        except BaseException:
            log("%s: FAIL" % name)
            raise
        log("%s: ok compile %.1f s, steady %.4f s — %s"
            % (name, compile_s, steady_s, detail))
    log("total %.1f s" % (time.perf_counter() - t_all))

    if args.rehearse or phases != list(PHASES):
        log("NOT A PASS: %s" % ("rehearsal" if args.rehearse
                                else "phases %s only" % phases))
        return
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
