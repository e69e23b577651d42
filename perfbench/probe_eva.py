"""What the EvaByte cell's `check` cannot pose or see, run by hand when its
limits are set (docs/PERF_EVABYTE.md), not by the benchmark.

    python3 perfbench/probe_eva.py --workload <cell> [--seed n]
        [--parts op,faults] [--rehearse]

`op`: `ops.eva_attention` alone against the reference's one masked softmax,
float32 operands at the cell's shape (one sequence, the configuration's
heads, window and chunk): the output and the five gradients (q, k, v, phi,
mu), the largest difference over the reference's largest value and the
rel-rms; then the op's milliseconds in bfloat16, forward and forward +
backward.
`faults`: the float32 reference run again as seven broken programs — no
remote set (window-local attention alone), a sliding window in place of
the aligned one, the two softmax maps averaged (each normalised alone), mu
left out, plain mean pooling (phi ignored), heads 1 .. P-1 out of the
loss, the residual stream rounded to bfloat16 after every add — each
through the driver's own comparison (`rel_rms`, `update_agreement`, the
three inequalities that decide `correct`); the line ends with the limits it
fails by, or with `passes`.
"""
import argparse
import os
import sys
import time
from unittest import mock

import run as harness            # perfbench/run.py, beside this file


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--parts", default="op,faults")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    _, wl, cfg = harness.resolve(bench, args.workload, args.rehearse)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["MXTPU_FLASH_INTERPRET"] = "1"
    sys.path.insert(0, harness.ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from incubator_mxnet_tpu.ops import eva_attention as op
    driver = harness.load_module("drivers", wl["driver"])
    builder = harness.load_module("builders", cfg["builder"])
    reference = harness.load_module("reference", cfg["reference"])
    traffic, check = wl["traffic"], wl["check"]
    shapes = builder.shapes(cfg)
    seq, window, chunk = traffic["seq_len"], shapes["window"], \
        shapes["chunk"]
    parts = args.parts.split(",")

    # ---- the op alone ----------------------------------------------------
    if "op" in parts:
        keys = jax.random.split(jax.random.PRNGKey(args.seed % (2 ** 31)), 6)
        h, d = shapes["heads"], shapes["head_dim"]
        operands = tuple(
            jax.random.normal(key, shape, jnp.float32) * scale
            for key, shape, scale in zip(
                keys, ((1, h, seq, d),) * 3 + ((h, d),) * 2,
                (1, 1, 1, cfg["init_phi_std"], cfg["init_mu_std"])))
        cot = jax.random.normal(keys[5], operands[0].shape, jnp.float32)

        def of(attend):
            def loss(*a):
                o = attend(*a, window, chunk)
                return (o.astype(jnp.float32) * cot).sum(), o
            return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(5)),
                                              has_aux=True))

        def far(got, want):
            got, want = (np.asarray(jax.device_get(x), np.float32)
                         for x in (got, want))
            return "%.3g of the largest, rel-rms %.3g" % (
                np.abs(got - want).max() / np.abs(want).max(),
                driver.rel_rms(got, want))

        with jax.default_matmul_precision("highest"):
            (_, o_ref), g_ref = of(reference.eva_attention)(*operands)
            # float32 operands at full matmul precision: the streamed
            # backward's tiles at blocks of 1024 then exceed its scoped VMEM
            # (30.5 MiB of 26, read on a v5e); blocks of 512 for this
            # comparison alone, by the documented knobs
            with mock.patch.dict(os.environ, {"MXTPU_FLASH_BLOCK_Q": "512",
                                              "MXTPU_FLASH_BLOCK_K": "512"}):
                (_, o_sys), g_sys = of(op.eva_attention)(*operands)
        print("the op alone, float32, 1 x %d x %d x %d, window %d, chunk "
              "%d (%d exact + %d summary pairs a head): o %s; gradients %s"
              % ((seq, h, d, window, chunk) + op.seen_pairs(seq, window,
                                                            chunk)
                 + (far(o_sys, o_ref), "; ".join(
                     "d%s %s" % (n, far(a, b)) for n, a, b in zip(
                         ("q", "k", "v", "phi", "mu"), g_sys, g_ref)))),
              flush=True)
        low = tuple(x.astype(jnp.bfloat16) for x in operands[:3]) \
            + operands[3:]
        forward = jax.jit(lambda *a: op.eva_attention(*a, window, chunk))
        for name, fn in (("forward", forward), ("forward + backward",
                                                of(op.eva_attention))):
            jax.block_until_ready(fn(*low))
            t0 = time.perf_counter()
            for _ in range(3):
                out = fn(*low)
            jax.block_until_ready(out)
            print("the op alone, bfloat16, %s: %.1f ms a call (host clock "
                  "around 3 calls)" % (
                      name, (time.perf_counter() - t0) / 3 * 1e3), flush=True)
        del operands, low, g_ref, g_sys, o_ref, o_sys

    # ---- the seven broken programs ---------------------------------------
    if "faults" not in parts:
        return
    built = builder.build(cfg, args.seed, seq)
    params = builder.reference_params(built["model"])
    tokens, labels = next(harness.load_module(
        "traffic", traffic["generator"]).generate(traffic, args.seed, cfg))
    n, tail = check["sequences"], check["tail_positions"]
    tokens, labels = tokens[:n], labels[:n]

    def evaluate():
        out, loss = jax.device_get(jax.jit(lambda p: reference.forward(
            p, cfg, tokens, labels, tail))(params))
        grads = jax.device_get(jax.jit(lambda p: reference.checked_grads(
            p, cfg, tokens, labels))(params))
        return out, loss, grads

    exact = {name: getattr(reference, name) for name in (
        "pool", "masks", "attend", "stream", "loss_heads")}

    def no_remote(t, s, window, chunk):
        seen_l, seen_r = exact["masks"](t, s, window, chunk)
        return seen_l, jnp.zeros_like(seen_r)

    def sliding(t, s, window, chunk):
        _, seen_r = exact["masks"](t, s, window, chunk)
        back = t[:, None] - jnp.arange(s)[None, :]
        return (back >= 0) & (back < window), seen_r

    def averaged(sc_l, sc_r, seen_l, seen_r, v, vt):
        a_l = jax.nn.softmax(jnp.where(seen_l, sc_l, -jnp.inf), -1)
        some = seen_r.any(-1, keepdims=True)
        a_r = jnp.where(some, jax.nn.softmax(jnp.where(
            seen_r | ~some, sc_r, -jnp.inf), -1), 0.0)
        o_l = jnp.einsum("bhqk,bhkd->bhqd", a_l, v)
        o_r = jnp.einsum("bhqk,bhkd->bhqd", a_r, vt)
        return jnp.where(some, 0.5 * (o_l + o_r), o_l)

    def no_mu(k, v, phi, mu, chunk, scale):
        return exact["pool"](k, v, phi, 0.0 * mu, chunk, scale)

    def mean_pool(k, v, phi, mu, chunk, scale):
        return exact["pool"](k, v, 0.0 * phi, mu, chunk, scale)

    def head0_only(config):
        return (0,)

    def bf16_stream(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    out0, loss0, grads0 = evaluate()
    names = sorted(grads0)
    print("limits of %s: outputs %g, loss %g, update %g" % (
        args.workload, check["outputs_rel_rms"], check["loss_rel"],
        check["update_agreement"]))
    for name, patch in (
            ("no remote set", {"masks": no_remote}),
            ("a sliding window of %d" % window, {"masks": sliding}),
            ("two maps averaged", {"attend": averaged}),
            ("mu left out", {"pool": no_mu}),
            ("mean pooling (phi ignored)", {"pool": mean_pool}),
            ("heads 1 .. %d out of the loss" % (shapes["pred_heads"] - 1),
             {"loss_heads": head0_only}),
            ("the stream in bfloat16", {"stream": bf16_stream})):
        for attr, fn in patch.items():
            setattr(reference, attr, fn)
        try:
            out, loss, grads = evaluate()
        finally:
            for attr in patch:
                setattr(reference, attr, exact[attr])
        zero = {k: np.zeros_like(g) for k, g in grads.items()}
        # a zero gradient moves nothing: Adam's first step goes by the sign
        moved = {k: -np.sign(g) for k, g in grads.items()}
        agreement = driver.update_agreement(zero, moved, grads0)
        out_err = driver.rel_rms(out, out0)
        loss_err = float(np.max(np.abs(loss - loss0) / np.abs(loss0)))
        worst = min(agreement, key=lambda k: agreement[k][0])
        fails = [what for what, bad in (
            ("outputs", out_err > check["outputs_rel_rms"]),
            ("loss", loss_err > check["loss_rel"]),
            ("update", agreement[worst][0] < check["update_agreement"]))
            if bad]
        print("%-30s outputs rel-rms %.4g, loss rel %.4g, update: least %s "
              "%.4f; %s: %s" % (
                  name, out_err, loss_err, worst, agreement[worst][0],
                  ", ".join("%s %.4f" % (k, agreement[k][0]) for k in names),
                  "FAILS BY " + ", ".join(fails) if fails else "passes"),
              flush=True)


if __name__ == "__main__":
    main()
