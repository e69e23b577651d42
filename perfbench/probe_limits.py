"""Do a cell's `check` limits tell a lower precision from the one served?
Run once when limits are set (PERF.md, Findings), not by the benchmark.

    python3 perfbench/probe_limits.py --workload <cell> [--seed n] [--rehearse]

The configuration's float32 reference is run on the first batch as it
stands, and again with the operands of every dense layer rounded to
bfloat16 (what the system serves: this must pass) and to an 8-bit float
(this must fail), and with the log-softmax of the loss in bfloat16. Each
variant's distance from the exact reference is printed beside the limit
the workload file sets on the system's: outputs (rel-rms), loss, and where
the cell checks the first update, the share of the exact gradient's
magnitude on weights whose degraded gradient has the same sign.
"""
import argparse
import os
import sys

import run as harness            # perfbench/run.py, beside this file


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    _, wl, cfg = harness.resolve(bench, args.workload, args.rehearse)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, harness.ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np
    driver = harness.load_module("drivers", wl["driver"])
    builder = harness.load_module("builders", cfg["builder"])
    reference = harness.load_module("reference", cfg["reference"])
    traffic, check = wl["traffic"], wl["check"]
    built = builder.build(cfg, args.seed, traffic["seq_len"])
    params = builder.reference_params(built["model"])
    tokens, labels = next(harness.load_module(
        "traffic", traffic["generator"]).generate(traffic, args.seed, cfg))
    n, tail = check["sequences"], check["tail_positions"]
    tokens, labels = tokens[:n], labels[:n]
    with_update = "update_agreement" in check

    def evaluate():
        out, loss = jax.device_get(jax.jit(
            lambda p: reference.forward(p, cfg, tokens, labels, tail))(params))
        grads = jax.device_get(jax.jit(
            lambda p: reference.checked_grads(p, cfg, tokens, labels))(
                params)) if with_update else None
        return out, loss, grads

    exact_dense, exact_lsm = reference._dense, jax.nn.log_softmax

    def rounded_dense(dtype):
        def r(a):
            return a.astype(dtype).astype(jnp.float32)
        return lambda p, x: r(x) @ r(p["w"]).T + p["b"]

    variants = [
        ("bfloat16 dense operands", rounded_dense(jnp.bfloat16), exact_lsm),
        ("float8_e4m3 dense operands", rounded_dense(jnp.float8_e4m3fn),
         exact_lsm),
        ("bfloat16 log-softmax", exact_dense,
         lambda x, axis=-1: exact_lsm(x.astype(jnp.bfloat16), axis).astype(
             jnp.float32)),
    ]
    out0, loss0, grads0 = evaluate()
    print("limits of %s: outputs %g, loss %g%s" % (
        args.workload, check["outputs_rel_rms"], check["loss_rel"],
        ", update %g" % check["update_agreement"] if with_update else ""))
    for name, dense, lsm in variants:
        reference._dense, jax.nn.log_softmax = dense, lsm
        try:
            out, loss, grads = evaluate()
        finally:
            reference._dense, jax.nn.log_softmax = exact_dense, exact_lsm
        line = "%-28s outputs rel-rms %.4g, loss rel %.4g" % (
            name, driver.rel_rms(out, out0),
            float(np.max(np.abs(loss - loss0) / np.abs(loss0))))
        if with_update:
            zero = {k: np.zeros_like(g) for k, g in grads.items()}
            moved = {k: -np.sign(g) for k, g in grads.items()}
            agreement = driver.update_agreement(zero, moved, grads0)
            line += ", update " + ", ".join(
                "%s %.4f" % (k, a) for k, (a, _) in sorted(agreement.items()))
        print(line, flush=True)


if __name__ == "__main__":
    main()
