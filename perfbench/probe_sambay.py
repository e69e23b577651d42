"""Do the SambaY cell's `check` limits tell the three faults its new
mechanisms invite: the second softmax map never subtracted, the window
never applied, a scan state kept in bfloat16? probe_limits.py's question
for faults it cannot pose (it rounds dense operands only). Run once when
limits are set (PERF.md, Findings), not by the benchmark.

    python3 perfbench/probe_sambay.py --workload <cell> [--seed n] [--rehearse]

The configuration's float32 reference is run on the first batch as it
stands; again with `a_1 - l a_2` replaced by `a_1` in every differential
layer; again with the window of the `S` layers as long as the sequence;
and again with the scan's state rounded to bfloat16 after every position,
and once a chunk. Each variant's distance from the exact reference goes
through the driver's own comparison (`rel_rms`, `update_agreement`, the
three inequalities that decide `correct`) and the line ends with the
limits it fails by, or with `passes`.
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
    from incubator_mxnet_tpu.ops.selective_scan import _CHUNK
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

    def evaluate(config):
        out, loss = jax.device_get(jax.jit(lambda p: reference.forward(
            p, config, tokens, labels, tail))(params))
        grads = jax.device_get(jax.jit(lambda p: reference.checked_grads(
            p, config, tokens, labels))(params))
        return out, loss, grads

    exact_scan, exact_differ = reference._scan, reference._differ

    def bf16_state_scan(every):
        """The recurrence with the state rounded to bfloat16 after every
        `every`-th position (straight-through, so the gradient still
        flows; `reduce_precision`, because XLA is free to drop a pair of
        converts)."""
        def scan(x, dt, a, bm, cm):
            def step(state, at):
                x_t, dt_t, b_t, c_t, rounds = at
                state = jnp.exp(dt_t[..., None] * a) * state \
                    + (dt_t * x_t)[..., None] * b_t[:, None, :]
                state = state + jnp.where(rounds, jax.lax.stop_gradient(
                    jax.lax.reduce_precision(state, 8, 7) - state), 0.0)
                return state, (state * c_t[:, None, :]).sum(-1)

            by_time = tuple(t.swapaxes(0, 1) for t in (x, dt, bm, cm)) \
                + (jnp.arange(x.shape[1]) % every == every - 1,)
            _, y = jax.lax.scan(jax.checkpoint(step), jnp.zeros(
                x.shape[:1] + a.shape, jnp.float32), by_time)
            return y.swapaxes(0, 1)

        return scan

    out0, loss0, grads0 = evaluate(cfg)
    print("limits of %s: outputs %g, loss %g, update %g" % (
        args.workload, check["outputs_rel_rms"], check["loss_rel"],
        check["update_agreement"]))
    no_window = dict(cfg, sliding_window=traffic["seq_len"])
    for name, scan, differ, config in (
            ("a_2 never subtracted", exact_scan,
             lambda a, lam: a[:, :, 0] + 0.0 * lam, cfg),
            ("no window", exact_scan, exact_differ, no_window),
            ("bfloat16 state, a position", bf16_state_scan(1), exact_differ,
             cfg),
            ("bfloat16 state, a chunk", bf16_state_scan(_CHUNK),
             exact_differ, cfg)):
        reference._scan, reference._differ = scan, differ
        try:
            out, loss, grads = evaluate(config)
        finally:
            reference._scan, reference._differ = exact_scan, exact_differ
        zero = {k: np.zeros_like(g) for k, g in grads.items()}
        # a zero gradient moves nothing: Adam's first step goes by the sign
        moved = {k: -np.sign(g) for k, g in grads.items()}
        agreement = driver.update_agreement(zero, moved, grads0)
        out_err = driver.rel_rms(out, out0)
        loss_err = float(np.max(np.abs(loss - loss0) / np.abs(loss0)))
        # the driver's three inequalities
        fails = [what for what, bad in (
            ("outputs", out_err > check["outputs_rel_rms"]),
            ("loss", loss_err > check["loss_rel"]),
            ("update", min(a for a, _ in agreement.values())
             < check["update_agreement"])) if bad]
        print("%-26s outputs rel-rms %.4g, loss rel %.4g, update %s: %s" % (
            name, out_err, loss_err,
            ", ".join("%s %.4f (%.0f %% moved)" % (k, a, 100 * m)
                      for k, (a, m) in sorted(agreement.items())),
            "FAILS BY " + ", ".join(fails) if fails else "passes"),
            flush=True)


if __name__ == "__main__":
    main()
