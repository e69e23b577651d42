"""Do the Solar cell's `check` limits tell the faults its new mechanisms
invite: b without its factor 2 (no negative eigenvalue), the decay made a
scalar a head (the mean over its channels), the gate of `G` left out, a
delta-rule state kept in bfloat16? probe_limits.py's question for faults it
cannot pose (it rounds dense operands only). Run once when limits are set
(PERF.md, Findings), not by the benchmark.

    python3 perfbench/probe_delta.py --workload <cell> [--seed n] [--rehearse]

The configuration's float32 reference is run on the first batch as it
stands, and again with each fault in it. Each variant's distance from the
exact reference goes through the driver's own comparison (`rel_rms`,
`update_agreement`, the three inequalities that decide `correct`) and the
line ends with the limits it fails by, or with `passes`.
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

    def evaluate(config):
        out, loss = jax.device_get(jax.jit(lambda p: reference.forward(
            p, config, tokens, labels, tail))(params))
        grads = jax.device_get(jax.jit(lambda p: reference.checked_grads(
            p, config, tokens, labels))(params))
        return out, loss, grads

    exact_rule, exact_gate = reference.delta_rule, reference._gated

    def scalar_decay(q, k, v, g, beta):
        return exact_rule(q, k, v, jnp.broadcast_to(
            g.mean(-1, keepdims=True), g.shape), beta)

    def bf16_state(every):
        """The recurrence with the state rounded to bfloat16 after every
        `every`-th position (straight-through, so the gradient still
        flows; `reduce_precision`, because XLA is free to drop a pair of
        converts)."""
        def rule(q, k, v, g, beta):
            def step(state, at):
                q_t, k_t, v_t, g_t, b_t, rounds = at
                state = jnp.exp(g_t)[..., None] * state
                u = b_t[..., None] * (v_t - (state * k_t[..., None]).sum(-2))
                state = state + k_t[..., None] * u[..., None, :]
                state = state + jnp.where(rounds, jax.lax.stop_gradient(
                    jax.lax.reduce_precision(state, 8, 7) - state), 0.0)
                return state, (state * q_t[..., None]).sum(-2)

            by_time = tuple(t.swapaxes(0, 1) for t in (q, k, v, g, beta)) \
                + (jnp.arange(q.shape[1]) % every == every - 1,)
            _, o = jax.lax.scan(jax.checkpoint(step), jnp.zeros(
                q.shape[:1] + q.shape[2:] + v.shape[-1:], jnp.float32),
                by_time)
            return o.swapaxes(0, 1)

        return rule

    out0, loss0, grads0 = evaluate(cfg)
    print("limits of %s: outputs %g, loss %g, update %g" % (
        args.workload, check["outputs_rel_rms"], check["loss_rel"],
        check["update_agreement"]))
    for name, rule, gate, config in (
            ("b without its factor 2", exact_rule, exact_gate,
             dict(cfg, kda_allow_neg_eigval=False)),
            ("a scalar decay a head", scalar_decay, exact_gate, cfg),
            ("no gate on G", exact_rule, lambda out, z: out + 0.0 * z, cfg),
            ("bfloat16 state, a position", bf16_state(1), exact_gate, cfg),
            ("bfloat16 state, a chunk",
             bf16_state(cfg["delta_rule_chunk"]), exact_gate, cfg)):
        reference.delta_rule, reference._gated = rule, gate
        try:
            out, loss, grads = evaluate(config)
        finally:
            reference.delta_rule, reference._gated = exact_rule, exact_gate
        zero = {k: np.zeros_like(g) for k, g in grads.items()}
        # a zero gradient moves nothing: Adam's first step goes by the sign
        moved = {k: -np.sign(g) for k, g in grads.items()}
        agreement = driver.update_agreement(zero, moved, grads0)
        out_err = driver.rel_rms(out, out0)
        loss_err = float(np.max(np.abs(loss - loss0) / np.abs(loss0)))
        low = min(agreement, key=lambda k: agreement[k][0])
        # the driver's three inequalities
        fails = [what for what, bad in (
            ("outputs", out_err > check["outputs_rel_rms"]),
            ("loss", loss_err > check["loss_rel"]),
            ("update", agreement[low][0] < check["update_agreement"])) if bad]
        print("%-26s outputs rel-rms %.4g, loss rel %.4g, update lowest "
              "%s %.4f (%s): %s" % (
                  name, out_err, loss_err, low, agreement[low][0],
                  ", ".join("%s %.3f" % (k, a) for k, (a, _)
                            in sorted(agreement.items()) if a < 0.99),
                  "FAILS BY " + ", ".join(fails) if fails else "passes"),
              flush=True)


if __name__ == "__main__":
    main()
