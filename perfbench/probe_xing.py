"""Do the Xing cell's `check` limits tell these broken programs from the
sound one? Run once when the limits are set (PERF.md, Findings; every
reading: docs/PERF_XING4.md), not by the benchmark.

    python3 perfbench/probe_xing.py [--seed n] [--rehearse] [--faults a,b]

probe_limits.py poses a lower precision of every matmul; this file poses
what it cannot, each as the float32 reference with ONE of its functions
replaced, on the cell's first batch:

  sinkhorn_1    the Sinkhorn rounds stopped after 1 of 20
  res_T         Hres applied transposed (column i gives stream i)
  post_1        Hpost = sigmoid(.) without the factor 2
  rope_plain    the rotary frequencies theta^(-2i/d): YaRN left off
  scale_plain   the softmax scale 192^-1/2 without m^2
  q_unnormed    the query latent's RMSNorm left out: q = (h W_qa) W_qb
  rope_a_head   the rotary key differs from head to head (head h's is the
                one key turned by 2 h channels) instead of one for all
  bf16_dense, f8_dense   probe_limits.py's two: every matmul's operands
                rounded (the first is what the system serves and must pass)

A line a program: its distance from the exact reference in the compared
tensor and in each of its three parts (the continuous trunk | X' of the
first layer alone | the last mixer hyper-connection's maps), its loss,
and the lowest share of the exact gradient's magnitude on weights whose
broken gradient has the same sign (with the parameter's name); then the
SOUND SYSTEM's distance from that program's outputs, whole and by part:
what the cell would read of a system with the fault, the error being on
one side or the other.
"""
import argparse
import os
import sys

import run as harness            # perfbench/run.py, beside this file
from probe_ling import posed     # `with posed(reference, {fn: broken}):`

CELL = "xing4.0-29b-a4b.train-s8k"
PARTS = ("trunk", "layer 0 alone", "maps")


def faults(reference):
    """{name: {function of the reference: its broken form}}."""
    import jax
    import jax.numpy as jnp

    def rope_a_head(k_rope, heads, config):
        return jnp.stack([reference.rotary(jnp.roll(k_rope, 2 * h, -1),
                                           reference.inv_freq(config))
                          for h in range(heads)], 1)

    def rope_plain(config):
        d = config["qk_rope_head_dim"]
        return float(config["rope_theta"]) ** (
            -jnp.arange(0, d, 2, dtype=jnp.float32) / d)

    def rounded_dense(dtype):
        def r(a):
            return a.astype(dtype).astype(jnp.float32)
        return lambda p, x: r(x) @ r(p["w"]).T + p["b"]

    return {
        "sinkhorn_1": {"sinkhorn_rounds": lambda config: 1},
        "res_T": {"mix_res": lambda h_res, x: h_res.T @ x},
        "post_1": {"post_gate": jax.nn.sigmoid},
        "rope_plain": {"inv_freq": rope_plain},
        "scale_plain": {"score_scale": lambda config: (
            config["qk_nope_head_dim"] + config["qk_rope_head_dim"]) ** -0.5},
        "q_unnormed": {"query_latent": lambda p, x, config:
                       reference._mm(p["q_down"], x)},
        "rope_a_head": {"rotary_key": rope_a_head},
        "bf16_dense": {"_dense": rounded_dense(jnp.bfloat16)},
        "f8_dense": {"_dense": rounded_dense(jnp.float8_e4m3fn)},
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--faults", default="")
    args = ap.parse_args()
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    _, wl, cfg = harness.resolve(bench, CELL, args.rehearse)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["MXTPU_FLASH_INTERPRET"] = "1"
    sys.path.insert(0, harness.ROOT)
    import jax
    import numpy as np
    from incubator_mxnet_tpu import jit, nd
    driver = harness.load_module("drivers", wl["driver"])
    builder = harness.load_module("builders", cfg["builder"])
    reference = harness.load_module("reference", cfg["reference"])
    traffic, check = wl["traffic"], wl["check"]
    built = builder.build(cfg, args.seed, traffic["seq_len"])
    params = builder.reference_params(built["model"])
    tokens, labels = next(harness.load_module(
        "traffic", traffic["generator"]).generate(traffic, args.seed, cfg))
    tail = check["tail_positions"]
    system = jit.EvalStep(built["eval_net"])(nd.array(tokens)).asnumpy()[
        :, -tail:]
    del built
    units = cfg["hidden_size"]
    ends = (units, units * (1 + cfg["hc_mult"]), system.shape[-1])

    def by_part(got, want):
        return ", ".join("%s %.4g" % (name, driver.rel_rms(
            got[..., lo:hi], want[..., lo:hi]))
            for name, lo, hi in zip(PARTS, (0,) + ends, ends))

    def evaluate():
        return jax.device_get(jax.jit(lambda p: (
            reference.forward(p, cfg, tokens, labels, tail),
            reference.checked_grads(p, cfg, tokens, labels)))(params))

    all_faults = faults(reference)
    wanted = [f for f in args.faults.split(",") if f] or list(all_faults)
    (out0, loss0), grads0 = evaluate()
    print("limits of %s: outputs %g, loss %g, update %g" % (
        CELL, check["outputs_rel_rms"], check["loss_rel"],
        check["update_agreement"]), flush=True)
    print("%-12s the sound system from the exact reference: outputs %.4g "
          "(%s)" % ("sound", driver.rel_rms(system, out0),
                    by_part(system, out0)), flush=True)
    for name in wanted:
        with posed(reference, all_faults[name]):
            (out, loss), grads = evaluate()
        zero = {k: np.zeros_like(g) for k, g in grads.items()}
        moved = {k: -np.sign(g) for k, g in grads.items()}
        agreement = driver.update_agreement(zero, moved, grads0)
        lowest = min(agreement, key=lambda k: agreement[k][0])
        print("%-12s from the exact reference: outputs %.4g (%s), loss rel "
              "%.4g, update lowest %s %.4f; under 0.95: %s | the sound "
              "system from it: outputs %.4g (%s)" % (
                  name, driver.rel_rms(out, out0), by_part(out, out0),
                  float(np.max(np.abs(loss - loss0) / np.abs(loss0))),
                  lowest, agreement[lowest][0],
                  ", ".join("%s %.3f" % (k, a) for k, (a, _) in
                            sorted(agreement.items()) if a < 0.95) or "none",
                  driver.rel_rms(system, out), by_part(system, out)),
              flush=True)


if __name__ == "__main__":
    main()
