"""Do the Ling cell's `check` limits tell these broken programs from the
sound one? Run once when the limits are set (PERF.md, Findings; every
reading: docs/PERF_LING3.md), not by the benchmark.

    python3 perfbench/probe_ling.py [--seed n] [--rehearse] [--faults a,b]
    python3 perfbench/probe_ling.py --parts [--seed n,m] [--rehearse]

probe_limits.py poses a lower precision of every matmul; this file poses
what it cannot, each as the float32 reference with ONE of its functions
replaced, against the exact reference on the cell's first batch:

  rope_a_head   the rotary key differs from head to head (head h's is the
                one key turned by 2 h channels) instead of one for all
  rope_off      the rotary key is left unrotated (q's rotary part still turns)
  scale_128     the scores' scale is qk_nope_head_dim^-1/2, not 192^-1/2
  no_groups     the group limit is ignored: plain top-8 of all 512
  softplus      the decay is -exp(A_log) softplus(.), unbounded below
  bf16_rule     bfloat16 inside the delta rule: q, k, v, g rounded on the
                way in, the state and the pseudo-value after every position
  bf16_dense, f8_dense   probe_limits.py's two: every matmul's operands
                rounded (the first is what the system serves and must pass)

`--parts`: which tensor tells a fault? Five, each a position at unit RMS
over the cell's tail positions: the continuous trunk's final norm; the last
M mixer's and the last K mixer's output ALONE ON THE EMBEDDINGS (what the
cell compares beside the trunk); the same two mixers' outputs IN THE STREAM
(where the layers before them have left their error). A line a seed and a
program: the program's distance from the exact reference in each part, the
SOUND SYSTEM's distance from that program's reference (what the cell would
read of a system with the fault, the error being on one side or the other),
and that reading for the cell's three parts side by side with the two
alone parts times 1 (what the cell compares), 2, 3, 4 and 5.

Without `--parts` each line is a fault's distance from the exact reference
beside the limit the workload file sets on the system's: outputs (rel-rms),
loss, and the lowest share of the exact gradient's magnitude on weights
whose broken gradient has the same sign (with the parameter's name).
"""
import argparse
import os
import sys

import run as harness            # perfbench/run.py, beside this file

CELL = "ling-3.0-flash.train-s8k"


def faults(reference):
    """{name: {function of the reference: its broken form}}."""
    import jax
    import jax.numpy as jnp

    def rope_a_head(p, k_rope, heads, config):
        return jnp.stack([reference.rotary(jnp.roll(k_rope, 2 * h, -1),
                                           config["rope_theta"])
                          for h in range(heads)], 1)

    def rope_off(p, k_rope, heads, config):
        return jnp.broadcast_to(k_rope[:, None], (k_rope.shape[0], heads)
                                + k_rope.shape[1:])

    def softplus(p, f, config):
        return -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(f)

    def rounded_dense(dtype):
        def r(a):
            return a.astype(dtype).astype(jnp.float32)
        return lambda p, x: r(x) @ r(p["w"]).T + p["b"]

    def bf16_step(state, at):
        def r(a):
            return a.astype(jnp.bfloat16).astype(jnp.float32)

        q_t, k_t, v_t, g_t, b_t = (r(a) for a in at)
        state = jnp.exp(g_t)[..., None] * state
        u = r(b_t[..., None] * (v_t - (state * k_t[..., None]).sum(-2)))
        state = r(state + k_t[..., None] * u[..., None, :])
        return state, (state * q_t[..., None]).sum(-2)

    return {
        "rope_a_head": {"rotary_key": rope_a_head},
        "rope_off": {"rotary_key": rope_off},
        "scale_128": {"score_scale": lambda config:
                      config["qk_nope_head_dim"] ** -0.5},
        "no_groups": {"kept_groups": lambda c, config:
                      jnp.ones(c.shape, bool)},
        "softplus": {"decay": softplus},
        "bf16_rule": {"rule_step": bf16_step},
        "bf16_dense": {"_dense": rounded_dense(jnp.bfloat16)},
        "f8_dense": {"_dense": rounded_dense(jnp.float8_e4m3fn)},
    }


class posed:
    """`with posed(reference, {function: broken form}):` the reference is
    the broken program."""

    def __init__(self, reference, broken):
        self.reference, self.broken = reference, broken

    def __enter__(self):
        self.sound = {fn: getattr(self.reference, fn) for fn in self.broken}
        for fn, broken in self.broken.items():
            setattr(self.reference, fn, broken)

    def __exit__(self, *_):
        for fn, was in self.sound.items():
            setattr(self.reference, fn, was)


PARTS = ("trunk", "M alone", "K alone", "M in stream", "K in stream")


def _unit_tail(jnp, tail, *parts):
    def unit(t):
        t = t.astype(jnp.float32)
        return t / jnp.sqrt(jnp.mean(t * t, -1, keepdims=True))
    return jnp.stack([unit(t[:, -tail:]) for t in parts])


def reference_parts(reference, p, cfg, tokens, tail):
    """PARTS of the reference as it stands (5, B, tail, U)."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        p = reference._f32(p)
        eps, own = cfg["rms_norm_eps"], {}
        x = p["tok_embed"][tokens]
        for layer, letter in zip(p["layers"], cfg["layer_pattern_run"]):
            own[letter] = reference._MIXERS[letter](
                layer, reference._rms(layer["norm1"], x, eps), cfg)
            x = x + own[letter]
            u = reference._rms(layer["norm2"], x, eps)
            x = x + (reference._swiglu(layer["dense_gate_up"],
                                       layer["dense_down"], u)
                     if "dense_gate_up" in layer
                     else reference.experts(layer, u, cfg, routed=False))
        return _unit_tail(jnp, tail, reference._rms(p["norm_f"], x, eps),
                          *reference.alone(p, cfg, tokens), own["M"], own["K"])


def system_parts(model, tail):
    """The block that hands out PARTS of the system (5, B, tail, U)."""
    import functools
    import jax.numpy as jnp
    from incubator_mxnet_tpu.gluon.block import HybridBlock
    from incubator_mxnet_tpu.ndarray import _apply

    class Parts(HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.model = model

        def forward(self, token_ids):
            embedded = x = model.tok_embed(token_ids)
            own, last = {}, {}
            for layer, letter in zip(model.layers, model.pattern):
                own[letter], last[letter] = layer.mixer(layer.norm1(x)), layer
                x = x + own[letter]
                ffn = getattr(layer.experts, "shared", layer.experts)
                x = x + ffn(layer.norm2(x))
            return _apply(
                functools.partial(_unit_tail, jnp, tail), model.norm_f(x),
                *(last[c].mixer(last[c].norm1(embedded)) for c in "MK"),
                own["M"], own["K"])

    return Parts()


def probe_parts(args, cfg, wl, builder, reference, driver):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from incubator_mxnet_tpu import jit, nd
    traffic, tail = wl["traffic"], wl["check"]["tail_positions"]
    seeds, system, params, batches = args.seed, {}, {}, {}
    for seed in seeds:
        model = builder.build(cfg, seed, traffic["seq_len"])["model"]
        batches[seed] = next(harness.load_module(
            "traffic", traffic["generator"]).generate(traffic, seed, cfg))[0]
        system[seed] = jit.EvalStep(system_parts(model, tail))(
            nd.array(batches[seed])).asnumpy()
        params[seed] = builder.reference_params(model)
        del model

    def evaluate():
        fn = jax.jit(lambda p, t: reference_parts(reference, p, cfg, t, tail))
        return {s: np.asarray(fn(params[s], batches[s])) for s in seeds}

    def line(name, seed, got, exact):
        def weighed(w):
            mix = lambda a: np.concatenate([a[0], w * a[1], w * a[2]], -1)
            return driver.rel_rms(mix(system[seed]), mix(got))
        print("%-12s seed %d | from the exact reference: %s | the sound "
              "system from it: %s | the cell's three parts at weights 1-5: "
              "%s" % (name, seed, ", ".join(
                  "%s %.4g" % (n, driver.rel_rms(g, e))
                  for n, g, e in zip(PARTS, got, exact)), ", ".join(
                  "%s %.4g" % (n, driver.rel_rms(s, g))
                  for n, s, g in zip(PARTS, system[seed], got)), ", ".join(
                  "%.4g" % weighed(w) for w in range(1, 6))), flush=True)

    exact = evaluate()
    for seed in seeds:
        line("exact", seed, exact[seed], exact[seed])
    all_faults = faults(reference)
    for name in [f for f in args.faults.split(",") if f] or list(all_faults):
        with posed(reference, all_faults[name]):
            got = evaluate()
        for seed in seeds:
            line(name, seed, got[seed], exact[seed])


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", default=[0],
                    type=lambda s: [int(n) for n in s.split(",")])
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--faults", default="")
    ap.add_argument("--parts", action="store_true")
    args = ap.parse_args()
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    _, wl, cfg = harness.resolve(bench, CELL, args.rehearse)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, harness.ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np
    driver = harness.load_module("drivers", wl["driver"])
    builder = harness.load_module("builders", cfg["builder"])
    reference = harness.load_module("reference", cfg["reference"])
    if args.parts:
        return probe_parts(args, cfg, wl, builder, reference, driver)
    (seed,) = args.seed
    traffic, check = wl["traffic"], wl["check"]
    built = builder.build(cfg, seed, traffic["seq_len"])
    params = builder.reference_params(built["model"])
    del built
    tokens, labels = next(harness.load_module(
        "traffic", traffic["generator"]).generate(traffic, seed, cfg))
    tail = check["tail_positions"]

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
    for name in wanted:
        with posed(reference, all_faults[name]):
            (out, loss), grads = evaluate()
        zero = {k: np.zeros_like(g) for k, g in grads.items()}
        moved = {k: -np.sign(g) for k, g in grads.items()}
        agreement = driver.update_agreement(zero, moved, grads0)
        lowest = min(agreement, key=lambda k: agreement[k][0])
        print("%-12s outputs rel-rms %.4g, loss rel %.4g, update lowest "
              "%s %.4f; under 0.95: %s" % (
                  name, driver.rel_rms(out, out0),
                  float(np.max(np.abs(loss - loss0) / np.abs(loss0))),
                  lowest, agreement[lowest][0],
                  ", ".join("%s %.3f" % (k, a) for k, (a, _) in
                            sorted(agreement.items()) if a < 0.95) or "none"),
              flush=True)


if __name__ == "__main__":
    main()
