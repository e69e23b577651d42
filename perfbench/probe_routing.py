"""How often does the system route a token as the float32 reference does,
and what does a different choice cost? Run once when a mixture-of-experts
cell's limits are set (PERF.md, Findings), not by the benchmark.

    python3 perfbench/probe_routing.py --workload <cell> [--seed n] [--rehearse]

Top-k is discontinuous: where two experts' probabilities are closer than the
rounding of the router's bfloat16 input, system and reference choose
differently, and the token's output then differs by two whole expert
outputs, which is no error of precision. On the cell's first batch (the
`check`'s sequences) this prints the share of (token, expert) choices of the
last layer that are the reference's, the share of tokens whose k choices all
are, and the outputs' rel-rms error over the checked tail split the same
way, beside the limit the workload file sets on the whole.
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
        os.environ["MXTPU_FLASH_INTERPRET"] = "1"
    sys.path.insert(0, harness.ROOT)
    import jax
    import numpy as np
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.parallel.moe import MoELayer
    driver = harness.load_module("drivers", wl["driver"])
    builder = harness.load_module("builders", cfg["builder"])
    reference = harness.load_module("reference", cfg["reference"])
    traffic, check = wl["traffic"], wl["check"]
    built = builder.build(cfg, args.seed, traffic["seq_len"])
    tokens, labels = next(harness.load_module(
        "traffic", traffic["generator"]).generate(traffic, args.seed, cfg))
    n, tail = check["sequences"], check["tail_positions"]
    tokens, labels = tokens[:n], labels[:n]

    # the system's choices, where its routers return them (the last call
    # is the last layer's)
    chosen, route = [], MoELayer.route
    MoELayer.route = lambda self, *a: chosen.append(route(self, *a)) \
        or chosen[-1]
    try:
        feats = built["eval_net"](nd.array(tokens)).asnumpy()
    finally:
        MoELayer.route = route
    got = np.asarray(chosen[-1][3])                       # (T, k)
    params = builder.reference_params(built["model"])
    want = np.asarray(jax.jit(
        lambda p: reference.routing(p, cfg, tokens))(params))
    want_feats = np.asarray(jax.jit(lambda p: reference.forward(
        p, cfg, tokens, labels, tail))(params)[0])

    k = got.shape[1]
    shared = np.array([len(set(a) & set(b)) for a, b in zip(got, want)])
    same = (shared == k).reshape(n, -1)[:, -tail:]        # the checked tail
    feats = np.asarray(feats, np.float32)[:, -tail:]
    print("%s seed %d: %d tokens x top-%d of %d experts" % (
        args.workload, args.seed, len(got), k, cfg["num_experts"]))
    print("choices that are the reference's: %.4f of (token, expert) "
          "pairs; tokens whose %d choices all are: %.4f"
          % (shared.mean() / k, k, (shared == k).mean()))
    print("outputs rel-rms over the last %d positions: all %.4g (limit %g); "
          "tokens routed as the reference (%.1f %%) %.4g; the others %.4g"
          % (tail, driver.rel_rms(feats, want_feats),
             check["outputs_rel_rms"], 100 * same.mean(),
             driver.rel_rms(feats[same], want_feats[same]),
             driver.rel_rms(feats[~same], want_feats[~same])
             if (~same).any() else float("nan")), flush=True)


if __name__ == "__main__":
    main()
