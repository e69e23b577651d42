"""What the Keye cell's `check` cannot pose or see, run by hand when its
limits are set (docs/PERF_KEYE_VL2.md), not by the benchmark.

    python3 perfbench/probe_sparse.py --workload <cell> [--seed n]
        [--parts op,choices,faults,loads] [--steps n] [--rehearse]

`op`: `ops.sparse_attention` alone against the reference's, float32
operands at the cell's shape (one sequence, the configuration's heads):
outputs, the KL and the six gradients, rel-rms; then the op's milliseconds
in bfloat16, forward and forward + backward.
`choices`: layer by layer, the share of the (query, key) pairs the program
chooses (bfloat16 maps, on its own layer input) that the reference chooses
too (float32, same input), and whether the program chose exactly
min(topk, t + 1) keys a row.
`faults`: the float32 reference run again as five broken programs — no
selection (dense causal attention), selection by the first `topk` keys,
index scores without the relu, index scores rounded to bfloat16, the KL
term left out of the loss — each through the driver's own comparison
(`rel_rms`, `update_agreement`, the three inequalities that decide
`correct`); the line ends with the limits it fails by, or with `passes`.
`loads` (not among the default parts): the cell's own train step (the
driver's trainer) for `--steps` steps on the traffic's batches; before each
step, on that step's batch, the rows every HELD expert of every layer is
sent (fewest, mean, most, against the even share) and how much of each
router's input is one vector at every position (the norm of the mean row
over the rows' rms norm): a share's experts that drift empty, or a router
whose tokens all look alike, show here step by step.
"""
import argparse
import os
import sys
import time

import run as harness            # perfbench/run.py, beside this file


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--parts", default="op,choices,faults")
    ap.add_argument("--steps", type=int, default=24)
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
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.ops import sparse_attention as op
    driver = harness.load_module("drivers", wl["driver"])
    builder = harness.load_module("builders", cfg["builder"])
    reference = harness.load_module("reference", cfg["reference"])
    traffic, check = wl["traffic"], wl["check"]
    shapes = builder.shapes(cfg)
    seq, topk = traffic["seq_len"], shapes["topk"]
    parts = args.parts.split(",")

    def rel(got, want):
        return driver.rel_rms(jax.device_get(got), jax.device_get(want))

    # ---- the op alone ----------------------------------------------------
    if "op" in parts:
        keys = jax.random.split(jax.random.PRNGKey(args.seed % (2 ** 31)), 7)
        h, g, d = shapes["q_heads"], shapes["kv_heads"], shapes["head_dim"]
        j, di = shapes["index_heads"], shapes["index_dim"]
        operands = tuple(
            jax.random.normal(key, (1, seq) + tail, jnp.float32) * scale
            for key, tail, scale in zip(keys, (
                (h, d), (g, d), (g, d), (j, di), (di,), (j,)),
                (1, 1, 1, 1, 1, (j * di) ** -0.5)))
        cot = jax.random.normal(keys[6], operands[0].shape, jnp.float32)

        def of(attend):
            def loss(*a):
                o, kl = attend(*a, topk)
                return (o.astype(jnp.float32) * cot).sum() + kl.sum(), (o, kl)
            return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6)),
                                              has_aux=True))

        with jax.default_matmul_precision("highest"):
            (_, (o_ref, kl_ref)), g_ref = of(reference.sparse_attention)(
                *operands)
            (_, (o_sys, kl_sys)), g_sys = of(op.sparse_attention)(*operands)
        print("the op alone, float32, 1 x %d x %d x %d (%d kv, %d x %d "
              "index heads), %d keys a query: o rel-rms %.3g, KL %.6g vs "
              "%.6g, gradients rel-rms %s" % (
                  seq, h, d, g, j, di, topk, rel(o_sys, o_ref),
                  float(kl_sys[0]), float(kl_ref[0]), ", ".join(
                      "d%s %.3g" % (n, rel(a, b)) for n, a, b in zip(
                          ("q", "k", "v", "qi", "ki", "w"), g_sys, g_ref))),
              flush=True)
        low = tuple(x.astype(jnp.bfloat16) for x in operands[:5]) \
            + operands[5:]
        forward = jax.jit(lambda *a: op.sparse_attention(*a, topk))
        for name, fn in (("forward", forward), ("forward + backward",
                                                of(op.sparse_attention))):
            jax.block_until_ready(fn(*low))
            t0 = time.perf_counter()
            for _ in range(3):
                out = fn(*low)
            jax.block_until_ready(out)
            print("the op alone, bfloat16, %s: %.1f ms a call (host clock "
                  "around 3 calls)" % (
                      name, (time.perf_counter() - t0) / 3 * 1e3), flush=True)
        del operands, low, g_ref, g_sys, o_ref, o_sys

    if not {"choices", "faults", "loads"} & set(parts):
        return
    built = builder.build(cfg, args.seed, seq)
    model = built["model"]
    params = builder.reference_params(model)
    tokens, labels = next(harness.load_module(
        "traffic", traffic["generator"]).generate(traffic, args.seed, cfg))
    n, tail = check["sequences"], check["tail_positions"]
    tokens, labels = tokens[:n], labels[:n]

    # ---- a share's rows, step by step (last: the steps move the weights) --
    if "loads" in parts:
        if set(parts) - {"loads", "op"}:
            raise SystemExit("probe_sparse: run `loads` without `choices` "
                             "and `faults`: its steps move the weights")
        from incubator_mxnet_tpu import gluon, jit
        trainer = gluon.Trainer(
            built["train_net"].collect_params(), "adam",
            {"learning_rate": 1e-4, "multi_precision": True})
        step = jit.TrainStep(built["train_net"], built["loss"], trainer)
        readings = jit.EvalStep(builder.router_readings(model))
        first, count = cfg["first_held_expert"], shapes["experts_held"]
        even = traffic["batch"] * seq * cfg["num_experts_per_tok"] \
            / shapes["experts_routed"]
        source = harness.load_module(
            "traffic", traffic["generator"]).generate(traffic, args.seed, cfg)
        fewest, total = [], []
        for i in range(args.steps):
            batch, target = next(source)
            means, loads = (x.asnumpy() for x in readings(nd.array(batch)))
            held = loads[:, first:first + count]
            fewest.append(held.min(-1))
            total.append(held.mean(-1))
            t0 = time.perf_counter()
            loss = float(step(nd.array(batch), nd.array(target))
                         .asnumpy().mean())
            print("step %2d loss %.4f (%.0f ms); held rows fewest / mean / "
                  "most (even %.0f), one-vector share of the router's "
                  "input, a layer: %s" % (
                      i + 1, loss, (time.perf_counter() - t0) * 1e3, even,
                      "; ".join("%d / %.0f / %d, %.2f" % (
                          h.min(), h.mean(), h.max(),
                          np.linalg.norm(m) / np.sqrt(m.size))
                          for h, m in zip(held, means))), flush=True)
        print("over %d steps: the fewest rows a held expert had, a layer: "
              "%s; the mean rows a held expert had, a layer: %s (even %.0f)"
              % (args.steps, np.min(fewest, 0).tolist(),
                 np.round(np.mean(total, 0), 1).tolist(), even), flush=True)
        return

    # ---- the choices, layer by layer -------------------------------------
    if "choices" in parts:
        positions = model.text_positions(nd.array(tokens))
        block = op._blocks(seq, None, None)[0]

        @jax.jit
        def agreement(sys_in, ref_in):
            tau, cut = op.select_thresholds(*sys_in, topk)

            def one(strip):
                mine = op.chosen_strip(sys_in[0][0], sys_in[1][0],
                                       sys_in[2][0], tau[0], cut[0], strip)
                rows = jax.lax.dynamic_slice_in_dim
                theirs = reference._chosen_block(reference._index_block(
                    rows(ref_in[0], strip * block, block, 1), ref_in[1],
                    rows(ref_in[2], strip * block, block, 1),
                    strip * block), topk)[0]
                return (jnp.sum(mine & theirs), jnp.sum(mine),
                        jnp.sum(theirs))

            both, mine, theirs = jax.lax.map(one, jnp.arange(seq // block))
            return both.sum(), mine.sum(), theirs.sum()

        x = model.tok_embed(nd.array(tokens))
        want = builder.chosen_pairs(seq, topk)
        for i, (layer, p) in enumerate(zip(model.layers, params["layers"])):
            normed = layer.norm1(x)
            sys_in = tuple(t._data for t in layer.attn.index(normed,
                                                              positions))
            with jax.default_matmul_precision("highest"):
                ref_in = reference.indexer(
                    reference._f32(p), normed._data.astype(jnp.float32),
                    positions._data, cfg)
            both, mine, theirs = (int(v) for v in agreement(sys_in, ref_in))
            print("layer %d: the program chose %d pairs, the reference %d "
                  "(min(topk, t + 1) a row is %d); %d chosen by both, "
                  "%.4f %% of the program's" % (
                      i, mine, theirs, want, both, 100.0 * both / mine),
                  flush=True)
            x = layer(x, positions)[0]

    # ---- the five broken programs ----------------------------------------
    if "faults" not in parts:
        return

    def evaluate():
        out, loss = jax.device_get(jax.jit(lambda p: reference.forward(
            p, cfg, tokens, labels, tail))(params))
        grads = jax.device_get(jax.jit(lambda p: reference.checked_grads(
            p, cfg, tokens, labels))(params))
        return out, loss, grads

    exact = {name: getattr(reference, name) for name in (
        "_chosen_block", "_index_block", "_loss_terms")}

    def causal(scores, topk):
        return scores > -jnp.inf

    def first_keys(scores, topk):
        return (scores > -jnp.inf) & (jnp.arange(scores.shape[-1]) < topk)

    def no_relu(qi_blk, ki, w_blk, start):
        relu, jax.nn.relu = jax.nn.relu, lambda r: r
        try:
            return exact["_index_block"](qi_blk, ki, w_blk, start)
        finally:
            jax.nn.relu = relu

    def bf16_scores(qi_blk, ki, w_blk, start):
        scores = exact["_index_block"](qi_blk, ki, w_blk, start)
        seen = scores > -jnp.inf
        safe = jnp.where(seen, scores, 0.0)
        return jnp.where(seen, safe + jax.lax.stop_gradient(
            jax.lax.reduce_precision(safe, 8, 7) - safe), -jnp.inf)

    def no_kl(p, config, tokens, labels, positions):
        lm, li = exact["_loss_terms"](p, config, tokens, labels, positions)
        return lm, 0.0 * jax.lax.stop_gradient(li)

    out0, loss0, grads0 = evaluate()
    print("limits of %s: outputs %g, loss %g, update %g" % (
        args.workload, check["outputs_rel_rms"], check["loss_rel"],
        check["update_agreement"]))
    for name, patch in (
            ("no selection (dense causal)", {"_chosen_block": causal}),
            ("the first %d keys" % topk, {"_chosen_block": first_keys}),
            ("index scores without relu", {"_index_block": no_relu}),
            ("index scores in bfloat16", {"_index_block": bf16_scores}),
            ("the KL term left out", {"_loss_terms": no_kl})):
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
              "%.4f (%.0f %% moved); %s: %s" % (
                  name, out_err, loss_err, worst, agreement[worst][0],
                  100 * agreement[worst][1], ", ".join(
                      "%s %.4f" % (k, agreement[k][0])
                      for k in ("iq", "ik", "iw", "q", "k", "v", "o",
                                "router")),
                  "FAILS BY " + ", ".join(fails) if fails else "passes"),
              flush=True)


if __name__ == "__main__":
    main()
