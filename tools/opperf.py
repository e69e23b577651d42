#!/usr/bin/env python
"""Per-op forward/backward micro-benchmark harness
(ref benchmark/opperf/opperf.py — the reference times every registered op;
here the op registry is the nd namespace).

Times eager forward and forward+backward for a representative op set (or
--ops to pick), with warmup and sync, printing a table + one JSON line.

Usage: python tools/opperf.py [--size 1024] [--runs 50] [--ops add,dot]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _op_set(size):
    from incubator_mxnet_tpu import nd
    n = size
    a = nd.random.uniform(shape=(n, n)) + 0.5
    b = nd.random.uniform(shape=(n, n)) + 0.5
    vec = nd.random.uniform(shape=(n * n,))
    img = nd.random.uniform(shape=(8, 16, 64, 64))
    w = nd.random.uniform(shape=(32, 16, 3, 3))
    idx = nd.array((nd.random.uniform(shape=(n,)) * (n - 1)).asnumpy())
    m = max(16, n // 16 * 16)  # batch_dot shapes need /16 divisibility
    bd_a = nd.random.uniform(shape=(16, m // 16 * 4, m // 4))
    bd_b = nd.random.uniform(shape=(16, m // 4, m // 16 * 4))
    bn_g, bn_b = nd.ones((16,)), nd.zeros((16,))
    bn_mm, bn_mv = nd.zeros((16,)), nd.ones((16,))
    return {
        # elemwise / broadcast
        "add": (lambda: a + b, [a, b]),
        "multiply": (lambda: a * b, [a, b]),
        "exp": (lambda: nd.exp(a), [a]),
        "tanh": (lambda: nd.tanh(a), [a]),
        "broadcast_add": (lambda: nd.broadcast_add(a, a[0:1]), [a]),
        # reduce
        "sum": (lambda: a.sum(), [a]),
        "mean_axis": (lambda: a.mean(axis=1), [a]),
        "argmax": (lambda: nd.argmax(a, axis=1), []),
        "topk": (lambda: nd.topk(a, k=8, axis=1), []),
        "sort": (lambda: nd.sort(vec), []),
        # matmul / nn
        "dot": (lambda: nd.dot(a, b), [a, b]),
        "batch_dot": (lambda: nd.batch_dot(bd_a, bd_b), [bd_a, bd_b]),
        "FullyConnected": (lambda: nd.FullyConnected(
            a, b, None, num_hidden=n, no_bias=True), [a, b]),
        "Convolution": (lambda: nd.Convolution(
            img, w, None, kernel=(3, 3), num_filter=32, no_bias=True,
            pad=(1, 1)), [img, w]),
        "softmax": (lambda: nd.softmax(a, axis=-1), [a]),
        # fwd column = inference-mode kernel; fwd+bwd runs under record()
        # and therefore times the training kernel (batch stats + VJP)
        "BatchNorm": (lambda: nd.BatchNorm(
            img, bn_g, bn_b, bn_mm, bn_mv), [img]),
        # indexing / shapes
        "take": (lambda: nd.take(a, idx), [a]),
        "transpose": (lambda: a.T.copy(), [a]),
        "concat": (lambda: nd.concat(a, b, dim=1), [a, b]),
        "one_hot": (lambda: nd.one_hot(idx, n), []),
    }


def bench_op(name, fn, grad_args, runs, warmup=5):
    from incubator_mxnet_tpu import autograd, nd

    for _ in range(warmup):
        fn().wait_to_read()
    t0 = time.perf_counter()
    for _ in range(runs):
        out = fn()
    out.wait_to_read()
    fwd_us = (time.perf_counter() - t0) / runs * 1e6

    bwd_us = float("nan")
    if grad_args:
        for x in grad_args:
            x.attach_grad()

        def fb():
            with autograd.record():
                loss = fn().sum()
            loss.backward()
            return loss

        for _ in range(warmup):
            fb()
        nd.waitall()  # backward dispatch is async: drain grads, not just loss
        t0 = time.perf_counter()
        for _ in range(runs):
            fb()
        nd.waitall()
        bwd_us = (time.perf_counter() - t0) / runs * 1e6
    return fwd_us, bwd_us


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", type=int, default=1024)
    ap.add_argument("--runs", type=int, default=30)
    ap.add_argument("--ops", default=None, help="comma-separated subset")
    args = ap.parse_args()

    import incubator_mxnet_tpu as mx
    mx.random.seed(0)
    table = _op_set(args.size)
    if args.ops:
        names = [t.strip() for t in args.ops.split(",") if t.strip()]
        unknown = [t for t in names if t not in table]
        if unknown:
            ap.error("unknown ops %s; choose from: %s"
                     % (unknown, ", ".join(sorted(table))))
    else:
        names = sorted(table)
    results = {}
    print("%-18s %12s %16s" % ("op", "fwd us", "fwd+bwd us"))
    for name in names:
        fn, grad_args = table[name]
        fwd, bwd = bench_op(name, fn, grad_args, args.runs)
        results[name] = {"fwd_us": round(fwd, 1),
                         "fwd_bwd_us": None if bwd != bwd else round(bwd, 1)}
        print("%-18s %12.1f %16s" % (name, fwd,
                                     "-" if bwd != bwd else "%.1f" % bwd))
    print(json.dumps({"metric": "opperf", "size": args.size,
                      "runs": args.runs, "results": results}))


if __name__ == "__main__":
    main()
