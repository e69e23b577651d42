"""Pilot measurement: fused Pallas conv-backward vs XLA's dgrad+wgrad pair.

The experiment behind docs/PERF_RESNET.md's "2.7x byte inflation" claim:
for a ResNet bottleneck 3x3/s1 stage, time BOTH lowerings of (dx, dw) on
the real chip via the device trace (the wall clock of these short
programs is dispatch-dominated; the trace's device track is not), and read XLA's
bytes_accessed straight from the trace against the kernel's analytic
fused-ideal bytes.

Usage: python benchmark/conv_bwd_pilot.py [stage ...] [--out /tmp/convpilot]
  stage in {conv2, conv3, conv4, conv5} (ResNet-50 bottleneck 3x3 shapes
  at batch 256) — default conv3, the stage PERF_RESNET.md names.
Prints one JSON line per stage + a markdown row for the docs table.
"""
import glob
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from incubator_mxnet_tpu.ops.conv_bwd import (
    conv3x3_bwd, conv3x3_bytes, _conv_fwd_ref)

STAGES = {
    # ResNet-50 bottleneck 3x3 convs at batch 256 (NHWC)
    "conv2": (256, 56, 56, 64, 64),
    "conv3": (256, 28, 28, 128, 128),
    "conv4": (256, 14, 14, 256, 256),
    "conv5": (256, 7, 7, 512, 512),
}
# conv5's (9C,K) fp32 dw accumulator is 9.4 MB on its own; a 2-image block
# keeps the rest under the scoped-vmem limit
BLOCK_N = {"conv5": 2}
REPS = 5


def device_ops(outdir):
    """All device-track ops from the newest trace under outdir."""
    paths = glob.glob(os.path.join(
        outdir, "plugins/profile/*/*.trace.json.gz"))
    path = max(paths, key=os.path.getmtime)
    d = json.load(gzip.open(path))
    return [e for e in d["traceEvents"]
            if e.get("pid") == 3 and e.get("tid") == 3 and e.get("ph") == "X"]


def profile(fn, args, outdir):
    """Trace REPS runs; return (device_ms_per_step, bytes_per_step)."""
    jax.block_until_ready(fn(*args))          # compile outside the trace
    with jax.profiler.trace(outdir):
        for _ in range(REPS):
            out = fn(*args)
        jax.block_until_ready(out)
    ops = device_ops(outdir)
    tot_us = sum(e["dur"] for e in ops)
    tot_bytes = sum(int(e.get("args", {}).get("bytes_accessed", 0) or 0)
                    for e in ops)
    return tot_us / REPS / 1e3, tot_bytes / REPS


def run_stage(name, out_root):
    N, H, W, C, K = STAGES[name]
    dt = jnp.bfloat16
    x = jax.random.normal(jax.random.PRNGKey(0), (N, H, W, C), dt)
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, C, K), dt)
    dy = jax.random.normal(jax.random.PRNGKey(2), (N, H, W, K), dt)

    xla = jax.jit(lambda x, w, dy: jax.vjp(_conv_fwd_ref, x, w)[1](dy))
    pal = jax.jit(lambda x, w, dy: conv3x3_bwd(
        x, dy, w, block_n=BLOCK_N.get(name)))

    xla_ms, xla_bytes = profile(xla, (x, w, dy), os.path.join(
        out_root, name + "_xla"))
    pal_ms, _ = profile(pal, (x, w, dy), os.path.join(
        out_root, name + "_pallas"))

    # numerics cross-check on the same data (bf16-level agreement)
    rx, rp = xla(x, w, dy), pal(x, w, dy)
    dx_err = float(jnp.max(jnp.abs(rp[0].astype(jnp.float32)
                                   - rx[0].astype(jnp.float32))))
    dx_scale = float(jnp.max(jnp.abs(rx[0].astype(jnp.float32))))

    ideal = conv3x3_bytes((N, H, W, C), K)
    flops = 2 * 2 * N * H * W * 9 * C * K
    rec = {
        "stage": name, "shape": [N, H, W, C, K],
        "xla_ms": round(xla_ms, 3), "pallas_ms": round(pal_ms, 3),
        "speedup": round(xla_ms / pal_ms, 2),
        "xla_bytes_gb": round(xla_bytes / 1e9, 3),
        "ideal_bytes_gb": round(ideal / 1e9, 3),
        "byte_inflation": round(xla_bytes / ideal, 2) if xla_bytes else None,
        "gflop": round(flops / 1e9, 1),
        "pallas_tflops": round(flops / (pal_ms / 1e3) / 1e12, 1),
        "xla_tflops": round(flops / (xla_ms / 1e3) / 1e12, 1),
        "dx_rel_err": round(dx_err / max(dx_scale, 1e-9), 4),
    }
    print(json.dumps(rec), flush=True)
    print("| %s | %dx%dx%d,C=%d | %.2f | %.2f | %.2fx | %.1f | %.1f | %.1fx |"
          % (name, N, H, W, C, xla_ms, pal_ms, rec["speedup"],
             rec["xla_bytes_gb"], rec["ideal_bytes_gb"],
             rec["byte_inflation"] or 0), flush=True)
    return rec


def main():
    argv = sys.argv[1:]
    out_root = "/tmp/convpilot"
    if "--out" in argv:
        i = argv.index("--out")
        out_root = argv[i + 1]
        del argv[i:i + 2]
    stages = [a for a in argv if not a.startswith("--")] or ["conv3"]
    for s in stages:
        run_stage(s, out_root)


if __name__ == "__main__":
    main()
