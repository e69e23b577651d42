"""Per-fusion roofline analysis from a jax.profiler trace.json.gz.

The XLA-on-TPU trace annotates every device op with model_flops and
bytes_accessed; this script aggregates them into the per-op and
per-category tables committed in docs/PERF_RESNET.md, including each op's
achieved HBM bandwidth / FLOP rate and its distance from the chip roofline.
The peaks come from the one table (telemetry/devstats.PEAK_TABLE) for the
device_kind the trace was captured on — named on the command line, because
a recorded trace is analysed on any host; an unknown kind is an error.

Usage: python benchmark/roofline.py <trace.json.gz> <device_kind> [n_steps]
  e.g. python benchmark/roofline.py trace.json.gz "TPU v5 lite" 3
"""
import collections
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load_ops(path):
    d = json.load(gzip.open(path))
    # pid 3 / tid 3 is the "XLA Ops" device track
    return [e for e in d["traceEvents"]
            if e.get("pid") == 3 and e.get("tid") == 3 and e.get("ph") == "X"]


def category(e):
    a = e.get("args", {})
    tf, hc = a.get("tf_op", ""), a.get("hlo_category", "")
    src, ln = a.get("source", ""), a.get("long_name", "")
    if "convolution" in hc:
        return "conv bwd" if "transpose" in tf else "conv fwd"
    if "batch_norm" in tf or "1351" in src:
        return "batchnorm"
    if "relu" in tf or "maximum" in tf:
        return "relu"
    if "select_and_scatter" in ln or "select-and-scatter" in hc \
            or "reduce_window" in tf:
        return "pool"
    if "/add:" in tf:
        return "residual-add"
    if "copy" in hc or e["name"].startswith("copy"):
        return "copy"
    if "optimizer" in src:
        return "optimizer"
    return "other"


def main():
    from incubator_mxnet_tpu.telemetry import devstats
    path = sys.argv[1]
    PEAK_F, _peak_int8, PEAK_B = devstats.device_peaks(sys.argv[2])
    steps = int(sys.argv[3]) if len(sys.argv) > 3 else 5
    ops = load_ops(path)
    per_op = {}
    per_cat = collections.defaultdict(lambda: dict(us=0.0, f=0, b=0))
    for e in ops:
        a = e.get("args", {})
        f = int(a.get("model_flops", 0) or 0)
        b = int(a.get("bytes_accessed", 0) or 0)
        r = per_op.setdefault(e["name"], dict(us=0.0, f=0, b=0,
                                              cat=category(e)))
        r["us"] += e["dur"]; r["f"] += f; r["b"] += b
        c = per_cat[category(e)]
        c["us"] += e["dur"]; c["f"] += f; c["b"] += b

    tu = sum(r["us"] for r in per_op.values())
    tf_ = sum(r["f"] for r in per_op.values())
    tb = sum(r["b"] for r in per_op.values())
    floor = sum(max(r["f"] / PEAK_F, r["b"] / PEAK_B)
                for r in per_op.values())
    print(f"device step time: {tu/steps/1e3:.2f} ms | "
          f"{tf_/steps/1e12:.2f} TFLOP -> MFU "
          f"{tf_/steps/(tu/steps*1e-6)/PEAK_F*100:.1f}% | "
          f"HBM {tb/steps/1e9:.1f} GB -> "
          f"{tb/steps/(tu/steps*1e-6)/PEAK_B*100:.1f}% of BW | "
          f"per-op roofline floor {floor/steps*1e3:.2f} ms "
          f"({floor/(tu*1e-6)*100:.0f}% achieved)")
    print(f"\n{'category':14} {'%time':>6} {'ms/st':>7} {'GB/st':>6} "
          f"{'TFLOP/st':>8} {'GB/s':>6} {'TF/s':>6}")
    for c, r in sorted(per_cat.items(), key=lambda kv: -kv[1]["us"]):
        us = r["us"] / steps
        print(f"{c:14} {r['us']/tu*100:6.1f} {us/1e3:7.2f} "
              f"{r['b']/steps/1e9:6.2f} {r['f']/steps/1e12:8.3f} "
              f"{r['b']/steps/(us*1e-6)/1e9:6.0f} "
              f"{r['f']/steps/(us*1e-6)/1e12:6.1f}")
    print(f"\ntop ops:\n{'op':28} {'%t':>5} {'ms/st':>6} {'TF/s':>6} "
          f"{'GB/s':>6} {'%roof':>5}  cat")
    for n, r in sorted(per_op.items(), key=lambda kv: -kv[1]["us"])[:25]:
        us = r["us"] / steps
        fl = max(r["f"] / steps / PEAK_F, r["b"] / steps / PEAK_B)
        print(f"{n[:28]:28} {r['us']/tu*100:5.1f} {us/1e3:6.2f} "
              f"{r['f']/steps/(us*1e-6)/1e12:6.1f} "
              f"{r['b']/steps/(us*1e-6)/1e9:6.0f} "
              f"{fl/(us*1e-6)*100:5.0f}  {r['cat']}")


if __name__ == "__main__":
    main()
