"""Cross-backend numerics sweep report (ref tests/python/gpu/
test_operator_gpu.py — the re-run-the-CPU-suite-on-device strategy,
distilled into an op-table walk with per-dtype tolerances).

Run on a TPU host: compares every table op CPU vs TPU at fp32/bf16/fp16.
Prints a markdown table; nonzero exit if any MISMATCH/ERROR rows appear.

Usage: python tools/numerics_sweep.py [--quick]
"""
import sys

from incubator_mxnet_tpu.test_utils import op_consistency_sweep
from incubator_mxnet_tpu import context


def main():
    quick = "--quick" in sys.argv
    rows = op_consistency_sweep(quick=quick)
    ctxs = "cpu vs %s" % context.current_context()
    print("# Numerics sweep (%s)\n" % ctxs)
    print("| op | dtype | max rel err | status |")
    print("|---|---|---|---|")
    bad = 0
    for name, dt, err, status in rows:
        if status != "ok":
            bad += 1
        print("| %s | %s | %s | %s |"
              % (name, dt, "%.2e" % err if err is not None else "-", status))
    n = len(rows)
    print("\n%d/%d clean" % (n - bad, n))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
