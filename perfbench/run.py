"""perfbench/run.py — one run of one benchmark cell.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data this file finds by name:
`BENCHMARK.json` (cells, configurations, metrics), `workloads/<cell>.json`
(driver, traffic generator and their parameters), the configuration's own
file, `builders/`, `drivers/`, `traffic/`, `reference/`, `layer_metrics/`.
Adding a cell, a configuration or a per-layer metric adds files and
`BENCHMARK.json` entries; nothing here is edited.

The run loads, warms every shape (that is `setup_s`), measures for
`--seconds`, checks the outputs against the configuration's float32
reference, and prints ONE JSON object as the last line of stdout with the
keys `correct`, `attempted`, `failed`, `metrics`, `device` (and
`breakdown` when traced). `--trace 0` reports the cell's end-to-end
metrics, `--trace 1` its per-layer metrics from a profiler capture.

It measures the chip: any platform but `tpu`, fewer chips than the cell
asks for, or a `device_kind` missing from `peaks.json` ends the run with a
non-zero code and no result line. `--rehearse` runs the same control flow
at the cell's tiny preset on the CPU (kernels interpreted); it prints
`metrics: {}` and the device as `cpu`, and is what the tests use.
"""
import time

_T_PROCESS = time.perf_counter()     # set-up is counted from here

import argparse
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: traces and other run-time leftovers; listed in .gitignore
OUT_DIR = os.path.join(ROOT, ".perfbench_out")


def log(msg):
    print(msg, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """perfbench/<kind>/<name>.py as a module. Names hold dots and
    hyphens (a configuration's reference is named after it), so this goes
    by path and not through the import system."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit("perfbench: no %s named %r (%s)" % (kind, name, path))
    mod_name = "perfbench_%s_%s" % (kind, "".join(
        c if c.isalnum() else "_" for c in name))
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def resolve(bench, cell_name, rehearse):
    """-> (cell entry, its workload file, the configuration as it is run).
    `--rehearse` overlays the workload's and the configuration's `rehearse`
    presets, which exist for the CPU tests and never run on the chip."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise SystemExit("perfbench: no cell %r in BENCHMARK.json (have %s)"
                         % (cell_name, sorted(cells)))
    cell = cells[cell_name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(ROOT, entry["file"])
    workload = load_json(HERE, "workloads", cell_name + ".json")
    if rehearse:
        config = {**config, **config.get("rehearse", {})}
        workload = {**workload, **workload.get("rehearse", {})}
    return cell, workload, config


def metrics_of(bench, group, cell_name):
    """The metrics of `group` this cell reports: all without a `workloads`
    key, and those that list the cell."""
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


class Run:
    """What a driver is handed: the cell's data, the clock that set-up is
    counted from, and the loaders for the cell's other files."""

    def __init__(self, args, cell, workload, config, device, peaks):
        self.cell, self.workload, self.config = cell, workload, config
        self.seed, self.seconds = args.seed, args.seconds
        self.trace, self.rehearse = bool(args.trace), args.rehearse
        self.device, self.peaks = device, peaks
        self.t_process = _T_PROCESS
        self.trace_dir = os.path.join(OUT_DIR, "trace", cell["name"])
        self.log = log
        self.load_module = load_module


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (default: "
                         "BENCHMARK.json run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny preset on the CPU, kernels interpreted: "
                         "control flow only, prints no metric")
    args = ap.parse_args(argv)

    bench = load_json(ROOT, "BENCHMARK.json")
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    cell, workload, config = resolve(bench, args.workload, args.rehearse)

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["MXTPU_FLASH_INTERPRET"] = "1"
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                "--xla_force_host_platform_device_count=%d %s"
                % (cell["chips"], os.environ.get("XLA_FLAGS", "")))
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    try:
        import jax
        # the import places JAX's persistent cache at <checkout>/.jax_cache
        # (config.place_compile_cache), or leaves JAX_COMPILATION_CACHE_DIR
        import incubator_mxnet_tpu  # noqa: F401
    except ImportError as e:
        raise SystemExit("perfbench: the system under test is not in this "
                         "checkout: %s" % e)

    devices = jax.devices()
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices)}
    if len(devices) < cell["chips"]:
        raise SystemExit("perfbench: cell %s needs %d chip(s), JAX has %d"
                         % (cell["name"], cell["chips"], len(devices)))
    if args.rehearse:
        peaks = None
        log("REHEARSAL of %s on %s: tiny preset, no metric is printed"
            % (cell["name"], device))
    else:
        if d.platform != "tpu":
            raise SystemExit("perfbench: no accelerator: JAX's default "
                             "backend is %r (%r); the benchmark measures "
                             "the chip and does not fall back"
                             % (d.platform, d.device_kind))
        table = load_json(HERE, "peaks.json")["device_kinds"]
        if d.device_kind not in table:
            raise SystemExit("perfbench: device_kind %r has no row in "
                             "perfbench/peaks.json (%s)"
                             % (d.device_kind, sorted(table)))
        peaks = table[d.device_kind]
    log("cell %s seed %d seconds %g trace %d on %s"
        % (cell["name"], args.seed, args.seconds, args.trace, device))

    run = Run(args, cell, workload, config, device, peaks)
    result = load_module("drivers", workload["driver"]).run(run)

    peak_bytes = max((dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for dev in devices[:cell["chips"]])
    result["context"]["peak_bytes"] = peak_bytes
    metrics = {}
    if not args.rehearse:
        if args.trace:
            for m in metrics_of(bench, "per_layer", cell["name"]):
                reader = load_module("layer_metrics", m["name"])
                value = reader.compute(result["context"])
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            for m in metrics_of(bench, "end_to_end", cell["name"]):
                metrics[m["name"]] = {
                    "value": result["end_to_end"][m["name"]],
                    "unit": m["unit"]}
    device["memory_peak_bytes"] = int(peak_bytes)
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]),
            "metrics": metrics, "device": device}
    reduced = result["context"].get("trace")
    if args.trace and reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                             "idle_gaps": reduced["idle_gaps"][:10]}
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
