"""profsum — summarize and diff jax.profiler capture directories.

The CLI face of telemetry/profstats.py (one trace parser in the repo):

    python tools/profsum.py <capture_dir | trace.json[.gz]> [--top N]
                            [--json] [--out summary.json]
    python tools/profsum.py diff <a> <b> [--threshold R] [--min-duty D]
                            [--json] [--inject-slowdown FACTOR]

``summarize`` prints a ranked-hotspot table
(profstats.format_table); ``--out`` writes the full summary
JSON, the artifact ``diff`` consumes. ``diff`` accepts summary JSON
files or capture dirs/trace files directly, and reports per-op / per-
category *duty* regressions (self-time normalized by the capture window,
so two captures of different lengths compare honestly) in the shared
mxtpulint/promcheck report shape {"tool", "ok", "findings", "counts",
"baselined"} — a latency regression becomes attributable to a
named op. ``--inject-slowdown`` doubles (or xN) the top op of ``b``
before diffing: the CI canary proving the gate still fires.

Rules: S001 an op's duty regressed (or a new op went hot);
       S002 a category's duty regressed.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEFAULT_THRESHOLD = 1.5
DEFAULT_MIN_DUTY = 0.01


def _profstats():
    from incubator_mxnet_tpu.telemetry import profstats
    return profstats


def load_input(path):
    """A capture dir, a single trace file, or a summary JSON written by
    ``--out`` — all become the shared summary dict."""
    ps = _profstats()
    if os.path.isdir(path):
        return ps.summarize_capture(path)
    if path.endswith((".trace.json", ".trace.json.gz")):
        return ps.summarize_trace(path)
    with open(path) as f:
        summary = json.load(f)
    if not isinstance(summary, dict) or \
            not str(summary.get("schema", "")).startswith(
                "mxtpu-profstats-summary"):
        raise ValueError("%s is not a profstats summary (schema %r)"
                         % (path, summary.get("schema")
                            if isinstance(summary, dict) else None))
    return summary


def _duty(self_us, window_us):
    return (self_us / window_us) if window_us > 0 else 0.0


def diff_report(a, b, threshold=DEFAULT_THRESHOLD,
                min_duty=DEFAULT_MIN_DUTY, b_path="<b>"):
    """The shared CI report shape over two summaries: every op (S001)
    and category (S002) whose duty — self-time per window microsecond —
    grew by >= ``threshold`` x in ``b``, ignoring ops under ``min_duty``
    in ``b`` (noise floor). Identical summaries diff empty."""
    findings = []
    wa = float(a.get("window_us") or 0.0)
    wb = float(b.get("window_us") or 0.0)
    a_ops = {(o["op"], o.get("module")): o for o in a.get("ops") or []}
    for o in b.get("ops") or []:
        db = _duty(o["self_us"], wb)
        if db < min_duty:
            continue
        ref = a_ops.get((o["op"], o.get("module")))
        da = _duty(ref["self_us"], wa) if ref else 0.0
        if ref is None:
            findings.append({
                "path": b_path, "line": 0, "rule": "S001",
                "message": "new hot op %r (%s): %.2f%% device duty "
                           "(absent from baseline)"
                           % (o["op"], o["category"], 100.0 * db)})
        elif da > 0 and db / da >= threshold:
            findings.append({
                "path": b_path, "line": 0, "rule": "S001",
                "message": "op %r (%s) duty x%.2f: %.2f%% -> %.2f%% of "
                           "the capture window (self %.3f ms -> %.3f ms)"
                           % (o["op"], o["category"], db / da,
                              100.0 * da, 100.0 * db,
                              ref["self_us"] / 1e3, o["self_us"] / 1e3)})
    a_cats = a.get("categories") or {}
    for cat, info in sorted((b.get("categories") or {}).items()):
        db = _duty(info["self_us"], wb)
        if db < min_duty:
            continue
        ref = a_cats.get(cat)
        da = _duty(ref["self_us"], wa) if ref else 0.0
        if ref is None:
            findings.append({
                "path": b_path, "line": 0, "rule": "S002",
                "message": "new hot category %r: %.2f%% device duty"
                           % (cat, 100.0 * db)})
        elif da > 0 and db / da >= threshold:
            findings.append({
                "path": b_path, "line": 0, "rule": "S002",
                "message": "category %r duty x%.2f: %.2f%% -> %.2f%% of "
                           "the capture window"
                           % (cat, db / da, 100.0 * da, 100.0 * db)})
    counts = {}
    for f in findings:
        counts[f["rule"]] = counts.get(f["rule"], 0) + 1
    return {"tool": "profsum", "ok": not findings, "findings": findings,
            "counts": counts, "baselined": 0}


def inject_slowdown(summary, factor):
    """Multiply the top op's self time by ``factor`` (shares and
    categories recomputed coherently) — the CI canary input proving the
    diff gate fires on a real regression shape."""
    ops = summary.get("ops") or []
    if not ops:
        return summary
    top = ops[0]
    delta = top["self_us"] * (factor - 1.0)
    top["self_us"] += delta
    cat = summary.get("categories", {}).get(top["category"])
    if cat:
        cat["self_us"] += delta
    total = sum(o["self_us"] for o in ops)
    for o in ops:
        o["share"] = o["self_us"] / total if total > 0 else 0.0
    for info in (summary.get("categories") or {}).values():
        info["share"] = info["self_us"] / total if total > 0 else 0.0
    ops.sort(key=lambda o: (-o["self_us"], o["op"]))
    return summary


def _cmd_summarize(args):
    ps = _profstats()
    summary = load_input(args.path)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1, sort_keys=True)
    if args.json:
        json.dump(summary, sys.stdout, indent=1, sort_keys=True)
        print()
    else:
        print("capture: %s (%d trace(s), %d op events, %d bad)"
              % (summary.get("capture_id") or args.path,
                 summary.get("traces", 0), summary.get("events", 0),
                 summary.get("trace_errors", 0)))
        print(ps.format_table(summary, top=args.top))
        if args.out:
            print("summary written to %s" % args.out)
    return 0


def _cmd_diff(args):
    a = load_input(args.a)
    b = load_input(args.b)
    if args.inject_slowdown:
        b = inject_slowdown(b, args.inject_slowdown)
    rep = diff_report(a, b, threshold=args.threshold,
                      min_duty=args.min_duty, b_path=args.b)
    if args.json:
        json.dump(rep, sys.stdout, indent=1, sort_keys=True)
        print()
    else:
        if rep["ok"]:
            print("profsum diff OK: no op/category duty regression "
                  ">= x%.2f" % args.threshold)
        for f in rep["findings"]:
            print("%s: %s [%s]" % (f["path"], f["message"], f["rule"]))
    return 0 if rep["ok"] else 1


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    parser = argparse.ArgumentParser(prog="profsum", description=__doc__)
    sub = parser.add_subparsers(dest="cmd")
    s = sub.add_parser("summarize", help="rank one capture's hotspots")
    s.add_argument("path")
    s.add_argument("--top", type=int, default=40)
    s.add_argument("--json", action="store_true")
    s.add_argument("--out", default=None,
                   help="write the full summary JSON (diff input)")
    d = sub.add_parser("diff", help="compare two summaries/captures")
    d.add_argument("a")
    d.add_argument("b")
    d.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD)
    d.add_argument("--min-duty", type=float, default=DEFAULT_MIN_DUTY)
    d.add_argument("--json", action="store_true")
    d.add_argument("--inject-slowdown", type=float, default=None,
                   metavar="FACTOR",
                   help="multiply b's top op self-time by FACTOR before "
                        "diffing (CI canary)")
    # bare `profsum <path>` == `profsum summarize <path>`
    if argv and argv[0] not in ("summarize", "diff", "-h", "--help"):
        argv.insert(0, "summarize")
    args = parser.parse_args(argv)
    if args.cmd == "diff":
        return _cmd_diff(args)
    if args.cmd == "summarize":
        return _cmd_summarize(args)
    parser.print_help()
    return 2


if __name__ == "__main__":
    sys.exit(main())
