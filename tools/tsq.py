#!/usr/bin/env python
"""tsq — query/diff exported metric history (stdlib only).

The metric-history daemon (telemetry/history.py) exports its store to
``MXTPU_HISTORY_FILE`` as canonical JSONL: one meta line
(``{"schema": "mxtpu-history-v1", ...}``), then one line per series
(``{"series", "raw": [[t, v], ...], "coarse": [[t, min, max, mean],
...]}``), sorted by series id, keys sorted, no whitespace — atomic
rotation means this tool never reads a torn file. This tool is the
offline half: it must run where the framework is NOT importable (a
laptop holding a downloaded incident artifact), so it is stdlib-only
and parses the JSONL directly.

Usage::

    python tools/tsq.py list  history.jsonl
    python tools/tsq.py query history.jsonl --series queue_depth
    python tools/tsq.py diff  before.jsonl after.jsonl [--tol 0.25]
    python tools/tsq.py roundtrip history.jsonl

``query`` renders an ASCII sparkline table (raw ring per matching
series, with min/max/mean/last columns). ``diff`` compares the shared
series' summary stats between two exports — the before/after artifact
check a perf investigation starts from. ``roundtrip`` re-serializes the
file canonically and verifies byte-stability (the CI proof that export
and tool agree on one serialization). Every subcommand accepts
``--json`` and emits the shared CI report shape
({"tool": "tsq", "ok", "findings", "counts", "baselined"} — same
one-parser aggregation as mxtpulint/promcheck/loadgen) with
rules:

- ``Q001`` — unreadable/malformed export (bad JSON line, wrong schema);
- ``Q002`` — a series present in A missing from B (diff);
- ``Q003`` — a shared series' mean shifted beyond ``--tol`` (diff);
- ``Q004`` — round-trip not byte-stable (export serialization drifted).
"""
from __future__ import annotations

import json
import os
import sys

SCHEMA = "mxtpu-history-v1"
SPARKS = "▁▂▃▄▅▆▇█"


def canon(obj):
    """The canonical serialization (MUST match telemetry/history._canon:
    sorted keys, no whitespace) — the byte-stability contract."""
    return json.dumps(obj, sort_keys=True, separators=(",", ":"))


def load(path):
    """(meta, [series rows]) from one export; raises ValueError with a
    line number on malformed input."""
    meta, rows = None, []
    with open(path) as f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                obj = json.loads(line)
            except ValueError:
                raise ValueError("line %d: not JSON" % i)
            if i == 1:
                if obj.get("schema") != SCHEMA:
                    raise ValueError(
                        "line 1: schema %r, want %r"
                        % (obj.get("schema"), SCHEMA))
                meta = obj
            else:
                if "series" not in obj:
                    raise ValueError("line %d: row without 'series'" % i)
                rows.append(obj)
    if meta is None:
        raise ValueError("line 1: empty export (no meta line)")
    return meta, rows


def sparkline(values, width=40):
    """Values folded to ``width`` columns (mean per column), each mapped
    onto the 8-level block ramp; flat series render as a low line."""
    if not values:
        return ""
    if len(values) > width:
        folded, per = [], len(values) / float(width)
        for c in range(width):
            chunk = values[int(c * per):max(int((c + 1) * per),
                                            int(c * per) + 1)]
            folded.append(sum(chunk) / len(chunk))
        values = folded
    lo, hi = min(values), max(values)
    span = hi - lo
    if span <= 0:
        return SPARKS[0] * len(values)
    return "".join(SPARKS[min(len(SPARKS) - 1,
                              int((v - lo) / span * len(SPARKS)))]
                   for v in values)


def _stats(row):
    vals = [v for _, v in row.get("raw", [])]
    if not vals:
        return None
    return {"n": len(vals), "min": min(vals), "max": max(vals),
            "mean": sum(vals) / len(vals), "last": vals[-1]}


def _match(rows, series):
    if not series:
        return rows
    return [r for r in rows
            if series in r["series"]
            or r["series"].split("{", 1)[0] == series]


def _report(findings):
    counts = {}
    for f in findings:
        counts[f["rule"]] = counts.get(f["rule"], 0) + 1
    return {"tool": "tsq", "ok": not findings, "findings": findings,
            "counts": counts, "baselined": 0}


# ------------------------------------------------------------ subcommands
def cmd_list(path, series=None):
    meta, rows = load(path)
    lines = ["%s  interval=%.3gs  %d series"
             % (path, meta.get("interval_s", 0.0), len(rows))]
    for r in _match(rows, series):
        lines.append("%-72s raw=%-5d coarse=%d"
                     % (r["series"], len(r.get("raw", [])),
                        len(r.get("coarse", []))))
    return lines


def cmd_query(path, series=None, since=None, width=40):
    _meta, rows = load(path)
    lines = []
    for r in _match(rows, series):
        raw = r.get("raw", [])
        if since is not None:
            raw = [p for p in raw if p[0] >= since]
        vals = [v for _, v in raw]
        st = _stats({"raw": raw})
        if st is None:
            lines.append("%-60s (empty)" % r["series"])
            continue
        lines.append("%-60s %s" % (r["series"], sparkline(vals, width)))
        lines.append("  n=%-5d min=%-12.6g max=%-12.6g mean=%-12.6g "
                     "last=%.6g" % (st["n"], st["min"], st["max"],
                                    st["mean"], st["last"]))
    return lines


def cmd_diff(path_a, path_b, series=None, tol=0.25):
    """Findings for series that vanished (Q002) or whose raw-ring mean
    moved by more than ``tol`` relative (Q003) between two exports.
    Series new in B are informational only — growth is not a
    regression."""
    _ma, rows_a = load(path_a)
    _mb, rows_b = load(path_b)
    a = {r["series"]: r for r in _match(rows_a, series)}
    b = {r["series"]: r for r in _match(rows_b, series)}
    findings, lines = [], []
    for sid in sorted(a):
        if sid not in b:
            findings.append({"path": path_b, "line": 0, "rule": "Q002",
                             "message": "series %r present in %s but "
                             "missing from %s" % (sid, path_a, path_b)})
            continue
        sa, sb = _stats(a[sid]), _stats(b[sid])
        if sa is None or sb is None:
            continue
        base = max(abs(sa["mean"]), 1e-12)
        shift = (sb["mean"] - sa["mean"]) / base
        marker = ""
        if abs(shift) > tol:
            marker = "  <-- Q003"
            findings.append(
                {"path": path_b, "line": 0, "rule": "Q003",
                 "message": "series %r mean shifted %+.1f%% "
                 "(%.6g -> %.6g, tol %.0f%%)"
                 % (sid, 100.0 * shift, sa["mean"], sb["mean"],
                    100.0 * tol)})
        lines.append("%-60s %+8.1f%%  %.6g -> %.6g%s"
                     % (sid, 100.0 * shift, sa["mean"], sb["mean"],
                        marker))
    new = sorted(set(b) - set(a))
    if new:
        lines.append("(%d series only in %s: %s)"
                     % (len(new), path_b, ", ".join(new[:5])
                        + ("..." if len(new) > 5 else "")))
    return lines, findings


def cmd_roundtrip(path):
    """Re-serialize canonically; byte-equality proves the export format
    and this tool share one serialization (a drift would silently break
    every diff baseline)."""
    meta, rows = load(path)
    out = canon(meta) + "\n" + "".join(canon(r) + "\n" for r in rows)
    with open(path, "rb") as f:
        original = f.read()
    if out.encode("utf-8") != original:
        return [{"path": path, "line": 0, "rule": "Q004",
                 "message": "round-trip is not byte-stable: canonical "
                 "re-serialization differs from the file (%d vs %d "
                 "bytes)" % (len(out), len(original))}]
    return []


# ------------------------------------------------------------------ main
def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    as_json = "--json" in argv
    argv = [a for a in argv if a != "--json"]
    if not argv:
        print(__doc__.strip().splitlines()[0])
        print("usage: tsq.py {list,query,diff,roundtrip} FILE [FILE2] "
              "[--series S] [--since T] [--tol F] [--json]")
        return 2
    cmd, rest = argv[0], argv[1:]
    opts, files = {}, []
    i = 0
    while i < len(rest):
        if rest[i] in ("--series", "--since", "--tol", "--width"):
            if i + 1 >= len(rest):
                print("missing value for %s" % rest[i], file=sys.stderr)
                return 2
            opts[rest[i][2:]] = rest[i + 1]
            i += 2
        else:
            files.append(rest[i])
            i += 1
    if not files:
        # the daemon-side default artifact path doubles as the tool-side
        # default input (stdlib tool: read the env directly; the knob is
        # registered in config.ENV_VARS for docs, the loadgen precedent)
        env = os.environ.get("MXTPU_HISTORY_FILE")
        if env:
            files = [env]
        else:
            print("no FILE given and MXTPU_HISTORY_FILE unset",
                  file=sys.stderr)
            return 2
    series = opts.get("series")
    findings, lines = [], []
    try:
        if cmd == "list":
            lines = cmd_list(files[0], series)
        elif cmd == "query":
            lines = cmd_query(
                files[0], series,
                since=float(opts["since"]) if "since" in opts else None,
                width=int(opts.get("width", 40)))
        elif cmd == "diff":
            if len(files) < 2:
                print("diff needs two files", file=sys.stderr)
                return 2
            lines, findings = cmd_diff(files[0], files[1], series,
                                       tol=float(opts.get("tol", 0.25)))
        elif cmd == "roundtrip":
            findings = cmd_roundtrip(files[0])
            if not findings:
                lines = ["roundtrip OK: %s is byte-stable" % files[0]]
        else:
            print("unknown subcommand %r" % cmd, file=sys.stderr)
            return 2
    except (OSError, ValueError) as e:
        findings = [{"path": files[0] if files else "<none>", "line": 0,
                     "rule": "Q001", "message": str(e)}]
    if as_json:
        json.dump(_report(findings), sys.stdout, indent=1)
        sys.stdout.write("\n")
    else:
        for line in lines:
            print(line)
        for f in findings:
            print("%s: %s" % (f["rule"], f["message"]), file=sys.stderr)
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
