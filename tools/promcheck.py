#!/usr/bin/env python
"""Validate Prometheus text exposition format (version 0.0.4) — stdlib only.

CI's observability stage scrapes the serving server's ``GET /metrics`` and
pipes the body through this checker; tests import ``validate()`` directly.
No external prometheus client is involved anywhere in the repo (the
serving image must not grow a dependency for its own monitoring).

Checks:
- every non-comment line parses as ``name{labels} value`` (timestamp
  optional), names/labels legal, values float-parsable;
- every sample belongs to a ``# TYPE``-declared metric family (histogram
  samples may use the ``_bucket``/``_sum``/``_count`` suffixes);
- histograms: every series has a ``+Inf`` bucket, bucket counts are
  cumulative non-decreasing in ``le`` order, the ``+Inf`` count equals
  ``_count``, and ``le`` bounds parse;
- counters never carry negative values.

Usage::

    python tools/promcheck.py metrics.txt            # or stdin with no arg
    python tools/promcheck.py metrics.txt --json     # CI report shape

``--json`` emits the same report shape as ``python -m tools.mxtpulint
--json`` and ``tools/loadgen.py --json``
(tool/ok/findings/counts/baselined), so CI aggregates every gate with
one parser; format violations carry rule id ``P001``, metadata-hygiene
violations carry ``P002``, naming-convention violations carry ``P003``
(counters end ``_total``; lowercase names; ``_seconds``/``_bytes`` base
units — with the pre-existing ``_ms`` latency histograms grandfathered
by name in ``P003_EXEMPT`` because SLOs, dashboards and tests pin
them):

- every exposed family must carry BOTH ``# HELP`` and ``# TYPE`` lines,
  in canonical order (HELP, then TYPE, then that family's samples) — a
  family without metadata renders as untyped garbage in most scrapers;
- one family name must never mix gauge and counter semantics: a
  re-declaration under a different type is rejected, and a plain
  family's name must not collide with another histogram/summary
  family's generated ``_bucket``/``_sum``/``_count`` sample names
  (ambiguous family resolution — a counter named ``x_count`` next to a
  histogram ``x`` makes every parser guess).
"""
from __future__ import annotations

import json
import math
import re
import sys

NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
# name{label="value",...} value [timestamp] — label values may contain
# escaped quotes/backslashes/newlines
SAMPLE_RE = re.compile(
    r'^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(?:\{(?P<labels>(?:[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*",?)*)\})?'
    r'\s+(?P<value>[^ ]+)(?:\s+(?P<ts>-?[0-9]+))?$')
LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')
TYPES = ("counter", "gauge", "histogram", "summary", "untyped")


def _parse_value(raw, line_no):
    if raw == "+Inf":
        return math.inf
    if raw == "-Inf":
        return -math.inf
    if raw == "NaN":
        return math.nan
    try:
        return float(raw)
    except ValueError:
        raise ValueError("line %d: unparsable sample value %r"
                         % (line_no, raw)) from None


def validate(text):
    """Validate one exposition; returns {family -> type}. Raises
    ValueError with a line-numbered message on the first violation."""
    types = {}                # family name -> declared type
    helped = set()
    samples = []              # (line_no, name, labels-dict, value)
    for i, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("#"):
            parts = line.split(None, 3)
            if len(parts) >= 3 and parts[1] == "TYPE":
                fam, typ = parts[2], (parts[3] if len(parts) > 3 else "")
                if not NAME_RE.match(fam):
                    raise ValueError("line %d: bad family name %r" % (i, fam))
                if typ not in TYPES:
                    raise ValueError("line %d: bad TYPE %r" % (i, typ))
                if fam in types:
                    raise ValueError("line %d: duplicate TYPE for %r"
                                     % (i, fam))
                types[fam] = typ
            elif len(parts) >= 3 and parts[1] == "HELP":
                helped.add(parts[2])
            continue
        m = SAMPLE_RE.match(line)
        if not m:
            raise ValueError("line %d: unparsable sample line %r" % (i, line))
        labels = dict(LABEL_RE.findall(m.group("labels") or ""))
        samples.append((i, m.group("name"), labels,
                        _parse_value(m.group("value"), i)))

    def family_of(name):
        for fam, typ in types.items():
            if typ == "histogram" and name in (
                    fam + "_bucket", fam + "_sum", fam + "_count"):
                return fam
            if typ == "summary" and name in (fam + "_sum", fam + "_count"):
                return fam
            if name == fam:
                return fam
        return None

    # histogram series accounting: (family, non-le label items) -> state
    hist = {}
    for line_no, name, labels, value in samples:
        fam = family_of(name)
        if fam is None:
            raise ValueError("line %d: sample %r has no # TYPE declaration"
                             % (line_no, name))
        typ = types[fam]
        if typ == "counter" and value < 0:
            raise ValueError("line %d: counter %r is negative (%r)"
                             % (line_no, name, value))
        if typ == "histogram":
            series = (fam, tuple(sorted((k, v) for k, v in labels.items()
                                        if k != "le")))
            st = hist.setdefault(series, {"buckets": [], "sum": None,
                                          "count": None})
            if name == fam + "_bucket":
                if "le" not in labels:
                    raise ValueError("line %d: %s_bucket without le"
                                     % (line_no, fam))
                st["buckets"].append((line_no, labels["le"], value))
            elif name == fam + "_sum":
                st["sum"] = value
            elif name == fam + "_count":
                st["count"] = value

    for (fam, lbls), st in hist.items():
        if not st["buckets"]:
            raise ValueError("histogram %r series %r has no buckets"
                             % (fam, lbls))
        bounds = []
        for line_no, le, v in st["buckets"]:
            bounds.append((math.inf if le == "+Inf" else
                           _parse_value(le, line_no), line_no, v))
        bounds.sort(key=lambda b: b[0])
        if bounds[-1][0] != math.inf:
            raise ValueError("histogram %r series %r lacks a +Inf bucket"
                             % (fam, lbls))
        prev = -1.0
        for bound, line_no, v in bounds:
            if v < prev:
                raise ValueError(
                    "line %d: histogram %r bucket le=%r count %r below the "
                    "previous bucket (%r) — not cumulative"
                    % (line_no, fam, bound, v, prev))
            prev = v
        if st["count"] is None:
            raise ValueError("histogram %r series %r lacks _count"
                             % (fam, lbls))
        if bounds[-1][2] != st["count"]:
            raise ValueError(
                "histogram %r series %r: +Inf bucket (%r) != _count (%r)"
                % (fam, lbls, bounds[-1][2], st["count"]))
    return types


def validate_metadata(text):
    """P002: HELP/TYPE hygiene over one exposition. Returns a list of
    ``(line_no, message)`` violations (all of them, not first-only — the
    exposition still parses, so every hygiene miss is reportable):

    - a ``# TYPE``-declared family with no ``# HELP`` line;
    - ``# HELP`` appearing AFTER its family's ``# TYPE`` (canonical
      order is HELP, TYPE, samples);
    - a family's first sample appearing BEFORE its ``# TYPE`` line;
    - gauge/counter (or any type) mixing under one name: a plain
      family whose name collides with a histogram/summary family's
      generated ``_bucket``/``_sum``/``_count`` sample names.
    """
    type_line, help_line, types = {}, {}, {}
    first_sample = {}
    out = []
    for i, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("#"):
            parts = line.split(None, 3)
            if len(parts) >= 3 and parts[1] == "TYPE":
                type_line.setdefault(parts[2], i)
                types.setdefault(parts[2],
                                 parts[3] if len(parts) > 3 else "")
            elif len(parts) >= 3 and parts[1] == "HELP":
                help_line.setdefault(parts[2], i)
            continue
        m = SAMPLE_RE.match(line)
        if m:
            first_sample.setdefault(m.group("name"), i)
    for fam, tline in sorted(type_line.items()):
        hline = help_line.get(fam)
        if hline is None:
            out.append((tline, "line %d: family %r has # TYPE but no "
                        "# HELP — undocumented metrics rot first" %
                        (tline, fam)))
        elif hline > tline:
            out.append((hline, "line %d: # HELP for %r comes after its "
                        "# TYPE (canonical order is HELP, TYPE, samples)"
                        % (hline, fam)))
        sline = first_sample.get(fam)
        if types.get(fam) == "histogram":
            sline = min((s for s in
                         (first_sample.get(fam + sfx) for sfx in
                          ("_bucket", "_sum", "_count")) if s is not None),
                        default=sline)
        if sline is not None and sline < tline:
            out.append((sline, "line %d: sample of %r appears before its "
                        "# TYPE declaration (line %d)" % (sline, fam,
                                                          tline)))
        if types.get(fam) in ("histogram", "summary"):
            for sfx in ("_bucket", "_sum", "_count"):
                other = fam + sfx
                if other in type_line:
                    out.append((type_line[other],
                                "line %d: family %r collides with %s "
                                "family %r's generated %s samples — one "
                                "name must never mix metric kinds"
                                % (type_line[other], other, types[fam],
                                   fam, sfx)))
    return out


# --------------------------------------------------------- P003: naming
# Prometheus naming conventions: counters end in ``_total``, family
# names are lowercase, durations use the base unit ``_seconds`` and
# sizes ``_bytes``. The ``_ms`` histograms below predate the check and
# their names are LOAD-BEARING — telemetry/slo.py's latency objectives,
# the SLO/telemetry/generate test suites and any deployed dashboards
# address them by name — so they are explicitly grandfathered here
# (visible, greppable, shrink-only) rather than renamed or silently
# skipped. New metrics get no such grace: P003 fires on the next
# ``_ms`` family that shows up on the scrape.
P003_EXEMPT = frozenset((
    "mxtpu_request_latency_ms",
    "mxtpu_serving_request_latency_ms",
    "mxtpu_gen_inter_token_ms",
))
# non-base unit suffix -> the base unit the convention wants
_NON_BASE_UNITS = (
    ("_milliseconds", "_seconds"), ("_microseconds", "_seconds"),
    ("_nanoseconds", "_seconds"), ("_minutes", "_seconds"),
    ("_hours", "_seconds"), ("_ms", "_seconds"), ("_us", "_seconds"),
    ("_ns", "_seconds"), ("_kib", "_bytes"), ("_mib", "_bytes"),
    ("_gib", "_bytes"), ("_kb", "_bytes"), ("_mb", "_bytes"),
    ("_gb", "_bytes"),
)


def validate_names(text):
    """P003: metric-name conventions over one exposition. Returns every
    ``(line_no, message)`` violation, anchored at the family's ``# TYPE``
    line:

    - a ``counter`` family whose name does not end ``_total``;
    - a family name containing uppercase (exposition names are
      conventionally ``snake_case``; mixed case breaks PromQL muscle
      memory and half the grep pipelines watching the scrape);
    - a duration/size family using a non-base unit suffix (``_ms``,
      ``_mb``, ...) instead of ``_seconds``/``_bytes``.

    Families in ``P003_EXEMPT`` are grandfathered by name (see the
    comment on the constant)."""
    out = []
    for i, line in enumerate(text.splitlines(), 1):
        if not line.startswith("#"):
            continue
        parts = line.split(None, 3)
        if len(parts) < 3 or parts[1] != "TYPE":
            continue
        fam, typ = parts[2], (parts[3] if len(parts) > 3 else "")
        if fam in P003_EXEMPT:
            continue
        lower = fam.lower()
        if fam != lower:
            out.append((i, "line %d: family %r contains uppercase — "
                        "exposition names are snake_case by convention"
                        % (i, fam)))
        if typ == "counter" and not lower.endswith("_total"):
            out.append((i, "line %d: counter %r does not end in '_total' "
                        "— the suffix is how consumers (and rate()) "
                        "recognize a monotone counter" % (i, fam)))
        for sfx, base in _NON_BASE_UNITS:
            if lower.endswith(sfx):
                out.append((i, "line %d: family %r uses non-base unit "
                            "%r — express it in %r (Prometheus base "
                            "units; scale at the edge, not in the name)"
                            % (i, fam, sfx, base)))
                break
    return out


_LINE_NO_RE = re.compile(r"line (\d+):")


def report(text, path="<stdin>"):
    """Validate and return the shared CI report shape (see tools/mxtpulint/
    core.py): {"tool", "ok", "findings", "counts", "baselined"}. The first
    format violation becomes one P001 finding; every metadata-hygiene
    violation becomes a P002 finding."""
    findings = []
    try:
        validate(text)
    except ValueError as e:
        msg = str(e)
        m = _LINE_NO_RE.search(msg)
        findings.append({"path": path, "line": int(m.group(1)) if m else 0,
                         "rule": "P001", "message": msg})
    for line_no, msg in validate_metadata(text):
        findings.append({"path": path, "line": line_no, "rule": "P002",
                         "message": msg})
    for line_no, msg in validate_names(text):
        findings.append({"path": path, "line": line_no, "rule": "P003",
                         "message": msg})
    counts = {}
    for f in findings:
        counts[f["rule"]] = counts.get(f["rule"], 0) + 1
    return {"tool": "promcheck", "ok": not findings, "findings": findings,
            "counts": counts, "baselined": 0}


def main(argv):
    args = [a for a in argv[1:] if a != "--json"]
    as_json = "--json" in argv[1:]
    path = args[0] if args else "<stdin>"
    text = open(args[0]).read() if args else sys.stdin.read()
    if as_json:
        rep = report(text, path=path)
        json.dump(rep, sys.stdout, indent=1)
        sys.stdout.write("\n")
        return 0 if rep["ok"] else 1
    types = validate(text)
    meta = validate_metadata(text)
    names = validate_names(text)
    if meta or names:
        for _line_no, msg in meta:
            print("P002: %s" % msg)
        for _line_no, msg in names:
            print("P003: %s" % msg)
        return 1
    n_hist = sum(1 for t in types.values() if t == "histogram")
    print("promcheck OK: %d metric families (%d histograms)"
          % (len(types), n_hist))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
