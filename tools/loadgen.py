#!/usr/bin/env python
"""Open-loop load generator for the serving HTTP front-end — stdlib only.

Closed-loop clients (N threads in a request/response loop) measure a
fiction under overload: a saturated server slows the *offered* load down,
so the reported latency quietly excludes the requests that would have
been sent — the coordinated-omission trap. This harness is OPEN-LOOP
(MLPerf inference "server" scenario, arxiv 1909.09756): arrivals are
scheduled by the clock from a defined arrival process (Poisson or
constant), never by completions, so overload shows up where it belongs —
in p99, in 429/504 shed rates, and in the goodput-vs-offered gap.

A run is a RAMP of stages (``[{"rps": r, "duration_s": d}, ...]``). Per
stage the report carries client-observed p50/95/99 latency, offered vs
goodput RPS, shed/error rates — and, because every request carries a
generated ``X-Request-Id`` that the server echoes into its spans, a
server-side JOIN: between stages the harness scrapes ``GET /metrics``
and ``GET /debug/spans`` and attributes each stage's time to queue wait
(``serve:queue``), batch dispatch (``serve:batch``), and device step
(``eval:step``) — *where* the time went, not just that it grew. The
scrape's devstats counters (telemetry/devstats.py) add device truth per
stage: ``server.metrics.device_s`` (measured block-until-ready device
seconds inside the stage) and ``server.metrics.mfu`` (the stage's
achieved FLOP/s against the server's advertised peak) — whether the
knee is compute, HBM, or host overhead is in the report, not a guess.

Saturation point (detect_saturation): the first stage where offered load
rose but goodput plateaued (less than ``goodput_frac`` of the added
offered load converted) while the tail diverged (p99 grew past
``p99_ratio``× the previous stage's, or the shed rate crossed
``shed_min`` while rising).

Usage::

    python tools/loadgen.py --url http://host:8080 --model m \\
        --item '[0.0, 0.0, 0.0, 0.0]' --stages 100x2,400x2,1600x2 \\
        --out report.json [--json] [--arrival poisson|constant]

Generative mode (``--generate PROMPT_LEN:MAX_NEW``): each arrival is one
``POST /generate`` whose chunked token stream is consumed as it arrives
(docs/GENERATE.md). The open-loop discipline is unchanged — arrivals are
scheduled by the clock — but the per-request record gains streaming
truth: TTFT (first token line), every inter-token gap, tokens received,
and the finish reason. Stage summaries gain a ``generate`` section
(tokens/s goodput, TTFT p50/95/99, inter-token p50/95/99, finish-reason
counts) and the span join additionally attributes ``gen:prefill`` and
``gen:decode_step`` time — prompts and sampling seeds are derived
deterministically from each request id, so a soak is replayable.

Chaos soak (``--faults [STAGE=]SPEC``, repeatable): arm a deterministic
faultlab spec (``POST /debug/faults``, telemetry/faultlab.py) entering a
given stage — e.g. ``--faults '1=batcher.dispatch:replica_kill:p=0.02'``
kills replica workers during stage 1 while the supervisor heals them,
and the report shows what the outage COST (availability, p99, shed mix)
per stage under the exact chaos it ran (each stage summary carries its
``fault_spec``). Whatever is still armed after the last stage is
disarmed (docs/RESILIENCE.md).

``--json`` additionally emits the shared CI report shape (``tool`` /
``ok`` / ``findings`` / ``counts`` / ``baselined`` — the same parser
that reads ``python -m tools.mxtpulint --json`` and ``tools/promcheck.py
--json`` reads this; violations carry rule id L001). The report's
``gate_metrics`` section is the run's flat summary, a name and a number
each (docs/LOADGEN.md).

The module is import-light on purpose: driving a remote server must not
require the framework (or jax) to be importable. The MXTPU_LOADGEN_*
knobs are therefore read from the environment here but REGISTERED in
incubator_mxnet_tpu/config.py (docs/ENV_VARS.md); tests pin the two
default tables in sync.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import random
import re
import sys
import threading
import time
import queue as _queue
import urllib.error
import urllib.request

__all__ = ["LoadGen", "HttpTransport", "GenHttpTransport",
           "InProcessTransport",
           "arrival_offsets", "percentile",
           "parse_prom", "summarize_stage", "detect_saturation",
           "gate_metrics", "report_ci", "REPORT_SCHEMA", "METRICS_SCHEMA"]

REPORT_SCHEMA = "mxtpu-loadgen-report-v1"
METRICS_SCHEMA = "mxtpu-perfgate-metrics-v1"

# Mirrors config.ENV_VARS (registered there for docs/ENV_VARS.md and env
# hygiene); tests/test_loadgen.py asserts the two tables agree.
ENV_DEFAULTS = {
    "MXTPU_LOADGEN_SEED": 0,
    "MXTPU_LOADGEN_TIMEOUT_S": 30.0,
    "MXTPU_LOADGEN_MAX_CLIENTS": 256,
}

#: status code recorded for transport-level failures (refused/reset/
#: timeout) — outside the HTTP space so it can't collide with a server code
TRANSPORT_ERROR = 599
#: status code recorded for arrivals shed CLIENT-side because the
#: in-flight bound (MXTPU_LOADGEN_MAX_CLIENTS) was hit
CLIENT_DROPPED = 0


def _env(name):
    default = ENV_DEFAULTS[name]
    raw = os.environ.get(name)
    return type(default)(raw) if raw is not None else default


# ------------------------------------------------------------------ arrivals
def arrival_offsets(mode, rps, duration_s, rng=None):
    """Send offsets in seconds from stage start, ascending.

    ``constant``: exactly ``round(rps * duration_s)`` arrivals on a fixed
    grid. ``poisson``: exponential inter-arrivals at rate ``rps`` (the
    memoryless open-loop process real traffic approximates) — fully
    deterministic given the seeded ``rng``.
    """
    if rps <= 0 or duration_s <= 0:
        return []
    if mode == "constant":
        return [i / float(rps) for i in range(int(round(rps * duration_s)))]
    if mode != "poisson":
        raise ValueError("unknown arrival mode %r (poisson|constant)" % mode)
    if rng is None:
        rng = random.Random(_env("MXTPU_LOADGEN_SEED"))
    out, t = [], 0.0
    while True:
        t += rng.expovariate(float(rps))
        if t >= duration_s:
            return out
        out.append(t)


def percentile(sorted_values, q):
    """Nearest-rank percentile of an ascending-sorted list (same epsilon
    semantics as serving.metrics.percentile; duplicated so the tool stays
    framework-import-free)."""
    if not sorted_values:
        return None
    n = len(sorted_values)
    q = min(max(float(q), 0.0), 100.0)
    rank = int(math.ceil(n * q / 100.0 - 1e-9))
    return sorted_values[min(max(rank, 1), n) - 1]


def _pctls(values):
    ordered = sorted(values)
    return {"p50": percentile(ordered, 50), "p95": percentile(ordered, 95),
            "p99": percentile(ordered, 99)}


# ------------------------------------------------------- Prometheus parsing
_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+(\S+)")
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_prom(text):
    """{(name, ((label, value), ...)) -> float} for every sample line —
    the minimal scrape reader (tools/promcheck.py is the format
    VALIDATOR; this only needs the values)."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        if not m:
            continue
        name, labels, raw = m.group(1), m.group(2) or "", m.group(3)
        try:
            v = float(raw)
        except ValueError:
            v = {"+Inf": math.inf, "-Inf": -math.inf}.get(raw, math.nan)
        out[(name, tuple(sorted(_LABEL_RE.findall(labels))))] = v
    return out


def _prom_sum(snapshot, name):
    return sum(v for (n, _l), v in snapshot.items() if n == name)


def _prom_series(snapshot, name):
    return {lbls: v for (n, lbls), v in snapshot.items() if n == name}


# ----------------------------------------------------------------- transport
class HttpTransport:
    """The real client: one ``POST /v1/models/<model>:predict`` per
    ``send()``, plus the scrape endpoints the per-stage join reads.
    ``item`` is ONE input item (no batch dim) — cross-request batching is
    the server's job."""

    def __init__(self, url, model, item, deadline_ms=None, timeout_s=None):
        self.url = url.rstrip("/")
        self._predict_url = "%s/v1/models/%s:predict" % (self.url, model)
        body = {"inputs": [item]}
        if deadline_ms is not None:
            body["deadline_ms"] = deadline_ms
        self._body = json.dumps(body).encode("utf-8")
        self._timeout = (float(timeout_s) if timeout_s is not None
                         else _env("MXTPU_LOADGEN_TIMEOUT_S"))

    def send(self, request_id, tenant=None):
        """Fire one predict; returns the HTTP status (TRANSPORT_ERROR for
        refused/reset/timeout). ``tenant`` (from the --tenants weighted
        mix) rides the X-MXTPU-Tenant header so the server's per-tenant
        accounting splits the soak."""
        headers = {"Content-Type": "application/json",
                   "X-Request-Id": request_id}
        if tenant is not None:
            headers["X-MXTPU-Tenant"] = tenant
        req = urllib.request.Request(self._predict_url, data=self._body,
                                     headers=headers)
        try:
            with urllib.request.urlopen(req, timeout=self._timeout) as r:
                r.read()
                return r.status
        except urllib.error.HTTPError as e:
            e.close()
            return e.code
        except Exception:  # refused / reset / timeout
            return TRANSPORT_ERROR

    def _get(self, path):
        with urllib.request.urlopen(self.url + path,
                                    timeout=self._timeout) as r:
            return r.read().decode("utf-8")

    def scrape(self):
        """GET /metrics text, or '' when unreachable (the join degrades,
        the soak itself keeps its client-side numbers)."""
        try:
            return self._get("/metrics")
        except Exception:
            return ""

    def spans(self):
        """GET /debug/spans JSONL, or ''."""
        try:
            return self._get("/debug/spans")
        except Exception:
            return ""

    def slo(self):
        """GET /debug/slo JSON text, or '' — the between-stage SLO
        snapshot (budget remaining, burn rates, alert states) the stage
        reports carry as a trajectory."""
        try:
            return self._get("/debug/slo")
        except Exception:
            return ""

    def numerics(self):
        """GET /debug/numerics JSON text, or '' — the numerics-sentinel
        snapshot (tap stats, storm episodes, shadow divergence) the
        stage reports carry alongside the SLO one."""
        try:
            return self._get("/debug/numerics")
        except Exception:
            return ""

    def history(self, since=None):
        """GET /debug/history JSON text, or '' — the metric-history
        rings (telemetry/history.py) the stage reports reduce to the
        per-stage ``history`` block (queue depth / MFU / inflight
        min-max-mean). ``since`` is epoch seconds: the stage's
        wall-clock start, so the block covers only this stage's
        samples."""
        try:
            path = "/debug/history"
            if since is not None:
                path += "?since=%.6f" % since
            return self._get(path)
        except Exception:
            return ""

    def arm_faults(self, spec):
        """POST /debug/faults with a faultlab spec ('' disarms) — the
        chaos-soak verb (--faults; docs/RESILIENCE.md). UNLIKE the scrape
        endpoints this raises on failure: a soak whose faults silently
        failed to arm would report a clean run while measuring nothing."""
        req = urllib.request.Request(
            self.url + "/debug/faults",
            data=json.dumps({"spec": spec}).encode("utf-8"),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=self._timeout) as r:
                return json.loads(r.read())
        except urllib.error.HTTPError as e:
            body = e.read().decode("utf-8", "replace")
            e.close()
            raise RuntimeError("arming faults %r failed: HTTP %d %s"
                               % (spec, e.code, body))


class GenHttpTransport(HttpTransport):
    """Streaming generative client: one ``POST /generate`` per ``send()``,
    consuming the chunked JSONL token stream as it arrives. ``send()``
    returns a RICH result dict (status + ttft_ms + itl_ms gaps + tokens +
    finish reason) instead of a bare status; the driver folds the extras
    into the per-request record and ``summarize_stage`` reduces them to
    the stage's ``generate`` section. Prompt token ids and the sampling
    seed are derived from the request id (crc32), so a rerun with the
    same --seed offers a byte-identical request mix."""

    def __init__(self, url, model, prompt_len, max_new, temperature=0.0,
                 top_k=0, deadline_ms=None, timeout_s=None, seed=0):
        self.url = url.rstrip("/")
        self._gen_url = self.url + "/generate"
        self._model = model
        self.prompt_len = int(prompt_len)
        self.max_new = int(max_new)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self._deadline_ms = deadline_ms
        self._seed = int(seed)
        self._timeout = (float(timeout_s) if timeout_s is not None
                         else _env("MXTPU_LOADGEN_TIMEOUT_S"))

    def send(self, request_id, tenant=None):
        import zlib
        rng = random.Random(zlib.crc32(request_id.encode("utf-8"))
                            ^ self._seed)
        body = {"model": self._model,
                # never token 0: that is the engine's EOS and a prompt
                # containing it is still legal but ends runs instantly,
                # which would make tokens/s depend on the rid mix
                "prompt": [rng.randrange(1, 256)
                           for _ in range(self.prompt_len)],
                "max_new_tokens": self.max_new,
                "temperature": self.temperature,
                "top_k": self.top_k,
                "seed": rng.randrange(1 << 30)}
        if self._deadline_ms is not None:
            body["deadline_ms"] = self._deadline_ms
        headers = {"Content-Type": "application/json",
                   "X-Request-Id": request_id}
        if tenant is not None:
            headers["X-MXTPU-Tenant"] = tenant
        req = urllib.request.Request(
            self._gen_url, data=json.dumps(body).encode("utf-8"),
            headers=headers)
        t0 = time.monotonic()
        ttft, t_prev, gaps, ntok, reason = None, None, [], 0, None
        try:
            # http.client dechunks transparently; the server flushes one
            # chunk per JSON line, so readline returns tokens as they are
            # generated — the timestamps below are real streaming truth
            with urllib.request.urlopen(req, timeout=self._timeout) as r:
                for line in r:
                    if not line.strip():
                        continue
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    now = time.monotonic()
                    if rec.get("done"):
                        reason = rec.get("reason")
                        break
                    if "token" not in rec:
                        continue
                    ntok += 1
                    if ttft is None:
                        ttft = (now - t0) * 1e3
                    else:
                        gaps.append((now - t_prev) * 1e3)
                    t_prev = now
                status = r.status
        except urllib.error.HTTPError as e:
            e.close()
            return e.code
        except Exception:  # refused / reset / timeout
            return TRANSPORT_ERROR
        if reason is None:
            # the stream died without its terminal line — a served-but-
            # truncated response is a server error, not a success
            return {"status": 500, "ttft_ms": ttft, "tokens": ntok,
                    "itl_ms": gaps, "reason": "truncated"}
        return {"status": status, "ttft_ms": ttft, "tokens": ntok,
                "itl_ms": gaps, "reason": reason}


class InProcessTransport:
    """Drive a live ``ModelRegistry`` directly — no HTTP, no sockets.

    The stdlib thread-per-connection front-end tops out around a few
    hundred requests/s of Python HTTP handling on one host, which is an
    order of magnitude BELOW what 8 data-parallel replica workers can
    dispatch — measured through HTTP, replica scaling saturates on the
    web server, not on serving. This transport submits straight into the
    registry (router -> replica queues -> workers), so a soak measures
    the serving core itself; the scrape endpoints read the same
    process-wide telemetry registry and span ring the HTTP routes serve,
    so the X-Request-Id join works unchanged. Status mapping mirrors
    server.py's error contract (429/504/503/500). Used by
    ``ci/run.sh sharded`` for the 1-vs-8-replica goodput-scaling gate
    (docs/SERVING.md, docs/LOADGEN.md).

    Imports of the framework happen lazily at construction, keeping this
    module import-light for the remote-HTTP use case.
    """

    def __init__(self, registry, model, item, deadline_ms=None,
                 timeout_s=None, dtype="float32"):
        import numpy as onp
        self._registry = registry
        self._model = model
        self._item = onp.asarray(item, dtype=onp.dtype(dtype))
        self._deadline_ms = deadline_ms
        self._timeout = (float(timeout_s) if timeout_s is not None
                         else _env("MXTPU_LOADGEN_TIMEOUT_S"))

    def send(self, request_id, tenant=None):
        from incubator_mxnet_tpu.serving import batcher as _batcher
        from incubator_mxnet_tpu.serving.registry import ModelNotFoundError
        try:
            self._registry.predict(self._model, self._item,
                                   deadline_ms=self._deadline_ms,
                                   timeout=self._timeout,
                                   request_id=request_id, tenant=tenant)
            return 200
        except _batcher.QueueFullError:
            return 429
        except (_batcher.DeadlineExceededError, TimeoutError):
            return 504
        except _batcher.ServingClosedError:
            return 503
        except ModelNotFoundError:
            return 404
        except Exception:  # servable failure — server.py maps this to 500
            return 500

    def scrape(self):
        from incubator_mxnet_tpu import telemetry
        try:
            return telemetry.export_text()
        except Exception:
            return ""

    def spans(self):
        from incubator_mxnet_tpu.telemetry import spans as _spans
        try:
            return _spans.export_jsonl()
        except Exception:
            return ""

    def slo(self):
        """The same /debug/slo payload the HTTP route serves, read
        straight off the process-wide SLO registry. NB: per-tenant/SLO
        accounting lives in the HTTP front-end, so an in-process soak
        only sees SLO movement when something else feeds the ledger."""
        from incubator_mxnet_tpu.telemetry import slo as _slo
        try:
            return json.dumps(_slo.REGISTRY.describe())
        except Exception:
            return ""

    def numerics(self):
        """The same /debug/numerics payload the HTTP route serves, read
        straight off the numerics sentinel."""
        from incubator_mxnet_tpu.telemetry import numwatch as _numwatch
        try:
            return json.dumps(_numwatch.describe())
        except Exception:
            return ""

    def history(self, since=None):
        """The same /debug/history payload the HTTP route serves, read
        straight off the process-wide history store (empty until
        history.start() or sample_once() has run — the soak script owns
        the daemon lifecycle, the transport only reads)."""
        from incubator_mxnet_tpu.telemetry import history as _history
        try:
            return json.dumps(_history.query(since=since))
        except Exception:
            return ""

    def arm_faults(self, spec):
        """Arm the process-wide faultlab directly (same semantics as the
        HTTP transport's POST /debug/faults; raises ValueError on a
        malformed spec — loudly, like the route's 400)."""
        from incubator_mxnet_tpu.telemetry import faultlab as _faultlab
        return _faultlab.arm(spec)


class _MonotonicClock:
    """The real clock: monotonic now() + time.sleep."""

    def now(self):
        return time.monotonic()

    def sleep(self, s):
        time.sleep(s)


# --------------------------------------------------------------- summarizing
def summarize_stage(stage_cfg, n_offered, results, span_text="",
                    prom_before=None, prom_after=None,
                    scrape_window_s=None, slo_text="", numerics_text="",
                    history_text=""):
    """One stage's report entry from raw per-request results.

    ``results``: [{"rid", "status", "latency_ms"}, ...] for every arrival
    (CLIENT_DROPPED status for arrivals shed by the in-flight bound; a
    ``tenant`` key when a --tenants mix is configured — those stages
    additionally carry per-tenant offered/goodput/latency columns).
    ``span_text``: /debug/spans JSONL scraped AFTER the stage — spans are
    joined by the request ids this stage generated.
    ``slo_text``: /debug/slo JSON scraped AFTER the stage — parsed into
    the stage's ``slo`` entry, so a ramp's report carries the
    budget/burn-rate trajectory alongside its latency one.
    ``numerics_text``: /debug/numerics JSON scraped AFTER the stage —
    parsed into the stage's ``numerics`` entry (tap health + shadow
    divergence trajectory, telemetry/numwatch.py).
    ``history_text``: /debug/history JSON scraped AFTER the stage
    (``since`` = the stage's wall start) — reduced to the stage's
    ``history`` block: min/max/mean of queue depth, window MFU, and
    HTTP inflight over the stage's self-scrape samples
    (telemetry/history.py). Point-in-time scrapes only see the queue
    at stage boundaries; the history block sees what it did BETWEEN
    them.
    ``scrape_window_s``: wall time between the two /metrics scrapes,
    reported as ``server.metrics.mfu_window_s``. It is NOT the MFU
    denominator (that is the chip-seconds delta, topology-exact); it is
    the honest wall window the counter deltas cover — the scrapes
    bracket the drain of in-flight requests too, so under overload it
    exceeds ``duration_s`` — and the busy fraction
    ``device_s / mfu_window_s`` is the idleness/host-overhead signal.
    Defaults to ``duration_s`` for direct callers.
    """
    duration = float(stage_cfg["duration_s"])
    by_status = {}
    ok_lat, all_lat = [], []
    rids, ok_rids = set(), set()
    for r in results:
        rids.add(r["rid"])
        s = r["status"]
        by_status[s] = by_status.get(s, 0) + 1
        if s != CLIENT_DROPPED:
            all_lat.append(r["latency_ms"])
        if s == 200:
            ok_lat.append(r["latency_ms"])
            ok_rids.add(r["rid"])
    ok = by_status.get(200, 0)
    shed = by_status.get(429, 0) + by_status.get(504, 0)
    dropped = by_status.get(CLIENT_DROPPED, 0)
    errors = sum(c for s, c in by_status.items()
                 if s not in (200, 429, 504, CLIENT_DROPPED))
    out = {
        "rps": stage_cfg["rps"],
        "duration_s": duration,
        "offered": n_offered,
        "offered_rps": n_offered / duration if duration else 0.0,
        "completed": len(results) - dropped,
        "ok": ok,
        "goodput_rps": ok / duration if duration else 0.0,
        "shed": shed,
        "shed_rate": shed / n_offered if n_offered else 0.0,
        "errors": errors,
        # server/transport failures only: a client-side drop (in-flight
        # bound hit) is harness capacity, not a server regression — it
        # gets its own rate so the two can't mask each other
        "error_rate": errors / n_offered if n_offered else 0.0,
        "client_dropped": dropped,
        "client_drop_rate": dropped / n_offered if n_offered else 0.0,
        "status_counts": {str(s): c for s, c in sorted(by_status.items())},
        "latency_ms": _pctls(ok_lat),
        "latency_all_ms": _pctls(all_lat),
    }
    tenants = _tenant_columns(results, duration)
    if tenants:
        out["tenants"] = tenants
    gen = _generate_columns(results, duration)
    if gen:
        out["generate"] = gen
    if slo_text:
        try:
            out["slo"] = json.loads(slo_text)
        except ValueError:
            out["slo"] = None
    if numerics_text:
        try:
            out["numerics"] = json.loads(numerics_text)
        except ValueError:
            out["numerics"] = None
    if history_text:
        out["history"] = _history_columns(history_text)
    out["server"] = _join_spans(rids, ok_rids, span_text)
    if prom_before is not None and prom_after is not None:
        window = scrape_window_s if scrape_window_s else duration
        out["server"]["metrics"] = _metrics_delta(prom_before, prom_after,
                                                  duration_s=window)
    return out


#: /debug/history series each stage-report history column reduces
_HISTORY_COLUMNS = (("queue_depth", "mxtpu_serving_queue_depth"),
                    ("window_mfu", "mxtpu_history_window_mfu"),
                    ("inflight", "mxtpu_http_inflight_requests"))


def _history_columns(history_text):
    """The /debug/history payload reduced to the stage's ``history``
    block: {column: {min, max, mean, n} | None} pooling every label set
    of the column's metric (all models' queue depths together — the
    per-model split stays queryable on the server). None (not {}) when
    the payload does not parse, so a broken scrape is visible."""
    try:
        series = json.loads(history_text).get("series", {})
    except (ValueError, AttributeError):
        return None
    out = {}
    for col, base in _HISTORY_COLUMNS:
        vals = []
        for sid, entry in series.items():
            if sid.split("{", 1)[0] != base:
                continue
            vals.extend(p[1] for p in entry.get("raw", []))
        out[col] = ({"min": min(vals), "max": max(vals),
                     "mean": sum(vals) / len(vals), "n": len(vals)}
                    if vals else None)
    return out


def _tenant_columns(results, duration):
    """Per-tenant offered/goodput/shed/latency breakdown of one stage's
    results ({} when no result carries a tenant — the mix was not
    configured)."""
    groups = {}
    for r in results:
        t = r.get("tenant")
        if t is None:
            continue
        groups.setdefault(t, []).append(r)
    out = {}
    for t, rs in sorted(groups.items()):
        ok_lat = [r["latency_ms"] for r in rs if r["status"] == 200]
        shed = sum(1 for r in rs if r["status"] in (429, 504))
        errors = sum(1 for r in rs if r["status"] not in
                     (200, 429, 504, CLIENT_DROPPED))
        out[t] = {
            "offered": len(rs),
            "ok": len(ok_lat),
            "goodput_rps": len(ok_lat) / duration if duration else 0.0,
            "shed": shed,
            "errors": errors,
            "client_dropped": sum(1 for r in rs
                                  if r["status"] == CLIENT_DROPPED),
            "latency_ms": _pctls(ok_lat),
        }
    return out


def _generate_columns(results, duration):
    """The stage's streaming-generation reduction ({} when no result is
    from a generate transport): tokens/s goodput (tokens received on OK
    streams over the stage window — the number continuous batching must
    beat sequential decode on), TTFT and inter-token percentiles over
    every stream's raw gaps, and the finish-reason counts (a rising
    kv_oom share is the capacity signal)."""
    rs = [r for r in results if "tokens" in r]
    if not rs:
        return {}
    tokens_ok = sum(r["tokens"] for r in rs if r["status"] == 200)
    ttfts = [r["ttft_ms"] for r in rs if r.get("ttft_ms") is not None]
    gaps = [g for r in rs for g in (r.get("itl_ms") or ())]
    reasons = {}
    for r in rs:
        if r.get("reason"):
            reasons[r["reason"]] = reasons.get(r["reason"], 0) + 1
    return {"requests": len(rs),
            "tokens_ok": tokens_ok,
            "tokens_per_s": tokens_ok / duration if duration else 0.0,
            "ttft_ms": _pctls(ttfts),
            "inter_token_ms": dict(_pctls(gaps), count=len(gaps)),
            "finish_reasons": dict(sorted(reasons.items()))}


def _join_spans(rids, ok_rids, span_text):
    """Attribute the stage's server-side time by span kind, joined on the
    X-Request-Id each request carried: queue wait (serve:queue), batch
    dispatch (serve:batch), the per-replica servable call
    (serve:dispatch — additionally broken out by its ``replica`` arg, so
    a slow chip shows up as ONE replica's latency, not a fleet-wide
    blur), device step (eval:step), and the server's own view of the
    request (http:predict)."""
    kinds = {"serve:queue": "queue_ms", "serve:batch": "batch_ms",
             "serve:dispatch": "dispatch_ms",
             "eval:step": "device_ms", "http:predict": "http_ms",
             # generative serving (docs/GENERATE.md): the batched-prefill
             # leg and every decode step this stage's sequences rode in
             # (decode_step spans carry every rider's id in
             # args.request_ids, same as serve:batch)
             "http:generate": "http_ms",
             "gen:prefill": "prefill_ms",
             "gen:decode_step": "decode_step_ms"}
    durs = {v: [] for v in kinds.values()}
    replica_durs = {}
    joined_rids = set()
    for line in span_text.splitlines():
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        rid = rec.get("request_id")
        hit = rid in rids
        if not hit:
            # a batch span parents onto ONE request but carries every
            # rider's id in args.request_ids — credit those too
            riders = (rec.get("args") or {}).get("request_ids") or ()
            hit = any(r in rids for r in riders)
        if not hit:
            continue
        key = kinds.get(rec.get("name"))
        if key is None:
            continue
        ms = rec.get("dur_us", 0.0) / 1e3
        durs[key].append(ms)
        if rec.get("name") == "serve:dispatch":
            rep = (rec.get("args") or {}).get("replica")
            if rep is not None:
                replica_durs.setdefault(str(rep), []).append(ms)
        if rec.get("name") == "serve:queue" and rid in ok_rids:
            joined_rids.add(rid)
    out = {}
    for key, vals in durs.items():
        out[key] = dict(_pctls(vals), count=len(vals),
                        mean=(sum(vals) / len(vals)) if vals else None)
    if replica_durs:
        out["replica_ms"] = {
            rep: dict(_pctls(vals), count=len(vals),
                      mean=sum(vals) / len(vals))
            for rep, vals in sorted(replica_durs.items())}
    # coverage over OK responses only: a dispatched-then-504'd request
    # also leaves a serve:queue span, and counting it against the OK
    # denominator would push coverage past 1.0 under overload (masking a
    # real join regression at the max-aggregating gate)
    out["join_coverage"] = (len(joined_rids & ok_rids) / len(ok_rids)
                            if ok_rids else None)
    return out


_DELTA_COUNTERS = (
    "mxtpu_serving_requests_total", "mxtpu_serving_ok_total",
    "mxtpu_serving_rejected_total", "mxtpu_serving_expired_total",
    "mxtpu_serving_errors_total", "mxtpu_serving_batches_total",
    "mxtpu_serving_batched_items_total", "mxtpu_jit_compiles_total",
    # device truth (telemetry/devstats.py): window deltas of these give
    # the stage's achieved utilization, independent of the rolling gauges
    "mxtpu_device_flops_total", "mxtpu_device_bytes_accessed_total",
    "mxtpu_device_dispatch_seconds_total", "mxtpu_device_chip_seconds_total",
)
_SNAP_GAUGES = (
    "mxtpu_serving_queue_depth", "mxtpu_http_inflight_requests",
)


def _metrics_delta(before, after, duration_s=None):
    """Per-stage server-side counter deltas + end-of-stage gauge snapshot
    from two /metrics scrapes (label sets summed per family). With the
    stage ``duration_s`` and the devstats counters in the scrape, the
    stage's device truth rides along: ``device_s`` (measured device
    dispatch seconds inside the stage window) and ``mfu`` (the stage's
    achieved FLOP/s over the server's advertised peak,
    mxtpu_device_peak_flops) — docs/OBSERVABILITY.md "Device truth"."""
    out = {"delta": {}, "gauges": {}}
    for name in _DELTA_COUNTERS:
        d = _prom_sum(after, name) - _prom_sum(before, name)
        if d or _prom_series(after, name):
            out["delta"][name] = d
    batches = out["delta"].get("mxtpu_serving_batches_total", 0)
    items = out["delta"].get("mxtpu_serving_batched_items_total", 0)
    out["mean_batch_size"] = (items / batches) if batches else None
    out["device_s"] = out["delta"].get(
        "mxtpu_device_dispatch_seconds_total")
    d_flops = out["delta"].get("mxtpu_device_flops_total")
    d_chip_s = out["delta"].get("mxtpu_device_chip_seconds_total")
    peak = _prom_sum(after, "mxtpu_device_peak_flops")
    # mfu_window_s is set on BOTH branches: consumers computing the busy
    # fraction device_s / mfu_window_s must not KeyError on a stage with
    # zero instrumented dispatches
    out["mfu_window_s"] = duration_s
    if d_flops is not None and d_chip_s and peak:
        # per-chip MFU WHILE EXECUTING: flops per chip-second over one
        # chip's peak — exact under any replica/tp topology (dividing the
        # fleet-total flops by a wall window and ONE chip's peak would
        # overstate an N-replica deployment N-fold). The busy fraction
        # device_s / mfu_window_s carries the idleness/host-overhead
        # signal separately.
        out["mfu"] = d_flops / d_chip_s / peak
    else:
        out["mfu"] = None
    for name in _SNAP_GAUGES:
        series = _prom_series(after, name)
        if series:
            out["gauges"][name] = _prom_sum(after, name)
    mfu_series = _prom_series(after, "mxtpu_device_mfu")
    if mfu_series:
        out["gauges"]["mxtpu_device_mfu"] = {
            "%s/%s/r%s" % (dict(l).get("model", "?"),
                           dict(l).get("kind", "?"),
                           dict(l).get("replica", "?")): v
            for l, v in mfu_series.items()}
    bucket = _prom_series(after, "mxtpu_serving_bucket_queue_depth")
    if bucket:
        out["gauges"]["mxtpu_serving_bucket_queue_depth"] = {
            dict(lbls).get("bucket", "?"): v for lbls, v in bucket.items()}
    return out


def detect_saturation(stages, goodput_frac=0.5, p99_ratio=1.2,
                      shed_min=0.01):
    """First stage where goodput plateaus while the tail diverges.

    A stage ``i`` saturates when offered load rose over stage ``i-1`` but
    (a) less than ``goodput_frac`` of the ADDED offered load converted to
    goodput, AND (b) p99 grew past ``p99_ratio`` × the previous stage's,
    or the shed rate crossed ``max(shed_min, 2 × previous)``. Both legs
    are required: a plateau alone can be a measurement floor; a p99 bump
    alone can be one slow batch. Returns the stage's summary slice or
    None (docs/LOADGEN.md has the worked example).
    """
    for i in range(1, len(stages)):
        prev, cur = stages[i - 1], stages[i]
        d_off = cur["offered_rps"] - prev["offered_rps"]
        if d_off <= 0:
            continue
        if (cur["goodput_rps"] - prev["goodput_rps"]) >= goodput_frac * d_off:
            continue
        p99p = prev["latency_ms"].get("p99")
        p99c = cur["latency_ms"].get("p99")
        tail = (p99p is not None and p99c is not None
                and p99c > p99_ratio * p99p)
        shed = cur["shed_rate"] > max(shed_min, 2.0 * prev["shed_rate"])
        if tail or shed:
            return {"stage": i, "offered_rps": cur["offered_rps"],
                    "goodput_rps": cur["goodput_rps"], "p99_ms": p99c,
                    "shed_rate": cur["shed_rate"],
                    "reason": ("tail" if tail else "")
                              + ("+" if tail and shed else "")
                              + ("shed" if shed else "")}
    return None


# -------------------------------------------------------------------- engine
class LoadGen:
    """Open-loop driver over an injectable transport + clock.

    The real run uses ``HttpTransport`` and the monotonic clock with a
    bounded worker pool (``max_clients`` in-flight; arrivals beyond the
    bound are recorded as client_dropped, never silently unsent — the
    offered-load accounting stays exact). Tests inject a fake clock and a
    synchronous fake transport (``run(sync=True)``): the identical
    scheduling/summarizing code runs with zero real sleeps.
    """

    def __init__(self, transport, stages, arrival="poisson", seed=None,
                 max_clients=None, clock=None, settle_s=0.25, run_id=None,
                 deadline_ms=None, tenants=None, faults=None):
        self.transport = transport
        self.stages = [{"rps": float(s["rps"]),
                        "duration_s": float(s["duration_s"])}
                       for s in stages]
        if not self.stages:
            raise ValueError("need at least one stage")
        self.arrival = arrival
        # weighted tenant mix: each arrival carries one tenant name drawn
        # deterministically (own seeded RNG, so adding --tenants never
        # perturbs the arrival schedule itself)
        self.tenants = None
        if tenants:
            norm = [(str(n), float(w)) for n, w in
                    (tenants.items() if isinstance(tenants, dict)
                     else tenants)]
            if any(w <= 0 for _n, w in norm):
                raise ValueError("tenant weights must be > 0: %r" % (norm,))
            self.tenants = norm
        self.seed = int(seed if seed is not None
                        else _env("MXTPU_LOADGEN_SEED"))
        self.max_clients = int(max_clients if max_clients is not None
                               else _env("MXTPU_LOADGEN_MAX_CLIENTS"))
        self.clock = clock if clock is not None else _MonotonicClock()
        self.settle_s = settle_s
        self.deadline_ms = deadline_ms
        # chaos soak (--faults; docs/RESILIENCE.md): {stage_index: spec}
        # armed via transport.arm_faults right before the stage's first
        # arrival. An arming PERSISTS into later stages until replaced
        # ('' disarms mid-ramp); whatever is still armed after the last
        # stage is disarmed, so a soak never leaves a server poisoned.
        self.faults = ({int(k): str(v) for k, v in faults.items()}
                       if faults else None)
        if self.faults and not hasattr(transport, "arm_faults"):
            raise ValueError("faults configured but transport %r has no "
                             "arm_faults()" % type(transport).__name__)
        if run_id is None:
            run_id = os.urandom(4).hex()
        self.run_id = run_id
        self._lock = threading.Lock()
        self._inflight = 0
        self._results = []            # per-request dicts, all stages

    # ------------------------------------------------------------- workers
    def _send(self, rid, tenant):
        """One transport send; a None tenant calls the legacy one-arg
        form so transports (and test fakes) without tenant support keep
        working unchanged."""
        if tenant is None:
            return self.transport.send(rid)
        return self.transport.send(rid, tenant)

    @staticmethod
    def _make_record(stage_idx, rid, tenant, status, lat):
        """Normalize one transport result: a bare int status, or a rich
        dict (streaming transports — GenHttpTransport) whose extras
        (ttft_ms, tokens, itl_ms, reason) ride the record into
        summarize_stage's ``generate`` reduction."""
        rec = {"stage": stage_idx, "rid": rid, "tenant": tenant}
        if isinstance(status, dict):
            extra = dict(status)
            rec["status"] = int(extra.pop("status", TRANSPORT_ERROR))
            rec.update(extra)
        else:
            rec["status"] = status
        rec["latency_ms"] = lat
        return rec

    def _worker(self, q):
        while True:
            item = q.get()
            if item is None:
                return
            stage_idx, rid, tenant = item
            t0 = self.clock.now()
            try:
                status = self._send(rid, tenant)
            except Exception:  # a raising transport is a transport error
                status = TRANSPORT_ERROR
            lat = (self.clock.now() - t0) * 1e3
            with self._lock:
                self._inflight -= 1
                self._results.append(self._make_record(
                    stage_idx, rid, tenant, status, lat))

    def _record_sync(self, stage_idx, rid, tenant):
        t0 = self.clock.now()
        try:
            status = self._send(rid, tenant)
        except Exception:
            status = TRANSPORT_ERROR
        lat = (self.clock.now() - t0) * 1e3
        self._results.append(self._make_record(
            stage_idx, rid, tenant, status, lat))

    # -------------------------------------------------------------- driving
    def _pick_tenant(self, rng):
        """One weighted draw from the tenant mix (None when no mix)."""
        if self.tenants is None:
            return None
        total = sum(w for _n, w in self.tenants)
        x = rng.random() * total
        for name, w in self.tenants:
            x -= w
            if x < 0:
                return name
        return self.tenants[-1][0]

    def _drive_stage(self, idx, stage, q, sync):
        rng = random.Random(self.seed * 1000003 + idx)
        # separate stream for tenant draws: the arrival schedule stays
        # byte-identical with and without a --tenants mix
        tenant_rng = random.Random(self.seed * 9176 + idx * 31 + 7)
        offsets = arrival_offsets(self.arrival, stage["rps"],
                                  stage["duration_s"], rng)
        t0 = self.clock.now()
        for seq, off in enumerate(offsets):
            delay = t0 + off - self.clock.now()
            if delay > 0:
                self.clock.sleep(delay)
            rid = "lg-%s-s%d-%d" % (self.run_id, idx, seq)
            tenant = self._pick_tenant(tenant_rng)
            if sync:
                self._record_sync(idx, rid, tenant)
                continue
            with self._lock:
                admit = self._inflight < self.max_clients
                if admit:
                    self._inflight += 1
            if admit:
                q.put((idx, rid, tenant))
            else:
                # open-loop honesty: the arrival happened; the client
                # could not carry it — recorded, not silently skipped
                with self._lock:
                    self._results.append(
                        {"stage": idx, "rid": rid, "tenant": tenant,
                         "status": CLIENT_DROPPED, "latency_ms": 0.0})
        return len(offsets)

    def _drain(self, budget_s=30.0):
        """Wait for in-flight requests to finish (bounded)."""
        deadline = self.clock.now() + budget_s
        while self.clock.now() < deadline:
            with self._lock:
                if self._inflight == 0:
                    return True
            self.clock.sleep(0.02)
        return False

    def run(self, sync=False):
        """Execute every stage; returns the report dict (REPORT_SCHEMA)."""
        q = None
        workers = []
        if not sync:
            q = _queue.SimpleQueue()
            # exactly max_clients workers: every ADMITTED request has a
            # thread to run on immediately. A pool smaller than the
            # admission bound would queue admitted requests client-side
            # with the wait excluded from latency — the coordinated-
            # omission bias this tool exists to avoid.
            workers = [threading.Thread(target=self._worker, args=(q,),
                                        daemon=True,
                                        name="loadgen-client-%d" % i)
                       for i in range(self.max_clients)]
            for w in workers:
                w.start()
        summaries = []
        t_run0 = self.clock.now()
        armed_spec = None
        try:
            prom_before = parse_prom(self.transport.scrape())
            t_scrape = self.clock.now()
            for idx, stage in enumerate(self.stages):
                if self.faults is not None and idx in self.faults:
                    spec = self.faults[idx]
                    self.transport.arm_faults(spec)
                    armed_spec = spec or None
                # wall-clock stage start: /debug/history samples are
                # epoch-stamped, so the per-stage history block filters
                # on wall time, not the harness's monotonic clock
                t_wall0 = time.time()
                n_offered = self._drive_stage(idx, stage, q, sync)
                if not sync:
                    self._drain()
                if self.settle_s:
                    # let worker-side telemetry of the final batch land
                    self.clock.sleep(self.settle_s)
                span_text = self.transport.spans()
                # between-stage SLO snapshot (transport-optional: fakes
                # and older transports without .slo() degrade to none)
                slo_fn = getattr(self.transport, "slo", None)
                slo_text = slo_fn() if slo_fn is not None else ""
                num_fn = getattr(self.transport, "numerics", None)
                numerics_text = num_fn() if num_fn is not None else ""
                hist_fn = getattr(self.transport, "history", None)
                history_text = hist_fn(since=t_wall0) \
                    if hist_fn is not None else ""
                prom_after = parse_prom(self.transport.scrape())
                now = self.clock.now()
                with self._lock:
                    mine = [r for r in self._results if r["stage"] == idx]
                summaries.append(summarize_stage(
                    stage, n_offered, mine, span_text,
                    prom_before, prom_after,
                    # the counters cover scrape→scrape (drain + settle
                    # included), so the MFU denominator must too
                    scrape_window_s=now - t_scrape, slo_text=slo_text,
                    numerics_text=numerics_text,
                    history_text=history_text))
                if self.faults is not None:
                    # which faults this stage ran under — the report's
                    # availability/latency numbers are meaningless
                    # without the chaos they were measured against
                    summaries[-1]["fault_spec"] = armed_spec
                prom_before = prom_after
                t_scrape = now
        finally:
            for _w in workers:
                q.put(None)
            for w in workers:
                w.join(5.0)
            if armed_spec is not None:
                try:
                    self.transport.arm_faults("")
                except Exception:
                    pass    # server gone/unreachable: nothing to disarm
        wall_s = self.clock.now() - t_run0
        report = {
            "schema": REPORT_SCHEMA,
            "run_id": self.run_id,
            "config": {"arrival": self.arrival, "seed": self.seed,
                       "max_clients": self.max_clients,
                       "deadline_ms": self.deadline_ms,
                       "tenants": self.tenants,
                       "faults": self.faults,
                       "stages": self.stages},
            "wall_s": wall_s,
            "stages": summaries,
            "saturation": detect_saturation(summaries),
        }
        report["gate_metrics"] = gate_metrics(report)
        return report


# ------------------------------------------------------------ the flat summary
def gate_metrics(report):
    """The run reduced to a flat {"schema", "metrics": {name: number}}
    summary: stage-0 (lowest-load) latency and conversion, whole-run
    error rate and span-join coverage, and the saturation verdict."""
    stages = report["stages"]
    st0 = stages[0]
    covs = [s["server"]["join_coverage"] for s in stages
            if s["server"].get("join_coverage") is not None]
    total_offered = sum(s["offered"] for s in stages)
    total_bad = sum(s["errors"] for s in stages)
    m = {
        "loadgen_stage0_p50_ms": st0["latency_ms"]["p50"],
        "loadgen_stage0_p99_ms": st0["latency_ms"]["p99"],
        "loadgen_stage0_goodput_frac":
            (st0["goodput_rps"] / st0["offered_rps"])
            if st0["offered_rps"] else 0.0,
        "loadgen_error_rate":
            (total_bad / total_offered) if total_offered else 0.0,
        "loadgen_join_coverage":
            (sum(covs) / len(covs)) if covs else 0.0,
        "loadgen_saturation_detected":
            1.0 if report.get("saturation") else 0.0,
    }
    sat = report.get("saturation")
    if sat:
        m["loadgen_saturation_goodput_rps"] = sat["goodput_rps"]
    # history-block facts: whole-run queue-depth/inflight peaks and mean
    # window MFU across the stages that carried a history block — the
    # between-scrape saturation evidence the boundary scrapes can't see
    hist = [s["history"] for s in stages if s.get("history")]
    for key, col in (("loadgen_history_queue_depth_max", "queue_depth"),
                     ("loadgen_history_inflight_max", "inflight")):
        peaks = [h[col]["max"] for h in hist if h.get(col)]
        if peaks:
            m[key] = max(peaks)
    mfus = [h["window_mfu"]["mean"] for h in hist
            if h.get("window_mfu")]
    if mfus:
        m["loadgen_history_window_mfu_mean"] = sum(mfus) / len(mfus)
    g0 = st0.get("generate")
    if g0:
        # generative-mode facts (docs/GENERATE.md): the tokens/s goodput
        # the CI stage compares against the sequential-decode baseline,
        # plus the streaming tails
        m["loadgen_gen_tokens_per_s"] = g0["tokens_per_s"]
        m["loadgen_gen_ttft_p99_ms"] = g0["ttft_ms"]["p99"]
        m["loadgen_gen_inter_token_p99_ms"] = g0["inter_token_ms"]["p99"]
    # a stage-0 with no OK responses has no percentiles — drop the Nones
    # rather than emit unparseable metrics
    return {"schema": METRICS_SCHEMA,
            "metrics": {k: v for k, v in m.items() if v is not None}}


def report_ci(report, path="<report>", max_error_rate=0.0,
              require_saturation=False):
    """The shared CI report shape (one parser for mxtpulint / promcheck /
    loadgen): rule L001 per stage whose hard-error rate
    exceeds ``max_error_rate``, plus one L001 when ``require_saturation``
    and the ramp never saturated (a gate that can't find the knee isn't
    measuring capacity)."""
    findings = []
    for i, s in enumerate(report["stages"]):
        if s["error_rate"] > max_error_rate:
            findings.append({
                "path": path, "line": 0, "rule": "L001",
                "message": "stage %d: server-error rate %.4f > %.4f "
                           "(%d errors of %d offered; %d client-dropped "
                           "reported separately)"
                           % (i, s["error_rate"], max_error_rate,
                              s["errors"], s["offered"],
                              s["client_dropped"])})
    if require_saturation and not report.get("saturation"):
        findings.append({
            "path": path, "line": 0, "rule": "L001",
            "message": "no saturation point detected across %d stages — "
                       "the ramp never found the knee (raise the top "
                       "stage's rps)" % len(report["stages"])})
    return {"tool": "loadgen", "ok": not findings, "findings": findings,
            "counts": {"L001": len(findings)} if findings else {},
            "baselined": 0}


# ----------------------------------------------------------------------- CLI
def _parse_stages(text):
    """'100x2,400x2,1600x2' -> [{"rps": 100, "duration_s": 2}, ...]"""
    stages = []
    for part in text.split(","):
        rps, _x, dur = part.strip().partition("x")
        if not _x:
            raise ValueError("bad stage %r (want RPSxSECONDS)" % part)
        stages.append({"rps": float(rps), "duration_s": float(dur)})
    return stages


def _parse_faults(parts):
    """Repeated ``--faults`` values -> {stage_index: spec}.

    Each value is ``STAGE=SPEC`` (arm SPEC entering stage STAGE) or a
    bare SPEC (stage 0). The forms are unambiguous because a faultlab
    spec's own ``=`` can only follow a ``site:kind`` prefix, which is
    never a pure integer. ``STAGE=`` with an empty spec disarms entering
    that stage (mid-ramp recovery measurement)."""
    if not parts:
        return None
    out = {}
    for part in parts:
        head, sep, rest = part.partition("=")
        if sep and head.strip().isdigit():
            idx, spec = int(head), rest.strip()
        else:
            idx, spec = 0, part.strip()
        if idx in out:
            raise ValueError("stage %d given twice in --faults" % idx)
        out[idx] = spec
    return out


def _parse_tenants(text):
    """'alice:3,bob:1' -> [("alice", 3.0), ("bob", 1.0)]; a bare name
    weighs 1. None/empty -> None (no tenant mix)."""
    if not text:
        return None
    out = []
    for part in text.split(","):
        name, sep, weight = part.strip().partition(":")
        if not name:
            raise ValueError("bad tenant %r (want NAME or NAME:WEIGHT)"
                             % part)
        out.append((name, float(weight) if sep else 1.0))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python tools/loadgen.py",
        description="open-loop load generator for the serving HTTP "
                    "front-end (Poisson/constant arrivals, ramp stages, "
                    "server-side span join, saturation detection)")
    ap.add_argument("--url", required=True, help="server base URL")
    ap.add_argument("--model", required=True, help="served model name")
    ap.add_argument("--item", default="[0.0]",
                    help="JSON for ONE input item, no batch dim "
                         "(default: [0.0])")
    ap.add_argument("--generate", default=None, metavar="PROMPT_LEN:MAX_NEW",
                    help="generative mode: each arrival is one streaming "
                         "POST /generate with a PROMPT_LEN-token prompt "
                         "asking for MAX_NEW tokens (--item is ignored); "
                         "stage reports gain tokens/s, TTFT and "
                         "inter-token percentiles")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="generative sampling temperature (0 = greedy; "
                         "only with --generate)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="generative top-k cutoff (0 = full vocab; only "
                         "with --generate)")
    ap.add_argument("--stages", default="50x2,200x2,800x2",
                    help="ramp as RPSxSECONDS comma list "
                         "(default: 50x2,200x2,800x2)")
    ap.add_argument("--arrival", default="poisson",
                    choices=("poisson", "constant"))
    ap.add_argument("--seed", type=int, default=None,
                    help="arrival RNG seed (default: MXTPU_LOADGEN_SEED)")
    ap.add_argument("--deadline-ms", type=float, default=None)
    ap.add_argument("--tenants", default=None,
                    help="weighted tenant mix as NAME:WEIGHT comma list "
                         "(e.g. alice:3,bob:1; bare NAME weighs 1) — "
                         "each arrival carries one X-MXTPU-Tenant drawn "
                         "from the mix, and stage reports gain "
                         "per-tenant columns")
    ap.add_argument("--max-clients", type=int, default=None,
                    help="in-flight bound (default: "
                         "MXTPU_LOADGEN_MAX_CLIENTS)")
    ap.add_argument("--faults", action="append", default=None,
                    metavar="[STAGE=]SPEC",
                    help="chaos soak: arm this faultlab spec (POST "
                         "/debug/faults) entering stage STAGE (default "
                         "0); repeatable, STAGE= with an empty spec "
                         "disarms mid-ramp, and whatever is still armed "
                         "is disarmed after the last stage "
                         "(docs/RESILIENCE.md)")
    ap.add_argument("--out", default=None, help="write the report here")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the shared CI report shape on stdout "
                         "(rule L001) instead of the human summary")
    ap.add_argument("--max-error-rate", type=float, default=0.0)
    ap.add_argument("--require-saturation", action="store_true")
    args = ap.parse_args(argv)

    if args.generate:
        plen, _sep, mnew = args.generate.partition(":")
        if not _sep:
            print("bad --generate %r (want PROMPT_LEN:MAX_NEW)"
                  % args.generate, file=sys.stderr)
            return 2
        transport = GenHttpTransport(
            args.url, args.model, int(plen), int(mnew),
            temperature=args.temperature, top_k=args.top_k,
            deadline_ms=args.deadline_ms,
            seed=args.seed if args.seed is not None
            else _env("MXTPU_LOADGEN_SEED"))
    else:
        transport = HttpTransport(args.url, args.model,
                                  json.loads(args.item),
                                  deadline_ms=args.deadline_ms)
    lg = LoadGen(transport, _parse_stages(args.stages),
                 arrival=args.arrival, seed=args.seed,
                 max_clients=args.max_clients, deadline_ms=args.deadline_ms,
                 tenants=_parse_tenants(args.tenants),
                 faults=_parse_faults(args.faults))
    report = lg.run()
    out_path = args.out or "<stdout>"
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    ci = report_ci(report, path=out_path,
                   max_error_rate=args.max_error_rate,
                   require_saturation=args.require_saturation)
    if args.as_json:
        json.dump(ci, sys.stdout, indent=1)
        sys.stdout.write("\n")
    else:
        for i, s in enumerate(report["stages"]):
            print("stage %d: offered %.0f rps -> goodput %.0f rps, "
                  "p50/p99 %s/%s ms, shed %.1f%%, errors %d"
                  % (i, s["offered_rps"], s["goodput_rps"],
                     s["latency_ms"]["p50"], s["latency_ms"]["p99"],
                     100 * s["shed_rate"], s["errors"]))
            g = s.get("generate")
            if g:
                print("  generate: %.0f tok/s (%d tokens), TTFT p50/p99 "
                      "%s/%s ms, inter-token p50/p99 %s/%s ms, reasons %s"
                      % (g["tokens_per_s"], g["tokens_ok"],
                         g["ttft_ms"]["p50"], g["ttft_ms"]["p99"],
                         g["inter_token_ms"]["p50"],
                         g["inter_token_ms"]["p99"],
                         g["finish_reasons"]))
            for t, tc in sorted(s.get("tenants", {}).items()):
                print("  tenant %-12s offered %4d, goodput %.0f rps, "
                      "p50/p99 %s/%s ms, shed %d"
                      % (t, tc["offered"], tc["goodput_rps"],
                         tc["latency_ms"]["p50"], tc["latency_ms"]["p99"],
                         tc["shed"]))
        sat = report["saturation"]
        print("saturation: %s" % (
            "stage %d (%.0f rps offered, %.0f goodput, %s)"
            % (sat["stage"], sat["offered_rps"], sat["goodput_rps"],
               sat["reason"]) if sat else "not reached"))
        if args.out:
            print("report: %s" % args.out)
    return 0 if ci["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
