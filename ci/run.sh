#!/usr/bin/env bash
# CI entry point (ref ci/docker/runtime_functions.sh + Jenkinsfile stages).
# One command green from a clean checkout:
#
#   ci/run.sh                 # all stages
#   ci/run.sh lint native     # selected stages
#
# Stages:
#   lint    - syntax walk over every python file (compileall) + the
#             framework-aware static-analysis gate (tools/mxtpulint/:
#             per-file rules R001-R008 + R012-R013 plus the whole-program
#             passes — lock-order cycles, cross-thread shared state,
#             jit-retrace hazards, call-graph-aware hot-path syncs —
#             over incubator_mxnet_tpu, with tools/ and tests/ under the
#             relaxed R003/R005/R006 profile) — hard fail on any
#             non-baselined finding, on a >30s wall time, and on the
#             seeded-defect canary (the testdata fixtures must yield
#             exactly the ten seeded findings); runs with
#             --check-suppressions on: a dead disable comment (X001) or
#             a stale baseline entry (X002) fails the stage
#   hlodiff - differential artifact gate (tools/hlodiff/): the five
#             seeded regression pairs (FLOPs growth, dropped donation,
#             dtype widening, gained collective, changed bucket ladder
#             — tools/hlolint/canary.py write_diff_canaries) must each
#             fire EXACTLY their D-rule while self-diffs stay empty;
#             then the deploy gate end-to-end: a donation-dropped hot
#             reload is refused with degraded reason hlodiff:D003 while
#             the prior version keeps serving zero-error under
#             concurrent clients, and a byte-identical redeploy cuts
#             over clean; wall budget 120s
#   hlolint - compiled-artifact static analysis (tools/hlolint/): trace
#             the serving-shaped programs the repo actually runs (fp32
#             dense eval buckets + a native-int8 quantized net) into a
#             fresh MXTPU_AOT_CACHE_DIR and gate the resulting
#             jax.export StableHLO artifacts through the H-rules with
#             the EMPTY committed baseline; then the seeded-defect
#             canary (one fp64 serve program + one donation-less train
#             module, tools/hlolint/canary.py) must fire exactly
#             H001+H002; finally the one-parser aggregation: the
#             mxtpulint / promcheck / hlolint / hlodiff --json reports
#             (hlodiff's from the byte-identical self-diff of the real
#             traced artifacts — the empty-diff contract) are merged
#             into a single per-run artifact and asserted to share the
#             exact report shape
#   native  - rebuild libmxtpu.so + libmxtpu_predict.so from src, then a
#             TSAN (-fsanitize=thread) compile of the native layer (the
#             race-detection build the TSAN test also uses; ref ASAN job)
#   suite   - quick test suite on the 8-device virtual CPU mesh
#   serving - inference serving subsystem end-to-end on CPU (dynamic
#             batching, hot reload, backpressure, HTTP front-end)
#   aot     - zero-recompile hot path: prewarm every batcher bucket
#             through the shared AOT executable cache, then replay a
#             traffic sweep across all buckets and HARD-FAIL if
#             mxtpu_jit_compiles_total moves (or any compile span lands)
#             during the post-warm window — the ROADMAP item 3 "p99 must
#             not see a compile" contract, gated
#   observability - boot the serving server, drive traffic, scrape
#             GET /metrics over the wire, and validate the Prometheus
#             exposition with the stdlib parser (tools/promcheck.py,
#             incl. the P002 HELP/TYPE hygiene rule);
#             also exercises the headless periodic-flush file path
#   devstats - device-truth gate (telemetry/devstats.py): a short
#             in-process soak through the serving registry asserting
#             nonzero mxtpu_device_mfu / mxtpu_aot_program_flops /
#             mxtpu_device_memory_bytes in the exposition, a fresh-
#             subprocess artifact-only load whose /metrics still
#             reports nonzero program FLOPs (device truth survives
#             zero-compile loads), and /debug/profile single-flight
#             (concurrent capture -> 409); wall budget 60s
#   profstats - op-level attribution gate (telemetry/profstats.py): a
#             matmul-dominated soak with traffic strictly inside the
#             capture window must rank a nonzero matmul-category entry
#             on GET /debug/hotspots AND cross-check within 20% of the
#             devstats dispatch-seconds counter delta (two independent
#             clocks agreeing on where the time went); the continuous
#             daemon's serving tax is gated (p99 within 10% of a
#             daemon-off baseline, interleaved repeats + minima); and
#             tools/profsum.py must diff identical summaries empty
#             while the injected-2x-op-time canary fires (S001 naming
#             the op class) — the gate can still fire; wall budget 120s
#   loadgen - open-loop load harness: three CPU soak repeats
#             (tools/loadgen.py: Poisson ramp over a timer-bound
#             servable, per-stage p50/95/99, X-Request-Id span join,
#             detected saturation point); each must end with zero hard
#             errors and a saturation point, and the span join must
#             cover the OK responses. Control flow only: a CPU wall
#             time is compared with nothing (docs/LOADGEN.md)
#   slo     - SLO engine e2e (telemetry/slo.py + the tenant wiring): a
#             tenant-mixed loadgen soak against a servable with an
#             injectable failure window proves the fast-burn alert
#             fires during the burst (flightrec event + firing gauge +
#             burn rate over threshold) and resolves after it via
#             scrapes alone, per-tenant counters split the soak, and
#             the live exposition passes promcheck; then the fake-clock
#             SLO/access-log unit tier (tests/test_slo.py, zero real
#             sleeps); wall budget 60s
#   generate - generative-inference serving gate (serving/generate.py +
#             ops/kvcache.py, docs/GENERATE.md): a saturating mixed
#             prefill/decode soak through tools/loadgen.py --generate
#             must beat the sequential-decode baseline on tokens/s
#             (continuous batching earning its keep), the post-warm
#             window must see zero compiles (counter + span patterns),
#             the decode window's op profile must be memory-bound
#             (non-matmul categories own the self time), and the
#             undonated-decode canary must fire hlolint H002 at error
#             severity with a nonzero exit
#   numerics - numerics-sentinel gate (telemetry/numwatch.py,
#             docs/OBSERVABILITY.md "Numerical health"): an injected-NaN
#             canary servable fires exactly ONE nan_storm flightrec
#             episode (hysteresis, not an event per poisoned batch); a
#             deliberately mis-calibrated int8 servable's shadow vs its
#             fp32 reference breaches and flips health to degraded while
#             a sanely calibrated twin stays clean; the tap reducers add
#             ZERO post-warm compiles (aot miss counter, kind
#             "numwatch"); and interleaved paired p99 repeats (profstats
#             phase-B methodology) hold the taps-on serving tax to
#             <= 1.10x
#   sharded - mesh-sharded serving gate on a forced-8-device CPU host:
#             two interleaved 1-replica vs 8-replica loadgen soaks of a
#             timer-bound servable driven through the in-process
#             transport (the stdlib HTTP front-end tops out an order of
#             magnitude below 8 replica workers, so HTTP would measure
#             the web server, not serving), saturation detected on BOTH
#             ramps, zero hard errors, and per-replica dispatch
#             balance asserted (every replica served, none hogged);
#             the 1->8 goodput ratio is printed, compared with nothing
#             (docs/SERVING.md)
#   chaos   - self-healing serving gate (telemetry/faultlab.py +
#             serving/resilience.py, docs/RESILIENCE.md): the chaos unit
#             tier (tests/test_resilience.py — deterministic fault
#             injection, retry/respawn/park, decode-loop resurrection,
#             last-known-good rollback, the 503 no_replicas contract,
#             and the <= 1.05x disarmed-guard-tax paired-p99 gate); then
#             a supervised 4-replica loadgen soak with seeded replica
#             kills injected mid-ramp (--faults) asserting availability
#             >= 97% under chaos, zero stranded arrivals, clean
#             before/after stages, retries + respawns actually observed,
#             and the fleet healed within the backoff budget; a decode
#             loop killed under supervision must finish its streams
#             after resurrection, and a degraded flip must roll dispatch
#             back to the prior version; finally the unsupervised canary
#             (same kill, no Supervisor) must FAIL the healed check —
#             proof the gate fires; wall budget 120s
#   history - metric flight recorder (telemetry/history.py,
#             docs/OBSERVABILITY.md "Metric history & incident
#             timelines"): the unit tier (tests/test_history.py — ring
#             retention, tiered downsampling, recording rules,
#             pressure_rising / mfu_droop hysteresis, /debug/ index pin,
#             detach-on-close, and the <= 1.05x self-scrape-tax
#             paired-p99 gate); then a supervised loadgen soak with a
#             seeded mid-run replica_kill asserting the incident
#             timeline carries the fault injection, the queue-depth
#             excursion, and the respawn in causal order; then the
#             early-warning e2e — a saturating submit ramp must fire
#             pressure_rising while the calm phase stays silent; and
#             the exported JSONL must round-trip byte-stable through
#             tools/tsq.py; wall budget 120s
#   diagnostics - the "why is it slow / why is it stuck" layer: span
#             tracing (nesting, queue-boundary propagation, chrome-trace
#             parenting, 16-thread race), flight recorder (ring bound,
#             crash dump), and the stall watchdog (forced-stall e2e:
#             blocked batcher worker -> one stack dump + tape tail while
#             /healthz keeps answering)
#   smoke   - driver contract: entry() jit-compiles on CPU and
#             dryrun_multichip(8) runs a full sharded train step
#   large   - int64 large-tensor tier (>2^31 elements; int8/uint8 dtypes
#             keep it ~2.2 GB — ref tests/nightly/test_large_array.py)
#   wheel   - sdist + wheel build including fresh native libs (ref
#             tools/pip staticbuild)
set -euo pipefail
cd "$(dirname "$0")/.."

STAGES=("$@")
[ ${#STAGES[@]} -eq 0 ] && STAGES=(lint hlolint hlodiff native suite serving aot observability devstats profstats loadgen slo generate numerics sharded chaos history diagnostics smoke large wheel)

has_stage() { local s; for s in "${STAGES[@]}"; do [ "$s" = "$1" ] && return 0; done; return 1; }

if has_stage lint; then
  echo "=== lint: syntax walk + mxtpulint gate (two-phase) ==="
  python -m compileall -q incubator_mxnet_tpu tests tools chip_smoke.py __graft_entry__.py
  # Per-file rules R001-R008 + R012-R013 over the runtime (tools/ and tests/ under
  # the relaxed R003/R005/R006 profile) + the whole-program passes
  # (R009-R011, interprocedural R001); exits nonzero on any finding that
  # is neither inline-suppressed nor in tools/mxtpulint/baseline.json.
  # One run emits the JSON artifact (shape shared with
  # `tools/promcheck.py --json`) so a downstream aggregator merges both
  # gates with one parser; on failure the findings are echoed
  # human-readably. Wall time is printed and budget-checked: the
  # content-hash AST cache keeps index+rules under 30s — a blowup here
  # is a lint-engine regression, not noise.
  # --check-suppressions is default-on here: X001 dead disable comments
  # and X002 stale baseline entries fail the stage (suppression debt is
  # paid forward, never accumulated)
  LINT_JSON=$(mktemp -t mxtpulint.XXXXXX.json)   # per-run: no clobber
  lint_t0=$SECONDS
  python -m tools.mxtpulint incubator_mxnet_tpu tools tests \
      --check-suppressions --json > "$LINT_JSON" \
    || { python -m tools.mxtpulint incubator_mxnet_tpu tools tests \
           --check-suppressions || true; exit 1; }
  lint_dt=$(( SECONDS - lint_t0 ))
  python -c "import json,sys; r=json.load(open(sys.argv[1])); \
print('mxtpulint OK: %d baselined, %ss wall, artifact %s' \
% (r['baselined'], sys.argv[2], sys.argv[1]))" "$LINT_JSON" "$lint_dt"
  [ "$lint_dt" -lt 30 ] || { echo "lint stage took ${lint_dt}s (budget 30s)"; exit 1; }
  # Seeded-defect canary: the whole-program passes must still FIRE. The
  # fixtures hold one known deadlock cycle, one unlocked cross-thread
  # write, one jax.jit retrace hazard, one AOT-boundary retrace hazard
  # (aot.compile_cached), one donation-less train-step jit (R012 — the
  # source-side mirror of hlolint H002), one host-device sync in the
  # replica dispatch hot path, one per-dispatch XLA cost_analysis walk
  # in the servable-call hot path, one per-dispatch profiler-trace
  # parse in the batch hot path, one per-element host-side
  # finite-check loop in the worker loop (seeded_batcher.py,
  # HOT_PATH_PATTERNS + the device-truth, trace-walk and finite-check
  # R001 sub-rules), and one unpaced respawn retry loop (R013 — the
  # source-side mirror of the supervisor's backoff/park policy);
  # full-profile analysis rooted at the fixture dir must report exactly
  # those ten.
  python - <<'EOF'
from tools.mxtpulint import analyze
found = sorted(f.rule for f in analyze(["tools/mxtpulint/testdata"],
                                       root="tools/mxtpulint/testdata"))
assert found == ["R001", "R001", "R001", "R001", "R009", "R010", "R011",
                 "R011", "R012", "R013"], found
print("seeded-defect canary OK: %s" % ", ".join(found))
EOF
fi

if has_stage hlolint; then
  echo "=== hlolint: compiled StableHLO artifact gate + seeded canary + one-parser aggregation ==="
  hl_t0=$SECONDS
  HL_DIR=$(mktemp -d -t mxtpu_hlolint.XXXXXX)
  # 1) Real artifacts, green with the EMPTY committed baseline: trace the
  # serving-shaped programs the repo actually runs — fp32 dense eval at
  # the default bucket ladder AND a native-int8 quantized net (whose i8
  # dot_general is the H006 negative: real int8 stays clean) — into a
  # fresh cache dir, then the gate must exit 0 with zero findings.
  JAX_PLATFORMS=cpu MXTPU_AOT_CACHE_DIR="$HL_DIR/cache" python - <<'EOF'
import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, jit, nd
from incubator_mxnet_tpu.contrib import quantization

mx.random.seed(0)
net = gluon.nn.Dense(8, in_units=16)
net.initialize(mx.init.Xavier())
for b in (1, 2, 4):
    jit.EvalStep(net)(nd.ones((b, 16)))
qsrc = gluon.nn.HybridSequential()
qsrc.add(gluon.nn.Dense(8, in_units=16))
qsrc.initialize(mx.init.Xavier())
qnet = quantization.quantize_net(qsrc, calib_data=[nd.ones((4, 16))])
jit.EvalStep(qnet)(nd.ones((4, 16)))
print("traced 4 real artifacts (3 fp32 buckets + 1 native-int8)")
EOF
  JAX_PLATFORMS=cpu python -m tools.hlolint "$HL_DIR/cache" --json --timing \
      > "$HL_DIR/hlolint.json" \
    || { JAX_PLATFORMS=cpu python -m tools.hlolint "$HL_DIR/cache" || true
         exit 1; }
  python -c "import json,sys; r=json.load(open(sys.argv[1])); \
assert r['ok'] and r['findings'] == [] and r['baselined'] == 0, r; \
print('hlolint OK on real artifacts (empty baseline, 0 findings)')" \
      "$HL_DIR/hlolint.json"
  # 2) Seeded-defect canary: the H-passes must still FIRE. One fp64
  # serve program and one donation-less train module must report exactly
  # H001 + H002 — anything else (more, fewer, different) hard-fails.
  JAX_PLATFORMS=cpu python -m tools.hlolint.canary "$HL_DIR/canary"
  if JAX_PLATFORMS=cpu python -m tools.hlolint "$HL_DIR/canary" \
      --no-baseline --json > "$HL_DIR/canary.json"; then
    echo "hlolint canary FAILED: seeded defects passed the gate"
    exit 1
  fi
  python - "$HL_DIR/canary.json" <<'EOF'
import json, sys
rep = json.load(open(sys.argv[1]))
rules = sorted(f["rule"] for f in rep["findings"])
assert rules == ["H001", "H002"], rules
assert rep["counts"] == {"H001": 1, "H002": 1}, rep["counts"]
print("hlolint seeded-defect canary OK: %s" % ", ".join(rules))
EOF
  # 3) One-parser aggregation: all four analyzers' --json reports into
  # a single per-run artifact, asserting the shared report shape
  # (tool/ok/findings/counts/baselined; findings path/line/rule/message)
  # so a downstream consumer can keep using ONE parser for every gate.
  # The lint stage's report is reused when it ran in this invocation
  # (the project-wide analysis costs up to its 30s budget); a standalone
  # `ci/run.sh hlolint` computes it fresh.
  if [ -n "${LINT_JSON:-}" ] && [ -s "${LINT_JSON:-}" ]; then
    cp "$LINT_JSON" "$HL_DIR/mxtpulint.json"
  else
    python -m tools.mxtpulint incubator_mxnet_tpu tools tests --json \
        > "$HL_DIR/mxtpulint.json"
  fi
  JAX_PLATFORMS=cpu python -c "import incubator_mxnet_tpu as mx; \
from incubator_mxnet_tpu import telemetry; \
open('$HL_DIR/metrics.prom', 'w').write(telemetry.export_text())"
  python tools/promcheck.py "$HL_DIR/metrics.prom" --json \
      > "$HL_DIR/promcheck.json"
  # the hlodiff report: a byte-identical self-diff of the real traced
  # artifacts — the acceptance contract's "redeploy of identical bytes
  # diffs EMPTY" — doubles as the 4th one-parser report
  JAX_PLATFORMS=cpu python -m tools.hlodiff "$HL_DIR/cache" \
      --base "$HL_DIR/cache" --no-baseline --json \
      > "$HL_DIR/hlodiff.json" \
    || { echo "hlodiff self-diff must be empty"; exit 1; }
  python - "$HL_DIR" <<'EOF'
import json, os, sys
hl_dir = sys.argv[1]
reports = [json.load(open(os.path.join(hl_dir, n)))
           for n in ("mxtpulint.json", "promcheck.json", "hlolint.json",
                     "hlodiff.json")]
keys = {"tool", "ok", "findings", "counts", "baselined"}
f_keys = {"path", "line", "rule", "message"}
for rep in reports:
    assert set(rep) == keys, (rep.get("tool"), sorted(rep))
    for f in rep["findings"]:
        assert set(f) == f_keys, (rep["tool"], sorted(f))
    assert rep["ok"], (rep["tool"], rep["findings"])
merged = {"schema": "mxtpu-lint-aggregate-v1",
          "ok": all(r["ok"] for r in reports),
          "reports": {r["tool"]: r for r in reports}}
out = os.path.join(hl_dir, "lint_reports.json")
with open(out, "w") as f:
    json.dump(merged, f, indent=1, sort_keys=True)
print("one-parser aggregation OK: %s (%s)"
      % (out, ", ".join(sorted(merged["reports"]))))
EOF
  hl_dt=$(( SECONDS - hl_t0 ))
  echo "hlolint stage wall time: ${hl_dt}s (budget 120s)"
  [ "$hl_dt" -lt 120 ] || { echo "hlolint stage took ${hl_dt}s (budget 120s)"; exit 1; }
fi

if has_stage hlodiff; then
  echo "=== hlodiff: differential artifact gate (regression vs last-known-good) ==="
  hd_t0=$SECONDS
  HD_DIR=$(mktemp -d -t mxtpu_hlodiff.XXXXXX)
  # 1) Seeded regression pairs (tools/hlolint/canary.py
  # write_diff_canaries): each candidate diffed against its base must
  # fire EXACTLY its rule — anything else (more, fewer, different)
  # hard-fails; and every pair self-diffed must be empty.
  JAX_PLATFORMS=cpu python - "$HD_DIR" <<'EOF'
import json, os, subprocess, sys
hd_dir = sys.argv[1]
from tools.hlolint.canary import write_diff_canaries

def diff(cand, base):
    r = subprocess.run(
        [sys.executable, "-m", "tools.hlodiff", cand, "--base", base,
         "--no-baseline", "--json"],
        capture_output=True, text=True, timeout=300)
    return r.returncode, json.loads(r.stdout)

pairs = write_diff_canaries(os.path.join(hd_dir, "pairs"))
assert set(pairs) == {"flops", "donation", "widened", "collective",
                      "ladder"}, sorted(pairs)
for name, (base_dir, cand_dir, expected) in sorted(pairs.items()):
    rc, rep = diff(cand_dir, base_dir)
    rules = {f["rule"] for f in rep["findings"]}
    assert rc == 1 and rules == expected, (name, rc, sorted(rules))
    rc, rep = diff(cand_dir, cand_dir)     # byte-identical: empty diff
    assert rc == 0 and rep["ok"] and rep["findings"] == [], (name, rep)
print("hlodiff seeded-regression canaries OK: 5 pairs, exact rules, "
      "self-diffs empty")
EOF
  # 2) Deploy gate end-to-end: a donation-dropped v2 hot reload is
  # REFUSED with degraded reason hlodiff:D003 while v1 keeps serving
  # (zero client-visible errors), and a byte-identical redeploy cuts
  # over clean with an empty diff.
  JAX_PLATFORMS=cpu MXTPU_AOT_CACHE_DIR="$HD_DIR/cache" python - <<'EOF'
import threading
import numpy as onp
from incubator_mxnet_tpu.serving import ModelRegistry
from tests.test_hlodiff import _ServeServable, _light

reg = ModelRegistry()
reg.load("ci", _ServeServable("ci-hlodiff-v1", _light, donate=(0,)),
         warm_spec=[((4, 4), "float32")], max_batch_size=2,
         batch_timeout_ms=1.0)
errs, stop = [], threading.Event()
def client():
    while not stop.is_set():
        try:
            out = reg.predict("ci", onp.ones((4, 4), "float32"),
                              timeout=30)
            assert float(out[0][0][0]) == 2.0
        except Exception as e:
            errs.append(e)
            return
threads = [threading.Thread(target=client) for _ in range(4)]
for t in threads: t.start()
try:
    reg.load("ci", _ServeServable("ci-hlodiff-v2", _light),
             warm_spec=[((4, 4), "float32")])
finally:
    stop.set()
    for t in threads: t.join(30)
assert not errs, errs
desc = [m for m in reg.models() if m["name"] == "ci"][0]
assert desc["current_version"] == 1, desc
assert desc["degraded"] and "hlodiff:D003" in desc["degraded"], desc
out = reg.predict("ci", onp.ones((4, 4), "float32"), timeout=30)
assert float(out[0][0][0]) == 2.0
# byte-identical redeploy: all cache hits, empty diff, clean cutover
v3 = reg.load("ci", _ServeServable("ci-hlodiff-v1", _light,
                                   donate=(0,)),
              warm_spec=[((4, 4), "float32")])
desc = [m for m in reg.models() if m["name"] == "ci"][0]
assert desc["current_version"] == v3 and desc["degraded"] is None, desc
reg.close()
print("hlodiff deploy gate OK: regressed reload refused "
      "(hlodiff:D003), v1 served 0-error throughout, byte-identical "
      "redeploy cut over clean")
EOF
  hd_dt=$(( SECONDS - hd_t0 ))
  echo "hlodiff stage wall time: ${hd_dt}s (budget 120s)"
  [ "$hd_dt" -lt 120 ] || { echo "hlodiff stage took ${hd_dt}s (budget 120s)"; exit 1; }
fi

if has_stage native; then
  echo "=== native: rebuild + TSAN compile ==="
  python - <<'EOF'
import jax
jax.config.update("jax_platforms", "cpu")
from incubator_mxnet_tpu.native import lib
print(lib.build(force=True))
print(lib.build_predict(force=True))
EOF
  TSAN_OUT=$(mktemp -d)/libmxtpu_tsan.so
  g++ -O1 -g -std=c++17 -shared -fPIC -pthread -fsanitize=thread \
      incubator_mxnet_tpu/native/src/recordio.cc \
      incubator_mxnet_tpu/native/src/image.cc \
      incubator_mxnet_tpu/native/src/c_api.cc \
      -o "$TSAN_OUT" -ljpeg
  echo "tsan build ok: $TSAN_OUT"
fi

if has_stage suite; then
  echo "=== suite: quick tests on the 8-device virtual CPU mesh ==="
  # test_serving.py runs in its own stage below — don't pay for the
  # 64-client e2e tier twice in the default pipeline
  MXTPU_TEST_QUICK=1 python -m pytest tests/ -q -x --ignore=tests/test_serving.py
fi

if has_stage serving; then
  echo "=== serving: inference serving subsystem e2e on CPU ==="
  python -m pytest tests/test_serving.py -q
fi

if has_stage aot; then
  echo "=== aot: zero-recompile post-warm serving sweep ==="
  # Warm a model (every configured bucket, via load(warm_spec=...)), then
  # replay a traffic sweep that exercises each bucket size and hard-fail
  # if the compile counter moves or any compile span lands after warm —
  # the executable-cache contract a perf PR must never silently lose.
  # Budgeted like the lint stage: a blowup here means the cache stopped
  # hitting, not noise.
  aot_t0=$SECONDS
  JAX_PLATFORMS=cpu python - <<'EOF'
import threading
import numpy as onp
import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, jit
from incubator_mxnet_tpu.serving import ModelRegistry
from incubator_mxnet_tpu.telemetry import spans

mx.random.seed(0)
net = gluon.nn.Dense(4, in_units=8)
net.initialize(mx.init.Xavier())
reg = ModelRegistry()
reg.load("aot-ci", net, max_batch_size=4, batch_timeout_ms=3.0,
         warm_spec=[((8,), "float32")])
warmed = reg.metrics("aot-ci").prewarm_count
assert warmed == 3, "expected buckets 1,2,4 warmed, got %d" % warmed
c0 = (jit._COMPILES.value(kind="eval") + jit._COMPILES.value(kind="train"))
mark = len(spans.snapshot())
# sweep: concurrent bursts sized to land in every bucket
for burst in (1, 2, 3, 4, 1, 4):
    reqs = [reg.submit("aot-ci", onp.full((8,), i, "float32"))
            for i in range(burst)]
    for r in reqs:
        r.result(60.0)
c1 = (jit._COMPILES.value(kind="eval") + jit._COMPILES.value(kind="train"))
assert c1 == c0, "mxtpu_jit_compiles_total moved post-warm: %s -> %s" % (c0, c1)
bad = [s["name"] for s in spans.snapshot()[mark:]
       if s["name"] in ("eval:compile", "train:compile", "eval:build",
                        "train:build")]
assert not bad, "compile spans landed in the post-warm window: %s" % bad
ok = reg.metrics("aot-ci").ok_count
reg.close()
print("aot OK: %d buckets prewarmed, %d post-warm requests, 0 compiles"
      % (warmed, ok))
EOF
  aot_dt=$(( SECONDS - aot_t0 ))
  echo "aot stage wall time: ${aot_dt}s (budget 60s)"
  [ "$aot_dt" -lt 60 ] || { echo "aot stage took ${aot_dt}s (budget 60s)"; exit 1; }
fi

if has_stage observability; then
  echo "=== observability: scrape /metrics + validate Prometheus text ==="
  JAX_PLATFORMS=cpu python - <<'EOF'
import json, sys, tempfile, threading, urllib.request
from tools import promcheck
from incubator_mxnet_tpu.serving import ModelRegistry, ServingServer
from incubator_mxnet_tpu import telemetry

class Echo:
    def predict_batch(self, x):
        return (x + 1.0,)

reg = ModelRegistry()
reg.load("ci", Echo(), max_batch_size=4, batch_timeout_ms=10.0)
with ServingServer(reg, port=0) as srv:
    def fire(i):
        body = json.dumps({"inputs": [[float(i)]]}).encode()
        req = urllib.request.Request(
            srv.url + "/v1/models/ci:predict", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.status == 200 and r.headers["X-Request-Id"]
    threads = [threading.Thread(target=fire, args=(i,)) for i in range(16)]
    for t in threads: t.start()
    for t in threads: t.join(60)
    with urllib.request.urlopen(srv.url + "/metrics", timeout=30) as r:
        assert r.headers["Content-Type"].startswith("text/plain"), \
            r.headers["Content-Type"]
        text = r.read().decode()
    with urllib.request.urlopen(srv.url + "/metrics.json", timeout=30) as r:
        legacy = json.loads(r.read())
types = promcheck.validate(text)
assert not promcheck.validate_metadata(text), promcheck.validate_metadata(text)
# P003 naming conventions over the LIVE scrape: counters end _total,
# lowercase names, base units — only the grandfathered _ms histograms
# (promcheck.P003_EXEMPT) get a pass, and the exempt list must not
# have silently grown past its three known names
assert not promcheck.validate_names(text), promcheck.validate_names(text)
assert len(promcheck.P003_EXEMPT) == 3, sorted(promcheck.P003_EXEMPT)
assert types["mxtpu_serving_requests_total"] == "counter", types
assert types["mxtpu_serving_batch_size"] == "histogram", types
assert 'mxtpu_serving_ok_total{model="ci"} 16' in text
assert legacy["ci"]["ok_count"] == 16, legacy
# headless path: flush the same registry to a file and re-validate
path = tempfile.mktemp(suffix=".prom")
telemetry.flush_to_file(path)
promcheck.validate(open(path).read())
print("observability OK: %d families scraped + flushed" % len(types))
EOF
fi

if has_stage devstats; then
  echo "=== devstats: device-truth gate (MFU + HBM + zero-compile survival) ==="
  # The attribution chain end-to-end: a soak drives the serving registry
  # and the exposition must carry nonzero per-dispatch MFU and program
  # FLOPs; the sampler must export memory series; /debug/profile must be
  # single-flight; and a FRESH process doing an artifact-only load (zero
  # compiles) must still report nonzero mxtpu_aot_program_flops — device
  # truth survives the zero-recompile path it exists to judge.
  dv_t0=$SECONDS
  DV_CACHE=$(mktemp -d -t mxtpu_devstats.XXXXXX)
  JAX_PLATFORMS=cpu MXTPU_AOT_CACHE_DIR="$DV_CACHE" python - <<'EOF'
import json, os, re, subprocess, sys, threading, time, urllib.request, urllib.error
import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, telemetry
from incubator_mxnet_tpu.telemetry import devstats
from incubator_mxnet_tpu.serving import ModelRegistry, ServingServer
from tools import loadgen, promcheck
import numpy as onp

def series(text, name):
    return [l for l in text.splitlines()
            if l.startswith(name) and not l.startswith("#")
            and not l.startswith(name + "_")]

def nonzero(text, name):
    vals = [float(l.rsplit(None, 1)[1]) for l in series(text, name)]
    assert vals and any(v > 0 for v in vals), (name, vals)

mx.random.seed(0)
net = gluon.nn.Dense(8, in_units=16)
net.initialize(mx.init.Xavier())
reg = ModelRegistry()
reg.load("devci", net, max_batch_size=4, batch_timeout_ms=1.0)

# HBM sampler up for the whole soak (heartbeat-registered daemon)
devstats.start(poll_s=0.05)

with ServingServer(reg, port=0) as srv:
    # HTTP loadgen soak: the stage report must attribute queue/batch/
    # device time AND carry the scrape-derived mfu/device_s columns
    tr = loadgen.HttpTransport(srv.url, "devci", [0.0] * 16)
    lg = loadgen.LoadGen(tr, stages=[{"rps": 120, "duration_s": 1.0}],
                         arrival="poisson", seed=0, max_clients=64)
    report = lg.run()
    st = report["stages"][0]
    assert st["ok"] > 0 and st["errors"] == 0, st
    srv_side = st["server"]
    for leg in ("queue_ms", "batch_ms", "device_ms"):
        assert srv_side[leg]["count"] > 0, (leg, srv_side[leg])
    m = srv_side["metrics"]
    assert m["device_s"] and m["device_s"] > 0, m
    assert m["mfu"] and m["mfu"] > 0, m
    print("loadgen attribution OK: queue/batch/device joined, "
          "stage mfu %.2e, device_s %.4fs" % (m["mfu"], m["device_s"]))

    with urllib.request.urlopen(srv.url + "/metrics", timeout=30) as r:
        text = r.read().decode()
    nonzero(text, "mxtpu_device_mfu")
    nonzero(text, "mxtpu_device_hbm_bw_util")
    nonzero(text, "mxtpu_aot_program_flops")
    nonzero(text, "mxtpu_device_flops_total")
    nonzero(text, "mxtpu_device_memory_bytes")
    nonzero(text, "mxtpu_device_peak_flops")
    promcheck.validate(text)
    assert not promcheck.validate_metadata(text)

    # /debug/profile single-flight: concurrent captures -> 200 + 409.
    # Deterministic overlap: fire the second request only once the first
    # capture HOLDS the single-flight lock (polling, not a fixed sleep —
    # a loaded box could otherwise serialize the two captures).
    codes = []
    def cap():
        try:
            with urllib.request.urlopen(
                    srv.url + "/debug/profile?seconds=1.5", timeout=30) as r:
                codes.append(r.status)
        except urllib.error.HTTPError as e:
            e.close()
            codes.append(e.code)
    threads = [threading.Thread(target=cap) for _ in range(2)]
    threads[0].start()
    deadline = time.monotonic() + 10.0
    while not devstats.capture_in_progress():
        assert time.monotonic() < deadline, "first capture never started"
        time.sleep(0.01)
    threads[1].start()
    for t in threads:
        t.join(30)
    assert sorted(codes) == [200, 409], codes
    print("profile single-flight OK: %s" % sorted(codes))

devstats.stop()
# detach-on-close: a stopped sampler must not export frozen bytes
assert not series(telemetry.export_text(), "mxtpu_device_memory_bytes")
print("devstats soak OK")
EOF
  # fresh process, artifact-only load: zero compiles, nonzero program flops
  JAX_PLATFORMS=cpu MXTPU_AOT_CACHE_DIR="$DV_CACHE" python - <<'EOF'
import json
import jax
jax.config.update("jax_platforms", "cpu")
import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import aot, gluon, jit, nd, telemetry

mx.random.seed(0)
net = gluon.nn.Dense(8, in_units=16)
net.initialize(mx.init.Xavier())
out = jit.EvalStep(net)(nd.ones((4, 16)))
hits = aot._ARTIFACT_HITS.value(kind="eval")
compiles = jit._COMPILES.value(kind="eval")
assert hits >= 1 and compiles == 0, (hits, compiles)
flops = [float(l.rsplit(None, 1)[1]) for l in telemetry.export_text().splitlines()
         if l.startswith("mxtpu_aot_program_flops{")]
assert flops and max(flops) > 0, flops
print("zero-compile survival OK: artifact_hits=%d compiles=%d "
      "program_flops=%s" % (hits, compiles, max(flops)))
EOF
  dv_dt=$(( SECONDS - dv_t0 ))
  echo "devstats stage wall time: ${dv_dt}s (budget 60s)"
  [ "$dv_dt" -lt 60 ] || { echo "devstats stage took ${dv_dt}s (budget 60s)"; exit 1; }
fi

if has_stage profstats; then
  echo "=== profstats: op-level attribution + daemon-tax + profsum gate ==="
  # Attribution is checked against an INDEPENDENT clock: the trace's
  # per-category self-times (XLA executor events) must agree within 20%
  # with the devstats dispatch-seconds counter delta (block-until-ready
  # wall, measured levels away). Traffic runs strictly inside the
  # capture window so every dispatch the counter counts had its device
  # time in the trace — without that protocol, edge dispatches straddling
  # the window make the comparison measure the protocol, not the parser.
  ps_t0=$SECONDS
  PS_DIR=$(mktemp -d -t mxtpu_profstats.XXXXXX)
  JAX_PLATFORMS=cpu MXTPU_PROFILE_DIR="$PS_DIR" python - <<'EOF'
import json, threading, time, urllib.request
import numpy as onp
import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon
from incubator_mxnet_tpu.telemetry import profstats
from incubator_mxnet_tpu.serving import ModelRegistry, ServingServer
from tools import profsum

mx.random.seed(0)

# ------------------------- phase A: attribution soak + /debug/hotspots
# a wide Dense so the matmul owns the window: the 20% cross-check needs
# compute, not per-dispatch overhead, to dominate both clocks
net = gluon.nn.Dense(4096, in_units=4096)
net.initialize(mx.init.Xavier())
reg = ModelRegistry()
reg.load("profci", net, max_batch_size=8, batch_timeout_ms=1.0)
item = onp.zeros((4096,), dtype=onp.float32)
errors = []

def churn(stop_t):
    while time.monotonic() < stop_t:
        try:
            reg.predict("profci", item, timeout=30.0)
        except Exception as e:
            errors.append(repr(e))
            return

# warm: every batch bucket this thread count produces compiles BEFORE
# the measured window (a compile inside it lands in dispatch-seconds
# but not in op self-time, blowing the 20% band)
warm_end = time.monotonic() + 2.0
ths = [threading.Thread(target=churn, args=(warm_end,)) for _ in range(2)]
for t in ths: t.start()
for t in ths: t.join(30.0)
assert not errors, errors

# throwaway capture: the first profiler session in a process pays a
# multi-second one-time setup that must not land in a measured window
profstats.capture_and_summarize(0.05, fold=False)

def timed_traffic():
    time.sleep(0.2)
    stop_t = time.monotonic() + 1.2
    inner = [threading.Thread(target=churn, args=(stop_t,))
             for _ in range(2)]
    for t in inner: t.start()
    for t in inner: t.join(30.0)

tt = threading.Thread(target=timed_traffic)
tt.start()
out, summary = profstats.capture_and_summarize(1.8)
tt.join(30.0)
assert not errors, errors

cats = summary["categories"]
assert cats.get("matmul", {}).get("self_us", 0) > 0, cats
assert summary["ops"][0]["category"] == "matmul", summary["ops"][0]
cat_s = sum(d["self_us"] for d in cats.values()) / 1e6
disp_s = summary["devstats"]["dispatch_s"]
assert disp_s > 0, summary["devstats"]
ratio = cat_s / disp_s
assert 0.8 <= ratio <= 1.2, (cat_s, disp_s, ratio)
print("attribution OK: top op %s, category-sum %.3fs vs "
      "dispatch-seconds %.3fs (ratio %.3f)"
      % (summary["ops"][0]["op"], cat_s, disp_s, ratio))

# the soak was folded into the rolling aggregates -> the live route
# must rank a nonzero matmul entry
with ServingServer(reg, port=0) as srv:
    with urllib.request.urlopen(srv.url + "/debug/hotspots?n=10",
                                timeout=30) as r:
        hs = json.loads(r.read())
    assert hs["captures"] >= 1, hs
    assert hs["categories"]["matmul"]["self_us"] > 0, hs["categories"]
    assert hs["ops"][0]["category"] == "matmul", hs["ops"][0]
    print("hotspots OK: %d captures, top %s" % (hs["captures"],
                                                hs["ops"][0]["op"]))

# summarize the capture dir NOW: phase B's daemon captures will prune
# it (MXTPU_PROFILE_KEEP) — the JSON summary is the durable artifact
import sys, tempfile, os
tmp = tempfile.mkdtemp(prefix="mxtpu_profsum.")
a_json = os.path.join(tmp, "a.json")
assert profsum.main(["summarize", out["dir"], "--out", a_json]) == 0

# --------------------------------- phase B: daemon serving-tax gate
# TIMER-bound servable: capacity set by clocks, so the p99 comparison
# measures the daemon's tax, not host speed; interleaved off/on repeats
# with per-arm minima recover clean numbers from co-tenant noise
class SlowEcho:
    def predict_batch(self, x):
        time.sleep(0.004)
        return (x + 1.0,)

reg2 = ModelRegistry()              # the first one died with its server
reg2.load("slowci", SlowEcho(), max_batch_size=4, batch_timeout_ms=1.0)
slow_item = onp.zeros((4,), dtype=onp.float32)

def p99(n=300):
    lat = []
    for _ in range(n):
        t0 = time.perf_counter()
        reg2.predict("slowci", slow_item, timeout=30.0)
        lat.append(time.perf_counter() - t0)
    lat.sort()
    return lat[int(0.99 * len(lat)) - 1]

# per-repeat PAIRED ratios, gate on their minimum: co-tenant noise and
# slow drift inflate one arm of one repeat, but a repeat where both
# arms ran clean yields the true tax — cross-repeat minima per arm
# would compare a lucky off against an unlucky on
# interval 1.0s with the 0.05s capture floor = 5% duty — a CI-
# compressed cycle (every measurement window sees captures) while
# staying near the production MXTPU_PROFSTATS_MAX_DUTY regime
pairs = []
for rep in range(4):
    off_i = p99()
    assert profstats.start(interval_s=1.0, capture_s=0.05)
    try:
        on_i = p99()
    finally:
        profstats.stop()
    pairs.append((off_i, on_i))
ratio = min(on_i / off_i for off_i, on_i in pairs)
assert ratio <= 1.10, (ratio, pairs)
print("daemon tax OK: best paired p99 ratio %.3f over %d repeats"
      % (ratio, len(pairs)))
reg2.close()

# ------------------------- phase C: profsum diff + injected canary
# identical summaries -> empty report, exit 0
assert profsum.main(["diff", a_json, a_json]) == 0
# injected 2x op-time canary -> S001 fires naming the op class
import io, contextlib
buf = io.StringIO()
with contextlib.redirect_stdout(buf):
    rc = profsum.main(["diff", a_json, a_json, "--json",
                       "--inject-slowdown", "2.0"])
assert rc == 1, rc
rep = json.loads(buf.getvalue())
assert not rep["ok"] and rep["counts"].get("S001"), rep
msg = " | ".join(f["message"] for f in rep["findings"])
assert "matmul" in msg, msg
print("profsum OK: identical diff empty, injected canary fired (%s)"
      % sorted(rep["counts"]))
EOF
  rm -rf "$PS_DIR"
  ps_dt=$(( SECONDS - ps_t0 ))
  echo "profstats stage wall time: ${ps_dt}s (budget 120s)"
  [ "$ps_dt" -lt 120 ] || { echo "profstats stage took ${ps_dt}s (budget 120s)"; exit 1; }
fi

if has_stage loadgen; then
  echo "=== loadgen: open-loop soak ==="
  # Three soak repeats against a TIMER-bound servable (fixed 5 ms per
  # dispatched batch), so capacity — and therefore the stage the ramp
  # saturates in — is set by clocks, not by host speed.
  lg_t0=$SECONDS
  LG_DIR=$(mktemp -d -t mxtpu_loadgen.XXXXXX)
  JAX_PLATFORMS=cpu python - "$LG_DIR" <<'EOF'
import json, sys, time
from tools import loadgen
from incubator_mxnet_tpu.serving import ModelRegistry, ServingServer

class SlowEcho:
    """Deterministic capacity: 5 ms per dispatched batch of <= 8, which
    with the 2 ms gather window and worker cycle overhead puts the knee
    inside the 100 -> 400 -> 2000 rps ramp on every machine (timer-bound,
    not host-bound)."""
    def predict_batch(self, x):
        time.sleep(0.005)
        return (x,)

out_dir = sys.argv[1]
reg = ModelRegistry()
reg.load("soak", SlowEcho(), max_batch_size=8, batch_timeout_ms=2.0,
         queue_size=16)
coverage = []
with ServingServer(reg, port=0) as srv:
    for rep in range(3):
        tr = loadgen.HttpTransport(srv.url, "soak", [0.0, 0.0, 0.0, 0.0])
        lg = loadgen.LoadGen(tr, stages=[{"rps": 100, "duration_s": 1.2},
                                         {"rps": 400, "duration_s": 1.2},
                                         {"rps": 2000, "duration_s": 1.2}],
                             arrival="poisson", seed=rep, max_clients=128)
        report = lg.run()
        path = "%s/report_%d.json" % (out_dir, rep)
        with open(path, "w") as f:
            json.dump(report, f, indent=1)
        ci = loadgen.report_ci(report, path, max_error_rate=0.0,
                               require_saturation=True)
        sat = report["saturation"]
        print("repeat %d: stage0 p50 %.1f ms, saturation at stage %s "
              "(%.0f rps goodput), join coverage %.2f"
              % (rep, report["gate_metrics"]["metrics"]
                 ["loadgen_stage0_p50_ms"],
                 sat["stage"] if sat else "-",
                 sat["goodput_rps"] if sat else -1,
                 report["gate_metrics"]["metrics"]
                 ["loadgen_join_coverage"]))
        assert ci["ok"], json.dumps(ci, indent=1)
        coverage.append(report["gate_metrics"]["metrics"]
                        ["loadgen_join_coverage"])
# the X-Request-Id join found the OK responses' spans (in the best repeat)
assert max(coverage) >= 0.65, coverage
print("loadgen OK: 3 reports in %s (schema %s)"
      % (out_dir, loadgen.REPORT_SCHEMA))
EOF
  lg_dt=$(( SECONDS - lg_t0 ))
  echo "loadgen stage wall time: ${lg_dt}s (budget 120s)"
  [ "$lg_dt" -lt 120 ] || { echo "loadgen stage took ${lg_dt}s (budget 120s)"; exit 1; }
fi

if has_stage slo; then
  echo "=== slo: burn-rate alert lifecycle + per-tenant accounting e2e ==="
  # A loadgen soak with a weighted tenant mix against a servable whose
  # failure window is injectable: the fast-burn alert must FIRE during
  # the 100%-bad burst (flightrec slo_alert event + firing gauge at 1 +
  # burn rate over threshold) and RESOLVE after it — resolution driven
  # by scrapes, not traffic. Per-tenant counters must split the soak,
  # stage reports must carry the /debug/slo trajectory, and the live
  # exposition (incl. the new mxtpu_slo_* families) must pass promcheck.
  # CI-scaled windows: 1 s short / 3 s long, one fast pair.
  slo_t0=$SECONDS
  JAX_PLATFORMS=cpu MXTPU_SLO_WINDOWS="1:3" MXTPU_SLO_FAST_BURN=10 \
    python - <<'EOF'
import json, time, urllib.request
from tools import loadgen, promcheck
from incubator_mxnet_tpu.serving import ModelRegistry, ServingServer

class Flaky:
    fail = False
    def predict_batch(self, x):
        if self.fail:
            raise RuntimeError("injected failure window")
        return (x + 1.0,)

sv = Flaky()
reg = ModelRegistry()
reg.load("cim", sv, max_batch_size=8, batch_timeout_ms=2.0)

with ServingServer(reg, port=0) as srv:
    def get(path):
        with urllib.request.urlopen(srv.url + path, timeout=10) as r:
            return r.read().decode()

    def soak(seconds, rps=80):
        tr = loadgen.HttpTransport(srv.url, "cim", [0.0])
        lg = loadgen.LoadGen(tr, [{"rps": rps, "duration_s": seconds}],
                             arrival="constant", seed=1, settle_s=0.1,
                             tenants=[("alice", 3.0), ("bob", 1.0)])
        return lg.run()

    def alert(field="state"):
        by = {s["name"]: s for s in json.loads(get("/debug/slo"))["slos"]}
        return by["cim/availability"]["alerts"][0][field]

    def tape_states():
        return [json.loads(l)["state"]
                for l in get("/debug/flightrec").splitlines()
                if '"slo_alert"' in l and '"cim/availability"' in l]

    # phase 1: healthy soak — per-tenant split lands, nothing fires
    st = soak(1.0)["stages"][0]
    t = st["tenants"]
    assert t["alice"]["ok"] > t["bob"]["ok"] > 0, t
    assert st["slo"]["slos"], "stage report missing /debug/slo scrape"
    assert alert() == "inactive", alert()

    # phase 2: injected failure window — the fast pair must FIRE
    sv.fail = True
    soak(1.2)
    assert alert() == "firing", alert()
    text = get("/metrics")
    assert ('mxtpu_slo_alert_firing{slo="cim/availability",pair="fast"}'
            ' 1') in text
    burn = [l for l in text.splitlines()
            if l.startswith('mxtpu_slo_burn_rate{slo="cim/availability"'
                            ',window="1s"}')]
    assert burn and float(burn[0].split()[-1]) > 10.0, burn
    assert "firing" in tape_states(), tape_states()

    # phase 3: failure ends — scrapes alone must RESOLVE the alert as
    # the bad events age out of the 3 s long window
    sv.fail = False
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline and alert() != "resolved":
        time.sleep(0.25)
    assert alert() == "resolved", alert()
    states = tape_states()
    assert states.index("firing") < states.index("resolved"), states
    text = get("/metrics")
    assert ('mxtpu_slo_alert_firing{slo="cim/availability",pair="fast"}'
            ' 0') in text
    # per-tenant accounting split the whole run, good and bad codes
    for needle in ('tenant="alice",code="200"', 'tenant="bob",code="200"',
                   'tenant="alice",code="500"'):
        assert needle in text, needle
    # the live exposition (with the new families) stays parser-clean
    for fam in ("mxtpu_slo_burn_rate", "mxtpu_slo_budget_remaining",
                "mxtpu_slo_events_total", "mxtpu_requests_total"):
        assert fam in text, fam
    rep = promcheck.report(text, path="live-scrape")
    assert rep["ok"], rep["findings"]
reg.close()
print("slo OK: fast alert fired in burst, resolved after, "
      "tenants split, promcheck clean")
EOF
  JAX_PLATFORMS=cpu python -m pytest tests/test_slo.py -q
  slo_dt=$(( SECONDS - slo_t0 ))
  echo "slo stage wall time: ${slo_dt}s (budget 60s)"
  [ "$slo_dt" -lt 60 ] || { echo "slo stage took ${slo_dt}s (budget 60s)"; exit 1; }
fi

if has_stage generate; then
  echo "=== generate: continuous-batching decode gate + decode H002 canary ==="
  # The generative-serving contract, gated four ways (docs/GENERATE.md):
  # continuous batching must BEAT the sequential-decode baseline on
  # tokens/s under a saturating mixed prefill/decode soak; the post-warm
  # window must see ZERO compiles (counter + span patterns); the decode
  # window's op profile must be memory-bound (gather/scatter/fusion own
  # the self time, not matmul — a decode step that goes compute-bound
  # has lost paged attention); and the undonated-decode canary must
  # fire H002 at error severity with a nonzero exit.
  gen_t0=$SECONDS
  GEN_DIR=$(mktemp -d -t mxtpu_generate.XXXXXX)
  JAX_PLATFORMS=cpu MXTPU_PROFILE_DIR="$GEN_DIR/prof" python - "$GEN_DIR" <<'EOF'
import json, random, sys, threading, time
from incubator_mxnet_tpu import jit
from incubator_mxnet_tpu.serving import ModelRegistry, ServingServer
from incubator_mxnet_tpu.telemetry import profstats, spans
from tools import loadgen

out_dir = sys.argv[1]
# tiny geometry keeps prewarm cheap while 4 sequences decode per step
reg = ModelRegistry()
reg.load_generator("gen-ci", seed=0, block_size=8, num_blocks=96,
                   max_batch=4, prefill_len=16, max_tokens=32)
eng = reg.generator("gen-ci")
PROMPT_LEN, MAX_NEW = 12, 24

# ---- sequential-decode baseline: one sequence at a time through the
# SAME compiled programs (bucket 1, private pool) — the per-request
# throughput continuous batching must beat
rng = random.Random(0)
prompts = [[rng.randrange(1, 256) for _ in range(PROMPT_LEN)]
           for _ in range(6)]
t0 = time.monotonic()
seq_tokens = 0
for p in prompts:
    toks, reason = eng.generate_sequential(p, max_new_tokens=MAX_NEW)
    assert reason == "max_tokens", reason
    seq_tokens += len(toks)
seq_tps = seq_tokens / (time.monotonic() - t0)

# ---- post-warm window opens here: NOTHING below may compile
kinds = ("train", "eval", "serve", "decode")
c0 = sum(jit._COMPILES.value(kind=k) for k in kinds)
mark = len(spans.snapshot())

with ServingServer(reg, port=0) as srv:
    # saturating open-loop soak: arrivals join mid-flight (mixed
    # prefill/decode), offered token rate ~3.9x the sequential baseline
    # so goodput measures capacity, with 429 shed doing backpressure
    tr = loadgen.GenHttpTransport(srv.url, "gen-ci",
                                  prompt_len=PROMPT_LEN, max_new=MAX_NEW)
    lg = loadgen.LoadGen(tr, stages=[{"rps": 150, "duration_s": 4.0}],
                         arrival="poisson", seed=0, max_clients=64)
    report = lg.run()
    st = report["stages"][0]
    gen = st["generate"]
    cont_tps = gen["tokens_per_s"]
    assert st["errors"] == 0, st
    assert gen["finish_reasons"] == {"max_tokens": st["ok"]}, gen
    ratio = cont_tps / seq_tps
    assert ratio > 1.3, (
        "continuous batching must beat sequential decode: "
        "%.0f vs %.0f tok/s (x%.2f)" % (cont_tps, seq_tps, ratio))

    # ---- decode is memory-bound: profile a decode-dominated window
    # (24 decode steps per 1 prefill per request) and require the
    # non-matmul categories to own the self time
    profstats.capture_and_summarize(0.05, fold=False)  # 1st-session setup
    def decode_traffic():
        time.sleep(0.2)
        stop_t = time.monotonic() + 1.4
        def churn(i):
            j = 0
            while time.monotonic() < stop_t:
                s = eng.submit(prompts[i % len(prompts)],
                               max_new_tokens=MAX_NEW,
                               request_id="prof-%d-%d" % (i, j))
                for _ in s:
                    pass
                j += 1
        ths = [threading.Thread(target=churn, args=(i,)) for i in range(4)]
        for t in ths: t.start()
        for t in ths: t.join(60.0)
    tt = threading.Thread(target=decode_traffic)
    tt.start()
    _, summary = profstats.capture_and_summarize(2.5)
    tt.join(60.0)
    cats = {k: v["self_us"] for k, v in summary["categories"].items()}
    total_us = sum(cats.values())
    mm_share = cats.get("matmul", 0) / max(total_us, 1)
    assert total_us > 0, summary
    assert mm_share < 0.5, (
        "decode window should be memory-bound, matmul owns %.0f%%: %s"
        % (100 * mm_share, cats))

    # ---- zero-compile contract over EVERYTHING since the mark
    c1 = sum(jit._COMPILES.value(kind=k) for k in kinds)
    assert c1 == c0, "compiles moved post-warm: %d -> %d" % (c0, c1)
    bad = [s["name"] for s in spans.snapshot()[mark:]
           if s["name"] in ("train:compile", "eval:compile", "gen:compile")]
    assert not bad, "compile spans landed post-warm: %s" % bad

with open(out_dir + "/gen_stage.json", "w") as f:
    json.dump({"seq_tok_per_s": seq_tps, "cont_tok_per_s": cont_tps,
               "ratio": ratio, "matmul_share": mm_share,
               "categories": cats, "stage": st}, f, indent=1)
print("generate OK: %.0f tok/s continuous vs %.0f sequential (x%.2f), "
      "0 post-warm compiles, matmul %.0f%% of decode self time"
      % (cont_tps, seq_tps, ratio, 100 * mm_share))
EOF
  # decode H002 canary: an undonated decode artifact must be REFUSED —
  # nonzero exit with exactly one error-severity H002
  JAX_PLATFORMS=cpu python -c "from tools.hlolint.canary import \
write_decode_canary; write_decode_canary('$GEN_DIR/decode_canary')"
  if JAX_PLATFORMS=cpu python -m tools.hlolint "$GEN_DIR/decode_canary" \
      --no-baseline --json > "$GEN_DIR/decode_canary.json"; then
    echo "generate decode canary FAILED: undonated decode passed the gate"
    exit 1
  fi
  python - "$GEN_DIR/decode_canary.json" <<'EOF'
import json, sys
from tools.hlolint.rules import severity_of
rep = json.load(open(sys.argv[1]))
assert rep["counts"] == {"H002": 1}, rep["counts"]
f = rep["findings"][0]
assert severity_of(f["rule"], f["path"]) == "error", f
print("decode H002 canary OK: error severity on %s"
      % f["path"].rsplit("/", 1)[-1])
EOF
  gen_dt=$(( SECONDS - gen_t0 ))
  echo "generate stage wall time: ${gen_dt}s (budget 120s)"
  [ "$gen_dt" -lt 120 ] || { echo "generate stage took ${gen_dt}s (budget 120s)"; exit 1; }
fi

if has_stage numerics; then
  echo "=== numerics: NaN canary + int8 shadow divergence + tap-tax gate ==="
  num_t0=$SECONDS
  JAX_PLATFORMS=cpu MXTPU_NUMWATCH_SAMPLE=1.0 python - <<'EOF'
import json, os, time
import numpy as onp
from incubator_mxnet_tpu import aot, nd, gluon
from incubator_mxnet_tpu.contrib import quantization
from incubator_mxnet_tpu.serving import ModelRegistry
from incubator_mxnet_tpu.telemetry import flightrec, numwatch

# --------------- phase A: injected-NaN canary -> exactly ONE episode
# The canary poisons every batch after its third: a contiguous storm.
# Hysteresis must collapse it into ONE nan_storm flightrec event (the
# per-batch evidence lives in the nonfinite counter), not one event
# per poisoned dispatch — the episode contract under a real divergence.
class NaNCanary:
    def __init__(self):
        self.n = 0

    def predict_batch(self, x):
        self.n += 1
        out = x + 1.0
        if self.n > 3:
            out = out.copy()
            out.flat[0] = float("nan")
        return (out,)

reg = ModelRegistry()
reg.load("nan-canary", NaNCanary(), max_batch_size=4, batch_timeout_ms=1.0)
item = onp.ones((4,), "float32")
for _ in range(12):
    reg.predict("nan-canary", item, timeout=30.0)
storms = [e for e in flightrec.snapshot() if e["event"] == "nan_storm"
          and e.get("model") == "nan-canary"]
assert len(storms) == 1, storms
d = numwatch.describe()["taps"]["nan-canary/serve:outputs"]
assert d["in_storm"] and d["storms"] == 1, d
assert d["nonfinite"] >= 5, d        # every poisoned tap still counted
print("nan canary OK: %d poisoned taps -> 1 nan_storm episode"
      % d["nonfinite"])

# ------- phase B: shadow divergence -> degraded flip (bad calib only)
# Same fp32 Dense twice through the int8 path: one calibrated to its
# real activation range, one to a sliver (the shipped-bad-constants
# accident). The bad one's shadow vs the fp32 reference must breach
# and flip health to degraded; the sane one must stay clean.
class NDServable:
    def __init__(self, fn):
        self._fn = fn

    def predict_batch(self, x):
        return (onp.asarray(self._fn(nd.array(x)), "float32"),)

net = gluon.nn.Dense(4, in_units=8)
net.initialize()
q_bad = quantization.QuantizedDense(net, -0.01, 0.01)
q_ok = quantization.QuantizedDense(net, -4.0, 4.0)
reg.load("int8-bad", NDServable(q_bad), max_batch_size=4,
         batch_timeout_ms=1.0)
reg.load("int8-ok", NDServable(q_ok), max_batch_size=4,
         batch_timeout_ms=1.0)
reg.register_shadow("int8-bad", NDServable(net), stride=1, threshold=0.05)
reg.register_shadow("int8-ok", NDServable(net), stride=1, threshold=0.5)
qx = onp.linspace(-2.0, 2.0, 8).astype("float32")
for _ in range(3):
    reg.predict("int8-bad", qx, timeout=30.0)
    reg.predict("int8-ok", qx, timeout=30.0)
assert numwatch.shadow_drain(60.0)
sh = numwatch.describe()["shadows"]
assert sh["int8-bad"]["breached"] and sh["int8-bad"]["samples"] >= 1, sh
assert not sh["int8-ok"]["breached"] and sh["int8-ok"]["breaches"] == 0, sh
h = reg.health()
assert h["status"] == "degraded", h
assert "int8-bad" in h["reason"] and "shadow divergence" in h["reason"], h
bad_desc = [m for m in reg.models() if m["name"] == "int8-bad"][0]
ok_desc = [m for m in reg.models() if m["name"] == "int8-ok"][0]
assert bad_desc["degraded"] and not ok_desc["degraded"]
print("shadow OK: bad calib max_abs_diff %.3g degraded, clean calib %.3g"
      % (sh["int8-bad"]["last"]["max_abs_diff"],
         sh["int8-ok"]["last"]["max_abs_diff"]))
reg.close()

# ---------------- phase C+D: zero post-warm compiles + paired tap tax
# TIMER-bound servable (profstats phase-B methodology): capacity set by
# clocks, so paired p99 measures the tap's tax, not host speed. Warm
# every reducer signature first; the timed window must then add ZERO
# aot misses of kind "numwatch", and the min per-repeat paired ratio
# must hold <= 1.10.
class SlowEcho:
    def predict_batch(self, x):
        time.sleep(0.004)
        return (x + 1.0,)

reg2 = ModelRegistry()
reg2.load("slownum", SlowEcho(), max_batch_size=4, batch_timeout_ms=1.0)
slow_item = onp.zeros((4,), dtype=onp.float32)
for _ in range(50):                  # warm reducers at every batch shape
    reg2.predict("slownum", slow_item, timeout=30.0)
misses0 = aot._MISSES.value(kind="numwatch")

def p99(n=300):
    lat = []
    for _ in range(n):
        t0 = time.perf_counter()
        reg2.predict("slownum", slow_item, timeout=30.0)
        lat.append(time.perf_counter() - t0)
    lat.sort()
    return lat[int(0.99 * len(lat)) - 1]

pairs = []
for rep in range(4):
    os.environ["MXTPU_NUMWATCH_SAMPLE"] = "0.0"
    off_i = p99()
    os.environ["MXTPU_NUMWATCH_SAMPLE"] = "1.0"
    on_i = p99()
    pairs.append((off_i, on_i))
ratio = min(on_i / off_i for off_i, on_i in pairs)
assert ratio <= 1.10, (ratio, pairs)
assert aot._MISSES.value(kind="numwatch") == misses0, \
    (misses0, aot._MISSES.value(kind="numwatch"))
print("tap tax OK: best paired p99 ratio %.3f over %d repeats, "
      "0 post-warm reducer compiles" % (ratio, len(pairs)))

# the loadgen between-stage scrape path: the in-process transport's
# numerics() feeds summarize_stage the same describe() snapshot
from tools import loadgen
num_text = json.dumps(numwatch.describe())
stage = loadgen.summarize_stage({"name": "numcheck", "rps": 0,
                                 "concurrency": 1, "duration_s": 1.0},
                                0, [], numerics_text=num_text)
assert "slownum/serve:outputs" in stage["numerics"]["taps"], \
    list(stage["numerics"]["taps"])
reg2.close()
print("numerics scrape OK: stage report carries the sentinel snapshot")
EOF
  num_dt=$(( SECONDS - num_t0 ))
  echo "numerics stage wall time: ${num_dt}s (budget 120s)"
  [ "$num_dt" -lt 120 ] || { echo "numerics stage took ${num_dt}s (budget 120s)"; exit 1; }
fi

if has_stage sharded; then
  echo "=== sharded: 1-vs-8 replica soaks (8-device CPU) ==="
  # Two interleaved repeats of (1 replica, 8 replicas) saturation soaks
  # against a TIMER-bound servable (20 ms per dispatched batch of <= 8):
  # each replica's capacity is set by clocks, so both ramps saturate on
  # every host. Driven through loadgen's InProcessTransport — the serving
  # core (router -> replica queues -> workers), not the stdlib HTTP
  # loop, is what this stage drives. It asserts control flow: a
  # saturation point on both ramps, zero hard errors, every replica
  # served. The goodput ratio is printed for the reader.
  sh_t0=$SECONDS
  JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python - <<'EOF'
import time
import jax
jax.config.update("jax_platforms", "cpu")
from tools import loadgen
from incubator_mxnet_tpu.serving import ModelRegistry

assert len(jax.devices()) == 8, jax.devices()


class SlowEcho:
    """Deterministic per-replica capacity: 20 ms per dispatched batch of
    <= 8, timer-bound on every host."""

    def predict_batch(self, x):
        time.sleep(0.02)
        return (x,)


def soak(tag, replicas, stages, seed):
    reg = ModelRegistry()
    reg.load(tag, SlowEcho(), max_batch_size=8, batch_timeout_ms=2.0,
             queue_size=16, replicas=replicas)
    tr = loadgen.InProcessTransport(reg, tag, [0.0, 0.0, 0.0, 0.0],
                                    timeout_s=5.0)
    lg = loadgen.LoadGen(tr, stages=stages, arrival="poisson", seed=seed,
                         max_clients=512)
    report = lg.run()
    counts = reg._entry(tag).batcher.replica_dispatch_counts()
    reg.close()
    sat = report["saturation"]
    assert sat is not None, "no saturation at %d replica(s): %s" % (
        replicas, [s["goodput_rps"] for s in report["stages"]])
    assert all(s["error_rate"] == 0.0 for s in report["stages"]), report
    return sat["goodput_rps"], counts


RAMP1 = [{"rps": r, "duration_s": 1.0} for r in (100, 200, 400, 800)]
RAMP8 = [{"rps": r, "duration_s": 1.0} for r in (800, 1600, 3200, 6400)]
for rep in range(2):
    g1, _ = soak("shard1-%d" % rep, 1, RAMP1, rep)
    g8, counts = soak("shard8-%d" % rep, 8, RAMP8, rep)
    # router balance at saturation: every replica worked, none hogged
    assert min(counts) > 0 and max(counts) <= 2 * min(counts), counts
    print("repeat %d: 1-rep %.0f rps -> 8-rep %.0f rps = %.2fx, "
          "dispatch balance %s" % (rep, g1, g8, g8 / g1, counts))
print("sharded soaks OK")
EOF
  sh_dt=$(( SECONDS - sh_t0 ))
  echo "sharded stage wall time: ${sh_dt}s (budget 120s)"
  [ "$sh_dt" -lt 120 ] || { echo "sharded stage took ${sh_dt}s (budget 120s)"; exit 1; }
fi

if has_stage chaos; then
  echo "=== chaos: faultlab + self-healing serving gate ==="
  ch_t0=$SECONDS
  # Phase A: the chaos unit tier — faultlab determinism (stride/p/seed/
  # budget), the bounded predict retry, replica respawn + the
  # supervisor's backoff/park state machine, decode-loop resurrection
  # (bit-exact survivors vs loud engine_restart), last-known-good
  # rollback + quarantine, the 503 no_replicas / dead-genloop-delisting
  # HTTP contract, and the <= 1.05x disarmed-guard-tax paired-p99 gate.
  JAX_PLATFORMS=cpu python -m pytest tests/test_resilience.py -q
  # Phase B: a supervised fleet UNDER chaos. A 3-stage in-process
  # loadgen ramp (clean -> seeded replica kills via --faults plumbing ->
  # recovery) against 4 replicas with a running Supervisor: the soak
  # must hold >= 97% availability during the kill stage, strand zero
  # arrivals, keep the clean stages error-free, actually observe the
  # injected kills / retries / respawns (a chaos run that injected
  # nothing proves nothing), and end with the fleet healed inside the
  # backoff budget. Then a supervised decode-loop kill must finish its
  # streams after resurrection, and a degraded flip must roll dispatch
  # back to the last known good version.
  JAX_PLATFORMS=cpu python - <<'EOF'
import time
import numpy as onp
from tools import loadgen
from incubator_mxnet_tpu.serving import ModelRegistry, Supervisor
from incubator_mxnet_tpu.serving import batcher as batcher_mod
from incubator_mxnet_tpu.telemetry import faultlab, flightrec


class SlowEcho:
    def predict_batch(self, x):
        time.sleep(0.005)
        return (x,)


def wait_for(pred, timeout, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return bool(pred())


reg = ModelRegistry()
reg.load("chaos", SlowEcho(), max_batch_size=8, batch_timeout_ms=2.0,
         queue_size=32, replicas=4, prewarm=False)
# crash_n high on purpose: this soak measures healing, not the park
# breaker (the breaker has its own unit coverage)
sup = Supervisor(reg, poll_s=0.02, backoff_base_s=0.05,
                 backoff_cap_s=0.2, crash_n=99,
                 crash_window_s=30.0).start()
tr = loadgen.InProcessTransport(reg, "chaos", [0.0, 0.0, 0.0, 0.0],
                                timeout_s=10.0)
stages = [{"rps": 150, "duration_s": 2.0},
          {"rps": 150, "duration_s": 3.0},
          {"rps": 150, "duration_s": 2.0}]
lg = loadgen.LoadGen(
    tr, stages=stages, arrival="poisson", seed=0, max_clients=256,
    # every 40th dispatched batch kills its replica worker during stage
    # 1 only — deterministic (stride, not p), so the soak ALWAYS injects
    faults={1: "batcher.dispatch:replica_kill:stride=40", 2: ""})
report = lg.run()
b = reg._entry("chaos").batcher

for i, s in enumerate(report["stages"]):
    print("stage %d (faults=%s): offered %d ok %d shed %d errors %d"
          % (i, s.get("fault_spec"), s["offered"], s["ok"], s["shed"],
             s["errors"]))
    # zero stranded arrivals: every arrival got a terminal status
    assert s["client_dropped"] == 0, s
    assert s["completed"] == s["offered"], s
s0, s1, s2 = report["stages"]
assert s0["errors"] == 0, s0         # clean before...
assert s2["errors"] == 0, s2         # ...and clean after recovery
avail = s1["ok"] / float(s1["offered"])
assert avail >= 0.97, "availability %.4f under replica kills" % avail
# the chaos actually bit: kills fired, retries happened, workers reborn
kills = faultlab._FIRED.value(site="batcher.dispatch",
                              kind="replica_kill")
assert kills >= 2, "only %d injected kills — soak too gentle" % kills
assert batcher_mod._RETRIES.value(model="chaos") >= 1
respawns = [e for e in flightrec.snapshot()
            if e["event"] == "replica_respawned"
            and e.get("model") == "chaos"]
assert respawns, "supervisor never respawned a worker"
# healed within the backoff budget (cap 0.2s + poll 0.02s << 5s)
assert wait_for(lambda: not b.dead_replicas(), 5.0), b.dead_replicas()
assert len(b.replica_dispatch_counts()) == 4
print("chaos soak OK: availability %.4f under %d kills, %d respawns, "
      "fleet healed" % (avail, kills, len(respawns)))

# -- supervised decode-loop kill: streams FINISH after resurrection
eng = reg.load_generator("chaos-gen", seed=0, block_size=8, num_blocks=48,
                         max_batch=4, prefill_len=16, max_tokens=16)
assert wait_for(lambda: getattr(eng, "_supervised", False), 5.0), \
    "supervisor never adopted the engine"
faultlab.arm("generate.step:replica_kill:stride=5:budget=1")
streams = [eng.submit([3, 1, 4], max_new_tokens=10, seed=7),
           eng.submit([2, 7, 1], max_new_tokens=10, seed=9)]
for st in streams:
    toks, reason = st.tokens(timeout=60.0)
    assert reason in ("max_tokens", "eos"), reason
    assert toks, "stream finished empty"
res = [e for e in flightrec.snapshot()
       if e["event"] == "genloop_resurrected"
       and e.get("model") == "chaos-gen"]
assert res, "decode loop was never killed+resurrected"
faultlab.disarm()
print("genloop chaos OK: %d streams finished across a resurrection"
      % len(streams))

# -- degraded flip rolls dispatch back to the last known good version
class Biased:
    def __init__(self, bias):
        self.bias = bias
    def predict_batch(self, x):
        return (x + self.bias,)

reg.load("chaos-roll", Biased(1.0), max_batch_size=4,
         batch_timeout_ms=1.0, queue_size=8, prewarm=False)
reg.load("chaos-roll", Biased(100.0), prewarm=False)
entry = reg._entry("chaos-roll")
entry.set_degraded("chaos-stage divergence breach")
d = entry.describe()
assert d["current_version"] == 1 and d["degraded"] is None, d
assert d["rolled_back"]["from_version"] == 2, d
out = reg.predict("chaos-roll", onp.float32([1.0]), timeout=10.0)
assert float(out[0][0]) == 2.0, out
print("rollback OK: degraded v2 -> serving v1, provenance %r"
      % (d["rolled_back"],))

sup.stop()
reg.close()
EOF
  # Phase C: the unsupervised canary — the SAME kill with no Supervisor
  # must fail the healed-within-budget check. If this inner script
  # passes, the phase-B healing assertion proves nothing; fail the stage.
  if JAX_PLATFORMS=cpu python - <<'EOF'
import sys, time
import numpy as onp
from incubator_mxnet_tpu.serving import ModelRegistry
from incubator_mxnet_tpu.telemetry import faultlab


class Echo:
    def predict_batch(self, x):
        return (x,)


reg = ModelRegistry()
reg.load("nosup", Echo(), max_batch_size=4, batch_timeout_ms=1.0,
         queue_size=8, replicas=2, prewarm=False)
b = reg._entry("nosup").batcher
faultlab.arm("batcher.dispatch:replica_kill:stride=1:budget=1")
try:
    reg.predict("nosup", onp.float32([1.0]), timeout=10.0)
except Exception:
    pass
deadline = time.monotonic() + 2.0
while time.monotonic() < deadline and not b.dead_replicas():
    time.sleep(0.02)
if not b.dead_replicas():
    sys.exit(1)        # worker never even died: not a valid canary run
deadline = time.monotonic() + 2.0
while time.monotonic() < deadline:
    if not b.dead_replicas():
        break          # "healed" with no supervisor: impossible
    time.sleep(0.05)
healed = not b.dead_replicas()
faultlab.disarm()
reg.close()
sys.exit(0 if healed else 1)
EOF
  then
    echo "chaos canary FAILED: a dead replica 'healed' with no supervisor"
    exit 1
  fi
  echo "chaos canary OK: without a supervisor the fleet stays dead"
  ch_dt=$(( SECONDS - ch_t0 ))
  echo "chaos stage wall time: ${ch_dt}s (budget 120s)"
  [ "$ch_dt" -lt 120 ] || { echo "chaos stage took ${ch_dt}s (budget 120s)"; exit 1; }
fi

if has_stage history; then
  echo "=== history: metric flight recorder + incident timeline gate ==="
  hi_t0=$SECONDS
  # Phase A: the unit tier — retention bounds, tiered downsampling
  # correctness, recording rules, early-warning hysteresis, the /debug/
  # index pin, detach-on-close, and the <= 1.05x self-scrape-tax gate.
  JAX_PLATFORMS=cpu python -m pytest tests/test_history.py -q
  HI_DIR=$(mktemp -d -t mxtpu_history.XXXXXX)
  # Phase B: the postmortem e2e. A supervised 2-stage loadgen soak
  # (calm -> seeded replica kills) with the history daemon self-scraping
  # at 20ms: the incident timeline around the first kill must carry the
  # fault injection, the queue-depth excursion it caused, and the
  # supervisor's respawn — in causal order on the shared monotonic
  # anchor. Then the early-warning e2e: with the fleet idle the detector
  # must stay silent across a calm window, and a submit ramp that
  # genuinely outruns the drain rate must fire pressure_rising (one
  # event, hysteresis-gated). Every tick also rotates the JSONL export
  # consumed by phase C.
  JAX_PLATFORMS=cpu MXTPU_HISTORY_FILE="$HI_DIR/history.jsonl" \
      python - <<'EOF'
import time
import numpy as onp
from tools import loadgen
from incubator_mxnet_tpu.serving import ModelRegistry, Supervisor
from incubator_mxnet_tpu.telemetry import flightrec, history


class SlowEcho:
    def __init__(self, delay_s):
        self.delay_s = delay_s
    def predict_batch(self, x):
        time.sleep(self.delay_s)
        return (x,)


reg = ModelRegistry()
# single-item batches at 20ms: the fleet drains ~200/s, so the calm
# stage (60 rps) keeps queues near zero while the kill stage (350 rps)
# genuinely saturates — the queue-depth excursion must land LATE in the
# kill stage, after the first injected fault, making the causal order
# fault -> excursion -> deterministic rather than a sampling accident
reg.load("histsoak", SlowEcho(0.02), max_batch_size=1,
         batch_timeout_ms=2.0, queue_size=32, replicas=4, prewarm=False)
sup = Supervisor(reg, poll_s=0.02, backoff_base_s=0.05,
                 backoff_cap_s=0.2, crash_n=99,
                 crash_window_s=30.0).start()
history.start(interval_s=0.02)
tr = loadgen.InProcessTransport(reg, "histsoak", [0.0, 0.0, 0.0, 0.0],
                                timeout_s=10.0)
lg = loadgen.LoadGen(
    tr, stages=[{"rps": 60, "duration_s": 1.5},
                {"rps": 350, "duration_s": 2.0}],
    arrival="poisson", seed=0, max_clients=256,
    faults={1: "batcher.dispatch:replica_kill:stride=40"})
report = lg.run()
from incubator_mxnet_tpu.telemetry import faultlab
faultlab.disarm()                # the kill must not leak past the soak
assert report["stages"][0]["errors"] == 0, report["stages"][0]

# stage reports carry the between-stage history block (both transports
# resolve it; the in-process one reads the store directly)
hb = report["stages"][1]["history"]
assert hb and hb["queue_depth"] and hb["queue_depth"]["n"] >= 2, hb
print("stage history block: depth max %.1f over %d samples"
      % (hb["queue_depth"]["max"], hb["queue_depth"]["n"]))

# the incident report, windowed around the first injected kill. Event
# times convert to the sample axis through the constant epoch-mono
# offset — the same join incident() itself performs.
from incubator_mxnet_tpu import profiler
kills = [e for e in flightrec.snapshot()
         if e["event"] == "fault_injected"
         and e.get("kind") == "replica_kill"]
assert kills, "soak injected no kills — nothing to narrate"
off = profiler.now_us() / 1e6 - time.perf_counter()
t_kill = kills[0]["mono_us"] / 1e6 + off
inc = history.incident(around=t_kill, before_s=5.0, after_s=10.0)
types = [(e["type"], e.get("event"),
          (e.get("series") or "").split("{", 1)[0])
         for e in inc["timeline"]]
i_fault = types.index(("event", "fault_injected", ""))
i_resp = types.index(("event", "replica_respawned", ""))
i_exc = types.index(("excursion", None, "mxtpu_serving_queue_depth"))
assert i_fault < i_resp, (i_fault, i_resp)
assert i_fault < i_exc, (i_fault, i_exc)
ts = [e["t"] for e in inc["timeline"]]
assert ts == sorted(ts), "timeline not causally ordered"
print("incident OK: fault@%d -> depth excursion@%d -> respawn@%d "
      "of %d timeline entries" % (i_fault, i_exc, i_resp, len(ts)))

# -- early warnings: silent on calm, loud on a saturating ramp
history.stop()                   # deterministic ticks from here on
calm0 = history._WARNINGS.value(kind="pressure_rising")
for _ in range(10):              # idle fleet: depth flat at 0
    history.sample_once()
    time.sleep(0.01)
assert history._WARNINGS.value(kind="pressure_rising") == calm0, \
    "pressure_rising fired on a calm window"
# drain 1.25/s (80ms per 1-item batch) vs 25/s submitted: the queue
# depth ramps linearly toward capacity 64 and the trend line must call
# it before it lands
reg.load("histpress", SlowEcho(0.08), max_batch_size=1,
         batch_timeout_ms=0.5, queue_size=64, replicas=1, prewarm=False)
b = reg._entry("histpress").batcher
pending = []
for i in range(50):
    pending.append(b.submit(onp.float32([1.0])))
    history.sample_once()
    time.sleep(0.04)
fired = history._WARNINGS.value(kind="pressure_rising") - calm0
assert fired >= 1, "saturating ramp never fired pressure_rising"
evs = [e for e in flightrec.snapshot()
       if e["event"] == "pressure_rising"
       and e.get("model") == "histpress"]
assert evs and evs[0]["slope_per_s"] > 0, evs
print("early warning OK: pressure_rising fired (eta %.1fs, slope "
      "%.2f/s), calm window silent"
      % (evs[0]["eta_s"], evs[0]["slope_per_s"]))
history.export_jsonl()           # final rotation for phase C
sup.stop()
reg.close()
EOF
  # Phase C: the offline half. The export must round-trip byte-stable
  # through tools/tsq.py (the canonical-serialization contract a diff
  # baseline depends on), and the CI-shaped --json report must agree.
  python tools/tsq.py roundtrip "$HI_DIR/history.jsonl"
  python tools/tsq.py roundtrip "$HI_DIR/history.jsonl" --json \
    | python -c "import json,sys; r=json.load(sys.stdin); \
assert r['tool']=='tsq' and r['ok'] and not r['findings'], r; \
print('tsq report shape OK')"
  # sed drains its input (head would SIGPIPE the tool under pipefail)
  python tools/tsq.py list "$HI_DIR/history.jsonl" | sed -n '1,5p'
  hi_dt=$(( SECONDS - hi_t0 ))
  echo "history stage wall time: ${hi_dt}s (budget 120s)"
  [ "$hi_dt" -lt 120 ] || { echo "history stage took ${hi_dt}s (budget 120s)"; exit 1; }
fi

if has_stage diagnostics; then
  echo "=== diagnostics: spans + flight recorder + stall watchdog ==="
  # focused gate for the two acceptance e2es — the parented span chain
  # (HTTP -> queue -> batch -> device in one chrome dump) and the forced
  # stall (blocked worker -> exactly one stack dump while /healthz keeps
  # answering) — runnable on their own during an incident
  JAX_PLATFORMS=cpu python -m pytest tests/test_spans.py \
      tests/test_watchdog.py -q
fi

if has_stage smoke; then
  echo "=== smoke: driver contract ==="
  python -c "
import jax; jax.config.update('jax_platforms','cpu')
import __graft_entry__ as g
fn, a = g.entry()
jax.jit(fn)(*a).block_until_ready()
print('entry() ok')"
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('dryrun ok')"
fi

if has_stage large; then
  echo "=== large: int64 large-tensor tier ==="
  MXTPU_TEST_LARGE_TENSOR=1 JAX_PLATFORMS=cpu \
    python -m pytest tests/test_large_tensor.py -q
fi

if has_stage wheel; then
  echo "=== wheel: sdist + bdist incl. native libs ==="
  rm -rf build dist *.egg-info
  python setup.py -q sdist bdist_wheel
  ls -la dist/
fi

echo "CI GREEN (${STAGES[*]})"
