"""Typed environment-variable registry (ref docs/faq/env_var.md and the
dmlc::Parameter idiom — every knob declared, typed, and documented in ONE
place instead of scattered os.environ reads).

``describe()`` renders the registry (the env_var.md analog);
``get_env(name)`` is the typed accessor every subsystem uses.
"""
from __future__ import annotations

import os

__all__ = ["ENV_VARS", "get_env", "describe", "place_compile_cache"]

ENV_VARS = {
    # name: (type, default, doc)
    "MXTPU_COORD_ADDR": (
        str, None,
        "jax.distributed coordinator host:port. Set by tools/launch.py; "
        "initialises the runtime at package import (multi-host DCN)."),
    "MXTPU_NUM_PROC": (
        int, 1, "Number of distributed worker processes (tools/launch.py)."),
    "MXTPU_PROC_ID": (
        int, 0, "This worker's process id in [0, MXTPU_NUM_PROC)."),
    "MXTPU_FLASH_INTERPRET": (
        bool, False,
        "Run the flash-attention Pallas kernels in interpret mode on CPU "
        "(CI/testing; ops/attention.py)."),
    "MXTPU_FLASH_BLOCK_Q": (
        int, 0,
        "Override the flash-attention q-block size (ops/attention.py). "
        "0 = auto (largest of 1024/512/256/128 dividing S; 1024 measured "
        "fastest on v5e at S>=8k for fwd+bwd). Must divide S."),
    "MXTPU_FLASH_BLOCK_K": (
        int, 0,
        "Override the flash-attention k-block size. 0 = auto. Must "
        "divide S."),
    "MXTPU_ASYNC_STALENESS": (
        int, 4,
        "dist_async staleness bound: pushes per key between cross-process "
        "parameter averages (kvstore.DistAsyncKVStore — the local-SGD "
        "analog of the reference's async parameter server)."),
    "MXTPU_INT8_SIM": (
        bool, False,
        "Force the fp32-simulated path for quantized matmul/conv instead "
        "of native int8 dot_general with int32 accumulation "
        "(ndarray/contrib.py quantized_* ops)."),
    "MXTPU_MATMUL_PRECISION": (
        str, None,
        "Matmul/conv precision on the MXU: 'default' (bf16 multiplies, "
        "fp32 accumulate — fastest), 'high' (3-pass), 'highest' (fp32). "
        "Applied at package import via jax_default_matmul_precision; the "
        "numerics sweep (test_utils.op_consistency_sweep) verifies "
        "CPU<->TPU agreement of matmul-class ops under 'highest'."),
    "MXTPU_NO_NATIVE": (
        bool, False,
        "Disable the native C++ library even if it builds (forces the "
        "pure-Python IO tiers)."),
    "MXTPU_PREDICT_LIB": (
        str, None,
        "Path to libmxtpu_predict.so for C/C++/Perl predict clients "
        "(cpp_package, perl_package); defaults to the loader path."),
    "MXTPU_PYTHON": (
        str, None,
        "Interpreter the embedded C predict API boots (c_predict_api.cc); "
        "defaults to the build-time python."),
    "MXTPU_KVSTORE_DEBUG": (
        int, 0,
        "Verbose logging in the kvstore server-role facade "
        "(kvstore_server.py)."),
    "MXTPU_ROLE": (
        str, "worker",
        "Process role for launch scripts that branch on it "
        "(kvstore_server._init_kvstore_server_module): 'worker' or "
        "'server'. DMLC_ROLE, when set, takes precedence (reference "
        "launcher compatibility)."),
    "MXTPU_EXEC_CACHE_SIZE": (
        int, 16,
        "Bound on each per-block hybridize() shape-keyed jit cache (the "
        "CachedOp analog); least-recently-dispatched entry is evicted "
        "past the bound. TrainStep/EvalStep/ServedModel executables "
        "moved to the shared AOT cache — size THAT with "
        "MXTPU_AOT_CACHE_SIZE (docs/AOT.md)."),
    "MXTPU_AOT_CACHE_SIZE": (
        int, 64,
        "Bound on the process-wide AOT compiled-executable cache "
        "(aot.CACHE — the shared replacement for the per-instance "
        "TrainStep/EvalStep/ServedModel caches). Eviction is LRU by "
        "last-dispatch time and each eviction increments "
        "mxtpu_aot_evictions_total; size it to hold every live "
        "(model, bucket, dtype) combination or post-warm traffic "
        "recompiles (docs/AOT.md)."),
    "MXTPU_AOT_CACHE_DIR": (
        str, None,
        "Directory for persisted jax.export (StableHLO) executables, one "
        "artifact per AOT cache key. A fresh process pointed here loads "
        "programs instead of re-tracing the Python model (artifact hit); "
        "unset disables the persistent layer. Artifacts are versioned by "
        "jax version + format version; train-kind programs are never "
        "persisted (docs/AOT.md)."),
    "MXTPU_AOT_PREWARM": (
        bool, True,
        "Pre-warm every configured batcher bucket of an incoming model "
        "version during ModelRegistry.load() hot-reloads (background "
        "thread, smallest bucket first so traffic cuts over early) so the "
        "swap never puts a compile window into request p99. Per-call "
        "override via load(prewarm=)."),
    "MXTPU_AOT_WARM_TIMEOUT_S": (
        float, 60.0,
        "Bound on how long ModelRegistry.load() blocks for the prewarm "
        "thread to finish compiling all buckets before returning anyway "
        "(the warm continues in the background; remaining buckets "
        "compile-on-first-dispatch as before)."),
    "MXTPU_NO_DONATE": (
        bool, False,
        "Disable input-buffer donation in the fused train/eval steps "
        "(jit.py). Donation updates parameters in place (kWriteInplace); "
        "turn off when debugging needs pre-step values alive."),
    "MXTPU_REMAT": (
        bool, False,
        "Default jax.checkpoint (rematerialisation) for TrainStep when the "
        "caller does not pass remat= explicitly — trades FLOPs for "
        "activation memory (MXNET_BACKWARD_DO_MIRROR analog)."),
    "MXTPU_ENGINE_BULK_SIZE": (
        int, 15,
        "Initial engine bulk size (MXNET_ENGINE_BULK_SIZE analog). "
        "Informational on TPU: XLA already compiles the whole step as one "
        "program; kept for API parity with engine.set_bulk_size."),
    "MXTPU_PROFILER_AUTOSTART": (
        bool, False,
        "Start the profiler at package import and dump on interpreter exit "
        "(MXNET_PROFILER_AUTOSTART analog)."),
    "MXTPU_PROFILER_FILENAME": (
        str, "profile.json",
        "Chrome-trace output path used by the autostarted profiler dump "
        "(MXNET_PROFILE_FILENAME analog; profiler.set_config overrides)."),
    "MXTPU_KVSTORE_BIGARRAY_BOUND": (
        int, 1000000,
        "Element-count bound above which a dense value gets its OWN host "
        "allgather instead of riding the per-dtype batched concat "
        "(MXNET_KVSTORE_BIGARRAY_BOUND analog — bounds peak host memory of "
        "the batch buffer)."),
    "MXTPU_P3_SLICE": (
        int, 1000000,
        "P3 slice bound in ELEMENTS for dist_async priority averaging "
        "(kvstore.DistAsyncKVStore._average_batch — ref p3store_dist.h "
        "slicing): no collective carries more than this many elements, so "
        "time-to-first-averaged-parameter is bounded by the slice, not "
        "the largest tensor."),
    "MXTPU_SERVE_MAX_BATCH": (
        int, 8,
        "Dynamic batcher dispatch bound (serving/batcher.py): a batch is "
        "dispatched when this many requests are waiting, or when "
        "MXTPU_SERVE_TIMEOUT_MS elapses after the first one. Match it to "
        "the batch axis the servable compiles best at (an exported .mxtpu "
        "artifact re-chunks buckets onto its one exported batch shape)."),
    "MXTPU_SERVE_TIMEOUT_MS": (
        float, 5.0,
        "Dynamic batcher coalescing window in milliseconds: the longest a "
        "request waits for companions before a partial batch is flushed. "
        "Raise to trade tail latency for bigger batches (TF-Serving "
        "batch_timeout_micros analog)."),
    "MXTPU_SERVE_QUEUE_SIZE": (
        int, 64,
        "PER-REPLICA bound on each model's serving dispatch queues "
        "(serving/batcher.py; total capacity = this x MXTPU_SERVE_REPLICAS)."
        " When every live replica's queue is full, submits reject with "
        "QueueFullError (HTTP 429) — explicit backpressure instead of "
        "unbounded latency; /healthz reports degraded at >= 80% aggregate "
        "occupancy."),
    "MXTPU_SERVE_REPLICAS": (
        int, 1,
        "Data-parallel replica executors per served model "
        "(serving/batcher.py): each replica owns a bounded dispatch queue "
        "and worker thread, fed by a least-depth router in submit(), so "
        "aggregate goodput scales with chips. Replica-aware servables "
        "(ServedModel, MeshServable) pin each replica's executable to its "
        "own device; a dead replica worker drains back through the router "
        "and /healthz reports degraded. Per-model override via "
        "load(replicas=) at first load (docs/SERVING.md)."),
    "MXTPU_SERVE_TP": (
        int, 1,
        "Default tensor-parallel degree for serving.sharded.MeshServable "
        "when no mesh is passed: weights shard over a 'tp' mesh axis of "
        "this size via jax.sharding.NamedSharding (GSPMD inserts the "
        "collectives), for models too big for one chip. 1 = single-device "
        "predict (docs/SERVING.md)."),
    "MXTPU_SERVE_DEADLINE_MS": (
        float, None,
        "Default per-request serving deadline in milliseconds: requests "
        "still queued when it passes fail with DeadlineExceededError "
        "(HTTP 504) instead of dispatching stale work. None = no deadline; "
        "a request's own deadline_ms overrides."),
    "MXTPU_SERVE_PORT": (
        int, 8080,
        "Default port for serving.ServingServer's HTTP front-end "
        "(serving/server.py); 0 picks an ephemeral port (tests)."),
    "MXTPU_FAULTLAB": (
        str, None,
        "Faultlab arming spec applied at import (telemetry/faultlab.py): "
        "';'-separated 'site:kind[:key=value...]' entries, kind in "
        "{exception, replica_kill, slow_ms, kv_oom, nan_poison, "
        "artifact_corrupt}, keys stride=/p=/seed=/budget=/ms=. Unset = "
        "disarmed (hot-path fault points are near-zero-cost no-ops). "
        "Runtime arming via POST /debug/faults (docs/RESILIENCE.md)."),
    "MXTPU_RESILIENCE_RETRY": (
        bool, True,
        "Single bounded retry of idempotent predict requests that failed "
        "because their replica worker died (serving/resilience.py): the "
        "request re-enters the router once, still under its original "
        "deadline; a second death fails it. Counted on "
        "mxtpu_retries_total{model}. Off = replica death fails the batch "
        "immediately."),
    "MXTPU_RESILIENCE_ROLLBACK": (
        bool, True,
        "Last-known-good rollback (serving/registry.py): when a live "
        "version flips to degraded (shadow breach, numerics storm, "
        "hlolint refusal) and a previous healthy version is still "
        "resident, repoint to it instead of serving degraded — flightrec "
        "'rolled_back_to' + sticky describe() provenance. Off = degraded "
        "is sticky until a human reloads (pre-resilience behavior)."),
    "MXTPU_RESILIENCE_BACKOFF_BASE_S": (
        float, 0.1,
        "Supervisor respawn backoff base in seconds "
        "(serving/resilience.py): the Nth consecutive death of a replica "
        "waits base * 2^(N-1) (+ seeded jitter) before respawn, capped at "
        "MXTPU_RESILIENCE_BACKOFF_CAP_S."),
    "MXTPU_RESILIENCE_BACKOFF_CAP_S": (
        float, 5.0,
        "Upper bound on the supervisor's exponential respawn backoff."),
    "MXTPU_RESILIENCE_CRASH_N": (
        int, 5,
        "Crash-loop circuit breaker: a replica that dies this many times "
        "within MXTPU_RESILIENCE_CRASH_WINDOW_S is PARKED (no further "
        "respawns, flightrec 'replica_parked', /healthz degraded) instead "
        "of being respawned into the same crash."),
    "MXTPU_RESILIENCE_CRASH_WINDOW_S": (
        float, 30.0,
        "Sliding window in seconds for the crash-loop circuit breaker's "
        "death count (MXTPU_RESILIENCE_CRASH_N)."),
    "MXTPU_RESILIENCE_POLL_S": (
        float, 0.05,
        "Supervisor poll interval in seconds (serving/resilience.py): how "
        "often dead replicas / dead decode loops are scanned for. The "
        "floor on detection latency; respawn timing adds the backoff."),
    "MXTPU_TELEMETRY_FLUSH_S": (
        float, 0.0,
        "Periodic telemetry flush interval in seconds (telemetry package): "
        "> 0 starts a daemon thread at package import that writes the full "
        "Prometheus exposition to MXTPU_TELEMETRY_FILE every interval — "
        "how headless training jobs emit metrics without the HTTP server. "
        "0 disables (telemetry.start_periodic_flush() still works)."),
    "MXTPU_TELEMETRY_FILE": (
        str, "telemetry.prom",
        "Path the periodic telemetry flusher writes (atomic tmp+rename; "
        "node-exporter textfile-collector compatible)."),
    "MXTPU_TELEMETRY_MAX_SERIES": (
        int, 64,
        "Per-metric bound on distinct label combinations in the telemetry "
        "registry. Past the bound, new label values are clamped onto the "
        "'_other_' series with a one-time RuntimeWarning — unbounded label "
        "cardinality (request ids) must never OOM the process."),
    "MXTPU_SPANS_BUFFER": (
        int, 8192,
        "Bound on the finished-span ring buffer (telemetry/spans.py): "
        "oldest spans age out past it. The buffer backs GET /debug/spans "
        "and spans.export_jsonl()/dump_jsonl()."),
    "MXTPU_SPANS_HISTOGRAM": (
        bool, False,
        "Opt-in bridge feeding every finished span's duration into the "
        "mxtpu_span_seconds{span=<name>} histogram on the shared registry "
        "(spans.set_histogram_bridge overrides at runtime). Off by "
        "default: per-span observe() is only worth paying for when "
        "something scrapes the histogram."),
    "MXTPU_FLIGHTREC_SIZE": (
        int, 2048,
        "Bound on the flight-recorder event ring "
        "(telemetry/flightrec.py): step/compile/dispatch/io/kvstore phase "
        "events, oldest aged out — the black-box tape dumped on crashes, "
        "stalls, and GET /debug/flightrec."),
    "MXTPU_FLIGHTREC_FILE": (
        str, "flightrec.jsonl",
        "Path the flight recorder writes its JSONL tape to on unhandled "
        "exceptions (install_crash_dump) and flightrec.dump()."),
    "MXTPU_FLIGHTREC_DUMP_ON_CRASH": (
        bool, True,
        "Dump the flight-recorder tape to MXTPU_FLIGHTREC_FILE when an "
        "unhandled exception kills the main thread or a worker thread "
        "(sys/threading excepthook chain installed at package import). "
        "Only fires when the tape is non-empty."),
    "MXTPU_HLOLINT_GATE": (
        bool, True,
        "Lint freshly prewarmed serve/eval AOT artifacts (tools/hlolint "
        "H-rules over the persisted StableHLO modules) inside "
        "ModelRegistry.load()'s warm path, BEFORE dispatch cuts over to "
        "the incoming version: error-severity findings (fp64 leak, host "
        "round-trip, predicted HBM overrun, corrupt artifact) refuse the "
        "cutover with a degraded reason in describe()/health(); warns "
        "land in flightrec + mxtpu_hlolint_findings_total{rule}. Only "
        "artifacts are linted, so loads without MXTPU_AOT_CACHE_DIR (or "
        "without prewarm) skip the gate (docs/STATIC_ANALYSIS.md)."),
    "MXTPU_HLOLINT_HBM_BUDGET": (
        float, None,
        "Per-device HBM budget in BYTES the hlolint H004 rule compares "
        "each artifact's header peak_bytes (memory_analysis, persisted "
        "at export) against — a program predicted to overrun is rejected "
        "before deploy instead of OOMing after cutover. Unset: the "
        "devstats per-device-kind capacity table "
        "(telemetry/devstats.py hbm_capacity()); backends the table "
        "doesn't know (CPU) skip H004 entirely."),
    "MXTPU_HLODIFF_GATE": (
        bool, True,
        "Diff freshly prewarmed AOT artifacts against the currently "
        "ROUTED version's programs (tools/hlodiff D-rules, matched per "
        "(kind, bucket, mesh_sig)) inside ModelRegistry.load()'s warm "
        "path, AFTER the hlolint pass: error-severity findings (D001 "
        "FLOPs growth / D003 donation regression on serve-/decode-kind "
        "programs) refuse the cutover with degraded reason "
        "hlodiff:<rule> and ride the last-known-good rollback; warns "
        "land in flightrec + mxtpu_hlodiff_findings_total{rule}. First "
        "loads (no routed reference) and byte-identical redeploys "
        "(cache hit, nothing fresh) skip the diff "
        "(docs/STATIC_ANALYSIS.md)."),
    "MXTPU_HLODIFF_FLOPS_TOL": (
        float, 0.1,
        "hlodiff D001 tolerance: flag a candidate program whose header "
        "FLOPs (cost_analysis, persisted at export) exceed its base "
        "program's by more than this fraction (0.1 = +10%). On "
        "serve-/decode-kind artifacts the finding is error severity and "
        "the deploy gate refuses the cutover."),
    "MXTPU_HLODIFF_PEAK_TOL": (
        float, 0.1,
        "hlodiff D002 tolerance: flag a candidate program whose header "
        "peak_bytes (memory_analysis) exceed its base program's by more "
        "than this fraction (0.1 = +10%) — predicted HBM headroom "
        "shrinking deploy over deploy ends in H004/OOM; warn severity."),
    "MXTPU_HLOLINT_PAD_WASTE": (
        float, 0.5,
        "hlolint H005 threshold: flag a compiled shape bucket whose "
        "worst-fit padded batch wastes more than this fraction of its "
        "compute relative to the next smaller compiled bucket "
        "((b - (b'+1))/b across the artifact set's bucket ladder). The "
        "default 0.5 keeps power-of-two ladders (worst case 37.5%) "
        "clean and fires on gap-toothed ladders like {1, 64}."),
    "MXTPU_GEN_BLOCK_SIZE": (
        int, 16,
        "Token slots per KV-cache block (ops/kvcache.py paged pool). "
        "Smaller blocks waste less tail capacity per sequence but grow "
        "the block tables; docs/GENERATE.md has the sizing math."),
    "MXTPU_GEN_KV_BLOCKS": (
        int, 256,
        "KV pool capacity in blocks, preallocated in HBM at engine "
        "construction (serving/generate.py). Admission of new sequences "
        "backpressures when the free list runs dry; size against "
        "devstats hbm_capacity() per docs/GENERATE.md."),
    "MXTPU_GEN_MAX_BATCH": (
        int, 8,
        "Upper decode-batch bucket of the continuous-batching loop (and "
        "the prefill batcher's max batch). The decode bucket ladder is "
        "powers of two up to this; every bucket is AOT-prewarmed so "
        "steady-state decode never compiles."),
    "MXTPU_GEN_PREFILL_LEN": (
        int, 64,
        "Fixed prompt shape of the compiled prefill programs: prompts "
        "are padded to this length (true length rides as data), longer "
        "ones are rejected 400. One shape keeps prefill on the bucketed "
        "batcher's handful of compiled programs."),
    "MXTPU_GEN_MAX_TOKENS": (
        int, 128,
        "Cap on max_new_tokens per generate request; also sizes the "
        "per-sequence block-table width (with MXTPU_GEN_PREFILL_LEN)."),
    "MXTPU_GEN_STEP_IDLE_MS": (
        float, 1.0,
        "Decode-loop sleep granularity when NO sequence is in flight "
        "(the loop never sleeps between steps while anything decodes)."),
    "MXTPU_GEN_SLO_INTER_TOKEN_MS": (
        float, None,
        "When set, each tenant generating on a model gets a "
        "<model>/inter_token/<tenant> SLO (telemetry/slo.py kind="
        "inter_token) fed one outcome per token gap against this "
        "threshold in ms — burn-rate alerts and /debug/slo rows per "
        "tenant. Unset: no inter-token objectives are minted."),
    "MXTPU_GEN_PREWARM": (
        bool, True,
        "AOT-compile (or artifact-load) every generative program bucket "
        "at engine construction and route fresh decode artifacts "
        "through the hlolint gate. Disable only in tests that assert "
        "compile-counting behavior."),
    "MXTPU_WATCHDOG": (
        bool, False,
        "Autostart the stall watchdog monitor thread at package import "
        "(telemetry/watchdog.py; watchdog.start()/stop() at runtime). "
        "Instrumented loops heartbeat regardless — the knob only controls "
        "the monitor."),
    "MXTPU_WATCHDOG_QUIET_S": (
        float, 60.0,
        "Default quiet period in seconds before a heartbeat channel "
        "(train step, batcher worker, io prefetch) is declared stalled "
        "and an all-thread stack + flight-recorder report is emitted — "
        "once per stall episode, process never killed. Per-channel "
        "override via watchdog.register(quiet_s=)."),
    "MXTPU_WATCHDOG_POLL_S": (
        float, 1.0,
        "Watchdog monitor poll interval in seconds (stall detection "
        "latency is quiet period + up to one poll)."),
    "MXTPU_WATCHDOG_FILE": (
        str, None,
        "File the watchdog APPENDS stall reports to (all-thread stacks + "
        "flight-recorder tail). None: reports go to logging.error and "
        "stay readable at watchdog.last_report() / GET /debug/stacks."),
    "MXTPU_DEVICE_PEAK_FLOPS": (
        float, None,
        "Override the per-chip peak FLOP/s the device-truth MFU gauges "
        "(mxtpu_device_mfu, telemetry/devstats.py) divide by. Unset: "
        "resolved from jax.devices()[0].device_kind via the built-in "
        "peak table; unknown kinds (CPU) fall back to a report-only "
        "nominal peak (docs/OBSERVABILITY.md 'Device truth')."),
    "MXTPU_DEVICE_PEAK_HBM_BPS": (
        float, None,
        "Override the per-chip peak HBM bytes/s the "
        "mxtpu_device_hbm_bw_util gauge divides by. Unset: device_kind "
        "table, else report-only fallback (telemetry/devstats.py)."),
    "MXTPU_DEVSTATS": (
        bool, False,
        "Autostart the device-memory sampler daemon at package import "
        "(telemetry/devstats.py; devstats.start()/stop() at runtime): "
        "polls device.memory_stats() into "
        "mxtpu_device_memory_bytes{device,stat} and files a flightrec "
        "hbm_pressure event at >90% of bytes_limit. Per-dispatch MFU "
        "gauges are driven by the hot paths regardless — the knob only "
        "controls the sampler."),
    "MXTPU_DEVSTATS_POLL_S": (
        float, 1.0,
        "Device-memory sampler poll interval in seconds "
        "(telemetry/devstats.py)."),
    "MXTPU_DEVSTATS_EVAL_SYNC": (
        bool, False,
        "Block-until-ready inside STANDALONE EvalStep dispatches so the "
        "eval mxtpu_device_mfu observation measures exact device time. "
        "Off by default: a direct eval loop overlaps host prep with "
        "device execution and the sync would serialize it. Serving "
        "dispatches (under the batcher's devstats dispatch context) "
        "always observe — there the next step is a host materialization "
        "anyway (docs/OBSERVABILITY.md 'Device truth')."),
    "MXTPU_DEVSTATS_TRAIN_SYNC": (
        bool, False,
        "Block-until-ready inside the TrainStep dispatch window so the "
        "train mxtpu_device_mfu observation measures exact device time. "
        "Off by default: the sync defeats donated-buffer step chaining "
        "(steps serialize on the host), so unsynced train MFU can read "
        "HIGH when steps pipeline — turn on when attributing a training "
        "regression, off for peak throughput (docs/OBSERVABILITY.md)."),
    "MXTPU_PROFILE_DIR": (
        str, None,
        "Directory for on-demand jax.profiler captures "
        "(GET /debug/profile?seconds=N, devstats.capture_profile). "
        "Unset: <tmpdir>/mxtpu_profile. Bounded: only the newest "
        "MXTPU_PROFILE_KEEP captures are kept."),
    "MXTPU_PROFILE_KEEP": (
        int, 4,
        "How many on-demand profiler captures survive in "
        "MXTPU_PROFILE_DIR (oldest pruned after each capture)."),
    "MXTPU_PROFILE_MAX_S": (
        float, 60.0,
        "Upper clamp on GET /debug/profile?seconds=N capture length — an "
        "operator typo must not leave the profiler tracing for an hour."),
    "MXTPU_PROFILE_PYTHON_TRACER": (
        bool, False,
        "Include python frames in profiler captures. OFF by default: the "
        "python tracer taxes every interpreter call while tracing (~30% "
        "on a timer-bound serving request), which lands on p99 whenever "
        "a capture overlaps traffic — the continuous profstats daemon's "
        "whole operating mode. The XLA op events the attribution layer "
        "reads survive with it off."),
    "MXTPU_PROFSTATS": (
        bool, False,
        "Autostart the continuous low-duty-cycle profiler daemon at "
        "package import (telemetry/profstats.py; profstats.start()/"
        "stop() at runtime): every MXTPU_PROFSTATS_INTERVAL_S it "
        "captures MXTPU_PROFSTATS_CAPTURE_S of jax.profiler trace and "
        "folds the per-op summary into "
        "mxtpu_profile_op_seconds_total{model,category} / "
        "mxtpu_profile_device_idle_ratio and GET /debug/hotspots "
        "(docs/OBSERVABILITY.md 'Op-level attribution')."),
    "MXTPU_PROFSTATS_INTERVAL_S": (
        float, 300.0,
        "Seconds between continuous-profiler capture cycles "
        "(telemetry/profstats.py daemon)."),
    "MXTPU_PROFSTATS_CAPTURE_S": (
        float, 2.0,
        "Trace length per continuous-profiler cycle; clamped to "
        "MXTPU_PROFSTATS_MAX_DUTY x MXTPU_PROFSTATS_INTERVAL_S so the "
        "profiler stays a sampling tax, never steady tracing."),
    "MXTPU_PROFSTATS_MAX_LOAD": (
        float, 0.5,
        "Queue-occupancy ceiling above which a continuous-profiler "
        "cycle is skipped (outcome=skipped_load on "
        "mxtpu_profile_captures_total): profiling is for finding the "
        "MFU gap, not for widening it under overload. Load probes: "
        "each serving ModelRegistry registers its max replica-queue "
        "occupancy (profstats.add_load_probe)."),
    "MXTPU_PROFSTATS_MAX_DUTY": (
        float, 0.02,
        "Overhead budget: max fraction of each daemon interval spent "
        "tracing (the capture length clamp)."),
    "MXTPU_PROFSTATS_SUMMARIES": (
        int, 32,
        "How many capture summaries the bounded profstats store keeps "
        "for GET /debug/hotspots?capture=<id> re-fetch — summaries "
        "outlive the pruned capture dirs themselves "
        "(MXTPU_PROFILE_KEEP)."),
    "MXTPU_HISTORY": (
        bool, False,
        "Autostart the metric-history daemon at package import "
        "(telemetry/history.py; history.start()/stop() at runtime): "
        "every MXTPU_HISTORY_INTERVAL_S it self-scrapes the telemetry "
        "registry into bounded per-series rings, evaluates the "
        "recording rules (rate(), queue-depth slope, window MFU, "
        "burn-rate trajectory) and the pressure_rising/mfu_droop early "
        "warnings, and serves GET /debug/history and /debug/incident "
        "(docs/OBSERVABILITY.md 'Metric history & incident timelines')."),
    "MXTPU_HISTORY_INTERVAL_S": (
        float, 10.0,
        "Seconds between metric-history self-scrape ticks. Retention is "
        "a direct function of it: MXTPU_HISTORY_RAW ticks of raw points "
        "plus MXTPU_HISTORY_COARSE x MXTPU_HISTORY_COARSE_EVERY ticks "
        "of min/max/mean summaries."),
    "MXTPU_HISTORY_RAW": (
        int, 512,
        "Raw ring length per history series: the newest N (t, value) "
        "points kept at full scrape resolution (telemetry/history.py). "
        "At the default 10s interval: ~85 minutes of raw history."),
    "MXTPU_HISTORY_COARSE": (
        int, 512,
        "Coarse ring length per history series: N downsampled "
        "{t, min, max, mean} points, each folding "
        "MXTPU_HISTORY_COARSE_EVERY raw samples — the long-horizon tier "
        "raw points age out into."),
    "MXTPU_HISTORY_COARSE_EVERY": (
        int, 8,
        "Raw samples folded into one coarse min/max/mean point. The "
        "fold keeps extremes honest: a one-tick queue spike survives "
        "into the coarse tier as that window's max, never averaged "
        "away."),
    "MXTPU_HISTORY_MAX_SERIES": (
        int, 1024,
        "Bound on distinct series the history store retains (scraped + "
        "derived recording-rule series). Past it, NEW series are "
        "dropped and counted on "
        "mxtpu_history_store_dropped_series_total; established series "
        "keep recording — history must never OOM the process it "
        "observes."),
    "MXTPU_HISTORY_FILE": (
        str, None,
        "When set, every history tick also exports the full store to "
        "this path as canonical JSONL (atomic tmp+rename rotation) — "
        "the offline artifact tools/tsq.py queries, diffs, and "
        "sparkline-renders."),
    "MXTPU_HISTORY_SLOPE_WINDOW_S": (
        float, 60.0,
        "Trailing window for the least-squares slope recording rules "
        "(queue depth, SLO burn rate) — the trend the pressure_rising "
        "predictor extrapolates."),
    "MXTPU_HISTORY_PRESSURE_HORIZON_S": (
        float, 60.0,
        "pressure_rising fires when a model's queue-depth trend line "
        "predicts crossing its capacity within this many seconds; the "
        "open episode only closes when the prediction retreats past "
        "twice the horizon (hysteresis) or the slope turns "
        "non-positive."),
    "MXTPU_HISTORY_PRESSURE_DEPTH": (
        float, None,
        "Fallback saturation depth for pressure_rising when a model "
        "exports no mxtpu_serving_queue_capacity gauge (the serving "
        "batcher exports queue_size x replicas automatically). None: "
        "no capacity, no prediction."),
    "MXTPU_HISTORY_DROOP_FRAC": (
        float, 0.7,
        "mfu_droop fires when the window MFU falls below this fraction "
        "of its trailing MXTPU_HISTORY_DROOP_WINDOW_S median; the "
        "episode re-arms only after MFU recovers halfway back to the "
        "median (hysteresis)."),
    "MXTPU_HISTORY_DROOP_WINDOW_S": (
        float, 600.0,
        "Trailing window whose median window-MFU is the mfu_droop "
        "baseline (the '10-minute median' the early warning compares "
        "against)."),
    "MXTPU_LOADGEN_SEED": (
        int, 0,
        "Arrival-process RNG seed for the open-loop load generator "
        "(tools/loadgen.py): Poisson inter-arrival draws are fully "
        "deterministic given it, so two soaks offer byte-identical "
        "schedules. Read stdlib-side by the tool (it must drive a remote "
        "server without the framework importable); registered here for "
        "docs and env hygiene (docs/LOADGEN.md)."),
    "MXTPU_LOADGEN_TIMEOUT_S": (
        float, 30.0,
        "Per-request HTTP timeout for the load generator's clients; a "
        "request past it records a transport error (status 599), never "
        "a hang. Read stdlib-side by tools/loadgen.py."),
    "MXTPU_LOADGEN_MAX_CLIENTS": (
        int, 256,
        "Bound on the load generator's concurrent in-flight requests. "
        "Arrivals past the bound are recorded as client-dropped (the "
        "offered-load accounting stays exact) instead of silently "
        "unsent or queued client-side — client-side queueing would "
        "re-introduce the coordinated-omission bias the open-loop "
        "design exists to avoid. Read stdlib-side by tools/loadgen.py."),
    "MXTPU_SLO_TARGET": (
        float, 0.99,
        "Default availability objective for the per-model SLOs the serving "
        "registry seeds at load (telemetry/slo.py): the fraction of "
        "eligible requests (2xx good; 429/504/5xx bad; other 4xx not "
        "counted) that must succeed. The error budget is 1 - target — "
        "burn rates are bad-fraction / (1 - target) "
        "(docs/OBSERVABILITY.md 'SLOs and tenants')."),
    "MXTPU_SLO_LATENCY_MS": (
        float, None,
        "When set, every served model also gets a latency SLO: a 2xx "
        "response slower than this many milliseconds end-to-end (the "
        "http:predict span window) counts against the latency error "
        "budget. None = availability SLO only (telemetry/slo.py)."),
    "MXTPU_SLO_WINDOW_S": (
        float, 3600.0,
        "Error-budget accounting window in seconds for "
        "mxtpu_slo_budget_remaining: the sliding window over which spent "
        "budget is computed (and refills as bad events age out). The SRE "
        "30-day convention is impractical for a process-local ledger; one "
        "hour is the operational default (telemetry/slo.py)."),
    "MXTPU_SLO_WINDOWS": (
        str, "300:3600,3600:21600",
        "Multi-window burn-rate alert pairs as SHORT:LONG second pairs, "
        "comma-separated, fastest first (default: the SRE-workbook 5m/1h "
        "fast pair and 1h/6h slow pair). An alert pair breaches only when "
        "BOTH its windows' burn rates exceed the pair's threshold — the "
        "short window gives detection speed, the long one suppresses "
        "blips. CI scales these down to seconds (telemetry/slo.py)."),
    "MXTPU_SLO_FAST_BURN": (
        float, 14.4,
        "Burn-rate threshold for the FIRST (fast) alert-window pair: 14.4 "
        "means the error budget is being spent 14.4x faster than the "
        "objective allows (the SRE-workbook page-now threshold — 2% of a "
        "30-day budget in one hour)."),
    "MXTPU_SLO_SLOW_BURN": (
        float, 6.0,
        "Burn-rate threshold for the second and later (slow) alert-window "
        "pairs (the SRE-workbook ticket threshold — 5% of a 30-day "
        "budget in six hours)."),
    "MXTPU_ACCESSLOG_SIZE": (
        int, 4096,
        "Bound on the structured per-request access-log ring "
        "(serving/accesslog.py): one record per terminal predict outcome "
        "{ts, request_id, tenant, model, code, shed_reason, queue_ms, "
        "batch_ms, device_ms, replica, bucket}, oldest aged out. Served "
        "at GET /debug/requests?n=."),
    "MXTPU_ACCESSLOG_FILE": (
        str, None,
        "When set, access-log records are ALSO appended to this path as "
        "JSONL (sampled by MXTPU_ACCESSLOG_SAMPLE). None disables file "
        "export; the in-memory ring and /debug/requests stay on "
        "regardless (serving/accesslog.py)."),
    "MXTPU_ACCESSLOG_SAMPLE": (
        float, 1.0,
        "Deterministic sampling rate (0..1) for the access-log JSONL file "
        "export: a stride sampler writes every record at 1.0, every "
        "second record at 0.5, none at 0 — deterministic, not random, so "
        "two identical runs export identical files "
        "(serving/accesslog.py)."),
    "MXTPU_NUMWATCH_SAMPLE": (
        float, 0.0,
        "Numerics-sentinel tap sampling rate (telemetry/numwatch.py): 0 "
        "disables the on-device stats taps (the default); a rate r in "
        "(0, 1] taps every round(1/r)-th dispatch at each site "
        "(deterministic stride, not random — two identical runs tap "
        "identical dispatches). Tap sites: TrainStep loss/params, "
        "serving dispatch outputs, decode-loop logits "
        "(docs/OBSERVABILITY.md 'Numerical health')."),
    "MXTPU_SHADOW_SAMPLE": (
        float, 0.0,
        "Default shadow-execution sampling rate for models with a "
        "registered reference servable (numwatch.register_shadow): 0 "
        "disables; rate r re-executes every round(1/r)-th dispatched "
        "batch through the reference on a background worker and compares "
        "outputs into mxtpu_shadow_divergence{model,metric}. A per-model "
        "stride passed to register_shadow overrides this."),
    "MXTPU_SHADOW_THRESHOLD": (
        float, 0.25,
        "Max-abs-diff breach threshold for shadow divergence: a shadow "
        "sample whose primary-vs-reference max absolute output "
        "difference exceeds this flips the served model's health to "
        "degraded (once per breach episode) and fires a shadow_breach "
        "flightrec event (telemetry/numwatch.py)."),
    "MXTPU_SEED": (
        int, None,
        "Global RNG seed applied at package import (MXNET_SEED analog): "
        "seeds nd.random, np.random and the functional key stream."),
    "MXTPU_CPU_WORKER_NTHREADS": (
        int, 4,
        "Default decode/augment thread count for the native "
        "ImageRecordIter when preprocess_threads is not given "
        "(MXNET_CPU_WORKER_NTHREADS analog)."),
    "MXTPU_TEST_LARGE_TENSOR": (
        bool, False,
        "Opt into the >2^31-element int64 large-tensor test tier "
        "(tests/test_large_tensor.py; ~2-6 GB of host RAM)."),
    "JAX_PLATFORMS": (
        str, None,
        "Backend selection (jax): 'cpu' forces the virtual-device CPU path "
        "used by tests and DataLoader process workers."),
    "XLA_FLAGS": (
        str, None,
        "XLA compiler flags; tests use "
        "--xla_force_host_platform_device_count=8 for the virtual mesh."),
}


def get_env(name):
    """Typed read of a registered variable (raises on unknown names)."""
    if name not in ENV_VARS:
        raise KeyError("unregistered env var %r — add it to config.ENV_VARS"
                       % name)
    typ, default, _doc = ENV_VARS[name]
    raw = os.environ.get(name)
    if raw is None:
        return default
    if typ is bool:
        return raw.strip().lower() not in ("0", "", "false", "no", "off")
    return typ(raw)


def place_compile_cache():
    """Give JAX's persistent compilation cache a home — the ONE place any
    entry point (chip_smoke.py, example/*, the server) gets it
    from, called at package import. It only sets config values; no
    backend is initialised. Returns the directory it set, or None.

    ``JAX_COMPILATION_CACHE_DIR`` set: the cache was placed from outside,
    JAX reads the variable itself and no directory is set here. Unset:
    ``<checkout>/.jax_cache``, computed from this package's own location —
    the directory is part of the cache key, so never a tempdir, a pid or
    a timestamp. Either way every program is kept, not only those over
    JAX's default 1 s of compile time: parameter initialisation, the
    eager forward that settles deferred shapes and the generative
    engine's buckets are hundreds of sub-second programs (with only the
    >= 1 s ones kept, a warm ResNet-50 first step still took 41.5 s on
    the chip against 79.3 s cold; 13.2 s with all — chip runs, PR 21).

    A process pinned to the CPU backend (the test suite, DataLoader
    workers, tools/launch.py local ranks) gets nothing from here:
    XLA:CPU executables are tied to the build machine's feature list and
    log an error-level line on every load.

    This is XLA's executable cache; the StableHLO artifact layer under
    MXTPU_AOT_CACHE_DIR (aot.py) is a different thing and still pays the
    XLA compile after a load."""
    import jax
    if jax.config.jax_platforms == "cpu":
        return None
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def evict_to_bound(cache, on_evict=None):
    """Drop least-recently-USED entries of an executable cache until it
    fits MXTPU_EXEC_CACHE_SIZE (call after inserting).

    LRU contract: python dicts iterate in insertion order, so a caller
    marking a hit must move the entry to the end (``cache[k] =
    cache.pop(k)``) — then insertion order IS recency order and the front
    entry is the least-recently-dispatched one. Pure insert-only callers
    degrade to the old FIFO behavior. ``on_evict(key, value)`` runs per
    victim (metrics hooks); the shared AOT cache (aot.AOTCache) has its
    own timestamped LRU + mxtpu_aot_evictions_total counter and does not
    route through here.
    """
    bound = max(1, get_env("MXTPU_EXEC_CACHE_SIZE"))
    while len(cache) > bound:
        key = next(iter(cache))
        value = cache.pop(key)
        if on_evict is not None:
            on_evict(key, value)


def describe():
    """Render the registry as the env_var.md-style table."""
    lines = ["%-24s %-6s %-10s %s" % ("Variable", "Type", "Default", "Doc")]
    for name, (typ, default, doc) in sorted(ENV_VARS.items()):
        lines.append("%-24s %-6s %-10s %s"
                     % (name, typ.__name__, str(default), doc))
    return "\n".join(lines)
