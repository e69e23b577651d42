"""hlolint tier: H-rule positive/negative fixtures on raw StableHLO
text (no jax in the loop), the CLI contract (exit codes, baseline
round-trip, --rules, the shared CI JSON shape), the seeded-defect
canary, artifact-vs-live-cache scan equivalence in a fresh subprocess,
the env-driven H004 budget, the registry load gate refusing an
error-severity artifact, and H006 reproducing on the real int8-quantized
servable path."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools import hlolint                                        # noqa: E402
from tools.hlolint import canary as hlolint_canary               # noqa: E402


def mk(kind, body_lines, args="%arg0: tensor<4x8xf32>", results="tensor<4x8xf32>",
       stats=None, path=None):
    """Assemble a minimal StableHLO module around ``body_lines``."""
    text = "module @jit_f {\n  func.func public @main(%s) -> (%s) {\n%s\n" \
           "    return %%0 : tensor<4x8xf32>\n  }\n}\n" % (
               args, results,
               "\n".join("    " + l for l in body_lines))
    return hlolint.program_from_text(
        path or ("jax-0/%s-cafe.mxtpu-aot" % kind), kind, text, stats)


def rules_of(findings):
    return sorted(f.rule for f in findings)


# ------------------------------------------------------------------ walker
def test_walker_args_ops_and_bucket():
    prog = mk("eval", [
        "%0 = stablehlo.dot_general %arg0, %arg1, contracting_dims = "
        "[1] x [0] : (tensor<4x8xf32>, tensor<8x2xf32>) -> tensor<4x2xf32>"],
        args='%arg0: tensor<4x8xf32> loc("input_datas[0]"), '
             '%arg1: tensor<8x2xf32> loc("param_datas[0]")')
    facts = prog.facts
    assert [a.dtype for a in facts.args] == ["f32", "f32"]
    assert facts.args[0].dims == (4, 8)
    assert facts.bucket() == 4                  # dim0 of the INPUT arg
    assert [a.name for a in facts.input_args()] == ["input_datas[0]"]
    ops = [op for op in facts.ops if op.name == "stablehlo.dot_general"]
    assert ops and ops[0].in_dtypes() == ["f32", "f32"]


def test_walker_sharding_attr_keeps_loc_name():
    """mhlo.sharding attr values contain a quoted `}` — the arg parser
    must not truncate there, or sharded (MeshServable) artifacts lose
    their loc names and bucket()/group_key silently degrade."""
    prog = mk("serve", [], args=(
        '%arg0: tensor<8x4xf32> {mhlo.sharding = '
        '"{devices=[2,1]<=[2]}"} loc("input_datas[0]"), '
        '%arg1: tensor<4x2xf32> {mhlo.sharding = "{replicated}"} '
        'loc("param_datas[0]")'))
    facts = prog.facts
    assert [a.name for a in facts.args] == ["input_datas[0]",
                                            "param_datas[0]"]
    assert facts.bucket() == 8
    assert facts.args[0].aliased is False


def test_walker_alias_attr_and_group_key():
    donated = mk("train", ["%0 = stablehlo.subtract %arg0, %arg1 : "
                           "(tensor<4x8xf32>, tensor<4x8xf32>) -> "
                           "tensor<4x8xf32>"],
                 args='%arg0: tensor<4x8xf32> {tf.aliasing_output = 0 : '
                      'i32} loc("w"), %arg1: tensor<4x8xf32> loc("g")')
    assert donated.facts.aliased_count() == 1
    a = mk("eval", [], args='%arg0: tensor<4x8xf32> loc("input_datas[0]")')
    b = mk("eval", [], args='%arg0: tensor<64x8xf32> loc("input_datas[0]")')
    assert a.facts.group_key() == b.facts.group_key()
    assert a.facts.bucket() == 4 and b.facts.bucket() == 64


# ------------------------------------------------------------------ H001
def test_h001_fp64_serve_fires_train_exempt():
    body = ["%0 = stablehlo.multiply %arg0, %arg0 : (tensor<4x8xf64>, "
            "tensor<4x8xf64>) -> tensor<4x8xf64>"]
    serve = mk("serve", body, args="%arg0: tensor<4x8xf64>")
    assert rules_of(hlolint.analyze_programs([serve])) == ["H001"]
    evalp = mk("eval", body, args="%arg0: tensor<4x8xf64>")
    assert "H001" in rules_of(hlolint.analyze_programs([evalp]))
    train = mk("train", body, args="%arg0: tensor<4x8xf64> "
                                   "{tf.aliasing_output = 0 : i32}")
    assert "H001" not in rules_of(hlolint.analyze_programs([train]))


def test_h001_clean_f32():
    serve = mk("serve", ["%0 = stablehlo.multiply %arg0, %arg0 : "
                         "(tensor<4x8xf32>, tensor<4x8xf32>) -> "
                         "tensor<4x8xf32>"])
    assert rules_of(hlolint.analyze_programs([serve])) == []


# ------------------------------------------------------------------ H002
def test_h002_train_without_aliasing_fires():
    train = mk("train", ["%0 = stablehlo.subtract %arg0, %arg1 : "
                         "(tensor<4x8xf32>, tensor<4x8xf32>) -> "
                         "tensor<4x8xf32>"],
               args="%arg0: tensor<4x8xf32>, %arg1: tensor<4x8xf32>")
    out = hlolint.analyze_programs([train])
    assert rules_of(out) == ["H002"]
    assert hlolint.severity_of("H002") == "warn"
    assert "donation miss" in out[0].message


def test_h002_negative_donated_and_non_train():
    donated = mk("train", [], args="%arg0: tensor<4x8xf32> "
                                   "{tf.aliasing_output = 0 : i32}, "
                                   "%arg1: tensor<4x8xf32>")
    assert "H002" not in rules_of(hlolint.analyze_programs([donated]))
    serve = mk("serve", [], args="%arg0: tensor<4x8xf32>")
    assert "H002" not in rules_of(hlolint.analyze_programs([serve]))


# ------------------------------------------------------------------ H003
def test_h003_host_roundtrips_in_serve():
    prog = mk("serve", [
        '%0 = stablehlo.custom_call @xla_python_cpu_callback(%arg0) : '
        '(tensor<4x8xf32>) -> tensor<4x8xf32>',
        '"stablehlo.outfeed"(%0) : (tensor<4x8xf32>) -> ()'])
    out = [f for f in hlolint.analyze_programs([prog])
           if f.rule == "H003"]
    assert len(out) == 2
    assert "xla_python_cpu_callback" in out[0].message
    assert hlolint.severity_of("H003") == "error"


def test_h003_device_kernels_and_eval_exempt():
    # custom_call is ALSO how pure device kernels ship — GSPMD markers,
    # Pallas/Mosaic kernels, RNG/library calls must never be refused as
    # host round-trips by an error-severity gate
    for target in ("Sharding", "tpu_custom_call", "cu_threefry2x32",
                   "ducc_fft", "lapack_sgesv"):
        benign = mk("serve", ['%%0 = stablehlo.custom_call @%s(%%arg0) : '
                              '(tensor<4x8xf32>) -> tensor<4x8xf32>'
                              % target])
        assert "H003" not in rules_of(hlolint.analyze_programs([benign])), \
            target
    # eval programs ARE the serving path (BlockServable -> jit.EvalStep):
    # a host callback there fires like in a serve program; only train
    # programs (off the dispatch path) are exempt
    evalp = mk("eval", ['%0 = stablehlo.custom_call '
                        '@xla_python_cpu_callback(%arg0) '
                        ': (tensor<4x8xf32>) -> tensor<4x8xf32>'])
    assert "H003" in rules_of(hlolint.analyze_programs([evalp]))
    trainp = mk("train", ['%0 = stablehlo.custom_call '
                          '@xla_python_cpu_callback(%arg0) '
                          ': (tensor<4x8xf32>) -> tensor<4x8xf32>'])
    assert "H003" not in rules_of(hlolint.analyze_programs([trainp]))


def test_h003_profiler_annotation_targets_exempt():
    """Annotation/profiler marker targets are exempt EVEN when their
    name matches the host-callback regex (e.g. '..._host_annotation'):
    they are metadata the device never blocks on, and refusing them
    would make every artifact exported during a profiling session
    undeployable. The marker list is owned by telemetry/profstats.py."""
    from incubator_mxnet_tpu.telemetry.profstats import \
        ANNOTATION_TARGET_MARKERS
    assert ANNOTATION_TARGET_MARKERS == ("profiler", "annotation",
                                         "named_scope")
    for target in ("mxtpu_profiler_host_annotation",
                   "xla_profiler_host_callback_marker",
                   "host_named_scope_begin"):
        prog = mk("serve", ['%%0 = stablehlo.custom_call @%s(%%arg0) : '
                            '(tensor<4x8xf32>) -> tensor<4x8xf32>'
                            % target])
        assert "H003" not in rules_of(hlolint.analyze_programs([prog])), \
            target
    # the exemption is narrow: a real host callback with no marker in
    # its name still fires alongside the exempted op
    mixed = mk("serve", [
        '%0 = stablehlo.custom_call @mxtpu_profiler_host_annotation'
        '(%arg0) : (tensor<4x8xf32>) -> tensor<4x8xf32>',
        '%1 = stablehlo.custom_call @xla_python_cpu_callback(%0) : '
        '(tensor<4x8xf32>) -> tensor<4x8xf32>'])
    out = [f for f in hlolint.analyze_programs([mixed])
           if f.rule == "H003"]
    assert len(out) == 1 and "xla_python_cpu_callback" in out[0].message


def test_h003_artifact_exported_under_profiler_capture_scans_clean(
        tmp_path):
    """Regression for the profiling-session scenario: a serve artifact
    exported while a jax.profiler trace (and named_scope annotations)
    is active must pass the load gate."""
    import jax
    import jax.numpy as jnp
    from jax import export as jax_export
    from tools.hlolint.artifact import load_dir
    from tools.hlolint.canary import _write_artifact
    spec = jax.ShapeDtypeStruct((8, 4), jnp.float32)

    def fn(x):
        with jax.named_scope("serve_annotated_block"):
            return x * 2.0

    trace_dir = tmp_path / "trace"
    with jax.profiler.trace(str(trace_dir)):
        exported = jax_export.export(jax.jit(fn))(spec)
    art_dir = tmp_path / "artifacts"
    _write_artifact(str(art_dir), "serve", exported)
    programs, errs = load_dir(str(art_dir))
    assert not errs and len(programs) == 1
    assert rules_of(hlolint.analyze_programs(programs)) == []


# ------------------------------------------------------------------ H004
def test_h004_env_budget_drives_the_gate(monkeypatch):
    """The satellite acceptance: H004 driven by the env-override budget
    (the devstats HBM table knows no CPU, so without the override the
    rule must SKIP, never guess)."""
    stats = {"flops": 1.0, "peak_bytes": 2 ** 20}
    prog = mk("serve", [], stats=stats)
    # CPU backend: no table entry, no env -> skipped
    monkeypatch.delenv("MXTPU_HLOLINT_HBM_BUDGET", raising=False)
    from incubator_mxnet_tpu.telemetry import devstats
    assert devstats.hbm_capacity() == (None, "unknown")
    assert "H004" not in rules_of(hlolint.analyze_programs([prog]))
    # env budget below the program's predicted peak -> error finding
    monkeypatch.setenv("MXTPU_HLOLINT_HBM_BUDGET", "1024")
    out = [f for f in hlolint.analyze_programs([prog])
           if f.rule == "H004"]
    assert len(out) == 1 and "OOM" in out[0].message
    assert hlolint.severity_of("H004") == "error"
    # budget above the peak -> clean
    monkeypatch.setenv("MXTPU_HLOLINT_HBM_BUDGET", str(2 ** 30))
    assert "H004" not in rules_of(hlolint.analyze_programs([prog]))


def test_h004_hbm_table_has_real_kinds():
    from incubator_mxnet_tpu.telemetry import devstats
    assert devstats.HBM_TABLE["TPU v5e"] == 16e9
    assert devstats.HBM_TABLE["TPU v4"] == 32e9


def test_hbm_capacity_word_boundary(monkeypatch):
    """An unlisted sub-variant kind must come back unknown (H004 then
    SKIPS) — never inherit a bigger sibling's capacity via a bare
    prefix hit and wave a predicted OOM through the gate."""
    import jax
    from incubator_mxnet_tpu.telemetry import devstats

    class _Dev:
        def __init__(self, kind):
            self.device_kind = kind

    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev("TPU v4i")])
    assert devstats.hbm_capacity() == (8e9, "table")
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev("TPU v7x")])
    assert devstats.hbm_capacity() == (None, "unknown")
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_Dev("TPU v5 lite pod")])
    assert devstats.hbm_capacity() == (16e9, "table")


# ------------------------------------------------------------------ H005
def _ladder(b_small, b_big):
    def prog(b):
        return mk("eval", [], args='%%arg0: tensor<%dx8xf32> '
                                   'loc("input_datas[0]")' % b,
                  stats={"flops": 100.0 * b},
                  path="jax-0/eval-%04d.mxtpu-aot" % b)
    return [prog(b_small), prog(b_big)]


def test_h005_gap_toothed_ladder_fires():
    out = hlolint.analyze_programs(_ladder(1, 64))
    assert rules_of(out) == ["H005"]
    assert out[0].path.endswith("eval-0064.mxtpu-aot")
    assert "97%" in out[0].message


def test_h005_power_of_two_ladder_clean_and_threshold_env(monkeypatch):
    assert rules_of(hlolint.analyze_programs(_ladder(4, 8))) == []
    # tighten the threshold: the same ladder now fires
    monkeypatch.setenv("MXTPU_HLOLINT_PAD_WASTE", "0.3")
    assert rules_of(hlolint.analyze_programs(_ladder(4, 8))) == ["H005"]


def test_h005_needs_a_group():
    # singleton bucket, and mismatched signatures, never fire
    single = _ladder(1, 64)[1:]
    assert rules_of(hlolint.analyze_programs(single)) == []
    mixed = [mk("eval", [], args='%arg0: tensor<1x8xf32> '
                                 'loc("input_datas[0]")'),
             mk("eval", [], args='%arg0: tensor<64x16xf32> '
                                 'loc("input_datas[0]")')]
    assert rules_of(hlolint.analyze_programs(mixed)) == []


# ------------------------------------------------------------------ H006
def test_h006_qdq_upcast_fires_native_int8_clean():
    qdq = mk("serve", [
        "%0 = stablehlo.convert %arg1 : (tensor<8x2xi8>) -> "
        "tensor<8x2xf32>",
        "%1 = stablehlo.dot_general %arg0, %0, contracting_dims = [1] x "
        "[0] : (tensor<4x8xf32>, tensor<8x2xf32>) -> tensor<4x2xf32>"],
        args="%arg0: tensor<4x8xf32>, %arg1: tensor<8x2xi8>")
    out = hlolint.analyze_programs([qdq])
    assert rules_of(out) == ["H006"]
    assert "1.78x" in out[0].message
    native = mk("serve", [
        "%0 = stablehlo.dot_general %arg0, %arg1, contracting_dims = [1]"
        " x [1] : (tensor<4x8xi8>, tensor<2x8xi8>) -> tensor<4x2xi32>",
        "%1 = stablehlo.convert %0 : (tensor<4x2xi32>) -> tensor<4x2xf32>"],
        args="%arg0: tensor<4x8xi8>, %arg1: tensor<2x8xi8>")
    assert rules_of(hlolint.analyze_programs([native])) == []


def test_h006_real_int8_quantized_servable_path(tmp_path, monkeypatch):
    """The acceptance fixture: the QDQ fallback (MXTPU_INT8_SIM=1) on a
    REAL quantized conv net, traced through EvalStep into a persisted
    artifact, must reproduce H006 — and the finding anchors at an actual
    i8->f32 convert line of the compiled module."""
    monkeypatch.setenv("MXTPU_AOT_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("MXTPU_INT8_SIM", "1")
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, jit, nd
    from incubator_mxnet_tpu.contrib import quantization
    mx.random.seed(0)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(4, 3, in_channels=2))
    net.initialize(mx.init.Xavier())
    qnet = quantization.quantize_net(net,
                                     calib_data=[nd.ones((2, 2, 8, 8))])
    jit.EvalStep(qnet)(nd.ones((2, 2, 8, 8)))
    findings = hlolint.scan_dir(str(tmp_path))
    h006 = [f for f in findings if f.rule == "H006"]
    assert len(h006) == 1, findings
    assert "stablehlo.convert" in h006[0].text
    assert "MXTPU_INT8_SIM" in h006[0].message


# ------------------------------------------------------------------ H000
def test_h000_corrupt_artifact_is_a_finding(tmp_path):
    d = tmp_path / "jax-0"
    d.mkdir()
    (d / "serve-feed.mxtpu-aot").write_bytes(b"not an artifact")
    (d / "bogus-feed.mxtpu-aot").write_bytes(b"x")
    findings = hlolint.scan_dir(str(tmp_path))
    assert rules_of(findings) == ["H000", "H000"]
    assert hlolint.severity_of("H000") == "error"
    # H000 honors --rules like every other id: a scan narrowed to a
    # different rule must not smuggle corrupt-artifact findings back in
    assert hlolint.scan_dir(str(tmp_path), only_rules={"H006"}) == []
    assert rules_of(hlolint.scan_dir(str(tmp_path),
                                     only_rules={"H000"})) \
        == ["H000", "H000"]


# ------------------------------------------------------------------- CLI
def run_cli(*args, env=None):
    full_env = dict(os.environ, JAX_PLATFORMS="cpu")
    if env:
        full_env.update(env)
    return subprocess.run(
        [sys.executable, "-m", "tools.hlolint"] + list(args),
        cwd=REPO, env=full_env, capture_output=True, text=True,
        timeout=300)


def test_cli_canary_exact_rules_and_baseline_round_trip(tmp_path):
    """The ci/run.sh hlolint-stage contract in one test: the seeded
    canary fires exactly H001+H002, --update-baseline grandfathers them,
    and the re-scan is then clean with baselined == 2."""
    paths = hlolint_canary.write_canary(str(tmp_path / "art"))
    assert [os.path.basename(p).split("-")[0] for p in paths] \
        == ["serve", "train"]
    r = run_cli(str(tmp_path / "art"), "--no-baseline", "--json")
    assert r.returncode == 1, r.stdout + r.stderr
    rep = json.loads(r.stdout)
    assert rep["tool"] == "hlolint" and not rep["ok"]
    assert sorted(f["rule"] for f in rep["findings"]) == ["H001", "H002"]
    assert rep["counts"] == {"H001": 1, "H002": 1}
    bl = tmp_path / "bl.json"
    r = run_cli(str(tmp_path / "art"), "--baseline", str(bl),
                "--update-baseline")
    assert r.returncode == 0 and "2 finding(s)" in r.stdout
    r = run_cli(str(tmp_path / "art"), "--baseline", str(bl), "--json")
    assert r.returncode == 0, r.stdout + r.stderr
    rep = json.loads(r.stdout)
    assert rep["ok"] and rep["findings"] == [] and rep["baselined"] == 2


def test_cli_rules_filter(tmp_path):
    hlolint_canary.write_canary(str(tmp_path))
    r = run_cli(str(tmp_path), "--no-baseline", "--rules", "H001",
                "--json")
    assert r.returncode == 1
    assert sorted(f["rule"] for f in json.loads(r.stdout)["findings"]) \
        == ["H001"]


def test_cli_usage_errors(tmp_path):
    assert run_cli(str(tmp_path / "nope")).returncode == 2
    hlolint_canary.write_canary(str(tmp_path))
    assert run_cli(str(tmp_path), "--rules", "H999").returncode == 2
    assert run_cli(str(tmp_path), "--rules", "H001",
                   "--update-baseline").returncode == 2
    # no dir given and MXTPU_AOT_CACHE_DIR unset -> usage error, never a
    # vacuous green
    env = {k: v for k, v in os.environ.items()
           if k != "MXTPU_AOT_CACHE_DIR"}
    r = subprocess.run([sys.executable, "-m", "tools.hlolint"],
                       cwd=REPO, env=dict(env, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 2 and "MXTPU_AOT_CACHE_DIR" in r.stderr


def test_cli_default_dir_from_env(tmp_path):
    hlolint_canary.write_canary(str(tmp_path))
    r = run_cli("--no-baseline", "--json",
                env={"MXTPU_AOT_CACHE_DIR": str(tmp_path)})
    assert r.returncode == 1
    assert sorted(f["rule"] for f in json.loads(r.stdout)["findings"]) \
        == ["H001", "H002"]


def test_cli_list_rules():
    r = run_cli("--list-rules")
    assert r.returncode == 0
    for rid in ("H000", "H001", "H002", "H003", "H004", "H005", "H006"):
        assert rid in r.stdout
    assert "cross-program" in r.stdout


# ----------------------------------------- artifact/live-cache equivalence
def test_fresh_subprocess_scan_matches_live_cache(tmp_path):
    """The two scan roots can never diverge: a fresh subprocess builds
    programs at a gap-toothed bucket ladder (so the scan is NON-vacuous:
    H005 fires), scans its own LIVE aot.CACHE in-process, and the
    parent's CLI scan of the artifact directory must be byte-identical
    to it."""
    script = textwrap.dedent("""
        import json, sys
        import incubator_mxnet_tpu as mx
        from incubator_mxnet_tpu import gluon, jit, nd
        from tools import hlolint
        mx.random.seed(0)
        net = gluon.nn.Dense(4, in_units=8)
        net.initialize(mx.init.Xavier())
        for b in (1, 64):
            jit.EvalStep(net)(nd.ones((b, 8)))
        findings = hlolint.scan_cache()          # the LIVE process cache
        json.dump([f.to_json() for f in findings], sys.stdout,
                  sort_keys=True)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXTPU_AOT_CACHE_DIR=str(tmp_path))
    r = subprocess.run([sys.executable, "-c", script], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    live = r.stdout
    assert json.loads(live), "vacuous equivalence: no findings fired"
    cli = run_cli(str(tmp_path), "--no-baseline", "--json",
                  env={"MXTPU_AOT_CACHE_DIR": str(tmp_path)})
    assert cli.returncode == 1
    dir_scan = json.dumps(json.loads(cli.stdout)["findings"],
                          sort_keys=True)
    assert dir_scan == live, (dir_scan, live)


# ------------------------------------------------------ registry load gate
class _F64Servable:
    """A servable whose compiled serve program silently computes in fp64
    — the x64 leak H001 exists for, persisted through the real AOT
    artifact layer so the load gate sees exactly what a deploy would.
    ``model_id`` must be unique per test: aot.CACHE is process-wide, and
    a cache HIT during warm means nothing fresh to lint."""

    def __init__(self, model_id):
        self._model_id = model_id

    def predict_batch(self, x):
        import numpy as onp
        import jax
        import jax.numpy as jnp
        from incubator_mxnet_tpu import aot
        key = aot.cache_key(self._model_id, aot.input_signature([x]),
                            kind="serve")
        specs = [jax.ShapeDtypeStruct(tuple(x.shape), jnp.float32)]

        def build():
            from jax import export as jax_export
            with jax.enable_x64():
                exported = jax_export.export(jax.jit(
                    lambda a: (a.astype(jnp.float64) * 2.0)
                    .astype(jnp.float32)))(*specs)
            return (jax.jit(exported.call).lower(*specs).compile(),
                    None, exported)

        entry = aot.compile_cached(key, build, exportable=True,
                                   arg_specs=specs)
        return (onp.asarray(entry.fn(jnp.asarray(x))),)


def test_registry_refuses_error_severity_artifact(tmp_path, monkeypatch):
    """The acceptance contract: load() lints the freshly warmed artifact
    and an error-severity finding refuses the cutover — the model stays
    unroutable, describe()/health() carry the loud degraded reason, and
    the findings counter moved."""
    import numpy as onp
    from incubator_mxnet_tpu.serving import ModelRegistry
    from incubator_mxnet_tpu.serving.registry import ModelNotFoundError
    from tools.hlolint import gate
    monkeypatch.setenv("MXTPU_AOT_CACHE_DIR", str(tmp_path))
    before = gate.findings_total().value(rule="H001")
    reg = ModelRegistry()
    try:
        reg.load("f64m", _F64Servable("hlolint-f64-refuse"), warm_spec=[((8,), "float32")],
                 max_batch_size=2, batch_timeout_ms=1.0)
        desc = [m for m in reg.models() if m["name"] == "f64m"][0]
        assert desc["current_version"] is None
        assert desc["degraded"] and "H001" in desc["degraded"]
        health = reg.health()
        assert health["status"] == "degraded"
        assert "hlolint" in health["reason"]
        with pytest.raises(ModelNotFoundError):
            reg.predict("f64m", onp.zeros((8,), "float32"), timeout=10)
        assert gate.findings_total().value(rule="H001") > before
        # a RETRIED load of the same model must be refused again — the
        # refusal evicts the executables from aot.CACHE, so the second
        # warm re-inserts (artifact load or recompile) and re-gates
        # rather than cache-HITting past the gate with nothing to lint
        reg.load("f64m", _F64Servable("hlolint-f64-refuse"),
                 warm_spec=[((8,), "float32")])
        desc = [m for m in reg.models() if m["name"] == "f64m"][0]
        assert desc["current_version"] is None
        assert desc["degraded"] and "H001" in desc["degraded"]
        with pytest.raises(ModelNotFoundError):
            reg.predict("f64m", onp.zeros((8,), "float32"), timeout=10)
    finally:
        reg.close()


def test_registry_gate_off_routes_the_same_artifact(tmp_path,
                                                    monkeypatch):
    """MXTPU_HLOLINT_GATE=0 is the operator escape hatch: the identical
    fp64 servable loads, routes, and serves."""
    import numpy as onp
    from incubator_mxnet_tpu.serving import ModelRegistry
    monkeypatch.setenv("MXTPU_AOT_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("MXTPU_HLOLINT_GATE", "0")
    reg = ModelRegistry()
    try:
        reg.load("f64ok", _F64Servable("hlolint-f64-gateoff"), warm_spec=[((8,), "float32")],
                 max_batch_size=2, batch_timeout_ms=1.0)
        desc = [m for m in reg.models() if m["name"] == "f64ok"][0]
        assert desc["current_version"] == 1 and desc["degraded"] is None
        out = reg.predict("f64ok", onp.ones((8,), "float32"), timeout=10)
        assert out[0].shape == (8,)
        assert reg.health()["status"] == "healthy"
    finally:
        reg.close()


def test_registry_hot_reload_keeps_old_version_on_refusal(tmp_path,
                                                          monkeypatch):
    """Refusing a hot reload must leave the PREVIOUS version serving —
    the cutover is what gets refused, not the model."""
    import numpy as onp
    from incubator_mxnet_tpu.serving import ModelRegistry
    monkeypatch.setenv("MXTPU_AOT_CACHE_DIR", str(tmp_path))

    class Echo:
        def predict_batch(self, x):
            return (x + 1.0,)

    reg = ModelRegistry()
    try:
        v1 = reg.load("mixed", Echo(), max_batch_size=2,
                      batch_timeout_ms=1.0)
        reg.load("mixed", _F64Servable("hlolint-f64-reload"),
                 warm_spec=[((8,), "float32")])
        desc = [m for m in reg.models() if m["name"] == "mixed"][0]
        assert desc["current_version"] == v1
        assert desc["degraded"] and "H001" in desc["degraded"]
        out = reg.predict("mixed", onp.zeros((8,), "float32"),
                          timeout=10)
        assert float(out[0][0]) == 1.0        # still the Echo servable
        # a clean reload clears the degraded flag
        reg.load("mixed", Echo())
        desc = [m for m in reg.models() if m["name"] == "mixed"][0]
        assert desc["degraded"] is None
    finally:
        reg.close()
