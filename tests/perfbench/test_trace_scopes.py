"""perfbench/trace/scopes.py and perfbench/scope_shares.py: from the compiled
step's HLO (its text, or the protos a capture carries) and a capture's
device events to seconds per scope class; held to a capture recorded on the
chip (trace/scope_fixtures/) and to a synthetic program that has one
instruction for every row of the class table and of the rule for fusions."""
import gzip
import importlib
import json
import os

import pytest

from perfbench_helpers import PERFBENCH

FIXTURES = os.path.join(PERFBENCH, "trace", "scope_fixtures")
CELL = "cerebras-gpt-1.3b.train-s16k"
CAPTURE = os.path.join(FIXTURES, CELL + ".xplane.pb.gz")

LAYER = "jit(step_fn)/jvp(net0)/net0_transformerencoderlayer0"
BACK = "jit(step_fn)/transpose(jvp(net0))/net0_transformerdecoderlayer1"
ATTN = "/net0_transformerencoderlayer0_multiheadattention0"

#: an optimised module in the form `compiled.as_text()` prints
TEXT = """HloModule jit_step_fn, is_scheduled=true

%fused_computation.1 (p0: bf16[8,8]) -> bf16[8,8] {
  %p0 = bf16[8,8]{1,0} parameter(0)
  %convolution.1 = bf16[8,8]{1,0} convolution(%p0, %p0), dim_labels=bf_io->bf, metadata={op_name="LAYERATTN/net0_dense0/dot_general" stack_frame_id=3}
  ROOT %add.1 = bf16[8,8]{1,0} add(%convolution.1, %p0), metadata={op_name="LAYER/add"}
}

%fused_computation.2 (p0: bf16[8,8]) -> (bf16[8,8], f32[8,8]) {
  %p0.1 = bf16[8,8]{1,0} parameter(0)
  %convolution.2 = bf16[8,8]{1,0} convolution(%p0.1, %p0.1), dim_labels=bf_io->bf, metadata={op_name="BACK/ffn/net0_dense1/dot_general"}
  %convert.2 = f32[8,8]{1,0} convert(%convolution.2), metadata={op_name="jit(step_fn)/optimizer/convert_element_type"}
  ROOT %tuple.2 = (bf16[8,8]{1,0}, f32[8,8]{1,0}) tuple(%convolution.2, %convert.2)
}

%fused_computation.3 (p0: bf16[8,8]) -> bf16[8,8] {
  %p0.2 = bf16[8,8]{1,0} parameter(0)
  %constant.3 = bf16[] constant(0), metadata={op_name="jit(step_fn)/optimizer/convert_element_type"}
  %convolution.3 = bf16[8,8]{1,0} convolution(%p0.2, %p0.2), dim_labels=bf_io->bf, metadata={op_name="BACK/ffn/net0_dense0/dot_general"}
  ROOT %maximum.3 = bf16[8,8]{1,0} maximum(%convolution.3, %constant.3), metadata={op_name="BACK/ffn/max"}
}

%fused_computation.4 (p0: bf16[8,8]) -> bf16[8,8] {
  %p0.3 = bf16[8,8]{1,0} parameter(0)
  ROOT %fusion.30 = bf16[8,8]{1,0} fusion(%p0.3), kind=kOutput, calls=%fused_computation.3
}

%fused_computation.5 (p0: f32[8,8]) -> f32[8] {
  %p0.4 = f32[8,8]{1,0} parameter(0)
  ROOT %reduce.5 = f32[8]{0} reduce(%p0.4, %p0.4), dimensions={1}, to_apply=%region_0.1, metadata={op_name="LAYER/net0_transformerencoderlayer0_layernorm0/jit(_var)/reduce_sum"}
}

ENTRY %main.9 (a: bf16[8,8]) -> bf16[8,8] {
  %a = bf16[8,8]{1,0} parameter(0), metadata={op_name="t_datas[0]"}
  %fusion.1 = bf16[8,8]{1,0} fusion(%a), kind=kOutput, calls=%fused_computation.1, metadata={op_name="LAYER/add"}
  %fusion.2 = (bf16[8,8]{1,0}, f32[8,8]{1,0}) fusion(%a), kind=kOutput, calls=%fused_computation.2, metadata={op_name="BACK/ffn/net0_dense1/dot_general"}
  %fusion.3 = bf16[8,8]{1,0} fusion(%a), kind=kOutput, calls=%fused_computation.3, metadata={op_name="BACK/ffn/max"}
  %fusion.4 = bf16[8,8]{1,0} fusion(%a), kind=kOutput, calls=%fused_computation.4
  %fusion.5 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.5
  %flash_bwd_dq.1 = f32[8,8]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="BACKATTN/flash_bwd_dq/pallas_call"}
  %multiply.6 = f32[8,8]{1,0} multiply(%a, %a), metadata={op_name="jit(step_fn)/optimizer/mul;jit(step_fn)/broadcast_in_dim"}
  %gather.7 = bf16[8,8]{1,0} gather(%a, %a), metadata={op_name="jit(step_fn)/jvp(net0)/net0_embedding0/jit(_take)/gather"}
  %add.7 = bf16[8,8]{1,0} add(%a, %a), metadata={op_name="jit(step_fn)/jvp(net0)/add"}
  %while.8 = bf16[8,8]{1,0} while(%a), condition=%cond, body=%body, metadata={op_name="jit(step_fn)/transpose(jvp(loss))/while"}
  %broadcast.9 = bf16[8,8]{1,0} broadcast(%a), dimensions={}, metadata={op_name="jit(step_fn)/broadcast_in_dim"}
  %rng.10 = u32[2]{0} rng-bit-generator(%a), metadata={op_name="jit(step_fn)/jit(_threefry_split)/threefry2x32"}
  ROOT %copy.11 = bf16[8,8]{1,0} copy(%a)
}
""".replace("LAYERATTN", LAYER + ATTN).replace("LAYER", LAYER) \
    .replace("BACKATTN", BACK + "/net0_transformerdecoderlayer1_"
             "multiheadattention0").replace("BACK", BACK)

#: (event text as the profiler names it, the class it must get)
EVENTS = [
    # the convolution's scope, not the residual add fused behind it
    ("%fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %a), kind=kOutput, "
     "calls=%fused_computation.1", "attn_block"),
    # a weight gradient with its update fused behind: before `optimizer`
    ("%fusion.2 = (bf16[8,8]{1,0}, f32[8,8]{1,0}) fusion(bf16[8,8]{1,0} %a),"
     " kind=kOutput, calls=%fused_computation.2", "update_fused_matmul"),
    # a constant XLA shares with the optimizer does not make it an update
    ("%fusion.3 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %a), kind=kOutput, "
     "calls=%fused_computation.3", "mlp_block"),
    # the convolution sits in a fusion nested in the one that ran
    ("%fusion.4 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %a), kind=kOutput, "
     "calls=%fused_computation.4", "mlp_block"),
    # no metadata of its own: its fused root's
    ("%fusion.5 = f32[8]{0} fusion(bf16[8,8]{1,0} %a), kind=kLoop, "
     "calls=%fused_computation.5", "norm_residual"),
    ("%flash_bwd_dq.1 = f32[8,8]{1,0} custom-call(bf16[8,8]{1,0} %a), "
     "custom_call_target=\"tpu_custom_call\"", "attn_block"),
    ("%multiply.6 = f32[8,8]{1,0} multiply(bf16[8,8]{1,0} %a, "
     "bf16[8,8]{1,0} %a)", "optimizer"),
    ("%gather.7 = bf16[8,8]{1,0} gather(bf16[8,8]{1,0} %a, bf16[8,8]{1,0} "
     "%a)", "embed_head_loss"),
    ("%add.7 = bf16[8,8]{1,0} add(bf16[8,8]{1,0} %a, bf16[8,8]{1,0} %a)",
     "embed_head_loss"),
    ("%while.8 = bf16[8,8]{1,0} while(bf16[8,8]{1,0} %a), condition=%cond, "
     "body=%body", "embed_head_loss"),
    ("%broadcast.9 = bf16[8,8]{1,0} broadcast(bf16[8,8]{1,0} %a), "
     "dimensions={}", "unscoped"),
    ("%rng.10 = u32[2]{0} rng-bit-generator(bf16[8,8]{1,0} %a)",
     "unscoped"),
    ("%copy.11 = bf16[8,8]{1,0} copy(bf16[8,8]{1,0} %a)", "unscoped"),
    # an instruction the program does not have
    ("%fusion.99 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %a), kind=kOutput, "
     "calls=%fused_computation.77", "unscoped"),
]


@pytest.fixture(scope="module")
def shares():
    """perfbench/scope_shares.py, imported as the layer metrics import it
    (perfbench/ is on sys.path), so that there is one module of it."""
    return importlib.import_module("scope_shares")


@pytest.fixture(scope="module")
def scopes(shares):
    return shares.scopes


@pytest.fixture(scope="module")
def program(scopes):
    return scopes.program_from_text(TEXT)


@pytest.fixture(scope="module")
def recorded(shares):
    """(the reduction of the recorded capture, the programs it carries)"""
    return (shares.reduce.reduce_capture(CAPTURE),
            shares.scopes.programs_from_capture(
                shares.scopes.read_capture_bytes(CAPTURE)))


@pytest.fixture(scope="module")
def expected():
    with open(os.path.join(FIXTURES, CELL + ".scopes.expected.json")) as f:
        return json.load(f)


def test_text_parser_reads_the_module(program):
    assert program.name == "main.9" and len(program.members) == 6
    assert program.roots["fused_computation.2"] == "tuple.2"
    assert program.instrs["tuple.2"].operands == ("convolution.2",
                                                  "convert.2")
    fusion = program.instrs["fusion.4"]
    assert fusion.opcode == "fusion" and fusion.op_name == ""
    assert fusion.calls == ("fused_computation.4",)
    assert program.matmul_inside("fusion.4").op_name.endswith(
        "net0_dense0/dot_general")
    assert program.matmul_inside("fusion.5") is None


@pytest.mark.parametrize("text,want", EVENTS,
                         ids=[e[0].split(" ")[0] for e in EVENTS])
def test_every_row_of_the_class_table(scopes, program, text, want):
    assert scopes.event_class(program, text) == want


def test_every_class_is_exercised():
    assert {want for _, want in EVENTS} == {
        "attn_block", "mlp_block", "norm_residual", "embed_head_loss",
        "optimizer", "update_fused_matmul", "unscoped"}


@pytest.mark.parametrize("op_name,want", [
    ("jit(step_fn)/jvp(checkpoint)/net0/net0_transformerencoderlayer3/ffn/"
     "net0_dense1/add", "mlp_block"),
    ("jit(step_fn)/transpose(jvp(featuresview0))/gptmodel0_layernorm0/"
     "jit(_var)/mul", "embed_head_loss"),
    ("jit(step_fn)/jvp(loss)/while/body/closed_call/dot_general",
     "embed_head_loss"),
    ("jit(step_fn)/jvp()/reduce_sum", "unscoped"),
    ("jit(step_fn)/optimizer/net0_transformerencoderlayer3/mul",
     "optimizer"),
    ("", "unscoped"), ("t_datas[0]", "unscoped")])
def test_scope_class_of_an_op_name(scopes, op_name, want):
    assert scopes.scope_class(op_name) == want


def test_seconds_by_class_keep_the_total(scopes, program):
    ops = [[text, "any", 0.25] for text, _ in EVENTS]
    seconds = scopes.seconds_by_class(program, ops)
    assert set(seconds) == set(scopes.CLASSES)
    assert sum(seconds.values()) == pytest.approx(0.25 * len(EVENTS))
    assert seconds["mlp_block"] == 0.5 and seconds["unscoped"] == 1.0


def test_a_program_without_scopes_reads_as_absent(shares, scopes):
    old = scopes.program_from_text(TEXT.replace("optimizer", "x")
                                   .replace("jvp(", "jvp(jit("))
    assert not scopes.has_scopes(old)
    ops = [[text, "any", 0.25] for text, _ in EVENTS]
    assert shares.by_class(ops, [old]) is None
    assert shares.by_class(ops, []) is None


# ------------------------------------------------------------ recorded
def test_recorded_capture_reproduces_its_classes(shares, recorded, expected):
    reduced, programs = recorded
    assert len(reduced["ops"]) == expected["instructions"]
    assert reduced["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-9)
    program = shares.scopes.pick_program(programs, reduced["ops"])
    assert program.name == "jit_step_fn"
    seconds = shares.by_class(reduced["ops"], programs)
    assert seconds == pytest.approx(expected["seconds"], rel=1e-9)
    # every op's self time lands in exactly one class
    assert sum(seconds.values()) == pytest.approx(reduced["busy_s"],
                                                  rel=1e-9)


def test_text_and_capture_routes_agree(shares, recorded, expected):
    with gzip.open(os.path.join(FIXTURES, CELL + ".hlo.txt.gz"), "rt") as f:
        from_text = shares.scopes.program_from_text(f.read())
    reduced, programs = recorded
    from_capture = shares.scopes.pick_program(programs, reduced["ops"])
    assert set(from_text.instrs) == set(from_capture.instrs)
    assert shares.by_class(reduced["ops"], [from_text]) == pytest.approx(
        expected["seconds"], rel=1e-9)
    for kernel in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"):
        named = [i for n, i in from_capture.instrs.items()
                 if n.split(".")[0] == kernel]
        assert named and all(i.opcode == "custom-call" and
                             "/%s/pallas_call" % kernel in i.op_name
                             for i in named)


def metric_names(bench):
    """The shares this PR added: the metrics whose file reads
    scope_shares.py, but for the one that reads the host plane."""
    names = []
    for m in bench["per_layer"]:
        with open(os.path.join(PERFBENCH, "layer_metrics",
                               m["name"] + ".py")) as f:
            if "scope_shares." in f.read() and m["unit"] == "%":
                names.append(m["name"])
    return names


def test_layer_metrics_read_the_recorded_capture(harness, bench, shares,
                                                 recorded, expected,
                                                 monkeypatch, capsys):
    """Each metric's file, as run.py calls it; the capture is found by
    `newest_capture`, pointed at the fixture here."""
    monkeypatch.setattr(shares, "newest_capture", lambda root=None: CAPTURE)
    context = {"trace": recorded[0]}
    got = {name: harness.load_module("layer_metrics", name).compute(context)
           for name in metric_names(bench)}
    want = dict(expected["shares"],
                flash_bwd_time_share=expected["flash_bwd_time_share"])
    assert got == pytest.approx(want, rel=1e-9)
    classes = [got[c + "_time_share"] for c in shares.scopes.CLASSES]
    assert sum(classes) == pytest.approx(100.0, abs=1e-6)
    assert got["unscoped_time_share"] < 5.0
    dispatch = harness.load_module(
        "layer_metrics", "train_dispatch_ms_per_step").compute(context)
    assert dispatch == pytest.approx(expected["train_dispatch_ms_per_step"])
    line = [l for l in capsys.readouterr().out.splitlines()
            if l.startswith("idle gaps of the idlest chip")]
    assert len(line) == 1
    assert json.loads(line[0].split(": ", 1)[1]) == expected["idle_gaps"]


def test_new_metrics_list_the_cells_they_can_be_trusted_in(bench):
    """By scope: the GPT cell only. JAX's compile cache leaves metadata
    out of its key, so where a program differs from its parent's in scope
    names alone (the BERT cells' step) a run is handed whichever executable
    was compiled first, with or without names (PERF.md, section 7)."""
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in metric_names(bench):
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["source"] == "device_trace"
    dispatch = by_name["train_dispatch_ms_per_step"]
    assert dispatch["source"] == "program_span"
    assert dispatch["workloads"] == sorted(
        w["name"] for w in bench["workloads"])


@pytest.mark.parametrize("context", [
    {"trace": None}, {"trace": {"busy_s": 0.0, "ops": []}}],
    ids=["untraced", "no-device-plane"])
def test_no_trace_reads_as_absent(harness, bench, context):
    for name in metric_names(bench) + ["train_dispatch_ms_per_step"]:
        if name == "train_dispatch_ms_per_step" and context["trace"]:
            continue        # it reads the host plane, not the reduction
        assert harness.load_module("layer_metrics", name).compute(
            context) is None, name


def test_host_spans_and_gaps_of_the_recorded_capture(shares, expected):
    spans, window, busy = shares.host_view(CAPTURE)
    names = {n for n, _, _ in spans}
    assert {"train:step", "train:host_transfer", "train:schedule",
            "train:dispatch", "bench:step_call", "bench:block"} <= names
    # the program's spans sit inside the benchmark's call into the step
    calls = [(s, e) for n, s, e in spans if n == "bench:step_call"]
    for n, s, e in spans:
        if n in ("train:step", "train:schedule", "train:dispatch"):
            assert any(cs <= s and e <= ce for cs, ce in calls), n
    assert (window[1] - window[0]) / 1e9 == expected["window_s"]
    assert shares.idle_gaps(CAPTURE, top=3) == expected["idle_gaps"][:3]


def test_newest_capture_goes_by_time_not_by_name(shares, tmp_path):
    assert shares.newest_capture(str(tmp_path)) is None
    for i, cell in enumerate(["b-cell", "a-cell"]):
        d = tmp_path / cell / "plugins" / "profile" / "t"
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_bytes(b"")
        os.utime(d / "host.xplane.pb", (1000 + i, 1000 + i))
    assert "a-cell" in shares.newest_capture(str(tmp_path))


def test_protos_of_a_cpu_capture(shares, tmp_path):
    """The wire-format reader on a capture made here: the profiler stores
    every program that ran, scope names and fused computations included."""
    import jax
    import jax.numpy as jnp

    def step(x, w):
        with jax.named_scope("net0_transformerencoderlayer0"):
            with jax.named_scope("ffn"):
                y = jnp.tanh(x @ w)
        return y.sum()

    grad = jax.jit(jax.grad(step, argnums=1))
    x = jnp.ones((32, 32))
    grad(x, x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        grad(x, x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    programs = shares.scopes.programs_from_capture(
        shares.scopes.read_capture_bytes(str(tmp_path)))
    mine = [p for p in programs if p.name == "jit_step"]
    assert len(mine) == 1
    dots = [i for i in mine[0].instrs.values() if i.opcode == "dot"]
    assert dots and all(
        shares.scopes.scope_class(i.op_name) == "mlp_block" for i in dots)
    fused = [n for n, i in mine[0].instrs.items() if i.opcode == "fusion"]
    assert all(mine[0].roots_of(n) for n in fused)
