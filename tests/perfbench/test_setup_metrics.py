"""The eight `setup_*` per-layer metrics (PR 36): each reader reads the
program's own registry or span ring through `perfbench/setup_phases.py`;
values come from what the program recorded, the printed line parses, a
program without the counters reads None (the parent of the PR that brought
them) and this one never does."""
import json

import pytest
from jax import monitoring

from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.ops import kernel_trace
from incubator_mxnet_tpu.telemetry import spans

from perfbench_helpers import load_by_path

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"

METRICS = {
    "setup_import_s": ("program_counter", "entry and executable cache"),
    "setup_init_s": ("program_span", "entry and executable cache"),
    "setup_trace_s": ("program_counter", "entry and executable cache"),
    "setup_lower_s": ("program_counter", "entry and executable cache"),
    "setup_backend_compile_s": ("program_counter",
                                "entry and executable cache"),
    "setup_cache_read_s": ("program_counter", "entry and executable cache"),
    "setup_kernel_trace_s": ("program_counter", "attention kernels"),
    "setup_unowned_compile_s": ("program_counter",
                                "entry and executable cache"),
}


@pytest.fixture(scope="module")
def phases():
    import setup_phases        # perfbench/ is on sys.path (perfbench_helpers)
    return setup_phases


def reader(name):
    return load_by_path("perfbench_layer_" + name, "layer_metrics",
                        name + ".py")


def jax_event(event, start, end, inside=None):
    monitoring.record_scalar(event, start, fun_name="f")
    if inside is not None:
        inside()
    monitoring.record_event_duration_secs(event, end - start, fun_name="f")
    monitoring.record_event_time_span(event, start, end, fun_name="f")


GROWTH = {"setup_trace_s": 3.25, "setup_lower_s": 2.0,
          "setup_cache_read_s": 1.0, "setup_backend_compile_s": 4.0,
          "setup_unowned_compile_s": 0.75, "setup_kernel_trace_s": 0.125}


def feed_setup():
    """A known set-up, on top of whatever the process had: the metrics grow
    by GROWTH."""
    with spans.span("gluon:initialize", params=3):
        jax_event(COMPILE, 1.0, 1.5)              # inside init, not a build
    with spans.span("train:step"):
        with spans.span("train:init_states"):
            pass
        with spans.span("train:build"):
            jax_event(TRACE, 10.0, 13.0, inside=lambda: jax_event(
                TRACE, 10.5, 11.0))               # nested: no seconds more
            jax_event(LOWER, 13.0, 15.0)
            jax_event(COMPILE, 15.0, 16.0,
                      inside=lambda: monitoring.record_event(HIT))
    with spans.span("eval:build"):
        jax_event(TRACE, 20.0, 20.25)
        jax_event(COMPILE, 21.0, 25.0)            # no hit: a real compile
    jax_event(TRACE, 30.0, 30.5)                  # the reference's: no span
    jax_event(LOWER, 30.5, 30.75)
    kernel_trace._SECONDS.inc(0.125, kernel="flash_fwd")
    kernel_trace._TRACES.inc(kernel="flash_fwd")


@pytest.fixture
def import_gauge():
    """The gauge with known parts (another test's telemetry.reset() may
    have dropped what the package's import set), put back afterwards."""
    gauge = telemetry.REGISTRY.get("mxtpu_import_seconds")
    saved = gauge.series()
    telemetry.setup_phases.record_import(5.5, 3.25)
    yield {"modules": 2.25, "backend": 3.25}
    for labels, v in saved:
        gauge.set(v, **labels)


@pytest.fixture
def filled(import_gauge):
    spans.reset()
    feed_setup()
    yield
    spans.reset()


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_entry_is_the_issues(bench, name):
    entry = {m["name"]: m for m in bench["per_layer"]}[name]
    source, layer = METRICS[name]
    assert entry == {"name": name, "unit": "s", "better": "lower",
                     "source": source, "layer": layer, "moves": "setup_s"}


def test_the_eight_are_appended_and_every_cell_reports_them(bench, harness):
    assert [m["name"] for m in bench["per_layer"]][-8:] == [
        "setup_import_s", "setup_init_s", "setup_trace_s", "setup_lower_s",
        "setup_backend_compile_s", "setup_cache_read_s",
        "setup_kernel_trace_s", "setup_unowned_compile_s"]
    for cell in bench["workloads"]:
        listed = {m["name"] for m in harness.metrics_of(
            bench, "per_layer", cell["name"])}
        assert set(METRICS) <= listed


def test_values_from_a_filled_registry(phases, capsys):
    before = {n: reader(n).compute({}) for n in GROWTH}
    feed_setup()
    after = {n: reader(n).compute({}) for n in GROWTH}
    capsys.readouterr()
    assert {n: after[n] - before[n] for n in GROWTH} \
        == pytest.approx(GROWTH)


def test_the_table_of_a_zeroed_registry_is_exact(phases):
    families = ("mxtpu_compile_phase_seconds_total",
                "mxtpu_compile_phase_events_total",
                "mxtpu_compile_cache_total",
                "mxtpu_kernel_trace_seconds_total",
                "mxtpu_kernel_traces_total")
    saved = {name: dict(telemetry.REGISTRY.get(name)._series)
             for name in families}
    try:
        for name in families:
            telemetry.REGISTRY.get(name)._series.clear()
        spans.reset()
        feed_setup()
        table = phases.table()
        assert table["phases"] == {
            "train:build": {"trace": [3.0, 2], "lower": [2.0, 1],
                            "cache_read": [1.0, 1]},
            "eval:build": {"trace": [0.25, 1],
                           "backend_compile": [4.0, 1]},
            "gluon:initialize": {"backend_compile": [0.5, 1]},
            "other": {"trace": [0.5, 1], "lower": [0.25, 1]}}
        assert table["cache"] == {"train:build": {"hit": 1}}
        assert table["kernels"] == {"flash_fwd": [0.125, 1]}
    finally:
        spans.reset()
        for name, series in saved.items():
            metric = telemetry.REGISTRY.get(name)
            metric._series.clear()
            metric._series.update(series)


def test_the_init_metric_reads_the_three_spans(phases):
    def at(name, us):
        spans.record_span(name, 0.0, us)

    before = reader("setup_init_s").compute({})
    at("gluon:initialize", 2e6)
    at("gluon:cast", 0.5e6)
    at("train:init_states", 1e6)
    at("train:build", 64e6)                     # not an initialisation
    assert reader("setup_init_s").compute({}) - before \
        == pytest.approx(3.5)


def test_the_import_metric_is_both_parts(phases, import_gauge):
    assert dict((labels["part"], v) for labels, v in
                phases.series("mxtpu_import_seconds")) == import_gauge
    assert reader("setup_import_s").compute({}) == 5.5


def test_the_printed_line_parses_and_holds_the_whole_split(filled, capsys):
    value = reader("setup_trace_s").compute({})
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1, "ONE line"
    head, _, body = out[0].partition(": ")
    assert head == "set-up as the program saw it"
    table = json.loads(body)
    assert {"phases", "cache", "kernels", "import", "init_spans",
            "first_calls"} <= set(table)
    seconds, events = table["phases"]["train:build"]["trace"]
    assert events >= 2 and seconds >= 3.0
    assert table["cache"]["train:build"]["hit"] >= 1
    assert table["kernels"]["flash_fwd"][1] >= 1
    assert set(table["import"]) == {"modules", "backend"}
    assert value >= 3.25
    # where the first call's time went: train:step built, so it is listed
    # with its build, each with what lies outside its children
    listed = {c["span"]: c for c in table["first_calls"]}
    assert {"train:step", "train:build", "eval:build"} <= set(listed)
    assert set(listed["train:build"]["children"]) == {
        "train:trace", "train:lower", "train:cache_read"}
    assert listed["train:step"]["outside_children_s"] >= 0.0


def test_children_of_one_name_that_overlap_count_once(phases):
    parent = {"span_id": "p", "start_us": 0.0, "dur_us": 10e6}
    records = [
        {"span_id": "a", "parent_id": "p", "name": "train:trace",
         "start_us": 1e6, "dur_us": 4e6},
        {"span_id": "b", "parent_id": "p", "name": "train:trace",
         "start_us": 2e6, "dur_us": 1e6},       # inside the first
        {"span_id": "c", "parent_id": "p", "name": "train:lower",
         "start_us": 5e6, "dur_us": 2e6},
        {"span_id": "d", "parent_id": "p", "name": "train:compile",
         "start_us": 0.0, "dur_us": 10e6},      # the lump: no part
        {"span_id": "e", "parent_id": "x", "name": "train:layout",
         "start_us": 0.0, "dur_us": 9e6},       # another parent's
    ]
    kids, outside = phases.children(records, parent)
    assert kids == {"train:trace": 4.0, "train:lower": 2.0}
    assert outside == pytest.approx(4.0)


def test_never_none_with_this_program_and_none_without_the_counters(
        phases, monkeypatch, capsys):
    for name in METRICS:
        value = reader(name).compute({})
        assert isinstance(value, float) and value >= 0.0, name
    # a series nobody has written yet reads 0.0, not None
    assert phases.total("mxtpu_compile_phase_seconds_total",
                        phase="cache_read", owner="nobody") == 0.0
    # the parent of this PR has no such family: the metric is left out,
    # nothing raises, and the table still prints
    missing = type(telemetry.REGISTRY)()
    monkeypatch.setattr(telemetry, "REGISTRY", missing)
    monkeypatch.delattr(telemetry, "setup_phases")
    for name in METRICS:
        assert reader(name).compute({}) is None, name
    table = phases.table()
    assert table["phases"] == {} and table["first_calls"] == []
    capsys.readouterr()
