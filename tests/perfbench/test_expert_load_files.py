"""What PR 50 adds to the benchmark: four per-layer metrics that read the
rows each expert got out of the program's own `train:counters` records
(`perfbench/expert_load.py`, one `layer_metrics/` file each). The entries
are held here by MEMBERSHIP, never by position (every later metric is
appended behind them); each reader reads `None` on a program without the
channel and the expected value on a recorded ring of three steps."""
import json

import pytest

from incubator_mxnet_tpu import jit
from incubator_mxnet_tpu.telemetry import spans

from perfbench_helpers import load_by_path

HELD = {"nemotron-3-super.train-s8k", "solar-open2.train-s8k",
        "keye-vl-2.0.train-s16k", "ling-3.0-flash.train-s8k"}
CELLS = {"held_window_fill": HELD, "held_windows_per_pass": HELD,
         "expert_load_min_share": HELD | {"olmoe-1b-7b.train-s4k"},
         "held_expert_us_per_live_row": HELD}
UNITS = {"held_window_fill": ("%", "higher"),
         "held_windows_per_pass": ("count", "lower"),
         "expert_load_min_share": ("%", "higher"),
         "held_expert_us_per_live_row": ("us", "lower")}

#: two held layers (W = 100, even load 10 rows an expert) and one that holds
#: every expert, three steps: in the second the first layer needs a second
#: window, in the third its last expert starves
STEPS = [
    ([30, 30, 20, 20], [10, 10, 10, 10], [8, 12, 10, 10, 10, 10, 10, 10]),
    ([90, 40, 10, 10], [10, 10, 10, 10], [10] * 8),
    ([40, 30, 10, 0], [5, 15, 10, 10], [10] * 8),
]
WANT = {
    # (100 + 40) / 200, (150 + 40) / 300, (80 + 40) / 200 -> the median
    "held_window_fill": 100.0 * 190 / 300,
    # windows a held layer a step: 1 1, 2 1, 1 1
    "held_windows_per_pass": 7 / 6,
    # the worst layer's least loaded expert over 10 rows: 80, 100, 0
    "expert_load_min_share": 80.0,
    # 0.9 s under moe_experts over the 450 live rows of the held layers
    "held_expert_us_per_live_row": 0.9e6 / 450,
}


def _layer(name, values, held):
    return dict(name=name, values=values, held=held, even_rows=10.0,
                window_rows=100 if held else 80)


def record(step, first, second, whole):
    spans.record_span(
        "train:counters", 1000.0 * step, 50.0, step=step, counters=[
            _layer("net0_moelayer0", first, True),
            # a counter that is no expert layer's is passed over
            dict(name="net0_moelayer0:top_idx", values=[1, 2, 3]),
            _layer("net0_moelayer1", second, True),
            _layer("net0_moelayer2", whole, False)])


def reader(name):
    return load_by_path("perfbench_layer_" + name, "layer_metrics",
                        name + ".py")


def context(steps=3):
    # a traced run's, with moe_seconds as moe_shares.py leaves it there
    return {"steps": steps, "trace": {"busy_s": 3.0},
            "moe_seconds": {"block": 2.0, "moe_experts": 0.9}}


@pytest.fixture(autouse=True)
def no_capture(monkeypatch):
    """No capture on disk: the line's count of device events reads null."""
    import scope_shares        # perfbench/ is on sys.path (perfbench_helpers)
    monkeypatch.setattr(scope_shares, "newest_capture", lambda: None)


@pytest.fixture
def ring():
    jit.flush_step_counters()
    spans.reset()
    # an older step, outside the window's three
    record(1, [100, 0, 0, 0], [0, 0, 0, 0], [80, 0, 0, 0, 0, 0, 0, 0])
    for step, rows in enumerate(STEPS, 2):
        record(step, *rows)
    yield
    spans.reset()


@pytest.mark.parametrize("name", sorted(CELLS))
def test_the_entry_is_there_with_its_cells(bench, name):
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert set(entry["workloads"]) == CELLS[name]
    assert (entry["unit"], entry["better"]) == UNITS[name]
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "parallel"
    assert entry["moves"] == "train_tok_per_s"
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    cells = {w["name"] for w in bench["workloads"]}
    assert CELLS[name] <= cells


@pytest.mark.parametrize("name", sorted(CELLS))
def test_a_reader_reads_the_windows_steps_off_the_ring(ring, name, capsys):
    assert reader(name).compute(context()) == pytest.approx(WANT[name])
    (line,) = [l for l in capsys.readouterr().out.splitlines()
               if l.startswith("expert load as the program saw it: ")]
    seen = json.loads(line.split(": ", 1)[1])
    assert [s["step"] for s in seen["steps"]] == [2, 3, 4]
    assert [layer["name"] for layer in seen["layers"]] == [
        "net0_moelayer0", "net0_moelayer1", "net0_moelayer2"]
    assert seen["steps"][1] == {"step": 3, "rows": [150, 40, 80],
                                "windows": [2, 1, 1], "min": [10, 10, 10],
                                "starved": [0, 0, 0]}
    assert seen["steps"][2]["starved"] == [1, 0, 0]
    assert seen["grouped_matmul_events"] is None


def test_the_line_is_printed_once_a_run(ring, capsys):
    shared = context()
    for name in sorted(CELLS):
        reader(name).compute(shared)
    assert capsys.readouterr().out.count("expert load as the program") == 1


@pytest.mark.parametrize("name", sorted(CELLS))
def test_a_program_without_the_channel_reads_none(ring, name, monkeypatch,
                                                  capsys):
    """The parent of PR 50 has no `jit.flush_step_counters` and records no
    `train:counters`: every reader reads None and prints nothing."""
    monkeypatch.delattr(jit, "flush_step_counters")
    assert reader(name).compute(context()) is None
    monkeypatch.undo()
    spans.reset()                  # the channel, and a model with no experts
    assert reader(name).compute(context()) is None
    assert "expert load" not in capsys.readouterr().out


def test_only_held_layers_count_for_the_held_metrics(capsys):
    """OLMoE's cell: one layer that holds every expert reports the least
    loaded expert's share and none of the three held metrics."""
    jit.flush_step_counters()
    spans.reset()
    spans.record_span("train:counters", 0.0, 1.0, step=1, counters=[
        _layer("net0_moelayer0", [8, 12] + [10] * 6, False)])
    shared = context(steps=1)
    assert reader("expert_load_min_share").compute(shared) == 80.0
    for name in sorted(set(CELLS) - {"expert_load_min_share"}):
        assert reader(name).compute(shared) is None
    spans.reset()


def test_the_devices_own_count_of_grouped_matmuls_in_the_window(monkeypatch):
    """`ragged-dot-*` events by name inside `bench:traced_window`, chip 0:
    what the published windows are held against in a traced run."""
    import expert_load         # perfbench/ is on sys.path (perfbench_helpers)
    import moe_shares

    def call(name, start):
        return ("%%%s = bf16[8,8]{1,0} custom-call(bf16[8,8]{1,0} %%a), "
                "custom_call_target=\"tpu_custom_call\"" % name, start,
                start + 5)
    planes = ({"/device:TPU:0": {"ops": [
        call("ragged-dot-none.3", 90),            # before the window opens
        call("ragged-dot-none.3", 100), call("ragged-dot-none.7", 150),
        call("ragged-dot-metadata.1", 120), call("flash_fwd.2", 130),
        call("ragged-dot-none", 199), call("ragged-dot-none.3", 200)],
        "async": []},
        "/device:TPU:1": {"ops": [call("ragged-dot-none.3", 110)],
                          "async": []}},
        [("bench:step_call", 0, 300), ("bench:traced_window", 100, 200)])
    monkeypatch.setattr(moe_shares.reduce, "read_planes", lambda path: planes)
    assert expert_load.grouped_matmul_events("a capture") == {
        "ragged-dot-none": 3, "ragged-dot-metadata": 1}
    monkeypatch.setattr(moe_shares.reduce, "read_planes",
                        lambda path: (planes[0], []))
    assert expert_load.grouped_matmul_events("no window") is None
