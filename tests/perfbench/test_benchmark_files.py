"""BENCHMARK.json and the files it names: the contract's letters, every
cell's files resolve, the yardstick agrees with itself and with the
program's peak table."""
import json
import os
import re

import numpy as np
import pytest

from perfbench_helpers import PERFBENCH, ROOT, cell_names

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    assert all(w["chips"] in (1, 4) for w in bench["workloads"])


def test_names_units_and_keys(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for group, key in (("configs", "name"), ("workloads", "name"),
                       ("workloads", "config"), ("workloads", "traffic")):
        for entry in bench[group]:
            assert NAME.match(entry[key]), entry[key]
            assert 1 <= len(entry["why"]) <= 200, (entry["name"],
                                                   len(entry["why"]))
    cells = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(cells)) == len(cells)


def test_every_cell_reports_what_the_contract_asks(bench, harness):
    for name in cell_names():
        e2e = [m["name"] for m in harness.metrics_of(bench, "end_to_end",
                                                     name)]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = harness.metrics_of(bench, "per_layer", name)
        assert layer and all(m["moves"] in e2e for m in layer)


def test_configs_resolve_and_state_their_cuts(bench):
    used = {w["config"] for w in bench["workloads"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    for c in bench["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not re.search(r"_dim$|_rank$|hidden|inner|embd|head",
                                 key), "a width may not be reduced: " + key
            assert cfg["reduced_from"][key] != cfg[key]
        assert os.path.isfile(os.path.join(
            PERFBENCH, "builders", cfg["builder"] + ".py"))
        assert os.path.isfile(os.path.join(
            PERFBENCH, "reference", cfg["reference"] + ".py"))


def _numbers_only(real, tiny, where):
    """The tiny preset may change numbers, never a name or a switch: what
    is no number selects a code path."""
    assert set(tiny) <= set(real) | {"rehearse"}, where
    for key, value in tiny.items():
        if isinstance(value, dict):
            _numbers_only(real.get(key, {}), value, where + "." + key)
        elif isinstance(value, bool) or not isinstance(value, (int, float)):
            assert real.get(key) == value, where + "." + key


@pytest.mark.parametrize("cell", cell_names())
def test_cell_files_resolve(bench, harness, cell):
    """Nothing here knows a cell, a configuration family or a kind of
    traffic: a later PR's files pass or fail on the same terms."""
    entry, workload, config = harness.resolve(bench, cell, rehearse=False)
    for kind, name in (("drivers", workload["driver"]),
                       ("traffic", workload["traffic"]["generator"]),
                       ("builders", config["builder"]),
                       ("reference", config["reference"])):
        assert os.path.isfile(os.path.join(PERFBENCH, kind, name + ".py"))
    for m in harness.metrics_of(bench, "per_layer", cell):
        reader = harness.load_module("layer_metrics", m["name"])
        assert callable(reader.compute)
    if "mesh" in workload:
        assert np.prod(list(workload["mesh"]["axes"].values())) \
            == entry["chips"]
    assert workload["check"]["why"], "every limit is written with its reason"
    _numbers_only(workload, workload.get("rehearse", {}), cell)
    _numbers_only(config, config.get("rehearse", {}), entry["config"])


@pytest.mark.parametrize("cell", cell_names())
def test_model_arithmetic_is_the_builders(bench, harness, cell):
    """MFU and the kernels' roofline are worked out with the builder's own
    count of the operations its model requires: what the train_step driver
    asks of a builder (another driver states its own terms)."""
    _, workload, config = harness.resolve(bench, cell, rehearse=False)
    if workload["driver"] != "train_step":
        pytest.skip("not a train_step cell")
    builder = harness.load_module("builders", config["builder"])
    seq_len = workload["traffic"]["seq_len"]
    model = builder.model_flops_per_token(config, seq_len)
    attention = builder.attention_flops_per_token(config, seq_len)
    assert isinstance(model, int) and 0 < attention < model
    # twice the context, twice the attention and nothing else
    assert builder.model_flops_per_token(config, 2 * seq_len) - model \
        == attention


def test_every_file_under_paths_has_a_contract_name(bench):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in bench["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert ok.match(rel) and len(rel) <= 200, rel


def test_peaks_equal_the_programs_row():
    from incubator_mxnet_tpu.telemetry import devstats
    with open(os.path.join(PERFBENCH, "peaks.json")) as f:
        row = json.load(f)["device_kinds"]["TPU v5 lite"]
    assert (row["bf16_flops_per_s"], row["int8_ops_per_s"],
            row["hbm_bytes_per_s"]) == devstats.device_peaks("TPU v5 lite")
    assert row["hbm_bytes"] == devstats.HBM_TABLE["TPU v5 lite"]


def _same(a, b):
    """Items of any generator: arrays, numbers and strings in tuples,
    lists and dicts."""
    if isinstance(a, dict):
        return set(a) == set(b) and all(_same(a[k], b[k]) for k in a)
    if isinstance(a, (tuple, list)):
        return len(a) == len(b) and all(_same(x, y) for x, y in zip(a, b))
    return np.array_equal(a, b)


@pytest.mark.parametrize("cell", cell_names())
def test_traffic_is_a_function_of_the_seed(bench, harness, cell):
    _, workload, config = harness.resolve(bench, cell, rehearse=True)
    traffic = workload["traffic"]
    gen = harness.load_module("traffic", traffic["generator"])

    def take(seed, n=3):
        it = gen.generate(traffic, seed, config)
        return [next(it) for _ in range(n)]

    a, b, c = take(7), take(7), take(8)
    assert _same(a, b)
    assert not _same(a[0], c[0]), "another seed, other items"
    assert not _same(a[0], a[1]), "fresh items, not one repeated"
    # what the items must look like is the generator's own knowledge
    gen.self_check(traffic, config, a + c)


def test_a_generators_self_check_can_fail(bench, harness):
    _, workload, config = harness.resolve(bench, cell_names()[0],
                                          rehearse=True)
    traffic = workload["traffic"]
    gen = harness.load_module("traffic", traffic["generator"])
    tokens, labels = next(gen.generate(traffic, 7, config))
    with pytest.raises(AssertionError):
        gen.self_check(traffic, config, [(tokens, labels[:, ::-1] + 1)])


def test_flops_arithmetic_matches_the_issue(bench, harness):
    import flops
    # 6 x (24 x 12 U^2 + U^2 + V U) + 24 x 12 S U at BERT-large, S = 512
    assert flops.transformer_train_flops_per_token(
        1024, 4096, 24, 1024 * 1024 + 30522 * 1024, 512, causal=False) \
        == 6 * (24 * 12 * 1024 ** 2 + 1024 ** 2 + 30522 * 1024) \
        + 24 * 12 * 512 * 1024
    assert flops.attention_train_flops_per_token(2048, 16384, True) \
        == 6 * 16384 * 2048
    # and the two builders apply it to their configurations' own keys
    for cell, want in (("bert-large.train-s512", 2156752896),
                       ("cerebras-gpt-1.3b.train-s16k", 2630823936)):
        _, workload, config = harness.resolve(bench, cell, rehearse=False)
        builder = harness.load_module("builders", config["builder"])
        assert builder.model_flops_per_token(
            config, workload["traffic"]["seq_len"]) == want
