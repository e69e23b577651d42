"""`--rehearse` of every cell: the same control flow as on the chip, at the
tiny preset, in this process. The last line holds exactly the contract's
keys, no metric and the device as cpu; `correct` includes the agreement of
the tiny-preset system with the configuration's float32 reference."""
import json

import pytest

from perfbench_helpers import cell_names

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def rehearse(harness, monkeypatch, capsys, cell, trace):
    # run.main() sets these itself; setting them here first lets
    # monkeypatch restore them, so other test files see no change
    for var in ("JAX_PLATFORMS", "MXTPU_FLASH_INTERPRET"):
        monkeypatch.setenv(var, "1" if var.startswith("MXTPU") else "cpu")
    harness.main(["--workload", cell, "--rehearse", "--seed", "3",
                  "--seconds", "0.5", "--trace", str(trace)])
    out = capsys.readouterr().out.strip().splitlines()
    return out, json.loads(out[-1])


@pytest.mark.parametrize("cell", cell_names())
def test_rehearsal_ends_with_the_contracts_line(harness, monkeypatch, capsys,
                                                cell):
    out, line = rehearse(harness, monkeypatch, capsys, cell, trace=0)
    assert set(line) == KEYS
    assert line["correct"] is True, "\n".join(out)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"] == {}
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert any("agreement with reference/" in l and l.endswith(": ok")
               for l in out)


def test_traced_rehearsal_captures_and_reduces(harness, monkeypatch, capsys,
                                               tmp_path):
    """The capture and the reduction run on the CPU too; a capture with no
    device plane reduces to nothing, and the line then has no breakdown."""
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))   # tens of MB
    out, line = rehearse(harness, monkeypatch, capsys,
                         "cerebras-gpt-1.3b.train-s16k", trace=1)
    assert set(line) == KEYS and line["correct"] is True, "\n".join(out)


def test_unknown_cell_is_refused(harness):
    with pytest.raises(SystemExit):
        harness.main(["--workload", "no-such-cell", "--rehearse"])


def test_a_cpu_is_not_measured(harness):
    """Without --rehearse the run measures the chip: here, on the CPU, it
    ends with a non-zero code and prints no result."""
    with pytest.raises(SystemExit) as e:
        harness.main(["--workload", cell_names()[0]])
    assert e.value.code not in (0, None)


def test_update_agreement_tells_a_wrong_gradient(harness):
    """The check of the first update: weights that moved against the
    reference gradient count with that gradient's magnitude, per checked
    parameter, so one wrong gradient cannot hide behind a larger right one."""
    import numpy as np
    driver = harness.load_module("drivers", "train_step")
    rng = np.random.default_rng(0)
    g = {"q": 1e-3 * rng.standard_normal((64, 64)),
         "v": rng.standard_normal((64, 64))}
    before = {n: rng.standard_normal((64, 64)) for n in g}
    right = {n: before[n] - 1e-4 * np.sign(g[n]) for n in g}
    right["v"][:16] = before["v"][:16]              # too coarse to move
    got = driver.update_agreement(before, right, g)
    assert got["q"] == (1.0, 1.0) and got["v"] == (1.0, 0.75)
    unrelated = dict(right, q=before["q"] - 1e-4 * np.sign(
        rng.standard_normal((64, 64))))
    got = driver.update_agreement(before, unrelated, g)
    assert 0.4 < got["q"][0] < 0.6 and got["v"][0] == 1.0
    flipped = dict(right, q=before["q"] + 1e-4 * np.sign(g["q"]))
    assert driver.update_agreement(before, flipped, g)["q"][0] == 0.0
