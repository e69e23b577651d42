"""perfbench/trace/reduce.py: the interval arithmetic on hand-made planes,
and the whole reduction on the captures recorded on the chip (fixtures/):
busy/idle, op classes, collective overlap and gap attribution reproduce the
numbers recorded beside each capture."""
import glob
import json
import os

import pytest

from perfbench_helpers import PERFBENCH

FIXTURES = os.path.join(PERFBENCH, "trace", "fixtures")


def test_interval_arithmetic(reducer):
    merged = reducer.merge([(5, 7), (0, 2), (1, 3), (7, 7), (6, 9)])
    assert merged == [[0, 3], [5, 9]]
    assert reducer.length(merged) == 7
    assert reducer.gaps(merged, -1, 12) == [(-1, 0), (3, 5), (9, 12)]
    # of [0,3]+[5,9], what [2,6]+[8,20] leaves uncovered: [0,2] and [6,8]
    assert reducer.uncovered(merged, [[2, 6], [8, 20]]) == 4
    assert reducer.uncovered(merged, []) == 7
    assert reducer.uncovered([], merged) == 0


def hlo(name, opcode, out="bf16[8,128]{1,0:T(8,128)(2,1)}", rest=""):
    """An event name as the chip's captures have them: the instruction as
    the optimised HLO prints it."""
    return "%%%s = %s %s(bf16[8]{0:T(1024)(128)(2,1)S(1)} %%all-gather.9)%s" \
        % (name, out, opcode, rest)


MATMUL = hlo("fusion.1", "fusion", rest=", kind=kOutput, calls=%fused.1")
LOOP = hlo("fusion.2", "fusion", rest=", kind=kLoop, calls=%fused.2")
KERNEL = hlo("jvp__.4", "custom-call", rest=', custom_call_target="tpu_c"')
ALLREDUCE = hlo("all-reduce.1", "all-reduce", rest=", replica_groups={}")


def test_classes_come_from_the_instruction_not_its_operands(reducer):
    # every one of these names an %all-gather operand: that decides nothing
    assert reducer.classify(MATMUL) == "matmul"
    assert reducer.classify(LOOP) == "other"
    assert reducer.classify(KERNEL) == "pallas"
    assert reducer.classify(ALLREDUCE) == "collective"
    assert reducer.classify(hlo("all-gather-start.3", "all-gather-start",
                                out="(bf16[8], bf16[32])")) == "collective"
    assert reducer.classify(hlo("convolution.7", "convolution")) == "matmul"
    assert reducer.classify(hlo("while.8", "while")) == "container"
    # the breakdown sums instructions alike but for their number
    assert reducer.label(MATMUL) == "fusion kOutput -> bf16[8,128]"
    assert reducer.label(KERNEL) == "jvp__ custom-call -> bf16[8,128]"
    assert reducer.label(hlo("copy", "copy")) == "copy copy -> bf16[8,128]"


def test_self_time_of_nested_events(reducer):
    body = [(LOOP, 10, 30), (MATMUL, 30, 70)]
    out = dict(reducer.self_times([(hlo("while.8", "while"), 0, 100)] + body))
    assert out[hlo("while.8", "while")] == 40
    assert out[LOOP] == 20 and out[MATMUL] == 40


def test_reduction_of_hand_made_planes(reducer):
    ms = 1_000_000
    device = {
        "/device:TPU:0": {
            "ops": [(MATMUL, 0, 40 * ms), (KERNEL, 40 * ms, 50 * ms),
                    (ALLREDUCE, 60 * ms, 80 * ms), (LOOP, 85 * ms, 95 * ms)],
            # an asynchronous all-gather from start to done, half of it
            # under the matmul
            "async": [(hlo("all-gather-start.3", "all-gather-start"),
                       30 * ms, 50 * ms)]},
        # the idlest chip: one op, and a long gap under bench:block
        "/device:TPU:1": {"ops": [(MATMUL, 0, 40 * ms)], "async": []},
    }
    host = [("bench:traced_window", 0, 100 * ms),
            ("bench:step_call", 0, 45 * ms),
            ("bench:block", 45 * ms, 100 * ms)]
    r = reducer.reduce_planes(device, host)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["per_device"][0]["busy_s"] == pytest.approx(0.080)
    assert r["per_device"][1]["idle_share"] == pytest.approx(0.6)
    assert r["idle_share_worst"] == pytest.approx(0.6)
    assert r["busy_s"] == pytest.approx((0.080 + 0.040) / 2)
    # seconds are means over the two chips
    assert r["matmul_s"] == pytest.approx(0.040)
    assert r["pallas_s"] == pytest.approx(0.005)
    assert r["other_s"] == pytest.approx(0.005)
    # the chip with most in flight, chip 0: [30,50] async + [60,80] op; the
    # matmul and the kernel hide [30,50], nothing hides the all-reduce
    assert r["collective_inflight_s"] == pytest.approx(0.040)
    assert r["collective_exposed_s"] == pytest.approx(0.020)
    # on the op stream itself: the all-reduce alone, mean of the two chips
    assert r["collective_op_s"] == pytest.approx(0.010)
    assert r["device_ops"][0] == ["fusion kOutput -> bf16[8,128]",
                                  pytest.approx(0.040)]
    assert r["ops"][0] == [MATMUL, "matmul", pytest.approx(0.040)]
    assert sum(s for _, _, s in r["ops"]) == pytest.approx(r["busy_s"])
    assert r["idle_gaps"][0] == ["bench:block", pytest.approx(0.060)]
    assert reducer.reduce_planes({}, host) is None


def test_a_capture_without_its_window_span_is_refused(reducer):
    """No span, or one on a clock the device does not share: an error, and
    never a quiet change of the denominator."""
    device = {"/device:TPU:0": {"ops": [(LOOP, 1000, 2000),
                                        (LOOP, 3000, 4000)], "async": []}}
    for host in ([], [("bench:traced_window", 10 ** 9, 2 * 10 ** 9)]):
        with pytest.raises(ValueError, match="bench:traced_window"):
            reducer.reduce_planes(device, host)


def test_parts_by_shape():
    import hlo_shapes
    scores = hlo("fusion.3", "fusion", out="bf16[16,16,512,512]{3,2,1,0}",
                 rest=", kind=kOutput, calls=%fused.3")
    head = hlo("fusion.4", "fusion", out="f32[166,50257]{1,0}",
               rest=", kind=kLoop, calls=%fused.4")
    square_weight = hlo("fusion.5", "fusion", out="bf16[512,512]{1,0}",
                        rest=", kind=kOutput, calls=%fused.5")
    assert hlo_shapes.dims_in(scores) == [(16, 16, 512, 512), (8,)]
    assert hlo_shapes.part(scores, "matmul", 512, 50257) == "scores"
    assert hlo_shapes.part(head, "other", 512, 50257) == "vocab"
    # a weight as wide as the sequence is long is no score tensor
    assert hlo_shapes.part(square_weight, "matmul", 512, 50257) \
        == "dense_matmul"
    assert hlo_shapes.part(LOOP, "other", 512, 50257) == "rest"
    context = {"trace": {"busy_s": 4.0, "ops": [[scores, "matmul", 1.0],
                                                [head, "other", 0.5],
                                                [MATMUL, "matmul", 2.0]]},
               "workload": {"traffic": {"seq_len": 512}},
               "config": {"vocab_size": 50257}}
    assert hlo_shapes.share_of_busy(context, "scores") == 25.0
    assert hlo_shapes.share_of_busy(context, "vocab") == 12.5
    assert hlo_shapes.share_of_busy(context, "dense_matmul") == 50.0
    context["workload"] = {"traffic": {}}       # a cell with no sequences
    assert hlo_shapes.share_of_busy(context, "scores") is None


def recorded():
    return sorted(glob.glob(os.path.join(FIXTURES, "*.expected.json")))


@pytest.mark.parametrize("expected_path", recorded(),
                         ids=[os.path.basename(p) for p in recorded()])
def test_recorded_capture_reproduces_its_numbers(reducer, harness, bench,
                                                 expected_path):
    with open(expected_path) as f:
        want = json.load(f)
    got = reducer.reduce_capture(expected_path.replace(
        ".expected.json", ".xplane.pb.gz"))
    # the by-shape readers, on the cell's own sequence length and vocabulary
    cell = os.path.basename(expected_path)[:-len(".expected.json")]
    _, workload, config = harness.resolve(bench, cell, rehearse=False)
    context = {"trace": got, "workload": workload, "config": config}
    want_shares = want.pop("shares")
    shares = {name: harness.load_module("layer_metrics", name).compute(
        context) for name in want_shares}
    assert shares == pytest.approx(want_shares, rel=1e-9)
    ops = got.pop("ops")
    assert len(ops) == want.pop("instructions")
    assert set(got) == set(want)
    for key, value in want.items():
        if isinstance(value, float):
            assert got[key] == pytest.approx(value, rel=1e-9), key
        else:
            assert got[key] == value, key
    assert got["busy_s"] > 0 and got["idle_gaps"]
    # every op's self time lands in exactly one class
    assert got["matmul_s"] + got["pallas_s"] + got["collective_op_s"] \
        + got["other_s"] == pytest.approx(got["busy_s"], rel=1e-6)
    assert sum(s for _, _, s in ops) == pytest.approx(got["busy_s"], rel=1e-6)
