"""From a profiler capture (.xplane.pb) to the numbers the per-layer metrics
read. The only reader of traces in the benchmark; checked against the
recorded captures in fixtures/ by tests/perfbench.

What a TPU capture of jax 0.9.0 holds (looked at by hand, PR 23):
planes `/device:TPU:<n>`, each with a line `XLA Ops` whose events are the
HLO instructions as they ran on that core. An event's name is the whole
instruction as the optimised HLO prints it, `%fusion.12 = bf16[..]{..}
fusion(%a, %b), kind=kOutput, calls=%fused_computation.7`; it carries no
category stat. A `while` (the chunked loss) is an event that contains its
body's events, so time is summed as self time. A line `Async XLA Ops` holds
the spans of asynchronous copies and collectives from start to done.
`/host:CPU` has one line per host thread, where
`jax.profiler.TraceAnnotation` spans appear under their own names; host and
device planes share one clock.

Classes, from the instruction's opcode: a collective by its name; a Pallas
kernel is a `custom-call` (these programs have no other); a matmul is a
bare `convolution`/`dot` or a fusion of `kind=kOutput` — on this backend
every dot is a convolution and XLA fuses it with its consumers as an
output fusion (checked on the optimised HLO of both configurations' train
steps: each kOutput fusion holds a convolution, itself or in a fusion
nested in it, and nothing else does). A matmul fusion's time includes what
XLA fused into it: the softmax around Q K^T, Adam behind a weight gradient.
So the class alone says little; the reduction also returns every instruction
with its time (`ops`), and hlo_shapes.py tells the attention scores, the
vocabulary head and the layers' dense matmuls apart by the shapes in it.

    python3 perfbench/trace/reduce.py <capture dir | .xplane.pb | .pb.gz>

prints the reduction as JSON, and with `--dump` the planes, lines and
longest events: look at a capture before trusting code written against it.
"""
import collections
import functools
import glob
import gzip
import json
import os
import re
import sys

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
WINDOW_SPAN = "bench:traced_window"
HOST_SPAN_PREFIX = "bench:"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast|async-collective")
INSTRUCTION = re.compile(r"^%?([^\s=]+)")
OPCODE = re.compile(r" ([a-z][a-z\-]*)\(")
FUSION_KIND = re.compile(r"kind=(k\w+)")
SHAPE = re.compile(r"= \(?([a-z0-9]+\[[0-9,]*\])")
#: instructions that contain the instructions of their body
CONTAINERS = ("while", "conditional", "call")


def find_xplane(path):
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % path)
    return found[-1]


# ------------------------------------------------------------ intervals
def merge(intervals):
    """Sorted, disjoint union of (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def length(merged):
    return sum(e - s for s, e in merged)


def uncovered(merged_a, merged_b):
    """Length of merged_a that merged_b does not cover."""
    total, j = 0, 0
    for s, e in merged_a:
        cur = s
        while j < len(merged_b) and merged_b[j][1] <= cur:
            j += 1
        k = j
        while k < len(merged_b) and merged_b[k][0] < e:
            bs, be = merged_b[k]
            if bs > cur:
                total += bs - cur
            cur = max(cur, be)
            if cur >= e:
                break
            k += 1
        if cur < e:
            total += e - cur
    return total


def gaps(merged, lo, hi):
    out, cur = [], lo
    for s, e in merged:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


# ------------------------------------------------------------ reading
def load(path):
    """A capture directory, an .xplane.pb, or a gzipped one (the recorded
    fixtures) -> jax.profiler.ProfileData."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(find_xplane(path))


def read_planes(path):
    """-> (device: {plane name: {"ops": [(text, start_ns, end_ns)],
                                 "async": [(text, start_ns, end_ns)]}},
           host spans: [(name, start_ns, end_ns)] of bench:* annotations)"""
    data = load(path)
    device, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {OPS_LINE: [], ASYNC_LINE: []}
            for line in plane.lines:      # a name can repeat
                if line.name in lines:
                    lines[line.name] += [
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events]
            if lines[OPS_LINE]:
                device[plane.name] = {"ops": lines[OPS_LINE],
                                      "async": lines[ASYNC_LINE]}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    return device, host


def parse(text):
    """An event's HLO text -> (instruction name, opcode, fusion kind)."""
    name = INSTRUCTION.match(text).group(1)
    opcode = OPCODE.search(text)
    kind = FUSION_KIND.search(text)
    return (name, opcode.group(1) if opcode else name.split(".")[0],
            kind.group(1) if kind else "")


@functools.lru_cache(maxsize=None)     # a few thousand distinct instructions
def classify(text):
    """-> 'collective' | 'pallas' | 'matmul' | 'container' | 'other'."""
    name, opcode, kind = parse(text)
    if COLLECTIVE.search(opcode) or COLLECTIVE.search(name):
        return "collective"
    if opcode == "custom-call":
        return "pallas"
    if opcode in ("convolution", "dot") or \
            (opcode == "fusion" and kind == "kOutput"):
        return "matmul"
    if opcode in CONTAINERS:
        return "container"
    return "other"


def label(text):
    """What the breakdown prints for an op: the instruction's name without
    its number, its fusion kind or opcode, and its output shape. A step
    has thousands of instructions and the breakdown ten lines, so those
    alike in all three are summed."""
    name, opcode, kind = parse(text)
    shape = SHAPE.search(text)
    return "%s %s%s" % (name.rsplit(".", 1)[0] if "." in name else name,
                        kind or opcode,
                        " -> " + shape.group(1) if shape else "")


def self_times(ops):
    """[(text, start, end)] of one line -> [(text, self_ns)]: an event's
    time less that of the events nested in it."""
    out, stack = [], []          # stack of [end, index into out]
    for text, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= min(e, stack[-1][0]) - s
        out.append([text, e - s])
        stack.append([e, len(out) - 1])
    return out


def reduce_planes(device, host):
    """The reduction proper, on what read_planes returns. Seconds are
    averaged over the chips that ran an op; idle is the idlest chip's, and
    the exposed collectives' seconds are those of the chip with most
    collectives in flight (this profiler records asynchronous spans on
    chip 0 only). The window is the host's `bench:traced_window` span: a
    capture without it, or with it on a clock the device planes do not
    share, is an error, not a reason to measure against something else."""
    if not device:
        return None
    window = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    dev_lo = min(s for d in device.values() for _, s, _ in d["ops"])
    dev_hi = max(e for d in device.values() for _, _, e in d["ops"])
    if not window or window[0][0] >= dev_hi or window[0][1] <= dev_lo:
        raise ValueError("no %s span over the device ops (%d found)"
                         % (WINDOW_SPAN, len(window)))
    lo, hi = window[0]
    window_ns = hi - lo
    n_dev = len(device)
    per_device, by_class = [], collections.Counter()
    by_text = collections.Counter()
    exposed = (0, 0)             # (in flight, exposed) where most in flight
    worst = None
    for plane in sorted(device):
        ops = clip3(device[plane]["ops"], lo, hi)
        busy = merge((s, e) for _, s, e in ops)
        for text, ns in self_times(ops):
            by_class[classify(text)] += ns
            by_text[text] += ns
        # collectives: the ops themselves and the asynchronous ones from
        # start to done; hidden while any other op computes on this chip
        coll = merge([(s, e) for t, s, e in ops
                      if classify(t) == "collective"]
                     + [(s, e) for t, s, e in clip3(device[plane]["async"],
                                                    lo, hi)
                        if classify(t) == "collective"])
        compute = merge((s, e) for t, s, e in ops
                        if classify(t) not in ("collective", "container"))
        exposed = max(exposed, (length(coll), uncovered(coll, compute)))
        idle = 1.0 - length(busy) / window_ns
        per_device.append({"plane": plane, "busy_s": length(busy) / 1e9,
                           "idle_share": idle, "ops": len(ops)})
        if worst is None or idle > worst[0]:
            worst = (idle, busy)
    host_spans = [(n, s, e) for n, s, e in host if n != WINDOW_SPAN]
    idle_gaps = [[_host_at(host_spans, g0, g1), (g1 - g0) / 1e9]
                 for g0, g1 in sorted(gaps(worst[1], lo, hi),
                                      key=lambda g: g[0] - g[1])[:10]]

    def sec(ns):
        return ns / n_dev / 1e9

    by_label = collections.Counter()
    for text, ns in by_text.items():
        by_label[label(text)] += ns
    return {
        "window_s": window_ns / 1e9, "devices": n_dev,
        "busy_s": sum(d["busy_s"] for d in per_device) / n_dev,
        "idle_share_worst": worst[0], "per_device": per_device,
        "matmul_s": sec(by_class["matmul"]),
        "pallas_s": sec(by_class["pallas"]),
        "collective_op_s": sec(by_class["collective"]),
        "other_s": sec(by_class["other"] + by_class["container"]),
        "collective_inflight_s": exposed[0] / 1e9,
        "collective_exposed_s": exposed[1] / 1e9,
        "device_ops": [[n, sec(ns)] for n, ns in by_label.most_common(10)],
        "idle_gaps": idle_gaps,
        # every instruction that ran: [HLO text, class, seconds], for the
        # readers that go by shape (hlo_shapes.py)
        "ops": [[text, classify(text), sec(ns)]
                for text, ns in by_text.most_common()],
    }


def clip3(events, lo, hi):
    return [(t, max(s, lo), min(e, hi)) for t, s, e in events
            if e > lo and s < hi]


def _host_at(spans, g0, g1):
    """The bench:* span that covers most of the gap; spans nest (a step
    call inside the window), so the shortest of the best wins."""
    best, best_key = "no bench span open", (0, 0)
    for n, s, e in spans:
        overlap = min(e, g1) - max(s, g0)
        if overlap > 0:
            key = (overlap, -(e - s))
            if key > best_key:
                best, best_key = n, key
    return best


def reduce_capture(path):
    return reduce_planes(*read_planes(path))


# ------------------------------------------------------------ by hand
def dump(path, top=25):
    data = load(path)
    for plane in data.planes:
        print("plane %r" % plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  line %r: %d events" % (line.name, len(events)))
            total = collections.Counter()
            for ev in events:
                total[ev.name] += ev.duration_ns
            for name, ns in total.most_common(
                    top if line.name in (OPS_LINE, ASYNC_LINE) else 6):
                print("    %10.3f ms  %s" % (ns / 1e6, name[:300]))


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    if "--dump" in sys.argv:
        dump(args[0])
    reduced = reduce_capture(args[0])
    reduced["ops"] = "%d instructions (left out)" % len(reduced["ops"])
    print(json.dumps(reduced, indent=1))
