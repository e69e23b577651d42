"""Device time by the block of the model an op belongs to.

The program names its scopes (PR 25): `Block.__call__` runs every block's
forward under `jax.named_scope(block.name)`, the two transformer layers put
their feed-forward expression under `ffn`, `TrainStep` opens the root
block's scope, `loss` and `optimizer`, and the three Pallas kernels are
called `flash_fwd`, `flash_bwd_dkv`, `flash_bwd_dq`. JAX writes the path of
scopes an op was traced under into the instruction's metadata,
`op_name="jit(step_fn)/transpose(jvp(<root>))/<block>/ffn/<block>/mul"`,
and XLA keeps it through its optimisations.

A profiler capture names a device event by the optimised instruction
(`%fusion.12 = ... fusion(...), kind=kOutput, calls=%fused_computation.7`)
and says nothing of its metadata, so the join needs the optimised program:
  - its text, `compiled.as_text()` (`jit.compiled_train_programs()` while
    the step is alive; the recorded fixture keeps a copy):
    `program_from_text`;
  - or the HLO protos the profiler itself stores in the capture, one per
    program that ran, on the plane `/host:metadata` (stat `Hlo Proto` of
    each event metadata): `programs_from_capture`. This is what a
    benchmark run reads, because run.py computes the per-layer metrics
    after the driver has returned, when the `TrainStep` and with it its
    cache entry are gone. Read with a few lines of protobuf wire format:
    jax.profiler.ProfileData does not reach a plane's event metadata.
Both give the same `Program`.

**The rule for fusions.** A matmul-class fusion (`reduce.classify` =
`matmul`) takes the op_name of the `convolution`/`dot` inside the
computation it calls, nested fusions followed: XLA fuses a residual add
behind a projection and Adam behind a weight gradient, and the fusion's own
metadata may then be the consumer's. Every other op takes its own, and
where it has none, its fused root's. A matmul-class fusion whose *root* is
(for a multi-output fusion, whose root is a tuple: one of the tuple's
elements) is under `optimizer` is booked neither to the block nor to the
optimizer but to `update_fused_matmul`: the time belongs to both.

**Classes**, first rule met wins, on the chosen op_name's scope components
(what lies between `jit(step_fn)` and the primitive, with `jvp(`,
`transpose(` and `)` stripped; a block is recognised by the stem its class
gives its name, as a substring):
  update_fused_matmul  see above
  optimizer            under `optimizer`
  attn_block           under a transformer layer and a MultiHeadAttention
  mlp_block            under a transformer layer and `ffn`
  norm_residual        under a transformer layer, neither of the two
  embed_head_loss      under `loss`, or under the outermost scope of the
                       differentiated function (JAX wraps exactly that
                       one in `jvp(..)`: the root block) and no
                       transformer layer
  unscoped             none of the above, or an event whose instruction
                       the program does not have
"""
import collections
import gzip
import importlib.util
import os
import re
import sys

CLASSES = ("attn_block", "mlp_block", "norm_residual", "embed_head_loss",
           "optimizer", "update_fused_matmul", "unscoped")
#: `Block._alias()` of the classes whose blocks are one transformer layer
LAYER_STEMS = ("transformerencoderlayer", "transformerdecoderlayer")
ATTENTION_STEM = "multiheadattention"
MATMUL_OPCODES = ("convolution", "dot")
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"


def _reducer():
    """perfbench/trace/reduce.py, under the name run.py and the tests load
    it by: its helpers are this reader's too."""
    name = "perfbench_trace_reduce"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "reduce.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


reduce = _reducer()

#: `operands` are kept for tuples only (the root of a multi-output fusion)
Instr = collections.namedtuple("Instr", "opcode op_name calls operands")


class Program:
    """One optimised HLO module: `instrs` {instruction: Instr}, `members`
    {computation: [instructions]}, `roots` {computation: root instruction}."""

    def __init__(self, name=""):
        self.name = name
        self.instrs, self.members, self.roots = {}, {}, {}

    def add(self, computation, name, opcode, op_name, calls, is_root,
            operands=()):
        self.instrs[name] = Instr(opcode, op_name, tuple(calls),
                                  tuple(operands))
        self.members.setdefault(computation, []).append(name)
        if is_root:
            self.roots[computation] = name

    def matmul_inside(self, name, depth=0):
        """The first convolution/dot in the computations `name` calls,
        fusions nested there followed -> its Instr, or None."""
        for comp in self.instrs[name].calls if depth < 8 else ():
            for member in self.members.get(comp, ()):
                inner = self.instrs[member]
                if inner.opcode in MATMUL_OPCODES:
                    return inner
                if inner.opcode == "fusion":
                    found = self.matmul_inside(member, depth + 1)
                    if found is not None:
                        return found
        return None

    def roots_of(self, name):
        """What the (first) computation `name` calls returns: its root
        instruction, or a tuple root's elements (a multi-output fusion)."""
        for comp in self.instrs[name].calls:
            root = self.instrs.get(self.roots.get(comp))
            if root is None:
                return []
            if root.opcode == "tuple":
                return [self.instrs[o] for o in root.operands
                        if o in self.instrs]
            return [root]
        return []


# ------------------------------------------------------------ from text
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([^\s(]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT )?%?([^\s=]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([^\s,}]+)")
_TUPLE_OPERANDS = re.compile(r" tuple\(([^)]*)\)")


def program_from_text(text):
    """`compiled.as_text()` -> Program."""
    prog, computation = Program(), None
    for line in text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            if m:
                computation = m.group(1)
                if line.startswith("ENTRY"):
                    prog.name = computation
            continue
        m = _INSTRUCTION.match(line)
        if m is None or computation is None:
            continue
        is_root, name, rest = m.groups()
        opcode = reduce.OPCODE.search(" " + rest)
        op_name = _OP_NAME.search(rest)
        operands = _TUPLE_OPERANDS.search(" " + rest)
        prog.add(computation, name,
                 opcode.group(1) if opcode else name.split(".")[0],
                 op_name.group(1) if op_name else "",
                 _CALLS.findall(rest), bool(is_root),
                 re.findall(r"%([^\s,]+)", operands.group(1))
                 if operands else ())
    return prog


# ------------------------------------------------------------ from the capture
def _varint(buf, i):
    """-> (the varint that starts at buf[i], the index after it)."""
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf):
    """Protobuf wire format: (field number, value) of one message; a varint
    as int, a length-delimited field as a memoryview, fixed ones skipped."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield number, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield number, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError("wire type %d in a profiler capture" % wire)


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _varints(value):
    """A repeated integer field: one value, or a packed run of them."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        one, i = _varint(value, i)
        out.append(one)
    return out


def _program_from_proto(module):
    """xla.HloModuleProto (name 1, computations 3 {name 1, instructions 2
    {name 1, opcode 2, metadata 7 {op_name 2}, id 35, operand_ids 36,
    called_computation_ids 38}, id 5, root_id 6}) -> Program."""
    prog, computations = Program(), []
    for number, value in _fields(module):
        if number == 1:
            prog.name = _text(value)
        elif number == 3:
            computations.append(value)
    parsed, names = [], {}           # names: computation id -> name
    for comp in computations:
        comp_name, comp_id, root_id, instrs = "", None, None, []
        for number, value in _fields(comp):
            if number == 1:
                comp_name = _text(value)
            elif number == 2:
                instrs.append(value)
            elif number == 5:
                comp_id = value
            elif number == 6:
                root_id = value
        names[comp_id] = comp_name
        parsed.append((comp_name, root_id, instrs))
    for comp_name, root_id, instrs in parsed:
        rows, by_id = [], {}         # by_id: this computation's id -> name
        for instr in instrs:
            name = opcode = op_name = ""
            instr_id, called, operands = None, [], []
            for number, value in _fields(instr):
                if number == 1:
                    name = _text(value)
                elif number == 2:
                    opcode = _text(value)
                elif number == 7:
                    for n2, v2 in _fields(value):
                        if n2 == 2:
                            op_name = _text(v2)
                elif number == 35:
                    instr_id = value
                elif number == 36:
                    operands += _varints(value)
                elif number == 38:
                    called += _varints(value)
            by_id[instr_id] = name
            rows.append((name, opcode, op_name, called, instr_id, operands))
        for name, opcode, op_name, called, instr_id, operands in rows:
            prog.add(comp_name, name, opcode, op_name,
                     [names[c] for c in called if c in names],
                     instr_id == root_id,
                     [by_id[o] for o in operands if o in by_id]
                     if opcode == "tuple" else ())
    return prog


def programs_from_capture(xspace_bytes):
    """Every program the profiler stored in the capture (tsl XSpace: planes
    1 {name 2, event_metadata 4 {value 2: {stats 5 {metadata_id 1,
    bytes_value 6}}}, stat_metadata 5 {value 2: {id 1, name 2}}}; the stat
    holds an xla.HloProto, hlo_module 1) -> [Program]."""
    out = []
    for number, plane in _fields(xspace_bytes):
        if number != 1:
            continue
        plane_fields = list(_fields(plane))
        if METADATA_PLANE not in (_text(v) for n, v in plane_fields
                                  if n == 2):
            continue
        proto_stat_ids = set()
        for n, entry in plane_fields:
            if n == 5:
                for n2, meta in _fields(entry):
                    if n2 == 2:
                        meta = dict(_fields(meta))
                        if _text(meta.get(2, b"")) == HLO_PROTO_STAT:
                            proto_stat_ids.add(meta.get(1))
        for n, entry in plane_fields:
            if n != 4:
                continue
            for n2, event_meta in _fields(entry):
                if n2 != 2:
                    continue
                for n3, stat in _fields(event_meta):
                    if n3 != 5:
                        continue
                    stat = dict(_fields(stat))
                    if stat.get(1) in proto_stat_ids and 6 in stat:
                        for n4, module in _fields(stat[6]):
                            if n4 == 1:
                                out.append(_program_from_proto(module))
    return out


def read_capture_bytes(path):
    """A capture directory, an .xplane.pb or a gzipped one -> its bytes."""
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return f.read()
    with open(reduce.find_xplane(path), "rb") as f:
        return f.read()


# ------------------------------------------------------------ classes
def components(op_name):
    """-> (scope components between `jit(..)` and the primitive, stripped
    of `jvp(`, `transpose(` and `)`; whether one of them was wrapped in
    `jvp(<name>)`, i.e. the op lies under the differentiated function's
    outermost scope). XLA joins the op_names of instructions it merged
    with `;`: the first one counts."""
    parts = op_name.split(";")[0].split("/")[1:-1]
    under_root = any("jvp(" in p and not p.endswith("jvp()") for p in parts)
    return [p.replace("transpose(", "").replace("jvp(", "").replace(")", "")
            for p in parts], under_root


def scope_class(op_name):
    """The class of one op_name, by the table above (without the fusion
    rule, which needs the program: `event_class`)."""
    parts, under_root = components(op_name)
    if "optimizer" in parts:
        return "optimizer"
    if any(stem in p for p in parts for stem in LAYER_STEMS):
        if any(ATTENTION_STEM in p for p in parts):
            return "attn_block"
        return "mlp_block" if "ffn" in parts else "norm_residual"
    if "loss" in parts or under_root:
        return "embed_head_loss"
    return "unscoped"


def event_class(program, text):
    """The class of one device event, named by its instruction's text."""
    name, opcode, _ = reduce.parse(text)
    instr = program.instrs.get(name)
    if instr is None:
        return "unscoped"
    op_name = instr.op_name
    if opcode == "fusion":
        roots = program.roots_of(name)
        if reduce.classify(text) == "matmul":
            if any("optimizer" in components(r.op_name)[0] for r in roots):
                return "update_fused_matmul"
            inner = program.matmul_inside(name)
            if inner is not None and inner.op_name:
                op_name = inner.op_name
        if not op_name:
            op_name = next((r.op_name for r in roots if r.op_name), "")
    return scope_class(op_name)


def has_scopes(program):
    """Whether the program names its scopes at all: since PR 25 every
    train step has ops under `optimizer`; an older program has none, and
    its time must read as absent, not as 100 % unscoped."""
    return any("optimizer" in components(i.op_name)[0]
               for i in program.instrs.values())


def seconds_by_class(program, ops):
    """`ops` = the reduction's [[instruction text, class, seconds]] (self
    times inside the window, mean over chips) -> {class: seconds}; they
    sum to what `ops` sums to, the reduction's busy time."""
    out = dict.fromkeys(CLASSES, 0.0)
    for text, _, seconds in ops:
        out[event_class(program, text)] += seconds
    return out


def pick_program(programs, ops):
    """Of the capture's programs, the one whose instructions account for
    most of the events' time: instruction names repeat across programs,
    and the window runs one program that matters, the train step."""
    best, best_s = None, 0.0
    for prog in programs:
        covered = sum(s for text, _, s in ops
                      if reduce.parse(text)[0] in prog.instrs)
        if covered > best_s:
            best, best_s = prog, covered
    return best
