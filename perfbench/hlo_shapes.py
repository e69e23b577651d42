"""Device time by the tensors an op touches. The profiler names a device
op by its whole HLO instruction, output and operand shapes included, and
until the program carries named scopes (PERF.md, Open questions) those
shapes are the only sound way to tell the attention scores, the vocabulary
head and the layers' dense matmuls apart: XLA fuses a softmax or an Adam
update into the convolution next to it, so its own fusion kinds cannot.

An op belongs to the first part whose rule it meets:
  scores        an operand or the output has rank >= 3 and ends in
                (seq_len, seq_len): Q K^T, the softmax, P V and their
                gradients in the composite attention. A kernel that keeps
                the scores on chip has no such tensor.
  vocab         an operand or the output has a dimension equal to the
                vocabulary: the output head, the loss, the embedding
                look-up, and their gradients and optimizer updates.
  dense_matmul  any other matmul-class op (trace/reduce.py: a convolution
                or an output fusion): the layers' projections and MLPs,
                with the bias, activation or statistics fused into them.
  rest          everything else.
"""
import re

SHAPE = re.compile(r"[a-z][a-z0-9]*\[([0-9,]*)\]")


def dims_in(text):
    """Every `type[d0,d1,..]` in an instruction's text, as tuples."""
    return [tuple(int(d) for d in m.split(",") if d)
            for m in SHAPE.findall(text)]


def part(text, op_class, seq_len, vocab_size):
    dims = dims_in(text)
    if any(len(d) >= 3 and d[-1] == d[-2] == seq_len for d in dims):
        return "scores"
    if any(vocab_size in d for d in dims):
        return "vocab"
    return "dense_matmul" if op_class == "matmul" else "rest"


def share_of_busy(context, which):
    """Percent of device-busy time in ops of part `which`, or None where
    the cell has no trace, no sequence length or no vocabulary."""
    trace = context["trace"]
    seq_len = context["workload"]["traffic"].get("seq_len")
    vocab_size = context["config"].get("vocab_size")
    if trace is None or not trace["busy_s"] or None in (seq_len, vocab_size):
        return None
    seconds = sum(s for text, op_class, s in trace["ops"]
                  if part(text, op_class, seq_len, vocab_size) == which)
    return 100.0 * seconds / trace["busy_s"]
