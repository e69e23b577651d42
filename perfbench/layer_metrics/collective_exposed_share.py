"""Share of the traced window in which a collective was in flight and no
other op computed: collective ops on the op stream, and asynchronous ones
(collective-permute chains) from start to done where nothing runs under
them. Read on the chip with most in flight; this profiler records
asynchronous spans on chip 0 only, so that is chip 0. What overlap with
the backward pass could still win."""


def compute(context):
    trace = context["trace"]
    if trace is None:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
