"""Share of device-busy time the op stream spends in collectives: the
synchronous all-reduce, all-gather and reduce-scatter ops and the start
and done ops of asynchronous ones, mean over the chips. What runs
asynchronously under other ops is not in it (collective_exposed_share
reads the part of that which nothing hides)."""


def compute(context):
    trace = context["trace"]
    if trace is None or not trace["busy_s"]:
        return None
    return 100.0 * trace["collective_op_s"] / trace["busy_s"]
