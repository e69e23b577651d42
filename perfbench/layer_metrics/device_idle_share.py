"""1 - (union of the device-op intervals / traced window), on the chip
that was idle most."""


def compute(context):
    trace = context["trace"]
    if trace is None:
        return None
    return 100.0 * trace["idle_share_worst"]
