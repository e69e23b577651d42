"""Median milliseconds a step waited for its batch: the generator thread's
queue plus the upload (`bench:next_batch`)."""
import statistics


def compute(context):
    waits = context["input_wait_ms"]
    return statistics.median(waits) if waits else None
