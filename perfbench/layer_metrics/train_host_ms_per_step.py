"""Median milliseconds inside the `step(...)` call, which returns before
the device finishes: the train step's host work (`bench:step_call`)."""
import statistics


def compute(context):
    calls = context["step_call_ms"]
    return statistics.median(calls) if calls else None
