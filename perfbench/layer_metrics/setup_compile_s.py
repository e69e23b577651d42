"""Seconds in every first call of a shape during set-up (trace + lower +
compile, or the read from JAX's persistent cache), by the benchmark's
clock around those calls."""


def compute(context):
    return context["setup_compile_s"]
