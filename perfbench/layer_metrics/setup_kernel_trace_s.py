"""Host seconds spent tracing Pallas kernel bodies while the program's
steps were traced (`mxtpu_kernel_trace_seconds_total`, all kernels): a part
of `setup_trace_s`, paid by every process whatever is cached."""
import setup_phases  # perfbench/setup_phases.py: run.py's directory is on sys.path


def compute(context):
    return setup_phases.total("mxtpu_kernel_trace_seconds_total")
