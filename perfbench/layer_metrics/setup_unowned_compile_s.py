"""Host seconds of JAX's compile pipeline, every phase, with no program
span open around them: the benchmark's float32 reference programs, uploads
and eager ops. Work of the program's own that lands here is a finding."""
import setup_phases  # perfbench/setup_phases.py: run.py's directory is on sys.path


def compute(context):
    return setup_phases.total("mxtpu_compile_phase_seconds_total",
                              owner=setup_phases.OTHER)
