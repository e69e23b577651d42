"""Host seconds of XLA compiles inside the program's build spans that the
persistent cache did NOT answer. About 0 on a warm run: anything else means
the cache missed (an entry evicted, a program that changed)."""
import setup_phases  # perfbench/setup_phases.py: run.py's directory is on sys.path


def compute(context):
    return setup_phases.phase_seconds("backend_compile")
