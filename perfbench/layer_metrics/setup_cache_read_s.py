"""Host seconds of compiles inside the program's build spans that the
persistent cache answered: reading, deserialising and loading the
executable."""
import setup_phases  # perfbench/setup_phases.py: run.py's directory is on sys.path


def compute(context):
    return setup_phases.phase_seconds("cache_read")
