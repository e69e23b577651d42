"""Host seconds of jaxpr -> MLIR inside the program's build spans, every
Mosaic kernel's lowering among them. Paid by every process, cached
executable or not."""
import setup_phases  # perfbench/setup_phases.py: run.py's directory is on sys.path


def compute(context):
    return setup_phases.phase_seconds("lower")
