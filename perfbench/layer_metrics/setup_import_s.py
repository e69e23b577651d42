"""Seconds of the package's own import, as the program times it
(`mxtpu_import_seconds`, both parts): the first device touch, which starts
the backend and runs a program (`part="backend"`), and every module
(`part="modules"`). No program change moves the first; `import jax` before
the package and the interpreter's own start are outside it."""
import setup_phases  # perfbench/setup_phases.py: run.py's directory is on sys.path


def compute(context):
    return setup_phases.total("mxtpu_import_seconds")
