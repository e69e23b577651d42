"""Windows the held dispatch ran a pass, a held layer a step, the mean over
the window's steps and layers, from the rows the program published
(`train:counters`): ceil(live rows / W). 1.0 while every layer's live rows
fit one window; a second window is a router sending its held experts more
than twice their even share, less than one a layer whose held experts got
no row."""
import expert_load  # perfbench/expert_load.py: run.py's directory is on sys.path


def compute(context):
    return expert_load.windows_per_pass(context)
