"""The least loaded expert's rows over the even load T k / E, percent, in
the worst expert layer, the median over the window's steps, from the rows
the program published (`train:counters`; the held experts of a held
layer): 100 is a balanced router, 0 an expert that starved."""
import expert_load  # perfbench/expert_load.py: run.py's directory is on sys.path


def compute(context):
    return expert_load.min_share(context)
