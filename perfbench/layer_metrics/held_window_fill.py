"""Percent of the rows of the held dispatch's windows that a held expert
owned, as the program published them (`mxtpu_moe_rows_total` /
`mxtpu_moe_windows_total`'s per-step values in the `train:counters`
records): live rows over windows run x W, over the held layers, the median
over the window's steps. W is twice the even share, so the shapes expect
about 50 (Nemotron 2 816 / 6 144 = 46, Solar 1 638 / 4 096 = 40); what a
seed's routers make of it is what this reads."""
import expert_load  # perfbench/expert_load.py: run.py's directory is on sys.path


def compute(context):
    return expert_load.window_fill(context)
