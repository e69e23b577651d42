"""Microseconds of device time under `moe_experts` (the grouped matmuls
and what sits between them, every pass; found as moe_shares.py finds it) a
LIVE row of the held layers, over the traced window's steps: the time of
the capture over the rows the program published for the same steps
(`train:counters`). What `held_expert_matmul_roofline` divides by the
expected rows, this divides by the ones that ran."""
import expert_load  # perfbench/expert_load.py: run.py's directory is on sys.path


def compute(context):
    return expert_load.us_per_live_row(context)
