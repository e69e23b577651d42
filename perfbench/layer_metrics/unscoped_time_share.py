"""Share of device-busy time in scope class `unscoped` (trace/scopes.py):
ops under no named scope (RNG, copies, the step's glue) and events whose
instruction the program does not have: the gauge of the naming's coverage."""
import scope_shares  # perfbench/scope_shares.py: run.py's directory is on sys.path


def compute(context):
    return scope_shares.share_of_busy(context, "unscoped")
