"""Median milliseconds of the program's own `train:dispatch` span (the call
into the compiled step with the write-back of its outputs, `jit.py`) inside
the traced window, read off the capture's host plane: the part of
`train_host_ms_per_step` that is the dispatch itself. Every traced training
cell reports it, so this is also where the traced run logs its idle gaps
labelled by the program's spans (`breakdown` keeps the benchmark's own)."""
import json

import scope_shares  # perfbench/scope_shares.py: run.py's directory is on sys.path


def compute(context):
    if context["trace"] is None:
        return None
    path = scope_shares.newest_capture()
    if path is None:
        return None
    print("idle gaps of the idlest chip, by the innermost train:* or "
          "bench:* span open on the host: %s"
          % json.dumps(scope_shares.idle_gaps(path)), flush=True)
    return scope_shares.dispatch_ms(path)
