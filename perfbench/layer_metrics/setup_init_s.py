"""Wall seconds of the program's own initialisation spans in its span ring:
`gluon:initialize` and `gluon:cast` (a small program or two a parameter)
and `train:init_states` (fp32 masters and the moments, inside the step's
first call). Their compiles and cache reads are inside them, not in the
`setup_trace_s` .. `setup_cache_read_s` four."""
import setup_phases  # perfbench/setup_phases.py: run.py's directory is on sys.path


def compute(context):
    return setup_phases.span_seconds(setup_phases.INIT_SPANS)
