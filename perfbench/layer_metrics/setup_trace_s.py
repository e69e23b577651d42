"""Host seconds JAX spent tracing Python into jaxprs inside the program's
build spans (`train:build`, `eval:build`, `aot:load`): the model's forward,
loss, backward and update, every Pallas kernel body among them
(`setup_kernel_trace_s` is a part of this). Paid by every process, cached
executable or not. Every traced cell reports it, so this is also where the
run logs the whole split by owner, ONE line."""
import setup_phases  # perfbench/setup_phases.py: run.py's directory is on sys.path


def compute(context):
    setup_phases.print_table()
    return setup_phases.phase_seconds("trace")
