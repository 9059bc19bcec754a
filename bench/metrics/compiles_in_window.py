"""Programs JAX traced inside the window (the program's `compile`
events on resource `jax`; warm-up makes it 0)."""
from bench.metrics._program_trace import traced


def read(run):
    if not traced(run):
        return None
    return sum(1 for ev in run.spans if ev.kind == "compile"
               and ev.resource == "jax"
               and run.window0 <= run.span_offset + ev.t1 <= run.window1)
