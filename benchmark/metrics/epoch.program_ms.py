"""Device ms per epoch of the resident step program (`jit_step`: the epoch
transition and the slot advance), from the trace. Nothing where the trace
holds fewer `jit_step` executions than the window ran epochs, or where its
program times disagree with its busy union."""
from benchmark.trace_reduce import holds, program_seconds

STEP = r"^jit_step$"


def read(run):
    epochs = run.work.get("epochs")
    if not epochs or not holds(run.trace, STEP, epochs):
        return None
    return 1000.0 * program_seconds(run.trace, STEP) / epochs
