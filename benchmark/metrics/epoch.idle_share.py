"""% of the traced window in which no operation ran on the chip (nothing
where the trace does not hold every epoch, as in epoch.program_ms)."""
from benchmark.trace_reduce import holds


def read(run):
    t = run.trace
    epochs = run.work.get("epochs")
    if not epochs or not t["window_s"] or not holds(t, r"^jit_step$", epochs):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
