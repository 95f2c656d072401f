"""The epoch program's share of its HBM roofline: the bytes any epoch
transition must move at least (benchmark/roofline.py, from the
configuration's validator count) over the chip's HBM bandwidth, as a % of
the step program's device time per epoch (nothing where the trace does not
hold every epoch's `jit_step`, as in epoch.program_ms)."""
from benchmark import roofline
from benchmark.trace_reduce import holds, program_seconds

STEP = r"^jit_step$"


def read(run):
    epochs = run.work.get("epochs")
    if not epochs or not holds(run.trace, STEP, epochs):
        return None
    least_bytes = roofline.epoch_min_bytes(int(run.config["validators"]))
    return roofline.roofline_share(0, least_bytes, program_seconds(run.trace, STEP) / epochs,
                                   run.peaks, "int8_ops_per_s")
