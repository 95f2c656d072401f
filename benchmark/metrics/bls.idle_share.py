"""% of the traced window in which no operation ran on the chip (nothing
where the trace does not hold every batch, as in bls.pairing_ms)."""
from benchmark.trace_reduce import holds


def read(run):
    t = run.trace
    batches = run.work.get("batches")
    if not batches or not t["window_s"] or not holds(t, r"pairing_check_rlc", batches):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
