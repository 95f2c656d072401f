"""Device ms per signature set of the pubkey aggregation (the sched `msm`
class: the G1 reduction and subgroup programs), from the trace (nothing
where the trace does not hold every batch, as in bls.pairing_ms)."""
from benchmark.trace_reduce import holds, program_seconds

PROGRAMS = r"_g1_(aggregate|subgroup)_program"


def read(run):
    batches, sets = run.work.get("batches"), run.work.get("sets")
    if not sets or not holds(run.trace, r"pairing_check_rlc", batches):
        return None
    seconds = program_seconds(run.trace, PROGRAMS)
    return None if seconds is None else 1000.0 * seconds / sets
