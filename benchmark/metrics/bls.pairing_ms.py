"""Device ms per signature set of the pairing kernels (ops/bls12_jax.py
over ops/fp_rns.py): the RLC check and, for a failed batch, the per-item
check that attributes it, from the trace. Nothing where the trace holds
fewer RLC checks than the window ran batches (one flush a batch), or where
its program times disagree with its busy union."""
from benchmark.trace_reduce import holds, program_seconds

PROGRAMS = r"pairing_check_(rlc|batch)"


def read(run):
    batches, sets = run.work.get("batches"), run.work.get("sets")
    if not sets or not holds(run.trace, r"pairing_check_rlc", batches):
        return None
    return 1000.0 * program_seconds(run.trace, PROGRAMS) / sets
