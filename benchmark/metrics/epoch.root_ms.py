"""Device ms per epoch of the state-root refresh (engine/incremental_root.py
over ops/sha256_jax.py): the dirty scan, the registry tree update or
rebuild, the wholesale field roots and the randao/slashings path updates.
Nothing where the trace does not hold every epoch (as in epoch.program_ms)."""
from benchmark.trace_reduce import holds, program_seconds

ROOT_PROGRAMS = r"^jit_(scan|update|build|light_field_roots|build_tree_levels)$"


def read(run):
    epochs = run.work.get("epochs")
    if not epochs or not holds(run.trace, r"^jit_step$", epochs):
        return None
    seconds = program_seconds(run.trace, ROOT_PROGRAMS)
    return None if seconds is None else 1000.0 * seconds / epochs
