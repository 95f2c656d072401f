"""Share of the window's signature sets whose signature the program checked
for G2 membership on the device (`bls_sig_subgroup_device_total` over the
sets), in %. None where no set ran, or where the program keeps no such
counter."""

CHECKED = "bls_sig_subgroup_device_total"


def read(run):
    sets = run.work.get("sets")
    if not sets or not any(k == CHECKED or k.startswith(CHECKED + "{")
                           for k in run._counters_at_end):
        return None
    return 100.0 * run.counter_delta(CHECKED) / sets
