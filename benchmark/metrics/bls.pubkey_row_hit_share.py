"""Share of the keys aggregated in the window whose Montgomery rows came
from the program's validated-key cache (`bls_pubkey_row_hits_total` over
`bls_pubkey_aggregate_device_keys_total`), in %. None where no key was
aggregated, or where the program keeps no such counter."""

HITS = "bls_pubkey_row_hits_total"


def read(run):
    keys = run.counter_delta("bls_pubkey_aggregate_device_keys_total")
    if not keys or not any(k == HITS or k.startswith(HITS + "{")
                           for k in run._counters_at_end):
        return None
    return 100.0 * run.counter_delta(HITS) / keys
