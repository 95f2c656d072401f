"""The reader of the validated-key row cache's share, on hand-made counters."""
import pytest

from benchmark import run as brun
from benchmark.context import Run

KEYS, HITS = "bls_pubkey_aggregate_device_keys_total", "bls_pubkey_row_hits_total"


@pytest.mark.parametrize("start,end,want", [
    ({KEYS: 512.0, HITS: 0.0}, {KEYS: 1512.0, HITS: 990.0}, 99.0),  # 990 of 1000 keys
    ({KEYS: 512.0, HITS: 0.0}, {KEYS: 512.0, HITS: 0.0}, None),  # no key aggregated
    ({KEYS: 0.0}, {KEYS: 497.0}, None),  # a program without the row cache's counter
])
def test_pubkey_row_hit_share_reads_the_window_counters(start, end, want):
    run = Run(1, {}, {}, {})
    run._counters_at_start, run._counters_at_end = start, end
    got = brun.load_reader("bls.pubkey_row_hit_share")(run)
    assert got == (None if want is None else pytest.approx(want))
