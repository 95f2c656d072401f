"""The readers of the flush's device G2 check, on hand-made spans and
counters."""
import pytest

from benchmark import run as brun
from benchmark.context import Run
from benchmark.tests.test_span_readers import run_with, span

CHECKED = "bls_sig_subgroup_device_total"


def test_sig_subgroup_ms_is_host_ms_a_set_in_its_span():
    read = brun.load_reader("bls.sig_subgroup_ms")
    name = "bls.flush.sig_subgroup"
    spans = [span(name, 1.0, 0.003), span(name, 2.0, 0.005),
             span(name, -1.0, 5.0),  # started before the window: not counted
             span("bls.flush.device", 3.0, 9.0)]
    assert read(run_with(spans, {"sets": 128})) == pytest.approx(8.0 / 128)
    assert read(run_with(spans, {})) is None  # no denominator
    assert read(run_with([span("bls.prep", 1.0, 1.0)], {"sets": 64})) is None  # a program without the span


@pytest.mark.parametrize("start,end,sets,want", [
    ({CHECKED: 17.0}, {CHECKED: 145.0}, 128, 100.0),  # every set's signature
    ({CHECKED: 0.0}, {CHECKED: 32.0}, 64, 50.0),
    ({CHECKED: 5.0}, {CHECKED: 5.0}, 0, None),  # no set in the window
    ({}, {"bls_pubkey_row_hits_total": 9.0}, 64, None),  # a program without the counter
])
def test_sig_subgroup_device_share_reads_the_window_counter(start, end, sets, want):
    run = Run(1, {}, {}, {})
    run._counters_at_start, run._counters_at_end = start, end
    run.work = {"sets": sets}
    got = brun.load_reader("bls.sig_subgroup_device_share")(run)
    assert got == (None if want is None else pytest.approx(want))
