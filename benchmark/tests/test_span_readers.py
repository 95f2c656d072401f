"""The readers of the program's spans, on hand-made span lists."""
import pytest

from benchmark import run as brun
from benchmark import spans as bspans
from benchmark.context import Run

PER_SET = {"bls.prep_ms": "bls.prep", "bls.sig_decode_ms": "bls.prep.sig_decode",
           "bls.hash_to_curve_ms": "bls.prep.hash_to_curve",
           "bls.pk_aggregate_host_ms": "bls.prep.aggregate"}
PER_EPOCH = {"epoch.root_refresh_ms": "engine.root_refresh",
             "epoch.root_readout_ms": "engine.root_readout",
             "epoch.root_assemble_ms": "engine.root_assemble",
             "epoch.state_root_ms": "engine.state_root",
             "epoch.epilogue_ms": "engine.epilogue"}


def span(name, t_start, duration, thread_id=1):
    return {"name": name, "t_start": t_start, "duration": duration, "thread_id": thread_id}


def run_with(spans, work, window=(0.0, 100.0)):
    """A Run whose window holds `spans` (the program's tracer, as a traced
    run installs it) and whose drivers counted `work`."""
    from consensus_specs_tpu.obs.metrics import MetricsRegistry
    from consensus_specs_tpu.obs.trace import Tracer

    run = Run(1, {}, {}, {})
    run._tracer = Tracer(registry=MetricsRegistry())
    run._tracer.finished = list(spans)
    run.window_t = window
    run.work = dict(work)
    return run


@pytest.mark.parametrize("metric,name,unit",
                         [(m, n, "sets") for m, n in PER_SET.items()]
                         + [(m, n, "epochs") for m, n in PER_EPOCH.items()])
def test_ms_per_unit_of_work_in_the_window(metric, name, unit):
    read = brun.load_reader(metric)
    spans = [span(name, 1.0, 0.004), span(name, 2.0, 0.006),
             span(name, -1.0, 5.0),  # started before the window: not counted
             span("other.span", 3.0, 9.0)]
    assert read(run_with(spans, {unit: 4})) == pytest.approx(2.5)  # 10 ms over 4
    assert read(run_with(spans, {})) is None  # no denominator
    assert read(run_with(spans, {unit: 0})) is None
    assert read(run_with([span("other.span", 1.0, 1.0)], {unit: 4})) is None  # never fired


def test_unspanned_is_the_flushes_self_time_on_their_thread():
    read = brun.load_reader("bls.unspanned_ms")
    spans = [
        span("bls.deferred_flush", 10.0, 10.0),
        span("sched.dispatch", 11.0, 8.0),  # [11, 19]
        span("bls.prep", 12.0, 3.0),  # inside the dispatch: adds nothing
        span("bls.flush.device", 18.5, 1.0),  # [18.5, 19.5]: extends to 19.5
        span("firehose.flush", 10.0, 10.0, thread_id=2),  # another thread
        span("bls.deferred_flush", 30.0, 1.0),  # nothing nested: all self time
        span("later.span", 31.0, 5.0),  # after the second flush
    ]
    # 10 - (19.5 - 11) = 1.5 s, plus 1.0 s; over 5 sets
    assert read(run_with(spans, {"sets": 5})) == pytest.approx(1000.0 * 2.5 / 5)
    assert read(run_with(spans, {})) is None
    assert read(run_with([span("bls.prep", 1.0, 1.0)], {"sets": 5})) is None


def test_self_seconds_clips_and_keeps_to_the_thread():
    outer = span("outer", 0.0, 10.0)
    inner = [outer, span("a", 2.0, 20.0), span("b", 1.0, 0.5, thread_id=9)]
    assert bspans.self_seconds(outer, inner) == pytest.approx(2.0)  # clipped at 10


def test_no_tracer_means_no_span_metric():
    """The untraced run, and a program that has none of these spans."""
    run = Run(1, {}, {}, {})
    run.work = {"sets": 64, "epochs": 10}
    for metric in list(PER_SET) + list(PER_EPOCH) + ["bls.unspanned_ms"]:
        assert brun.load_reader(metric)(run) is None
