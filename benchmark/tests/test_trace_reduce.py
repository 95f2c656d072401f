"""benchmark/trace_reduce.py on hand-made events and on a recorded trace."""
import os

import pytest

from benchmark import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "tpu_small.xplane.pb")


def test_reduction_of_known_events():
    # window [0, 100]; ops [10, 30] and [20, 40] overlap (busy 30),
    # [60, 70] (busy 10), [95, 120] clipped to [95, 100] (busy 5)
    annotations = [("bench.window", 0, 100), ("bench.step", 5, 45),
                   ("bench.wait", 40, 95), ("bench.inner", 41, 59)]
    ops = {0: [(10, 30), (20, 40), (60, 70), (95, 120)]}
    modules = {0: [(10, 40, "jit_a"), (60, 70, "jit_b"), (95, 120, "jit_a")]}
    r = tr.reduce_events(annotations, ops, modules)
    assert r["window_s"] == 100e-9
    assert r["busy_s"] == pytest.approx(45e-9)
    assert r["programs"] == {"jit_a": pytest.approx(35e-9), "jit_b": pytest.approx(10e-9)}
    assert r["launches"] == {"jit_a": 2, "jit_b": 1}
    # gaps: [0,10] mid 5 -> bench.step; [40,60] mid 50 -> bench.inner
    # (innermost); [70,95] mid 82.5 -> bench.wait
    assert r["idle_gaps"] == {"bench.step": pytest.approx(10e-9),
                              "bench.inner": pytest.approx(20e-9),
                              "bench.wait": pytest.approx(25e-9)}
    assert r["busy_s"] + sum(r["idle_gaps"].values()) == pytest.approx(r["window_s"])
    assert r["breakdown"]["device_ops"][0] == ["jit_a", pytest.approx(35e-9)]


def test_two_chips_are_averaged():
    annotations = [("bench.window", 0, 100)]
    r = tr.reduce_events(annotations, {0: [(0, 50)], 1: [(0, 100)]},
                         {0: [(0, 50, "jit_a")], 1: [(0, 100, "jit_a")]})
    assert r["busy_s"] == pytest.approx(75e-9) and r["chips"] == 2
    assert r["programs"]["jit_a"] == pytest.approx(75e-9)


def test_program_name_drops_the_execution_id():
    assert tr.program_name("jit_step(1234)") == "jit_step"
    assert tr.program_name("jit_step") == "jit_step"


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no recorded trace")
def test_recorded_tpu_trace():
    """benchmark/tests/record_trace_fixture.py on a v5e chip: three
    launches of jit_work, with two 50 ms host waits between them. The
    chip's clock in this trace runs about 3.8 ms behind the host's: each
    execution starts that much before the host annotation that launched
    it, so the first one falls just before the window and is clipped."""
    annotations, chips_ops, chips_modules = tr.read_planes(FIXTURE)
    assert chips_ops, "no device plane with ops in the fixture"
    assert [m[2] for m in chips_modules[0]] == ["jit_work"] * 3
    r = tr.reduce_file(FIXTURE)
    assert r["launches"]["jit_work"] == 2
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["programs"]["jit_work"] <= r["busy_s"] * 1.001
    assert r["busy_s"] + sum(r["idle_gaps"].values()) == pytest.approx(r["window_s"])
    label, seconds = r["breakdown"]["idle_gaps"][0]
    assert label == "bench.host_wait" and seconds >= 0.09
    # the union never exceeds the sum of the ops it covers
    lo, hi = [(s, e) for n, s, e in annotations if n == "bench.window"][0]
    total = sum(min(e, hi) - max(s, lo) for s, e in tr.clip(chips_ops[0], lo, hi))
    assert r["busy_s"] <= total / 1e9 + 1e-12


def test_holds_needs_every_execution_and_agreeing_times():
    annotations = [("bench.window", 0, 100)]
    r = tr.reduce_events(annotations, {0: [(10, 40), (60, 70)]},
                         {0: [(10, 40, "jit_step"), (60, 70, "jit_scan")]})
    assert tr.holds(r, "^jit_step$", 1)
    assert not tr.holds(r, "^jit_step$", 2)  # the window ran two, the trace holds one
    assert not tr.holds(r, "^jit_other$", 1)
    # program times that do not agree with the busy union: events lost
    r2 = tr.reduce_events(annotations, {0: [(10, 40), (60, 70)]}, {0: [(10, 40, "jit_step")]})
    assert not tr.holds(r2, "^jit_step$", 1)


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no recorded trace")
def test_recorded_tpu_trace_holds_its_executions():
    r = tr.reduce_file(FIXTURE)
    assert tr.holds(r, "^jit_work$", 2) and not tr.holds(r, "^jit_work$", 3)
