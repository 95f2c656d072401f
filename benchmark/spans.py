"""Arithmetic on the program's spans, shared by the span readers.

A span is a dict from the program's tracer (`Run.spans`): `t_start` and
`duration` in seconds on the monotonic clock, and the `thread_id` it ran on.
"""
from __future__ import annotations

from benchmark.trace_reduce import union_length


def ms_per(run, name: str, unit: str) -> float | None:
    """Host ms per unit of the window's work (`run.work[unit]`) in the
    spans called `name`; None where the work lacks the unit or no such span
    fired in the window."""
    count = run.work.get(unit)
    spans = run.spans(name)
    if not count or not spans:
        return None
    return 1000.0 * sum(s["duration"] for s in spans) / count


def self_seconds(outer: dict, spans: list) -> float:
    """Seconds of `outer` that no other span of its thread, nested in it,
    covers."""
    lo = outer["t_start"]
    hi = lo + outer["duration"]
    nested = [(max(s["t_start"], lo), min(s["t_start"] + s["duration"], hi))
              for s in spans
              if s is not outer and s["thread_id"] == outer["thread_id"]
              and lo <= s["t_start"] < hi]
    return outer["duration"] - union_length(nested)[0]
