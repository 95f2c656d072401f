"""Reduce a JAX profiler trace (`.xplane.pb`) to the window's device numbers.

The harness traces its window in a run of its own and wraps its calls into
each layer in `jax.profiler.TraceAnnotation("bench.<...>")`, so host and
device events share the profiler's clock. From the trace this computes:

- `window_s`: the length of the host annotation `bench.window`;
- `busy_s`: per chip, the union of the intervals in which a device
  operation ran inside the window, averaged over the chips;
- `programs`: device seconds per named program (jitted module, the
  `(<id>)` suffix stripped), averaged over the chips, and `launches`, the
  number of executions of each;
- `idle_gaps`: the device's idle time inside the window, each gap
  attributed to the innermost `bench.*` annotation open on the host at the
  gap's midpoint (`bench.window` where nothing narrower was open);
- `breakdown`: the ten programs that took most device time and the ten
  host annotations that the longest idle time fell under.

On a TPU the device planes are `/device:TPU:<n>`, with the op events on
the line `XLA Ops` and the program executions on `XLA Modules`. Host and
device events are compared as the trace gives them; on a v5e the chip's
events came out about 3.8 ms earlier than the host annotations that
launched them (benchmark/tests/data/tpu_small.xplane.pb), which is the
error of a window edge and of a gap's attribution. A CPU
trace (the CPU backend runs ops on host threads, each event carrying an
`hlo_module` stat) reduces the same way, which is what lets the test run
on a small trace recorded without a chip; there each op counts as a launch.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

WINDOW = "bench.window"
TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_ID = re.compile(r"\(\d+\)$")
TOP = 10


def program_name(module: str) -> str:
    """`jit_step(123)` -> `jit_step`."""
    return MODULE_ID.sub("", module)


def union_length(intervals: list) -> tuple[int, list]:
    """(total covered ns, merged sorted intervals) of [(start, end)]."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def clip(intervals, lo: int, hi: int) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _stats(event) -> dict:
    try:
        return {k: v for k, v in event.stats}
    except Exception:  # an event without readable stats carries none we use
        return {}


def read_planes(path: str):
    """(host `bench.*` annotations [(name, start, end)], each chip's op
    intervals {chip: [(start, end)]}, each chip's program executions
    {chip: [(start, end, program)]}), in ns."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    annotations, chips_ops, chips_modules = [], {}, {}
    cpu_ops: list = []
    for plane in pd.planes:
        m = TPU_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    chips_ops.setdefault(chip, []).extend(
                        (e.start_ns, e.start_ns + e.duration_ns) for e in line.events)
                elif line.name == "XLA Modules":
                    chips_modules.setdefault(chip, []).extend(
                        (e.start_ns, e.start_ns + e.duration_ns, program_name(e.name))
                        for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        annotations.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
                        continue
                    module = _stats(e).get("hlo_module")
                    if module is not None:
                        cpu_ops.append((e.start_ns, e.start_ns + e.duration_ns,
                                        program_name(str(module))))
    if not chips_ops and cpu_ops:  # CPU backend: one "chip", ops are modules
        chips_ops = {0: [(s, e) for s, e, _ in cpu_ops]}
        chips_modules = {0: cpu_ops}
    return annotations, chips_ops, chips_modules


def innermost_segments(annotations: list) -> tuple[list, list]:
    """Cut the time line at every annotation edge; each segment is labelled
    with the shortest annotation open over it (WINDOW where none is).
    Returns (segment starts, labels), the first segment starting at -inf."""
    edges = sorted({x for _, s, e in annotations for x in (s, e)})
    by_start = sorted(annotations, key=lambda a: a[1])
    starts, labels, open_, i = [float("-inf")], [WINDOW], [], 0
    for t in edges:
        while i < len(by_start) and by_start[i][1] <= t:
            open_.append(by_start[i])
            i += 1
        open_ = [a for a in open_ if a[2] > t]
        starts.append(t)
        labels.append(min(open_, key=lambda a: a[2] - a[1])[0] if open_ else WINDOW)
    return starts, labels


def reduce_file(path: str) -> dict:
    return reduce_events(*read_planes(path))


def reduce_events(annotations: list, chips_ops: dict, chips_modules: dict) -> dict:
    """The reduction of host annotations [(name, start, end)], each chip's
    op intervals {chip: [(start, end)]} and program executions
    {chip: [(start, end, program)]}, all in ns on one clock."""
    windows = [(s, e) for name, s, e in annotations if name == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW} annotation in the trace")
    lo, hi = windows[0]
    starts, labels = innermost_segments(
        [(n, s, e) for n, s, e in annotations if n != WINDOW and e > lo and s < hi])
    chips = sorted(chips_ops) or [0]
    busy_total, programs, launches, gaps = 0, {}, {}, {}
    for chip in chips:
        busy, merged = union_length(clip(chips_ops.get(chip, []), lo, hi))
        busy_total += busy
        for s, e, name in chips_modules.get(chip, []):
            if e > lo and s < hi:
                programs[name] = programs.get(name, 0) + min(e, hi) - max(s, lo)
                launches[name] = launches.get(name, 0) + 1
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge <= gs:
                continue
            label = labels[bisect.bisect_right(starts, (gs + ge) / 2) - 1]
            gaps[label] = gaps.get(label, 0) + ge - gs
    n = len(chips)
    programs_s = {k: v / n / 1e9 for k, v in programs.items()}
    gaps_s = {k: v / n / 1e9 for k, v in gaps.items()}
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_total / n / 1e9,
        "chips": n,
        "programs": programs_s,
        "launches": {k: v // n for k, v in launches.items()},
        "idle_gaps": gaps_s,
        "breakdown": {
            "device_ops": [[k, v] for k, v in
                           sorted(programs_s.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": [[k, v] for k, v in
                          sorted(gaps_s.items(), key=lambda kv: -kv[1])[:TOP]],
        },
    }


def reduce_dir(trace_dir: str) -> dict:
    """Reduce the one `.xplane.pb` the profiler wrote under `trace_dir`."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if len(paths) != 1:
        raise ValueError(f"{trace_dir}: expected one .xplane.pb, found {len(paths)}")
    return reduce_file(paths[0])


def holds(trace: dict, pattern: str, at_least: int, tolerance: float = 0.1) -> bool:
    """Whether the trace can be read per unit of the window's work: it
    holds at least `at_least` executions of the programs matching
    `pattern` (the window ran that many), and the device time of all its
    programs agrees with its busy union within `tolerance`, so no
    program's events were dropped or their durations stretched. A reader
    that gets False returns nothing."""
    rx = re.compile(pattern)
    launched = sum(v for k, v in trace["launches"].items() if rx.search(k))
    busy = trace["busy_s"]
    return (launched >= at_least > 0 and busy > 0
            and abs(sum(trace["programs"].values()) - busy) <= tolerance * busy)


def program_seconds(trace: dict, pattern: str) -> float | None:
    """Device seconds of the programs whose name matches `pattern`, or None
    where no such program ran in the window."""
    rx = re.compile(pattern)
    hits = [v for k, v in trace["programs"].items() if rx.search(k)]
    return sum(hits) if hits else None
