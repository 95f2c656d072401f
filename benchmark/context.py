"""What a run knows, handed to its driver and to the per-layer readers."""
from __future__ import annotations

import sys


def log(msg: str) -> None:
    """A line on standard error, before the result."""
    print(msg, file=sys.stderr, flush=True)


class Run:
    """One run of one cell.

    Drivers read `seed`, `config` and `traffic`, call `log`, and fill
    `work` with the counts of the window. Per-layer readers read `work`,
    `config`, `trace` (benchmark.trace_reduce's reduction of the profiler
    trace), `spans(name)` (the program's own spans inside the window;
    traced runs only), `counter_delta(name, **labels)` (how far one of the
    program's counters moved in the window), `peaks` and
    `compiles_in_window`."""

    log = staticmethod(log)

    def __init__(self, seed: int, config: dict, traffic: dict, peaks: dict):
        self.seed = int(seed)
        self.config = config
        self.traffic = traffic
        self.peaks = peaks
        self.window_t = None  # (start, end) on time.monotonic()
        self.compiles_in_window = None
        self.trace = None
        self.work: dict = {}
        self._tracer = None
        self._tracker = None
        self._registry = None
        self._counters_at_start: dict = {}
        self._counters_at_end: dict = {}

    def install_observers(self, traced: bool) -> None:
        """The program's compile counter always; its span tracer only in a
        traced run, so the end-to-end runs measure with tracing off."""
        from consensus_specs_tpu.obs.metrics import REGISTRY
        from consensus_specs_tpu.obs.recompile import CompileTracker

        self._registry = REGISTRY
        self._tracker = CompileTracker().install()
        if traced:
            from consensus_specs_tpu.obs.trace import Tracer

            self._tracer = Tracer(max_spans=1 << 20).install()

    def compiles(self) -> int:
        return sum(self._tracker.kernels().values())

    def compile_seconds(self) -> dict:
        """Backend compile (or cache load) seconds by program so far."""
        return {k: round(v, 3) for k, v in self._tracker.kernel_seconds().items()}

    def mark_window(self, start: float, end: float | None = None) -> None:
        """Called by the harness at the window's two edges."""
        snap = self._registry.snapshot()["counters"]
        if end is None:
            self._counters_at_start = snap
        else:
            self._counters_at_end = snap
            self.window_t = (start, end)

    def counter_delta(self, name: str, **labels) -> float:
        """How far the program's counter `name` moved in the window, summed
        over its series whose labels include `labels`."""
        want = [f'{k}="{v}"' for k, v in labels.items()]

        def total(snap):
            return sum(v for key, v in snap.items()
                       if (key == name or key.startswith(name + "{"))
                       and all(w in key for w in want))

        return total(self._counters_at_end) - total(self._counters_at_start)

    def spans(self, name: str) -> list[dict]:
        """Finished program spans called `name` that started in the window."""
        if self._tracer is None or self.window_t is None:
            return []
        lo, hi = self.window_t
        return [s for s in self._tracer.spans(name) if lo <= s["t_start"] <= hi]
