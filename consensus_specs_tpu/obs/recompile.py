"""Recompile / compile-cache-pressure tracker.

ROADMAP item 5 names the failure mode: as scenario diversity multiplies
shapes, every new (kernel, shape) pair silently costs a fresh XLA
compilation. This tracker counts DISTINCT jitted-shape compilations per
kernel so a test (or a soak) can pin "this loop compiles once" the same way
tests/test_rlc_grouped.py pins Miller-loop counts via eval_shape.

Two attachment points inside jax, both observational:

  * the lowering log record "Compiling <fun_name> with global shapes and
    types <args>." (jax._src.interpreters.pxla) carries the kernel NAME and
    the abstract shapes — a logging.Handler parses it into per-kernel
    counters (`compile_total{kernel=...}`) and a distinct-shape set. jax
    0.9 names the module `jit(<fun_name>)`; `kernel_name` strips that
    wrapper so the counters stay keyed by the bare function name;
  * `jax.monitoring`'s BACKEND_COMPILE_EVENT duration stream feeds a
    `compile_seconds` histogram (the wall-clock the cache pressure
    actually costs; a persistent-cache hit is timed too) and the
    per-kernel seconds behind `kernel_seconds()`.

jax is imported ONLY inside install(): off-device (or with jax absent) the
module stays importable and install() degrades to a no-op tracker, the same
contract the obs package promises tpulint's import-layering rule.

jax.monitoring has no single-listener unregister, so a module-level
trampoline registers ONCE and routes through the installed tracker global;
uninstall() just clears the global.
"""
from __future__ import annotations

import logging
import re
import threading
from typing import Optional

from .metrics import REGISTRY, MetricsRegistry

# The duration event dispatch.py records around every backend compile.
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_COMPILE_MSG_PREFIX = "Compiling %s"
# jax 0.9 logs the module name as `jit(<fun_name>)` (older builds logged the
# bare name, which passes through unchanged).
_WRAPPED_NAME = re.compile(r"^(?:jit|pjit)\((.*)\)$")


def kernel_name(module_name: str) -> str:
    """The bare jitted function name of a compile record's module name."""
    m = _WRAPPED_NAME.match(module_name)
    return m.group(1) if m else module_name


class _CompileLogHandler(logging.Handler):
    """Parses jax's per-compilation log records; attached to the pxla
    logger by install(). Never raises into jax's logging path."""

    def __init__(self, tracker: "CompileTracker", forward_level=None):
        super().__init__(level=logging.DEBUG)
        self._tracker = tracker
        # install() opened the logger below its old level and stopped its
        # propagation; records at or above the old level still go up
        self._forward_level = forward_level

    def emit(self, record: logging.LogRecord) -> None:
        try:
            if (self._forward_level is not None
                    and record.levelno >= self._forward_level):
                logging.getLogger(record.name).parent.handle(record)
            if not record.msg.startswith(_COMPILE_MSG_PREFIX) or not record.args:
                return
            kernel = kernel_name(str(record.args[0]))
            shapes = str(record.args[1]) if len(record.args) > 1 else ""
            self._tracker._on_compile(kernel, shapes)
        except Exception:
            pass


def _monitoring_trampoline(event: str, duration: float, **kwargs) -> None:
    tracker = _TRACKER
    if tracker is None or event != BACKEND_COMPILE_EVENT:
        return
    tracker._on_backend_compile(
        duration, kernel_name(str(kwargs.get("fun_name", "?"))))


_TRAMPOLINE_REGISTERED = False
_TRACKER: Optional["CompileTracker"] = None


class CompileTracker:
    """Counts per-kernel compilations and distinct (kernel, shape) pairs.

    install() wires the jax hooks (idempotent; returns self either way);
    uninstall() detaches the log handler and silences the trampoline.
    When jax cannot be imported, install() leaves the tracker enabled as a
    pure sink — counts stay zero, nothing raises."""

    def __init__(self, registry: MetricsRegistry = REGISTRY):
        self.registry = registry
        self._lock = threading.Lock()
        self._counts: dict[str, int] = {}
        self._shapes: dict[str, set] = {}
        self._seconds: dict[str, float] = {}
        self._handler: Optional[_CompileLogHandler] = None
        self._logger: Optional[logging.Logger] = None
        self._prev_level: Optional[int] = None

    # -- jax-side callbacks ----------------------------------------------------

    def _on_compile(self, kernel: str, shapes: str) -> None:
        with self._lock:
            self._counts[kernel] = self._counts.get(kernel, 0) + 1
            self._shapes.setdefault(kernel, set()).add(shapes)
            distinct = len(self._shapes[kernel])
        self.registry.counter("compile_total", kernel=kernel).inc()
        self.registry.gauge("compile_distinct_shapes", kernel=kernel).set(distinct)

    def _on_backend_compile(self, duration: float, kernel: str) -> None:
        self.registry.histogram("compile_seconds").observe(duration)
        with self._lock:
            self._seconds[kernel] = self._seconds.get(kernel, 0.0) + duration

    # -- readout ---------------------------------------------------------------

    def compiles(self, kernel: str) -> int:
        return self._counts.get(kernel, 0)

    def distinct_shapes(self, kernel: str) -> int:
        return len(self._shapes.get(kernel, ()))

    def kernels(self) -> dict[str, int]:
        with self._lock:
            return dict(sorted(self._counts.items()))

    def kernel_seconds(self) -> dict[str, float]:
        """Backend compile seconds per kernel (cache hits included)."""
        with self._lock:
            return dict(sorted(self._seconds.items()))

    # -- lifecycle -------------------------------------------------------------

    def install(self) -> "CompileTracker":
        global _TRACKER, _TRAMPOLINE_REGISTERED
        _TRACKER = self
        try:
            import jax.monitoring  # deferred: obs/ is jax-free at module level
            from jax._src.interpreters import pxla
        except Exception:
            return self  # no-op degrade: importable and callable without jax
        if not _TRAMPOLINE_REGISTERED:
            jax.monitoring.register_event_duration_secs_listener(
                _monitoring_trampoline)
            _TRAMPOLINE_REGISTERED = True
        if self._handler is None:
            logger = logging.getLogger(pxla.__name__)
            self._logger = logger
            self._prev_level = logger.level
            # The compile log is DEBUG unless jax_log_compiles; the logger
            # must be opened up for the handler to see it. jax's own stderr
            # handler on the "jax" logger prints whatever reaches it, so the
            # opened logger stops propagating and the handler forwards only
            # the records the ancestors saw before.
            forward = None
            if logger.getEffectiveLevel() > logging.DEBUG:
                forward = logger.getEffectiveLevel()
                logger.setLevel(logging.DEBUG)
                logger.propagate = False
            self._handler = _CompileLogHandler(self, forward)
            logger.addHandler(self._handler)
        return self

    def uninstall(self) -> None:
        global _TRACKER
        if _TRACKER is self:
            _TRACKER = None
        if self._handler is not None and self._logger is not None:
            self._logger.removeHandler(self._handler)
            if self._prev_level is not None:
                self._logger.setLevel(self._prev_level)
            self._logger.propagate = True
            self._handler = None
            self._logger = None
            self._prev_level = None


def current_tracker() -> Optional[CompileTracker]:
    return _TRACKER


def uninstall() -> None:
    """Detach whatever tracker is installed (test-teardown safety net)."""
    t = _TRACKER
    if t is not None:
        t.uninstall()
