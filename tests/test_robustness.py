"""Unit coverage for the robustness package: fault-plan determinism, the
retry policy + classification, circuit-breaker transitions, and engine
checkpoint capture/restore with the integrity digest. The end-to-end chaos
convergence runs live in tests/test_chaos_epoch.py."""
import numpy as np
import pytest

from consensus_specs_tpu.robustness import breaker as rbreaker
from consensus_specs_tpu.robustness.breaker import CircuitBreaker
from consensus_specs_tpu.robustness.checkpoint import (
    CheckpointIntegrityError,
    EngineCheckpoint,
)
from consensus_specs_tpu.robustness.faults import (
    CorruptAuxError,
    FatalFault,
    FaultPlan,
    FaultSpec,
    TransientFault,
    corrupt_array,
    fire,
    mangle_bytes,
)
from consensus_specs_tpu.robustness.retry import (
    RetryPolicy,
    call_with_retry,
    is_compile_or_resource_error,
    is_device_failure,
    is_retryable,
)


# --- fault plans -------------------------------------------------------------


def test_fault_plan_at_calls_exact_schedule():
    plan = FaultPlan(seed=1, sites={
        "s": FaultSpec(kind="raise", at_calls=(2, 4), exc="transient"),
    })
    fired = []
    with plan.active():
        for i in range(1, 6):
            try:
                fire("s")
            except TransientFault:
                fired.append(i)
    assert fired == [2, 4]
    assert plan.calls("s") == 5
    assert plan.fires("s") == 2
    assert [e.call_index for e in plan.events] == [2, 4]


def test_fault_plan_rate_is_seed_deterministic():
    def run(seed):
        plan = FaultPlan(seed=seed, sites={
            "s": FaultSpec(kind="raise", rate=0.4, exc="transient"),
        })
        fired = []
        with plan.active():
            for i in range(1, 41):
                try:
                    fire("s")
                except TransientFault:
                    fired.append(i)
        return fired

    a, b, c = run(7), run(7), run(8)
    assert a == b  # same seed -> identical schedule
    assert a != c  # different seed -> (overwhelmingly) different schedule
    assert 0 < len(a) < 40


def test_fault_plan_site_streams_are_independent():
    """Extra traffic on one site must not shift another site's schedule —
    each site draws from its own (seed, site)-keyed stream."""
    def fired_on_b(calls_on_a):
        plan = FaultPlan(seed=3, sites={
            "a": FaultSpec(kind="raise", rate=0.5, exc="transient"),
            "b": FaultSpec(kind="raise", rate=0.5, exc="transient"),
        })
        out = []
        with plan.active():
            for _ in range(calls_on_a):
                try:
                    fire("a")
                except TransientFault:
                    pass
            for i in range(1, 21):
                try:
                    fire("b")
                except TransientFault:
                    out.append(i)
        return out

    assert fired_on_b(0) == fired_on_b(50)


def test_fault_plan_max_fires_caps_without_shifting_draws():
    """max_fires suppresses fires past the cap but still consumes the RNG
    draw, so the uncapped and capped schedules agree on every index below
    the cap AND on which indices would have drawn true."""
    def run(cap):
        plan = FaultPlan(seed=5, sites={
            "s": FaultSpec(kind="raise", rate=0.5, max_fires=cap,
                           exc="transient"),
        })
        fired = []
        with plan.active():
            for i in range(1, 31):
                try:
                    fire("s")
                except TransientFault:
                    fired.append(i)
        return fired

    unbounded = run(None)
    capped = run(2)
    assert capped == unbounded[:2]


def test_corrupt_and_mangle_kinds():
    plan = FaultPlan(seed=9, sites={
        "c": FaultSpec(kind="corrupt", at_calls=(1, 2), corruption="nan"),
        "t": FaultSpec(kind="corrupt", at_calls=(1,), corruption="truncate"),
        "m": FaultSpec(kind="mangle", at_calls=(1, 2), corruption="truncate"),
    })
    with plan.active():
        arr = np.arange(6, dtype=np.uint64)
        nan = corrupt_array("c", arr)
        assert nan.dtype == np.float64 and nan.shape == arr.shape
        assert np.isnan(nan).all()
        truncated = corrupt_array("t", np.arange(4))  # "t" call 1: truncate
        assert truncated.shape == (3,)
        nan2 = corrupt_array("c", np.arange(4))  # "c" call 2: nan again
        assert nan2.shape == (4,) and nan2.dtype == np.float64
        half = mangle_bytes("m", b"0123456789")
        assert half == b"01234"
        assert mangle_bytes("m", b"ok") != b"ok"  # second at_call
        # a site past its schedule passes data through untouched
        assert mangle_bytes("m", b"ok") == b"ok"
        assert corrupt_array("t", np.arange(4)).shape == (4,)


def test_uninstalled_plan_is_a_noop():
    fire("anything")  # no plan installed: must not raise
    a = np.arange(3)
    assert corrupt_array("anything", a) is a
    assert mangle_bytes("anything", b"x") == b"x"


# --- classification + retry --------------------------------------------------


def test_classification():
    class FakeXla(Exception):
        pass

    FakeXla.__name__ = "XlaRuntimeError"
    assert is_retryable(TransientFault("x"))
    assert is_retryable(CorruptAuxError("x"))
    assert is_retryable(TimeoutError())
    assert is_retryable(ConnectionResetError())
    assert is_retryable(FakeXla("device gone"))
    assert not is_retryable(FatalFault("x"))
    assert not is_retryable(AssertionError("host bug"))
    assert not is_retryable(ValueError("host bug"))
    # degradation eligibility: retryables plus injected fatals
    assert is_device_failure(FatalFault("x"))
    assert is_device_failure(FakeXla("x"))
    assert not is_device_failure(ValueError("x"))


def _xla_error(msg):
    from jax.errors import JaxRuntimeError

    return JaxRuntimeError(msg)


# What the TPU runtime raises for a program that cannot compile or fit:
# re-issuing it fails the same way, and the host path would hide it.
_FATAL_XLA_MESSAGES = [
    "RESOURCE_EXHAUSTED: Out of memory allocating 17179869184 bytes.",
    "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
    "memory in memory space hbm.",
    "INTERNAL: Mosaic failed to compile TPU kernel.",
    "INVALID_ARGUMENT: Executable expected parameter 0 of size 8.",
    "UNIMPLEMENTED: unsupported op on this platform.",
    "Compilation failure: unsupported tiling.",
]


@pytest.mark.parametrize("msg", _FATAL_XLA_MESSAGES)
def test_compile_and_resource_errors_are_fatal(msg):
    exc = _xla_error(msg)
    assert is_compile_or_resource_error(exc)
    assert not is_retryable(exc)
    assert not is_device_failure(exc)


@pytest.mark.parametrize("msg", [
    "UNAVAILABLE: device lost", "DEADLINE_EXCEEDED: transfer", "device gone"])
def test_runtime_xla_errors_stay_retryable(msg):
    exc = _xla_error(msg)
    assert not is_compile_or_resource_error(exc)
    assert is_retryable(exc) and is_device_failure(exc)


def _raising_class(exc):
    """Scheduler work class whose device execute always raises `exc`;
    records how often each path ran."""
    from consensus_specs_tpu.sched.classes import WorkClass

    class RaisingClass(WorkClass):
        name = "raising"
        kinds = ("check",)
        executed = 0
        degraded = 0

        def execute(self, requests):
            self.executed += 1
            raise exc

        def execute_degraded(self, requests):
            self.degraded += 1
            return np.ones(len(requests), dtype=bool)

    return RaisingClass()


def _run_raising(exc):
    from consensus_specs_tpu.obs.metrics import MetricsRegistry
    from consensus_specs_tpu.sched import Request, Scheduler

    wc = _raising_class(exc)
    reg = MetricsRegistry()
    sch = Scheduler(classes=[wc], registry=reg,
                    retry_policy=RetryPolicy(max_attempts=4, base_delay=0.0,
                                             max_delay=0.0))
    h = sch.submit(Request(work_class="raising", kind="check", payload=()))
    return wc, reg, sch, h


@pytest.mark.parametrize("msg", [
    "RESOURCE_EXHAUSTED: Out of memory allocating 17179869184 bytes.",
    "INTERNAL: XLA:TPU compile permanent error.",
])
def test_sched_never_degrades_compile_or_resource_errors(msg):
    from jax.errors import JaxRuntimeError

    wc, reg, sch, h = _run_raising(_xla_error(msg))
    with pytest.raises(JaxRuntimeError):
        sch.flush("raising")
    assert wc.executed == 1  # no re-issue of a program that cannot run
    assert wc.degraded == 0  # and never answered on the host
    assert reg.counter_value("sched_degraded_total", work_class="raising") == 0
    assert sch.breaker("raising").events == []
    with pytest.raises(JaxRuntimeError):
        h.result()


@pytest.mark.parametrize("make_exc", [
    lambda: _xla_error("UNAVAILABLE: device lost"),
    lambda: TransientFault("injected"),
    lambda: FatalFault("injected"),
], ids=["unavailable", "transient_fault", "fatal_fault"])
def test_sched_still_degrades_unavailable_and_injected_faults(make_exc):
    wc, reg, sch, h = _run_raising(make_exc())
    sch.flush("raising")
    assert h.result() is True
    assert wc.degraded == 1
    assert reg.counter_value("sched_degraded_total", work_class="raising") == 1


def test_bridge_never_degrades_a_resource_error(monkeypatch):
    """The epoch bridge re-raises a RESOURCE_EXHAUSTED dispatch instead of
    running `spec.process_epoch` on the host."""
    from jax.errors import JaxRuntimeError

    from consensus_specs_tpu.engine import bridge

    def oom(*args, **kwargs):
        raise _xla_error("RESOURCE_EXHAUSTED: Out of memory in hbm.")

    class _Spec:
        host_epochs = 0

        def process_epoch(self, state):
            self.host_epochs += 1

    monkeypatch.setattr(bridge, "_apply_epoch_device", oom)
    spec, brk = _Spec(), CircuitBreaker(name="t")
    with pytest.raises(JaxRuntimeError):
        bridge.apply_epoch_via_engine(spec, object(), breaker=brk)
    assert spec.host_epochs == 0
    assert brk.degraded_epochs == 0 and brk.events == []


def test_retry_policy_delay_growth_and_ceiling():
    from random import Random

    p = RetryPolicy(max_attempts=0, base_delay=0.1, backoff=2.0,
                    max_delay=0.35, jitter=0.0)
    rng = Random(0)
    delays = [p.delay(a, rng) for a in (1, 2, 3, 4)]
    assert delays == [0.1, 0.2, 0.35, 0.35]  # doubles, then clamps
    jittered = RetryPolicy(base_delay=0.1, jitter=0.5).delay(1, Random(0))
    assert 0.1 <= jittered <= 0.15


def test_call_with_retry_absorbs_then_succeeds():
    calls = {"n": 0}
    slept = []

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransientFault("not yet")
        return "done"

    retries = []
    out = call_with_retry(
        flaky,
        RetryPolicy(max_attempts=4, base_delay=0.01, backoff=2.0,
                    max_delay=1.0, jitter=0.0),
        sleep=slept.append,
        on_retry=lambda attempt, exc: retries.append(attempt))
    assert out == "done" and calls["n"] == 3
    assert slept == [0.01, 0.02]
    assert retries == [1, 2]


def test_call_with_retry_raises_fatal_immediately_and_exhausts_budget():
    calls = {"n": 0}

    def fatal():
        calls["n"] += 1
        raise FatalFault("hard crash")

    with pytest.raises(FatalFault):
        call_with_retry(fatal, RetryPolicy(max_attempts=5, base_delay=0.0))
    assert calls["n"] == 1  # fatal: no second attempt

    calls["n"] = 0

    def always_transient():
        calls["n"] += 1
        raise TransientFault("still down")

    with pytest.raises(TransientFault):
        call_with_retry(always_transient,
                        RetryPolicy(max_attempts=3, base_delay=0.0,
                                    max_delay=0.0))
    assert calls["n"] == 3  # full budget consumed, final error re-raised


# --- circuit breaker ---------------------------------------------------------


def test_breaker_opens_probes_and_rearms():
    brk = CircuitBreaker(failure_threshold=2, name="t")
    assert brk.on_attempt() == "closed"
    brk.record_failure()
    assert brk.state == rbreaker.CLOSED  # below threshold: still closed
    assert brk.on_attempt() == "closed"
    brk.record_failure()
    assert brk.state == rbreaker.OPEN
    # open -> the next attempt is a half-open probe
    assert brk.on_attempt() == "probe"
    brk.record_failure()  # probe failed: re-open immediately
    assert brk.state == rbreaker.OPEN
    assert brk.on_attempt() == "probe"
    brk.record_success()  # probe succeeded: re-armed
    assert brk.state == rbreaker.CLOSED
    assert brk.consecutive_failures == 0
    assert brk.degraded_epochs == 3
    assert [e["event"] for e in brk.events] == [
        "degraded_to_python", "degraded_to_python", "opened",
        "half_open_probe", "degraded_to_python", "opened",
        "half_open_probe", "rearmed",
    ]
    brk.reset()
    assert brk.state == rbreaker.CLOSED and brk.events == []


def test_breaker_event_ring_is_bounded_with_drop_counter():
    """Regression for the unbounded event log: a week-long degraded soak
    must not grow `events` past the ring size, dropped entries are counted
    (on the ring AND in the registry), and the full per-event history
    survives in counter form after the ring wraps."""
    from consensus_specs_tpu.obs import metrics as obs_metrics

    brk = CircuitBreaker(failure_threshold=2, name="ring-test",
                         event_ring_size=8)
    base = obs_metrics.REGISTRY.counter_value(
        "breaker_events_total", breaker="ring-test", event="degraded_to_python")
    for _ in range(50):
        brk.record_failure()  # every one logs degraded_to_python
    assert len(brk.events) == 8
    assert brk.events.dropped == 50 + 1 - 8  # +1: the "opened" transition
    assert obs_metrics.REGISTRY.counter_value(
        "breaker_events_dropped_total", breaker="ring-test") == brk.events.dropped
    # counters kept the whole history the ring forgot
    assert obs_metrics.REGISTRY.counter_value(
        "breaker_events_total", breaker="ring-test",
        event="degraded_to_python") - base == 50
    # the ring still behaves like the list the older tests compare against
    assert brk.events[-1]["event"] == "degraded_to_python"
    brk.reset()
    assert brk.events == [] and brk.events.dropped == 0


# --- checkpoints -------------------------------------------------------------


@pytest.fixture(scope="module")
def spec():
    from consensus_specs_tpu.compiler import get_spec

    return get_spec("altair", "minimal")


def _engine(spec, seed=31):
    from consensus_specs_tpu.engine.resident import ResidentEpochEngine
    from consensus_specs_tpu.testlib.state import prepared_epoch_state

    st = prepared_epoch_state(spec, start_epoch=6, seed=seed)
    return ResidentEpochEngine(spec, st)


def test_checkpoint_roundtrip_and_tamper(spec, tmp_path):
    from consensus_specs_tpu.crypto import bls

    was = bls.bls_active
    bls.bls_active = False
    try:
        eng = _engine(spec)
        eng.step_epoch()
        eng.step_epoch()
        ck = EngineCheckpoint.capture(eng)
        assert ck.digest and ck.meta["format"] == "engine-checkpoint-v1"
        ck.verify()

        # disk roundtrip preserves the digest and every array bit
        path = tmp_path / "engine.ckpt.npz"
        ck.save(path)
        loaded = EngineCheckpoint.load(path)
        assert loaded.digest == ck.digest
        assert loaded.compute_digest() == ck.compute_digest()

        # restore continues to the same root as the original engine
        eng2 = loaded.restore(spec)
        eng.step_epoch()
        eng2.step_epoch()
        assert eng2.state_root() == eng.state_root()

        # fork mismatch is refused
        from consensus_specs_tpu.compiler import get_spec

        with pytest.raises(CheckpointIntegrityError):
            loaded.restore(get_spec("bellatrix", "minimal"))

        # tampering with an array breaks the digest loudly
        ck.dev["balances"] = ck.dev["balances"] + 1
        with pytest.raises(CheckpointIntegrityError):
            ck.verify()
        loaded.digest = "0" * 64
        with pytest.raises(CheckpointIntegrityError):
            loaded.restore(spec)
    finally:
        bls.bls_active = was


# --- import hygiene ----------------------------------------------------------


def test_robustness_importable_without_jax():
    """tpulint enforces this statically; this is the runtime twin — the
    whole package (and its consumers' import of it) must work in a process
    where jax cannot be imported at all."""
    import subprocess
    import sys

    code = """
import sys


class _Block:
    def find_spec(self, name, path=None, target=None):
        if name == "jax" or name.startswith("jax."):
            raise ImportError(f"poisoned for test: {name}")
        return None


sys.meta_path.insert(0, _Block())

from consensus_specs_tpu import robustness
from consensus_specs_tpu.robustness.faults import FaultPlan, FaultSpec, fire
from consensus_specs_tpu.robustness.retry import call_with_retry, RetryPolicy
from consensus_specs_tpu.robustness.breaker import CircuitBreaker
from consensus_specs_tpu.robustness.checkpoint import EngineCheckpoint

# the "xla" exc kind falls back to TransientFault when jax is absent
plan = FaultPlan(seed=1, sites={"s": FaultSpec(kind="raise", at_calls=(1,),
                                               exc="xla")})
with plan.active():
    try:
        fire("s")
    except robustness.TransientFault:
        pass
    else:
        raise SystemExit("expected the no-jax fallback fault")
print("ROBUSTNESS-NO-JAX-OK")
"""
    res = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300)
    assert res.returncode == 0, res.stderr
    assert "ROBUSTNESS-NO-JAX-OK" in res.stdout


# --- deadline-aware retry (the front-door admission budget) ------------------


def test_call_with_retry_deadline_stops_doomed_backoff():
    """Once the next backoff sleep would land past the deadline, the LAST
    error surfaces immediately instead of burning the budget on sleeps
    that cannot help."""
    from consensus_specs_tpu.obs import metrics as obs_metrics

    t = [0.0]
    slept = []
    calls = {"n": 0}

    def sleep(d):
        slept.append(d)
        t[0] += d

    def always_down():
        calls["n"] += 1
        raise TransientFault("device away")

    base = obs_metrics.REGISTRY.counter_value(
        "retries_deadline_exhausted_total", error="TransientFault")
    with pytest.raises(TransientFault):
        call_with_retry(
            always_down,
            RetryPolicy(max_attempts=10, base_delay=1.0, backoff=2.0,
                        max_delay=60.0, jitter=0.0),
            sleep=sleep, deadline=4.0, clock=lambda: t[0])
    # delays 1s, 2s are affordable (land at t=1, t=3); the third delay
    # (4s) would land at t=7 >= deadline 4 -> raise after 3 attempts
    assert slept == [1.0, 2.0] and calls["n"] == 3
    assert obs_metrics.REGISTRY.counter_value(
        "retries_deadline_exhausted_total",
        error="TransientFault") - base == 1


def test_call_with_retry_deadline_leaves_jitter_stream_untouched():
    """The backoff delay is computed BEFORE the deadline check, so adding
    a (generous) deadline must not shift a single jittered sleep — the
    chaos-replay bit-identity contract."""

    def run(deadline):
        calls = {"n": 0}
        slept = []

        def flaky():
            calls["n"] += 1
            if calls["n"] < 4:
                raise TransientFault("not yet")
            return "ok"

        out = call_with_retry(
            flaky,
            RetryPolicy(max_attempts=5, base_delay=0.1, backoff=2.0,
                        max_delay=1.0, jitter=0.5, seed=7),
            sleep=slept.append, deadline=deadline, clock=lambda: 0.0)
        assert out == "ok"
        return slept

    no_deadline = run(None)
    with_deadline = run(1e9)
    assert no_deadline == with_deadline and len(no_deadline) == 3


def test_call_with_retry_deadline_allows_fitting_attempts():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransientFault("x")
        return "done"

    assert call_with_retry(
        flaky, RetryPolicy(max_attempts=5, base_delay=0.0, max_delay=0.0,
                           jitter=0.0),
        sleep=lambda d: None, deadline=10.0, clock=lambda: 0.0) == "done"
    assert calls["n"] == 3


# --- breaker: the half-open probe is single under concurrency ----------------


def test_breaker_half_open_single_probe_under_concurrency():
    """Four threads race on_attempt() at the open->half_open boundary:
    every one gets probe mode (half-open means single-ATTEMPT, not
    single-caller), but the transition — and its half_open_probe event —
    happens exactly once per open, every round."""
    import threading

    from consensus_specs_tpu.obs import metrics as obs_metrics

    brk = CircuitBreaker(failure_threshold=1, name="probe-race")
    base = obs_metrics.REGISTRY.counter_value(
        "breaker_events_total", breaker="probe-race",
        event="half_open_probe")
    rounds = 20
    for _ in range(rounds):
        brk.record_failure()
        assert brk.state == rbreaker.OPEN
        barrier = threading.Barrier(4)
        modes = []
        lock = threading.Lock()

        def attempt():
            barrier.wait()  # maximize the race on the transition
            mode = brk.on_attempt()
            with lock:
                modes.append(mode)

        threads = [threading.Thread(target=attempt) for _ in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert modes == ["probe"] * 4
        probes = [e for e in brk.events if e["event"] == "half_open_probe"]
        assert len(probes) == 1  # the regression bar: never 0, never 2+
        brk.record_success()
        brk.events.clear()
    assert obs_metrics.REGISTRY.counter_value(
        "breaker_events_total", breaker="probe-race",
        event="half_open_probe") - base == rounds
