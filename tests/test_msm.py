"""Pippenger bucket-MSM: kernel equivalence, cost pins, the sched "msm"
work class, and the device committee-aggregation lane (PR 11).

Layers under test, cheapest first:

1. **Cost pins (shape-only, no compile)** — at the acceptance shape
   (n=128, b=255, w=4) the Pippenger Horner combine runs 63 sequential
   fori_loop trips vs the per-item ladder's 127, and the batched point-op
   bill is 10235 vs 49024 — asserted via jax.eval_shape over the kernel's
   own digit decomposition, the same stance as test_rlc_grouped's D+1 pin.
2. **Oracle equivalence** — g1_msm_device bit-identical to the host
   Σ scalar_i·P_i (crypto/kzg.py:_msm) on random and edge batches: zero
   scalars, repeated points, the all-zero (identity) sum, 255-bit scalars.
   Pads are (generator, scalar 0) — infinity-adjacent in the sense that
   they gather the bucket-0 Jacobian zero in every window.
3. **Sched work class** — marker protocol, host-degrade agreement, one
   XLA compile per (class, bucket) via the PR-6 CompileTracker, chaos
   corrupt faults at sched.dispatch absorbed by validation+retry, and the
   2G2T-style self-check catching a corrupt-but-WELL-FORMED value that
   shape/dtype validation provably lets through.
4. **Cold-lane committee aggregation** — first sighting routes through
   the device path (batched subgroup checks + aggregate tree via the msm
   class), second sighting hits the committee cache; hostile members
   (infinity, non-subgroup) reject exactly as the host oracle does.

Compile budget note: every fast device case here reuses one of three
small programs ((8,64,4)/(8,255,4)/(8,8,4) msm buckets plus the
64-bucket aggregate/subgroup programs) — the persistent compile cache in
tests/.jax_cache makes reruns cheap.  The two tests whose *job* is to
trigger brand-new XLA compiles (the per-bucket compile counting at
nbits=12 and the randomized sweep) live in the slow tier; tier-1 keeps
the zero-recompile replay half of that pin.
"""
import numpy as np
import pytest

from consensus_specs_tpu.crypto import bls12_381 as oracle
from consensus_specs_tpu.obs import metrics as obs_metrics
from consensus_specs_tpu.robustness.faults import FaultPlan, FaultSpec
from consensus_specs_tpu.robustness.retry import RetryPolicy
from consensus_specs_tpu.sched import (
    MsmWorkClass,
    Request,
    SchedSelfCheckError,
    Scheduler,
    reset_default_scheduler,
)

REG = obs_metrics.REGISTRY
FAST_RETRY = RetryPolicy(max_attempts=4, base_delay=0.0, backoff=1.0,
                         max_delay=0.0, jitter=0.0)


@pytest.fixture(autouse=True)
def _fresh_default_scheduler():
    reset_default_scheduler()
    yield
    reset_default_scheduler()


def _points(ks):
    """Affine [k]·G for each k (host oracle arithmetic)."""
    return [
        oracle.pt_to_affine(
            oracle.FP_FIELD, oracle.pt_mul(oracle.FP_FIELD, oracle.G1_GEN, k))
        for k in ks
    ]


def _host_msm(points_aff, scalars):
    from consensus_specs_tpu.crypto import kzg

    pts = [oracle.pt_from_affine(oracle.FP_FIELD, p) for p in points_aff]
    acc = kzg._msm(oracle.FP_FIELD, pts, scalars)
    return None if acc is None else oracle.pt_to_affine(oracle.FP_FIELD, acc)


# --- 1. cost pins (no compile) ----------------------------------------------


def test_msm_loop_count_pin_128x255():
    """Acceptance pin: at n=128 / b=255 the Pippenger combine's fori_loop
    trip count (63) is strictly below the per-item ladder's (127) —
    shape-only via eval_shape, like the grouped-RLC D+1 pin."""
    import jax
    import jax.numpy as jnp

    from consensus_specs_tpu.ops import bls12_jax as K

    bits = jnp.zeros((128, 255), dtype=bool)
    digits = jax.eval_shape(K.msm_window_digits, bits)
    assert digits.shape == (128, 64)  # 255 pads to 256 -> 64 4-bit windows
    assert K.msm_loop_count(digits) == 63
    assert K.g1_ladder_loop_count(bits) == 127
    assert K.msm_loop_count(digits) < K.g1_ladder_loop_count(bits)


def test_msm_point_op_budget_beats_ladder():
    """The batched point-op bill at the KZG shape: 10235 vs 49024 (the
    BASELINE.md stage table), and the gather-form advantage holds across
    the consumer shapes (64-bit KZG r-side, 488-member aggregation)."""
    from consensus_specs_tpu.ops import bls12_jax as K

    assert K.g1_msm_point_ops(128, 255, 4) == 10235
    assert K.g1_ladder_point_ops(128, 255) == 49024
    for n, b in ((128, 64), (128, 255), (512, 255), (64, 255)):
        assert K.g1_msm_point_ops(n, b, 4) < K.g1_ladder_point_ops(n, b)


def test_msm_window_digits_roundtrip():
    """Digits reassemble the scalar: Σ d_j·2^(w·j) == s, LSB-first."""
    import jax.numpy as jnp

    from consensus_specs_tpu.ops import bls12_jax as K

    scalars = [0, 1, 0xAB, 0x1234567, (1 << 64) - 1]
    bits = jnp.asarray(K._scalar_bits_lsb(scalars, 64))
    digits = np.asarray(K.msm_window_digits(bits, 4))
    assert digits.shape == (len(scalars), 16)
    for s, row in zip(scalars, digits):
        assert sum(int(d) << (4 * j) for j, d in enumerate(row)) == s


# --- 2. oracle equivalence ---------------------------------------------------


def test_msm_device_matches_host_oracle_64bit():
    """Random 64-bit batch with every edge in one bucket: zero scalar,
    scalar 1, repeated points, and pads past n=5 -> bucket 8."""
    from consensus_specs_tpu.ops import bls12_jax as K

    points = _points([2, 3, 3, 5, 9])  # index 1 == index 2: repeated point
    scalars = [0xDEADBEEFCAFE, 0, 1, 0xFFFFFFFFFFFFFFFF, 7]
    assert K.g1_msm_device(points, scalars, 64) == _host_msm(points, scalars)


def test_msm_device_matches_host_oracle_255bit():
    """Full-width scalars mod r — the KZG folded-side shape."""
    from consensus_specs_tpu.ops import bls12_jax as K

    points = _points([11, 13, 17, 19, 23, 29])
    scalars = [pow(7, i + 1, oracle.R) for i in range(6)]
    assert K.g1_msm_device(points, scalars, 255) == _host_msm(points, scalars)


def test_msm_device_zero_sum_is_none():
    """All-zero scalars (and a P + (-P) cancellation) produce the identity
    — returned as None, matching the host oracle."""
    from consensus_specs_tpu.ops import bls12_jax as K

    points = _points([2, 3, 4])
    assert K.g1_msm_device(points, [0, 0, 0], 64) is None
    p = _points([6])[0]
    neg = (p[0], (-p[1]) % oracle.P)
    assert K.g1_msm_device([p, neg], [5, 5], 64) is None


@pytest.mark.slow
def test_msm_device_randomized_sweep():
    """Wider randomized agreement: mixed windows, non-pow2 n, 255-bit
    scalars with zero/repeat riders — the grouped-vs-ungrouped style
    equivalence gate from ROADMAP item 1."""
    import random

    from consensus_specs_tpu.ops import bls12_jax as K

    rng = random.Random(1117)
    for n, window in ((12, 4), (20, 3)):
        ks = [rng.randrange(1, 1 << 20) for _ in range(n)]
        points = _points(ks)
        points[3] = points[0]  # repeated point
        scalars = [rng.randrange(oracle.R) for _ in range(n)]
        scalars[1] = 0
        scalars[n // 2] = scalars[0]
        assert K.g1_msm_device(points, scalars, 255, window) == \
            _host_msm(points, scalars)


# --- 3. the sched "msm" work class ------------------------------------------


def _msm_requests(nbits=8, tag=0):
    """Two small msm requests in the 8-bucket (scalars < 2^nbits)."""
    pts_a = _points([3 + tag, 5 + tag, 7 + tag])
    pts_b = _points([11 + tag, 13 + tag, 17 + tag, 19 + tag])
    return [
        Request(work_class="msm", kind="msm",
                payload=(tuple(pts_a), (5, 0, 200), nbits)),
        Request(work_class="msm", kind="msm",
                payload=(tuple(pts_b), (1, 255, 9, 128), nbits)),
    ]


def test_msm_class_matches_degraded_and_oracle():
    """Device markers == host-degrade markers == the host MSM oracle, for
    both kinds ("msm" + "aggregate") through one dispatch. The committee
    is 40 keys so the aggregate/subgroup programs land in the same
    64-bucket the cold-lane tests trace — no extra compile diversity."""
    from consensus_specs_tpu.crypto import bls_sig

    wc = MsmWorkClass()
    pks = tuple(bls_sig.SkToPk(900 + i) for i in range(40))
    reqs = _msm_requests() + [
        Request(work_class="msm", kind="aggregate", payload=pks)]
    dev = wc.execute(reqs)
    host = wc.execute_degraded(reqs)
    assert list(dev) == list(host)
    for r, row in zip(reqs[:2], dev):
        points, scalars, _ = r.payload
        want = _host_msm(list(points), list(scalars))
        assert row == ("point", want[0], want[1])


def test_msm_compile_replay_adds_zero():
    """Replaying an already-traced bucket must not re-trace: the cheap
    half of the one-compile-per-(class, bucket) pin, safe for tier-1
    because the (8-bucket, nbits=8) program is shared with the other
    sched tests in this process.  The fresh-compile counting half lives
    in test_msm_compile_pinned_one_per_bucket (@slow) — it exists to
    trigger brand-new XLA compiles, which is inherently expensive."""
    from consensus_specs_tpu.obs.recompile import CompileTracker

    kernel = "_g1_msm_program"
    tracker = CompileTracker(registry=obs_metrics.MetricsRegistry()).install()
    try:
        sch = Scheduler(classes=[MsmWorkClass()])

        def run(reqs):
            hs = [sch.submit(r) for r in reqs]
            sch.drain()
            return [h.result() for h in hs]

        run(_msm_requests(tag=0))
        after_first = tracker.compiles(kernel)
        run(_msm_requests(tag=30))  # same 8-bucket: cache hits, no trace
        assert tracker.compiles(kernel) == after_first
    finally:
        tracker.uninstall()


@pytest.mark.slow
def test_msm_compile_pinned_one_per_bucket():
    """Fixed bucket set => one XLA compile per (class, bucket): replaying
    the 8-bucket reuses the cached executable, only the 16-bucket adds a
    compile — the CompileTracker pin from the acceptance criteria. The
    tracker counts trace events (in-memory jit cache misses), so this test
    uses nbits=12 — a width no other test in this process traces."""
    from consensus_specs_tpu.obs.recompile import CompileTracker

    kernel = "_g1_msm_program"
    tracker = CompileTracker(registry=obs_metrics.MetricsRegistry()).install()
    try:
        sch = Scheduler(classes=[MsmWorkClass()])
        base = tracker.compiles(kernel)

        def run(reqs):
            hs = [sch.submit(r) for r in reqs]
            sch.drain()
            return [h.result() for h in hs]

        run(_msm_requests(nbits=12, tag=0))
        first = tracker.compiles(kernel) - base
        assert first >= 1
        run(_msm_requests(nbits=12, tag=30))  # same 8-bucket: cache hits
        assert tracker.compiles(kernel) - base == first
        big = Request(  # 12 items -> 16-bucket: exactly one new compile
            work_class="msm", kind="msm",
            payload=(tuple(_points(range(2, 14))), tuple(range(12)), 12))
        run([big])
        assert tracker.compiles(kernel) - base == first + 1
    finally:
        tracker.uninstall()


def test_chaos_msm_dispatch_corrupt_converges():
    """Corrupt faults at sched.dispatch (nan + truncate) on msm batches
    are caught by result validation and re-executed from intact host
    payloads — results bit-identical to the fault-free oracle, breaker
    closed throughout."""

    def run_all():
        sch = Scheduler(classes=[MsmWorkClass()], retry_policy=FAST_RETRY)
        hs = [sch.submit(r) for r in _msm_requests()]
        sch.drain()
        out = [h.result() for h in hs]
        assert sch.breaker("msm").state == "closed"
        return out

    want = run_all()
    for corruption in ("nan", "truncate"):
        plan = FaultPlan(seed=23, sites={"sched.dispatch": FaultSpec(
            kind="corrupt", at_calls=(1,), corruption=corruption)})
        with plan.active():
            assert run_all() == want
        assert plan.fired_sites() == {"sched.dispatch"}


def test_msm_self_check_catches_well_formed_corruption():
    """The 2G2T seam earns its keep exactly where shape/dtype validation
    is blind: a corrupted result row that is still a well-formed
    ("point", x, y) marker. With self_check ON the first dispatch raises
    the retryable SchedSelfCheckError BEFORE any handle resolves and the
    retry returns the true sum; with the flag OFF the same corruption
    resolves a handle with garbage — proving the check is load-bearing."""
    points, scalars, nbits = _msm_requests()[0].payload
    want = _host_msm(list(points), list(scalars))

    def corrupting(wc):
        real, state = wc.execute, {"calls": 0}

        def execute(requests):
            out = real(requests)
            state["calls"] += 1
            if state["calls"] == 1:
                tag, x, y = out[0]
                out[0] = (tag, x, (y + 1) % oracle.P)  # well-formed, wrong
            return out

        wc.execute = execute
        return state

    req = Request(work_class="msm", kind="msm",
                  payload=(points, scalars, nbits))
    wc = MsmWorkClass(self_check=True)
    state = corrupting(wc)
    sch = Scheduler(classes=[wc], retry_policy=FAST_RETRY)
    h = sch.submit(req)
    sch.drain()
    assert h.result() == ("point", want[0], want[1])
    assert state["calls"] == 2  # first attempt rejected by the self-check

    # the error itself is the retryable kind the dispatch loop absorbs
    bad = np.empty(1, dtype=object)
    bad[0] = ("point", want[0], (want[1] + 1) % oracle.P)
    with pytest.raises(SchedSelfCheckError):
        MsmWorkClass(self_check=True).verify_results([req], bad)

    # control: flag off, the same corruption escapes to the caller
    wc_off = MsmWorkClass(self_check=False)
    state = corrupting(wc_off)
    sch = Scheduler(classes=[wc_off], retry_policy=FAST_RETRY)
    h = sch.submit(req)
    sch.drain()
    assert h.result() == ("point", want[0], (want[1] + 1) % oracle.P)
    assert state["calls"] == 1


# --- 4. cold-lane committee aggregation -------------------------------------


def test_cold_committee_aggregation_routes_device_then_caches():
    """Firehose cold-lane regression: a first-sighting committee (caches
    cleared, 40 members >= DEVICE_AGGREGATE_MIN) aggregates through the
    device msm lane — one sched "aggregate" submit, one batched subgroup
    check covering every cold key — and matches the host oracle; the
    second sighting is served from the committee cache with zero new
    device work."""
    from consensus_specs_tpu.crypto import bls, bls_jax, bls_sig

    sks = [77001 + i for i in range(40)]
    pks = [bytes(bls_sig.SkToPk(sk)) for sk in sks]
    want = _points([sum(sks) % oracle.R])[0]  # Σ[sk]G == [Σsk]G

    bls.clear_caches()
    reset_default_scheduler()
    agg0 = REG.counter_value("bls_pubkey_aggregate_device_total")
    sub0 = REG.counter_value("bls_pubkey_subgroup_device_total")
    sched0 = REG.counter_value("sched_submitted_total", work_class="msm",
                               kind="aggregate")
    aff = bls_jax._aggregate_pubkeys_affine(pks)
    assert aff == want
    assert REG.counter_value("bls_pubkey_aggregate_device_total") - agg0 == 1
    assert REG.counter_value("bls_pubkey_subgroup_device_total") - sub0 == 40
    assert REG.counter_value("sched_submitted_total", work_class="msm",
                             kind="aggregate") - sched0 == 1

    # re-sighting: committee cache hit — no new dispatch, no new checks
    assert bls_jax._aggregate_pubkeys_affine(pks) == want
    assert REG.counter_value("bls_pubkey_aggregate_device_total") - agg0 == 1
    assert REG.counter_value("sched_submitted_total", work_class="msm",
                             kind="aggregate") - sched0 == 1

    # the flush-prep entry point rides the same lane
    msg = b"cold lane message"
    sig = bls_sig.Sign(sum(sks), msg)
    check = bls_jax.make_fast_aggregate_check(pks, msg, sig)
    assert check is not None and check.p1 == want


def test_cold_committee_aggregation_spans():
    """Under a tracer, the device aggregation shows its three stages inside
    the prep's `bls.prep.aggregate` and the msm class's dispatch; a warm
    committee (every key validated before) skips the subgroup stage."""
    from consensus_specs_tpu.crypto import bls, bls_jax, bls_sig
    from consensus_specs_tpu.obs import trace as obs_trace
    from consensus_specs_tpu.obs.metrics import MetricsRegistry

    sks = [79001 + i for i in range(40)]
    pks = [bytes(bls_sig.SkToPk(sk)) for sk in sks]
    msg = b"aggregation spans"
    bls.clear_caches()
    reset_default_scheduler()
    tr = obs_trace.Tracer(registry=MetricsRegistry()).install()
    try:
        assert bls_jax.make_fast_aggregate_check(
            pks, msg, bls_sig.Sign(sum(sks), msg)) is not None
        assert bls_jax.make_fast_aggregate_check(
            pks[1:], msg, bls_sig.Sign(sum(sks[1:]), msg)) is not None
    finally:
        tr.uninstall()
    stages = ["bls.aggregate.decode", "bls.aggregate.subgroup", "bls.aggregate.device"]
    names = [s["name"] for s in tr.spans()]
    first = names[:names.index("bls.prep.aggregate") + 1]
    assert first == stages + ["sched.dispatch", "bls.prep.aggregate"]
    assert names.count("bls.aggregate.subgroup") == 1  # the second is warm
    assert names.count("bls.aggregate.device") == 2
    for s in tr.spans():
        if s["name"] in stages:
            assert s["parent"] == "sched.dispatch" and s["depth"] == 2
    assert tr.spans("bls.aggregate.subgroup")[0]["attrs"]["keys"] == 40
    assert [s["parent"] for s in tr.spans("sched.dispatch")] == ["bls.prep.aggregate"] * 2


def test_cold_committee_hostile_members_reject_like_host():
    """Hostile first-sighting committees fail closed through the device
    lane: an infinity member and an on-curve-but-not-in-subgroup member
    ((0, 2) — only the DEVICE subgroup check can catch it post-decompress)
    both reject exactly as the host oracle contract demands."""
    from consensus_specs_tpu.crypto import bls, bls_jax, bls_sig

    bls.clear_caches()
    reset_default_scheduler()
    pks = [bytes(bls_sig.SkToPk(78001 + i)) for i in range(39)]
    assert bls_jax._aggregate_pubkeys_affine(
        pks + [oracle.g1_to_bytes(None)]) is None  # infinity member
    assert (0 * 0 * 0 + oracle.B_G1 - 2 * 2) % oracle.P == 0  # (0,2) on curve
    with pytest.raises(ValueError, match="subgroup"):
        bls_jax._aggregate_pubkeys_affine(
            [bytes(bls_sig.SkToPk(79001 + i)) for i in range(39)]
            + [oracle.g1_to_bytes((0, 2))])
    # aggregate_pubkeys_device mirrors AggregatePKs: infinity member raises
    with pytest.raises(ValueError, match="infinity"):
        bls_jax.aggregate_pubkeys_device(pks + [oracle.g1_to_bytes(None)])


# --- 5. the validated-key row cache ------------------------------------------


def _committee(base: int, n: int = 40):
    from consensus_specs_tpu.crypto import bls_sig

    sks = [base + i for i in range(n)]
    return sks, [bytes(bls_sig.SkToPk(sk)) for sk in sks]


def _sum_of(sks):
    return _points([sum(sks) % oracle.R])[0]  # Σ[sk]G == [Σsk]G


def _row_counters():
    return (REG.counter_value("bls_pubkey_row_hits_total"),
            REG.counter_value("bls_pubkey_subgroup_device_total"),
            REG.counter_value("bls_pubkey_aggregate_device_keys_total"))


def _assert_store_paired():
    """Every cached key's slot holds that key's point, and its rows are
    ints_to_mont_batch of that point."""
    from consensus_specs_tpu.crypto import bls_jax
    from consensus_specs_tpu.ops import bls12_jax as K

    store = bls_jax._PK_VALIDATED
    assert len(store) > 0
    for pk, s in store.slot_of.items():
        assert store.keys[s] == pk
        pt = store.points[s]
        assert pt == oracle.g1_from_bytes(pk, subgroup_check=False)
        np.testing.assert_array_equal(store.rows[s, 0], K.F.ints_to_mont_batch([pt[0]])[0])
        np.testing.assert_array_equal(store.rows[s, 1], K.F.ints_to_mont_batch([pt[1]])[0])


@pytest.mark.parametrize("case", ["full", "subset", "mix"])
def test_row_cache_aggregate_matches_oracle(case):
    """The committee aggregate from cached rows equals the host oracle's
    sum: the full committee warm, a 39-of-40 (97%) subset, and 20 warm
    keys with 40 cold ones. Warm keys count as row hits, cold keys as
    device subgroup checks, and every cached key stays paired."""
    from consensus_specs_tpu.crypto import bls, bls_jax

    sks, pks = _committee(81001 + 1000 * ["full", "subset", "mix"].index(case))
    bls.clear_caches()
    reset_default_scheduler()
    hits0, sub0, keys0 = _row_counters()
    assert bls_jax._aggregate_pubkeys_affine(pks) == _sum_of(sks)  # every key cold
    hits1, sub1, keys1 = _row_counters()
    assert (hits1 - hits0, sub1 - sub0, keys1 - keys0) == (0, 40, 40)

    if case == "full":  # the committee cache would answer the same list
        bls_jax._AGG_CACHE.clear()
        set_sks, set_pks, warm = sks, pks, 40
    elif case == "subset":
        set_sks, set_pks, warm = sks[:17] + sks[18:], pks[:17] + pks[18:], 39
    else:
        cold_sks, cold_pks = _committee(84001)
        set_sks, set_pks, warm = sks[::2] + cold_sks, pks[::2] + cold_pks, 20
    assert bls_jax._aggregate_pubkeys_affine(set_pks) == _sum_of(set_sks)
    hits2, sub2, keys2 = _row_counters()
    cold = len(set_pks) - warm
    assert (hits2 - hits1, sub2 - sub1, keys2 - keys1) == (warm, cold, len(set_pks))
    assert len(bls_jax._PK_VALIDATED) == 40 + cold
    _assert_store_paired()


def test_row_cache_eviction_keeps_point_and_rows_paired(monkeypatch):
    """At a cap of 8 keys, slots are reused oldest first: each survivor's
    point and rows stay its own, and sums over evicted, surviving and new
    keys stay right."""
    from consensus_specs_tpu.crypto import bls, bls_jax

    monkeypatch.setattr(bls_jax, "_PK_VALIDATED_MAX", 8)
    bls.clear_caches()
    reset_default_scheduler()
    sks, pks = _committee(85001)
    assert bls_jax._aggregate_pubkeys_affine(pks) == _sum_of(sks)
    store = bls_jax._PK_VALIDATED
    assert set(store.slot_of) == set(pks[-8:])  # the last 8 inserted survive
    _assert_store_paired()

    new_sks, new_pks = _committee(86001, 34)
    hits0, sub0, _ = _row_counters()
    set_sks, set_pks = sks[-8:] + sks[:4] + new_sks, pks[-8:] + pks[:4] + new_pks
    assert bls_jax._aggregate_pubkeys_affine(set_pks) == _sum_of(set_sks)
    hits1, sub1, _ = _row_counters()
    assert (hits1 - hits0, sub1 - sub0) == (8, 38)
    assert set(store.slot_of) == set(new_pks[-8:])
    _assert_store_paired()


def test_row_cache_keeps_no_rejected_key():
    """A set with a key outside the r-subgroup, or the infinity key, is
    rejected and caches none of its keys; a key listed twice takes one
    slot."""
    from consensus_specs_tpu.crypto import bls, bls_jax

    bls.clear_caches()
    reset_default_scheduler()
    _, pks = _committee(87001, 39)
    with pytest.raises(ValueError, match="subgroup"):
        bls_jax._aggregate_pubkeys_affine(pks + [oracle.g1_to_bytes((0, 2))])
    assert bls_jax._aggregate_pubkeys_affine(pks + [oracle.g1_to_bytes(None)]) is None
    assert len(bls_jax._PK_VALIDATED) == 0

    sks, pks = _committee(88001, 39)
    assert bls_jax._aggregate_pubkeys_affine(pks + pks[:1]) == _sum_of(sks + sks[:1])
    assert len(bls_jax._PK_VALIDATED) == 39
    _assert_store_paired()


def test_clear_caches_drops_points_and_rows():
    """bls.clear_caches() empties the store: afterwards every key is cold
    again and is validated on the device anew."""
    from consensus_specs_tpu.crypto import bls, bls_jax
    from consensus_specs_tpu.ops import bls12_jax as K

    bls.clear_caches()
    reset_default_scheduler()
    sks, pks = _committee(89001)
    assert bls_jax._aggregate_pubkeys_affine(pks) == _sum_of(sks)
    assert len(bls_jax._PK_VALIDATED) == 40
    bls.clear_caches()
    store = bls_jax._PK_VALIDATED
    assert len(store) == 0 and store.rows is None and store.points == []
    assert store.lookup(K.F, pks) == ([None] * 40, None)
    _, sub0, _ = _row_counters()
    assert bls_jax._aggregate_pubkeys_affine(pks) == _sum_of(sks)
    assert _row_counters()[1] - sub0 == 40


def test_rows_entries_match_point_wrappers_and_compile_nothing_new():
    """g1_aggregate_rows / g1_subgroup_check_rows on encoded rows answer
    as the point-taking wrappers do, and a warm aggregation compiles no
    program: the row path launches the same aggregation program."""
    from consensus_specs_tpu.crypto import bls, bls_jax
    from consensus_specs_tpu.obs.recompile import CompileTracker
    from consensus_specs_tpu.ops import bls12_jax as K

    sks = [90001 + i for i in range(40)]
    points = _points(sks)
    X = K.F.ints_to_mont_batch([p[0] for p in points])
    Y = K.F.ints_to_mont_batch([p[1] for p in points])
    assert K.g1_aggregate_rows(X, Y) == K.g1_aggregate_device(points) == _sum_of(sks)
    assert K.g1_subgroup_check_rows(X, Y).all()
    bad = points[:39] + [(0, 2)]
    np.testing.assert_array_equal(K.g1_subgroup_check_device(bad),
                                  [True] * 39 + [False])

    bls.clear_caches()
    reset_default_scheduler()
    c_sks, pks = _committee(91001)
    assert bls_jax._aggregate_pubkeys_affine(pks) == _sum_of(c_sks)
    tracker = CompileTracker(registry=obs_metrics.MetricsRegistry()).install()
    try:
        before = sum(tracker.kernels().values())
        assert bls_jax._aggregate_pubkeys_affine(pks[1:]) == _sum_of(c_sks[1:])
        assert sum(tracker.kernels().values()) == before
    finally:
        tracker.uninstall()
