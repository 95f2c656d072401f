"""BLS12-381 stack tests: field towers, curves, pairing, signature scheme.

Mirrors the coverage of the reference's BLS test-vector generator
(tests/generators/bls/main.py): sign/verify roundtrips, aggregation,
infinity/edge cases — plus algebraic self-checks (bilinearity, tower
inversions) that pin the from-scratch pairing implementation.
"""
import random

import pytest

from consensus_specs_tpu.crypto import bls
from consensus_specs_tpu.crypto import bls12_381 as c
from consensus_specs_tpu.crypto.hash_to_curve import (
    expand_message_xmd, hash_to_curve_g2, hash_to_field_fp2,
)

rng = random.Random(42)


def rand_f2():
    return (rng.randrange(c.P), rng.randrange(c.P))


def rand_f12():
    return tuple(rand_f2() for _ in range(6))


# --- fields ---

def test_f2_inv_sqrt():
    for _ in range(10):
        x = rand_f2()
        assert c.f2_mul(x, c.f2_inv(x)) == c.F2_ONE
        s = c.f2_sqrt(c.f2_sqr(x))
        assert s in (x, c.f2_neg(x))


def test_f2_nonresidue_sqrt_none():
    # u^2 = -1; find a non-square by trial
    found_none = False
    for _ in range(20):
        x = rand_f2()
        if c.f2_sqrt(x) is None:
            found_none = True
            break
    assert found_none  # ~half of Fp2 elements are non-squares


def test_f12_ops():
    for _ in range(5):
        x, y = rand_f12(), rand_f12()
        assert c.f12_mul(x, c.f12_inv(x)) == c.F12_ONE
        # commutativity + distributivity spot checks
        assert c.f12_mul(x, y) == c.f12_mul(y, x)
        z = rand_f12()
        lhs = c.f12_mul(x, c.f12_add(y, z))
        rhs = c.f12_add(c.f12_mul(x, y), c.f12_mul(x, z))
        assert lhs == rhs


def test_frobenius_is_pth_power():
    x = rand_f12()
    assert c.f12_frobenius(x, 1) == c.f12_pow(x, c.P)


# --- curves ---

def test_generators_validated():
    assert c.g1_on_curve(c.G1_GEN_AFF)
    assert c.g2_on_curve(c.G2_GEN_AFF)
    assert c.pt_mul(c.FP_FIELD, c.G1_GEN, c.R) is None
    assert c.pt_mul(c.FP2_FIELD, c.G2_GEN, c.R) is None


def test_scalar_mul_matches_addition():
    F = c.FP_FIELD
    p5 = c.pt_mul(F, c.G1_GEN, 5)
    acc = None
    for _ in range(5):
        acc = c.pt_add(F, acc, c.G1_GEN)
    assert c.pt_eq(F, p5, acc)
    # (a+b)G == aG + bG
    a, b = rng.randrange(1, c.R), rng.randrange(1, c.R)
    lhs = c.pt_mul(F, c.G1_GEN, (a + b) % c.R)
    rhs = c.pt_add(F, c.pt_mul(F, c.G1_GEN, a), c.pt_mul(F, c.G1_GEN, b))
    assert c.pt_eq(F, lhs, rhs)


def test_point_serialization_roundtrip():
    for k in (1, 2, 12345, rng.randrange(1, c.R)):
        g1 = c.pt_to_affine(c.FP_FIELD, c.pt_mul(c.FP_FIELD, c.G1_GEN, k))
        assert c.g1_from_bytes(c.g1_to_bytes(g1)) == g1
        g2 = c.pt_to_affine(c.FP2_FIELD, c.pt_mul(c.FP2_FIELD, c.G2_GEN, k))
        assert c.g2_from_bytes(c.g2_to_bytes(g2)) == g2
    assert c.g1_from_bytes(c.g1_to_bytes(None)) is None
    assert c.g2_from_bytes(c.g2_to_bytes(None)) is None


def test_g1_generator_known_compression():
    # The canonical compressed G1 generator (public, widely published).
    assert c.g1_to_bytes(c.G1_GEN_AFF).hex().startswith("97f1d3a73197d794")


def test_serialization_rejects_invalid():
    with pytest.raises(ValueError):
        c.g1_from_bytes(b"\x00" * 48)  # compression flag missing
    with pytest.raises(ValueError):
        c.g1_from_bytes(b"\xff" * 48)  # x >= p
    with pytest.raises(ValueError):
        c.g2_from_bytes(b"\x00" * 96)
    # valid x but not in subgroup: h1 > 1 so random curve points usually fail
    x = 5
    while c.fp_sqrt((x * x * x + c.B_G1) % c.P) is None:
        x += 1
    y = c.fp_sqrt((x * x * x + c.B_G1) % c.P)
    raw = bytearray(x.to_bytes(48, "big"))
    raw[0] |= 0x80 | (0x20 if y > (c.P - 1) // 2 else 0)
    with pytest.raises(ValueError):
        c.g1_from_bytes(bytes(raw))


# --- pairing ---

def test_pairing_bilinear():
    e = c.pairing(c.G2_GEN_AFF, c.G1_GEN_AFF)
    assert e != c.F12_ONE
    assert c.f12_pow(e, c.R) == c.F12_ONE
    a, b = rng.randrange(1, 2**32), rng.randrange(1, 2**32)
    aP = c.pt_to_affine(c.FP_FIELD, c.pt_mul(c.FP_FIELD, c.G1_GEN, a))
    bQ = c.pt_to_affine(c.FP2_FIELD, c.pt_mul(c.FP2_FIELD, c.G2_GEN, b))
    assert c.pairing(bQ, aP) == c.f12_pow(e, a * b)


# --- hash to curve ---

def test_expand_message_xmd_rfc_vector():
    # RFC 9380 K.1 (SHA-256), msg="", len_in_bytes=0x20
    out = expand_message_xmd(b"", b"QUUX-V01-CS02-with-expander-SHA256-128", 32)
    assert out.hex() == "68a985b87eb6b46952128911f2a4412bbc302a9d759667f87f7a21d803f07235"


def test_hash_to_field_deterministic_distinct():
    u = hash_to_field_fp2(b"abc", 2)
    v = hash_to_field_fp2(b"abc", 2)
    w = hash_to_field_fp2(b"abd", 2)
    assert u == v and u != w
    assert all(0 <= x < c.P for pair in u for x in pair)


def test_hash_to_curve_in_subgroup():
    h = hash_to_curve_g2(b"test message")
    assert c.g2_on_curve(h)
    assert c.pt_mul(c.FP2_FIELD, c.pt_from_affine(c.FP2_FIELD, h), c.R) is None
    assert hash_to_curve_g2(b"test message") == h
    assert hash_to_curve_g2(b"other") != h


# --- signature scheme ---

SK1, SK2, SK3 = 1234, 5678, 9999
MSG = b"consensus test message"


def test_sign_verify():
    pk = bls.SkToPk(SK1)
    sig = bls.Sign(SK1, MSG)
    assert bls.Verify(pk, MSG, sig)
    assert not bls.Verify(pk, b"other", sig)
    assert not bls.Verify(bls.SkToPk(SK2), MSG, sig)


def test_aggregate_same_message():
    pks = [bls.SkToPk(k) for k in (SK1, SK2, SK3)]
    agg = bls.Aggregate([bls.Sign(k, MSG) for k in (SK1, SK2, SK3)])
    assert bls.FastAggregateVerify(pks, MSG, agg)
    assert not bls.FastAggregateVerify(pks[:2], MSG, agg)


def test_aggregate_distinct_messages():
    msgs = [b"m1", b"m2"]
    agg = bls.Aggregate([bls.Sign(SK1, msgs[0]), bls.Sign(SK2, msgs[1])])
    pks = [bls.SkToPk(SK1), bls.SkToPk(SK2)]
    assert bls.AggregateVerify(pks, msgs, agg)
    assert not bls.AggregateVerify(pks, [b"m1", b"m1"], agg)
    assert not bls.AggregateVerify(list(reversed(pks)), msgs, agg)


def test_infinity_and_empty_edge_cases():
    sig = bls.Sign(SK1, MSG)
    inf_pk = b"\xc0" + b"\x00" * 47
    assert not bls.Verify(inf_pk, MSG, sig)
    assert not bls.KeyValidate(inf_pk)
    assert bls.KeyValidate(bls.SkToPk(SK1))
    assert not bls.FastAggregateVerify([], MSG, bls.G2_POINT_AT_INFINITY)
    assert not bls.AggregateVerify([], [], bls.G2_POINT_AT_INFINITY)
    with pytest.raises(ValueError):
        bls.Aggregate([])


def test_aggregate_pks_matches_sum():
    pks = [bls.SkToPk(k) for k in (SK1, SK2)]
    agg_pk = bls.AggregatePKs(pks)
    assert agg_pk == bls.SkToPk((SK1 + SK2) % c.R)


def test_bls_off_switch():
    bls.bls_active = False
    try:
        assert bls.Verify(b"junk", b"x", b"junk") is True
        assert bls.Sign(1, b"x") == bls.STUB_SIGNATURE
    finally:
        bls.bls_active = True


# --- RFC 9380 interoperability (VERDICT r1 item #3) -------------------------

def test_hash_to_curve_rfc9380_vector():
    """BLS12381G2_XMD:SHA-256_SSWU_RO_ suite vector (RFC 9380 J.10.1,
    msg=""): full affine output of hash_to_curve with the RFC test DST.
    This pins the SSWU + derived 3-isogeny + clear_cofactor pipeline to the
    published suite bit-for-bit."""
    from consensus_specs_tpu.crypto.hash_to_curve import (
        MAP_TO_CURVE_RFC_COMPLIANT,
        hash_to_curve_g2,
    )

    assert MAP_TO_CURVE_RFC_COMPLIANT is True
    dst = b"QUUX-V01-CS02-with-BLS12381G2_XMD:SHA-256_SSWU_RO_"
    pt = hash_to_curve_g2(b"", dst)
    assert pt[0] == (
        0x0141EBFBDCA40EB85B87142E130AB689C673CF60F1A3E98D69335266F30D9B8D4AC44C1038E9DCDD5393FAF5C41FB78A,
        0x05CB8437535E20ECFFAEF7752BADDF98034139C38452458BAEEFAB379BA13DFF5BF5DD71B72418717047F5B0F37DA03D,
    )
    assert pt[1] == (
        0x0503921D7F6A12805E72940B963C0CF3471C7B2A524950CA195D11062EE75EC076DAF2D4BC358C4B190C0C98064FDD92,
        0x12424AC32561493F3FE3C260708A12B7C620E7BE00099A974E259DDC7D1F6395C3C811CDD19F1E8DBF3E9ECFDCBAB8D6,
    )


def test_expand_message_xmd_structure():
    """expand_message_xmd self-consistency: deterministic, length-exact,
    DST-separated (full RFC vectors for the expansion live in the J.10.1
    check above, which exercises it end-to-end)."""
    from consensus_specs_tpu.crypto.hash_to_curve import expand_message_xmd

    a = expand_message_xmd(b"msg", b"DST-A", 96)
    b = expand_message_xmd(b"msg", b"DST-B", 96)
    assert len(a) == len(b) == 96
    assert a != b
    assert expand_message_xmd(b"msg", b"DST-A", 96) == a


# --- host-prep spans (crypto/bls_jax.py, sched/classes.py) -------------------


@pytest.fixture
def tracer():
    from consensus_specs_tpu.obs import trace as obs_trace
    from consensus_specs_tpu.obs.metrics import MetricsRegistry

    tr = obs_trace.Tracer(registry=MetricsRegistry()).install()
    try:
        yield tr
    finally:
        tr.uninstall()


def test_fast_aggregate_check_prep_spans(tracer):
    """Below DEVICE_AGGREGATE_MIN keys the prep is host only: one span per
    stage, in order, and the queued check is the aggregate's."""
    from consensus_specs_tpu.crypto import bls_jax

    sks = (SK1, SK2, SK3)
    assert len(sks) < bls_jax.DEVICE_AGGREGATE_MIN
    msg = b"prep spans fast aggregate"
    check = bls_jax.make_fast_aggregate_check(
        [bls.SkToPk(k) for k in sks], msg, bls.Sign(sum(sks), msg))
    assert check is not None
    assert [s["name"] for s in tracer.spans()] == [
        "bls.prep.aggregate", "bls.prep.sig_decode", "bls.prep.hash_to_curve"]
    assert tracer.spans("bls.prep.aggregate")[0]["attrs"]["keys"] == 3
    assert all(s["status"] == "ok" for s in tracer.spans())


def test_verify_check_prep_spans_and_a_bad_signature(tracer):
    from consensus_specs_tpu.crypto import bls_jax

    msg = b"prep spans verify"
    assert bls_jax.make_verify_check(bls.SkToPk(SK1), msg, bls.Sign(SK1, msg)) is not None
    assert [s["name"] for s in tracer.spans()] == [
        "bls.prep.pk_decode", "bls.prep.sig_decode", "bls.prep.hash_to_curve"]
    # an undecodable signature ends the prep in its decode span
    assert bls_jax.make_verify_check(bls.SkToPk(SK1), msg, b"\xff" * 96) is None
    last = tracer.spans()[-1]
    assert last["name"] == "bls.prep.sig_decode" and last["status"] == "error"
    assert len(tracer.spans("bls.prep.hash_to_curve")) == 1


def test_bls_prep_span_holds_each_request_prep(tracer, monkeypatch):
    """`bls.prep` wraps the work class's per-request prep loop; the device
    check after it is outside (stubbed here: host only)."""
    import numpy as np

    from consensus_specs_tpu.crypto import bls_jax
    from consensus_specs_tpu.sched.api import Request
    from consensus_specs_tpu.sched.classes import BlsWorkClass

    monkeypatch.setattr(bls_jax, "run_checks",
                        lambda checks: np.ones(len(checks), dtype=bool))
    msgs = [b"prep loop %d" % i for i in range(2)]
    reqs = [Request(work_class="bls", kind="verify",
                    payload=(bls.SkToPk(SK2), m, bls.Sign(SK2, m))) for m in msgs]
    assert BlsWorkClass().execute(reqs).tolist() == [True, True]
    (prep,) = tracer.spans("bls.prep")
    assert prep["attrs"]["checks"] == 2 and prep["depth"] == 0
    inner = [s for s in tracer.spans() if s["name"].startswith("bls.prep.")]
    assert len(inner) == 6
    assert all(s["parent"] == "bls.prep" and s["depth"] == 1 for s in inner)
    assert all(prep["t_start"] <= s["t_start"]
               and s["t_start"] + s["duration"] <= prep["t_start"] + prep["duration"]
               for s in inner)


# --- deferral-queue hygiene under flush failure (robustness PR) --------------

def test_deferred_queue_resets_after_flush_failure():
    """Regression: a BLSVerificationError escaping the outermost __exit__
    must leave the thread-local deferral state pristine — the next
    deferred_verification() on this thread starts with an empty queue, not
    the failed batch's leftovers (queue poisoning)."""
    pk, msg = bls.SkToPk(SK1), b"queue hygiene"
    sig = bls.Sign(SK1, msg)
    with pytest.raises(bls.BLSVerificationError):
        with bls.deferred_verification():
            assert bls.Verify(pk, msg, sig) is True  # optimistic
            assert bls.Verify(pk, b"forged", sig) is True  # fails at flush
    assert bls._deferral.queue is None
    assert bls._deferral.depth == 0
    # a fresh context on the same thread flushes ONLY its own checks
    with bls.deferred_verification():
        assert bls.Verify(pk, msg, sig) is True


def test_deferred_flush_retries_transient_fault():
    """The bls.flush fault seam + FLUSH_RETRY_POLICY: one injected transient
    failure is absorbed by the retry (same queue re-dispatched — queueing is
    side-effect-free), and the batch still verifies."""
    from consensus_specs_tpu.robustness.faults import FaultPlan, FaultSpec
    from consensus_specs_tpu.robustness.retry import RetryPolicy

    pk, msg = bls.SkToPk(SK1), b"transient flush"
    sig = bls.Sign(SK1, msg)
    saved = bls.FLUSH_RETRY_POLICY
    bls.FLUSH_RETRY_POLICY = RetryPolicy(max_attempts=3, base_delay=0.0,
                                         max_delay=0.0)
    plan = FaultPlan(seed=5, sites={
        "bls.flush": FaultSpec(kind="raise", at_calls=(1,), exc="transient"),
    })
    try:
        with plan.active():
            with bls.deferred_verification():
                assert bls.Verify(pk, msg, sig) is True
        assert plan.fires("bls.flush") == 1
        assert plan.calls("bls.flush") == 2  # failed attempt + clean retry
    finally:
        bls.FLUSH_RETRY_POLICY = saved


def test_deferred_flush_exhausted_retries_leaves_clean_state():
    """When every retry attempt fails, the transient error escapes — but the
    deferral state must STILL reset (the finally-reset, not the happy path,
    carries the invariant)."""
    from consensus_specs_tpu.robustness.faults import (
        FaultPlan,
        FaultSpec,
        TransientFault,
    )
    from consensus_specs_tpu.robustness.retry import RetryPolicy

    pk, msg = bls.SkToPk(SK1), b"doomed flush"
    sig = bls.Sign(SK1, msg)
    saved = bls.FLUSH_RETRY_POLICY
    bls.FLUSH_RETRY_POLICY = RetryPolicy(max_attempts=2, base_delay=0.0,
                                         max_delay=0.0)
    plan = FaultPlan(seed=6, sites={
        "bls.flush": FaultSpec(kind="raise", rate=1.0, exc="transient"),
    })
    try:
        with plan.active():
            with pytest.raises(TransientFault):
                with bls.deferred_verification():
                    assert bls.Verify(pk, msg, sig) is True
        assert plan.calls("bls.flush") == 2  # both attempts consumed
        assert bls._deferral.queue is None
        assert bls._deferral.depth == 0
        # the thread recovers: a later batch (no plan active) verifies
        with bls.deferred_verification():
            assert bls.Verify(pk, msg, sig) is True
    finally:
        bls.FLUSH_RETRY_POLICY = saved


def test_py_backend_survives_unimportable_bls_jax():
    """ADVICE r5: a pure-Python-oracle process (no jax importable) must be
    able to Sign/Verify, defer+flush, AggregatePKs, and clear_caches without
    the shim ever importing `bls_jax`. Run in a SUBPROCESS with the module
    poisoned via a meta-path blocker — referenced by bls.clear_caches's
    docstring as the coverage for its sys.modules.get guard."""
    import subprocess
    import sys

    code = """
import sys

BLOCKED = "consensus_specs_tpu.crypto.bls_jax"


class _Block:
    def find_spec(self, name, path=None, target=None):
        if name == BLOCKED or name.split(".")[-1] == "jax" or name == "jax":
            raise ImportError(f"poisoned for test: {name}")
        return None


sys.meta_path.insert(0, _Block())

from consensus_specs_tpu.crypto import bls

assert bls.backend() == "py"
pk, msg = bls.SkToPk(7), b"no-jax process message"
sig = bls.Sign(7, msg)
assert bls.Verify(pk, msg, sig)
assert not bls.Verify(pk, b"other", sig)
with bls.deferred_verification():
    assert bls.Verify(pk, msg, sig) is True
agg = bls.AggregatePKs([bls.SkToPk(7), bls.SkToPk(8)])
assert len(agg) == 48
bls.clear_caches()  # must not import bls_jax (sys.modules.get guard)
assert BLOCKED not in sys.modules
print("PY-BACKEND-OK")
"""
    res = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr
    assert "PY-BACKEND-OK" in res.stdout
