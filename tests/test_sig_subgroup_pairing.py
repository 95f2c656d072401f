"""Signatures outside G2 on the jax flush.

crypto/bls_jax decompresses a flush's signatures without the subgroup
check and checks them for G2 membership on the device, all at once, with
the ψ test (ops/bls12_jax.g2_subgroup_check_device) at the head of
run_checks. Checked here: the ψ test against the host [r]-ladder, on
signatures and on twist points outside G2; and a flush holding one such
signature, validly encoded, on both device routes (the RLC check at
RLC_MIN_BATCH live sets and more, the per-item check below that).

Named *_pairing* so `make testfast` skips it (device pairing compiles).
"""
from functools import lru_cache

import pytest

from consensus_specs_tpu.crypto import bls, bls_jax, bls_sig
from consensus_specs_tpu.crypto import bls12_381 as oracle
from consensus_specs_tpu.crypto.hash_to_curve import hash_to_field_fp2, map_to_curve_g2
from consensus_specs_tpu.obs.metrics import REGISTRY
from consensus_specs_tpu.sched.api import Request
from consensus_specs_tpu.sched.classes import BlsWorkClass

F2 = oracle.FP2_FIELD


@pytest.fixture(autouse=True)
def _real_bls_then_restore():
    prev_active, prev_backend = bls.bls_active, bls.backend()
    bls.bls_active = True
    yield
    bls.bls_active = prev_active
    bls.use_py() if prev_backend == "py" else bls.use_jax()


def _outside_g2(i: int):
    """A twist point outside G2: the map's output before cofactor clearing."""
    return map_to_curve_g2(hash_to_field_fp2(b"outside G2 %d" % i, 1)[0])


@lru_cache(maxsize=None)
def _signed(i: int):
    """(sk, message, signature bytes) of the i-th good set."""
    sk, msg = 101 + i, b"subgroup flush message %d" % i
    return sk, msg, bls_sig.Sign(sk, msg)


def _in_g2_by_ladder(aff) -> bool:
    return oracle.pt_mul(F2, oracle.pt_from_affine(F2, aff), oracle.R) is None


@pytest.mark.parametrize("kinds", [
    "sss",  # signatures only: the five pads of the bucket must not show
    "oooo",  # outside G2 only
    "sosoosso",  # mixed, a full bucket of 8
])
def test_psi_test_agrees_with_the_r_ladder_pairing(kinds):
    from consensus_specs_tpu.ops import bls12_jax as K

    points = [oracle.g2_from_bytes(_signed(i)[2]) if k == "s" else _outside_g2(i)
              for i, k in enumerate(kinds)]
    got = K.g2_subgroup_check_device(points)
    assert got.shape == (len(kinds),)
    assert got.tolist() == [_in_g2_by_ladder(p) for p in points]
    assert got.tolist() == [k == "s" for k in kinds]


def _requests(n_good: int, bad_at: int) -> list:
    """n_good good sets with a set whose signature lies outside G2 (validly
    encoded) inserted at `bad_at`; Verify and FastAggregateVerify mixed."""
    reqs = []
    for i in range(n_good):
        sk, msg, sig = _signed(i)
        if i % 2:
            reqs.append(Request(work_class="bls", kind="verify",
                                payload=(bls_sig.SkToPk(sk), msg, sig)))
        else:
            other = 7 + i
            agg = bls_sig.Aggregate([sig, bls_sig.Sign(other, msg)])
            reqs.append(Request(work_class="bls", kind="fast_aggregate", payload=(
                [bls_sig.SkToPk(sk), bls_sig.SkToPk(other)], msg, agg)))
    bad_sig = oracle.g2_to_bytes(_outside_g2(99))
    reqs.insert(bad_at, Request(work_class="bls", kind="verify", payload=(
        bls_sig.SkToPk(5), b"subgroup flush bad set", bad_sig)))
    return reqs


@pytest.mark.parametrize("n_good,bad_at,path", [
    (bls_jax.RLC_MIN_BATCH, 11, "rlc"),  # the randomized batch check
    (5, 2, None),  # below RLC_MIN_BATCH: the per-item check
])
def test_flush_fails_exactly_the_set_outside_g2_pairing(n_good, bad_at, path):
    reqs = _requests(n_good, bad_at)
    checked = REGISTRY.counter_value("bls_sig_subgroup_device_total")
    rejected = REGISTRY.counter_value("bls_sig_subgroup_rejected_total")
    rlc = REGISTRY.counter_value("bls_flush_total", path="rlc")

    got = BlsWorkClass().execute(reqs)
    assert got.tolist() == [i != bad_at for i in range(n_good + 1)]
    assert REGISTRY.counter_value("bls_sig_subgroup_device_total") - checked == n_good + 1
    assert REGISTRY.counter_value("bls_sig_subgroup_rejected_total") - rejected == 1
    assert REGISTRY.counter_value("bls_flush_total", path="rlc") - rlc == (path == "rlc")

    # the same sets through the shim's deferred flush name the set
    bls.use_jax()
    ops = {"verify": bls.Verify, "fast_aggregate": bls.FastAggregateVerify}
    with pytest.raises(bls.BLSVerificationError,
                       match=rf"failed for checks \[{bad_at}\]$"):
        with bls.deferred_verification():
            for r in reqs:
                ops[r.kind](*r.payload)


def test_prep_defers_the_check_and_the_cache_keeps_it():
    """The flush's prep queues a check for a validly encoded signature
    outside G2; the validated cache still refuses it, before and after."""
    aff = _outside_g2(3)
    sig = oracle.g2_to_bytes(aff)
    with pytest.raises(ValueError, match="G2 point not in r-subgroup"):
        bls_jax.g2_from_bytes(sig)
    check = bls_jax.make_verify_check(bls_sig.SkToPk(5), b"prep", sig)
    assert check is not None and check.q2 == aff
    with pytest.raises(ValueError, match="G2 point not in r-subgroup"):
        bls_jax.g2_from_bytes(sig)


def _x_not_on_twist() -> bytes:
    x0 = 1
    while oracle.f2_sqrt(oracle.f2_add(oracle.f2_mul(oracle.f2_sqr((x0, 0)), (x0, 0)),
                                       oracle.B_G2)) is not None:
        x0 += 1
    return bytes([0x80]) + bytes(47) + x0.to_bytes(48, "big")


@pytest.mark.parametrize("encoding", [
    lambda: _signed(0)[2][:95],  # wrong length
    lambda: bytes([0x80 | (oracle.P >> 376)]) + (oracle.P % (1 << 376)).to_bytes(47, "big")
    + bytes(48),  # x >= p
    _x_not_on_twist,
    lambda: bytes([0xC0]) + bytes(95),  # infinity
], ids=["length", "x_ge_p", "not_on_twist", "infinity"])
def test_prep_still_rejects_bad_encodings(encoding):
    sk, msg, _ = _signed(0)
    assert bls_jax.make_verify_check(bls_sig.SkToPk(sk), msg, encoding()) is None
