"""The plain references against published vectors and a second witness."""
import numpy as np

from benchmark.ref import bls as b
from benchmark.ref import epoch_altair as ref
from benchmark.tests.test_traffic import small_epoch_config

RFC_DST = b"QUUX-V01-CS02-with-BLS12381G2_XMD:SHA-256_SSWU_RO_"


def test_hash_to_curve_rfc9380_vector():
    # RFC 9380 appendix J.10.1, msg = ""
    x, y = b.hash_to_g2(b"", RFC_DST)
    assert x == (0x0141EBFBDCA40EB85B87142E130AB689C673CF60F1A3E98D69335266F30D9B8D4AC44C1038E9DCDD5393FAF5C41FB78A,
                 0x05CB8437535E20ECFFAEF7752BADDF98034139C38452458BAEEFAB379BA13DFF5BF5DD71B72418717047F5B0F37DA03D)
    assert y == (0x0503921D7F6A12805E72940B963C0CF3471C7B2A524950CA195D11062EE75EC076DAF2D4BC358C4B190C0C98064FDD92,
                 0x12424AC32561493F3FE3C260708A12B7C620E7BE00099A974E259DDC7D1F6395C3C811CDD19F1E8DBF3E9ECFDCBAB8D6)


def test_generators_and_encoding():
    assert b.on_curve(b.FP, b.G1) and b.in_subgroup(b.FP, b.G1)
    assert b.on_curve(b.FP2, b.G2) and b.in_subgroup(b.FP2, b.G2)
    assert b.sk_to_pk(1).hex().startswith("97f1d3a73197d794")  # compressed G1 generator
    pk = b.sk_to_pk(123456789)
    assert b.g1_compress(b.g1_decompress(pk)) == pk
    sig = b.sign(7, b"m")
    assert b.g2_compress(b.g2_decompress(sig)) == sig


def test_pairing_is_bilinear_and_verify_separates():
    a = 987654321
    ap = b.to_affine(b.FP, b.pt_mul(b.FP, b.from_affine(b.FP, b.G1), a))
    aq = b.to_affine(b.FP2, b.pt_mul(b.FP2, b.from_affine(b.FP2, b.G2), a))
    neg = (b.G1[0], b.P - b.G1[1])
    assert b.pairing_product_is_one([(ap, b.G2), (neg, aq)])
    assert not b.pairing_product_is_one([(ap, b.G2), (neg, b.G2)])
    sks = [11, 22, 33]
    pks = [b.sk_to_pk(k) for k in sks]
    sig = b.sign(sum(sks), b"root")
    assert b.fast_aggregate_verify(pks, b"root", sig)
    assert not b.fast_aggregate_verify(pks[:2], b"root", sig)
    assert not b.fast_aggregate_verify(pks, b"other", sig)
    assert not b.fast_aggregate_verify([], b"root", sig)


def test_bls_matches_the_programs_oracle():
    """Second witness: the program's pure-Python signer."""
    from consensus_specs_tpu.crypto import bls_sig

    assert b.sk_to_pk(1234) == bls_sig.SkToPk(1234)
    assert b.sign(1234, b"consensus test message") == bls_sig.Sign(1234, b"consensus test message")


def test_state_root_matches_the_programs_ssz():
    """Second witness: the program's SSZ hash_tree_root of the same state,
    before and after reference epochs (a rotation included)."""
    from consensus_specs_tpu.compiler import get_spec
    from consensus_specs_tpu.ssz import hash_tree_root

    from benchmark.drivers import epoch_loop

    config = small_epoch_config(300)
    c = ref.Spec(config["constants"])
    st = epoch_loop.build_state(config, 254, 99)
    spec = get_spec("altair", "mainnet")
    assert ref.state_root(st, c) == bytes(hash_tree_root(epoch_loop.to_spec_state(spec, st)))
    rng = np.random.default_rng(1)
    for _ in range(3):
        st.current_epoch_participation = (rng.random(300) < 0.97).astype(np.uint8) * 7
        ref.process_epoch(st, c, {})
        st.slot += c.SLOTS_PER_EPOCH
    assert len(st.historical_roots) == 1  # the epoch 255 -> 256 boundary
    assert ref.state_root(st, c) == bytes(hash_tree_root(epoch_loop.to_spec_state(spec, st)))
