"""debug/encode + decode roundtrips over randomized spec containers."""
from random import Random

import pytest

from consensus_specs_tpu.compiler import get_spec
from consensus_specs_tpu.debug import RandomizationMode, decode, encode, get_random_ssz_object
from consensus_specs_tpu.ssz import hash_tree_root, serialize


@pytest.fixture(scope="module")
def spec():
    return get_spec("altair", "minimal")


TYPES = ["Checkpoint", "AttestationData", "Attestation", "BeaconBlockHeader",
         "IndexedAttestation", "Deposit", "SyncAggregate", "Validator", "BeaconState"]


@pytest.mark.parametrize("type_name", TYPES)
@pytest.mark.parametrize("mode", list(RandomizationMode))
def test_encode_decode_roundtrip(spec, type_name, mode):
    typ = getattr(spec, type_name)
    rng = Random(hash((type_name, mode.value)) & 0xFFFF)
    value = get_random_ssz_object(rng, typ, 100, 5, mode)
    encoded = encode(value)
    back = decode(encoded, typ)
    assert hash_tree_root(back) == hash_tree_root(value)
    assert serialize(back) == serialize(value)


def test_chaos_mode_varies(spec):
    rng = Random(1)
    a = get_random_ssz_object(rng, spec.BeaconState, 100, 5, RandomizationMode.mode_random, chaos=True)
    b = get_random_ssz_object(rng, spec.BeaconState, 100, 5, RandomizationMode.mode_random, chaos=True)
    assert hash_tree_root(a) != hash_tree_root(b)


def test_serialization_roundtrip_random(spec):
    rng = Random(7)
    for type_name in TYPES:
        typ = getattr(spec, type_name)
        value = get_random_ssz_object(rng, typ, 50, 4, RandomizationMode.mode_random)
        decoded = typ.decode_bytes(serialize(value))
        assert hash_tree_root(decoded) == hash_tree_root(value)

