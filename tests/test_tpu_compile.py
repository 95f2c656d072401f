"""Deviceless TPU compiles of the main-path kernels at real widths.

The TPU compiler is installed with jaxlib, and it compiles for a chip that
is described, not attached (`jax.experimental.topologies`). Each test
lowers one kernel at the width the system runs it at, compiles it for one
v5e chip, and checks that its temporaries fit the chip's 16 GiB: what the
chip's compiler would refuse fails here, at no chip time. Nothing runs,
so these say nothing about results or speed.

The topology is described inside a module fixture (never at import): only
one process at a time may load libtpu, and under pytest-xdist only the
worker given this file must. The persistent compile cache is off around
these compiles: a deviceless entry cannot be read back without a chip.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 << 30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args, **static):
    compiled = fn.lower(*args, **static).compile()
    temps = compiled.memory_analysis().temp_size_in_bytes
    assert temps < HBM_BYTES, f"{temps} bytes of temporaries"
    return compiled


def _arg(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_fp_mont_mul_compiles_at_pairing_width(one_chip):
    """The RNS field multiply (with the int8 MXU base extension) at the
    batch-2048 pairing width."""
    from consensus_specs_tpu.ops import fp_rns

    a = _arg(one_chip, (2048, 64), jnp.int32)
    _compile(fp_rns.fp_mont_mul, a, a)


def test_sha256_compiles_at_registry_merkle_level(one_chip):
    """One Merkle level of 2^19 parent hashes: the widest level of a
    1M-validator registry column."""
    from consensus_specs_tpu.ops import sha256_jax

    w16 = _arg(one_chip, (1 << 19, 16), jnp.uint32)
    _compile(jax.jit(sha256_jax.sha256_64B_words), w16)


def test_multiproof_compiles_at_proof_lane_bucket(one_chip):
    """The light-client multiproof at the proof lane's 1M-registry bucket:
    6 registry columns (tree bucket 8) of 2^18 chunks, 512 queries."""
    from consensus_specs_tpu.ops import multiproof_jax

    k, c, q = 8, 1 << 18, 512
    _compile(multiproof_jax.sibling_rows_batch,
             _arg(one_chip, (k, c, 8), jnp.uint32),
             _arg(one_chip, (q,), jnp.int32),
             _arg(one_chip, (q,), jnp.int32))


def test_ghost_head_compiles_at_forkchoice_lane_shape(one_chip):
    """LMD-GHOST head (`_ghost_head_impl` under the service's vmapped
    bucket program) for one store at 65,536 validators x 512 blocks."""
    from consensus_specs_tpu.ops import forkchoice_jax

    s, b, v = 1, 512, 65_536
    _compile(forkchoice_jax.ghost_head_bucket,
             _arg(one_chip, (s, b), jnp.int32),
             _arg(one_chip, (s, b, 8), jnp.uint32),
             _arg(one_chip, (s, b, 2), jnp.int64),
             _arg(one_chip, (s, b, 2), jnp.int32),
             _arg(one_chip, (s, b), jnp.bool_),
             _arg(one_chip, (s, v), jnp.int32),
             _arg(one_chip, (s, v), jnp.int64),
             _arg(one_chip, (s, 4), jnp.int32),
             _arg(one_chip, (s, 4), jnp.int64))


def test_shuffle_compiles_at_mainnet_registry(one_chip):
    """The swap-or-not shuffle of 1,048,576 indices, mainnet's 90 rounds."""
    from consensus_specs_tpu.ops import shuffle

    _compile(shuffle.shuffled_index_map, 1 << 20,
             _arg(one_chip, (8,), jnp.uint32), 90)
