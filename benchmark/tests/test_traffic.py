"""Each traffic generator is a function of the seed alone."""
import json
import os

import numpy as np
import pytest

from benchmark.drivers import epoch_loop, sync_backfill

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def small_epoch_config(n=512):
    config = load("configs", "mainnet-1m")
    config["validators"] = n
    config["state"]["key_pool"] = 4
    return config


def states_equal(a, b) -> bool:
    for name, va in vars(a).items():
        vb = getattr(b, name)
        if isinstance(va, np.ndarray):
            if not np.array_equal(va, vb):
                return False
        elif isinstance(va, tuple) and va and isinstance(va[0], np.ndarray):
            if not (np.array_equal(va[0], vb[0]) and va[1:] == vb[1:]):
                return False
        elif va != vb:
            return False
    return True


def test_epoch_state_is_a_function_of_the_seed():
    config = small_epoch_config()
    seed = 2**33 + 17  # wider than 32 bits, as the driver's seeds are
    a = epoch_loop.build_state(config, 254, seed)
    b = epoch_loop.build_state(config, 254, seed)
    c = epoch_loop.build_state(config, 254, seed + 1)
    assert states_equal(a, b)
    assert not np.array_equal(a.balances, c.balances)


def test_participation_is_a_function_of_seed_stream_and_epoch():
    import jax

    flags = epoch_loop.participation_fn(4096, load("traffic", "epoch_loop")["participation"])
    key = jax.random.fold_in(jax.random.key(5), 1)
    a, b = np.asarray(flags(key, 1, 3)), np.asarray(flags(key, 1, 3))
    assert np.array_equal(a, b)
    assert not np.array_equal(a, np.asarray(flags(key, 0, 3)))  # warm-up stream differs
    assert not np.array_equal(a, np.asarray(flags(key, 1, 4)))
    share = [((a >> bit) & 1).mean() for bit in range(3)]
    assert 0.9 < share[0] < 1 and 0.9 < share[1] < 1 and 0.88 < share[2] < 1


class InProcess:
    """A signer with the pool's interface, for small traffic in a test."""

    def map(self, fn, items, chunksize=1):
        return [fn(x) for x in items]

    def starmap(self, fn, items, chunksize=1):
        return [fn(*x) for x in items]


def small_sync_traffic():
    config = load("configs", "mainnet-sync-committee")
    config["sync_committee_size"] = 8
    traffic = load("traffic", "sync_backfill")
    traffic.update(blocks_per_batch=8, warmup_batches=1, pool_batches=2,
                   forged_batch_every=2, forged_batch_offset=1)
    return config, traffic


def test_sync_traffic_is_a_function_of_the_seed():
    config, traffic = small_sync_traffic()
    a = sync_backfill.build_traffic(config, traffic, 2**34 + 5, InProcess())
    b = sync_backfill.build_traffic(config, traffic, 2**34 + 5, InProcess())
    c = sync_backfill.build_traffic(config, traffic, 2**34 + 6, InProcess())
    assert a == b
    assert a[0] != c[0]
    keys, warm, pool = a
    assert [blk.valid for batch in warm for blk in batch].count(False) == 4
    assert all(blk.valid for blk in pool[0])
    assert [blk.valid for blk in pool[1]].count(False) == 4
    assert len({blk.root for batch in warm + pool for blk in batch}) == 24


@pytest.mark.parametrize("seed", [1, 2**35 + 3, 99])
def test_forged_blocks_leave_no_half_of_a_batch_clean(seed):
    rng = np.random.default_rng(seed)
    for _ in range(50):
        at = sync_backfill.forged_positions(rng, 64, 4)
        assert len(at) == 4 and all(0 <= j < 64 for j in at)
        for half in (range(32), range(32, 64), range(0, 64, 2), range(1, 64, 2)):
            assert not at <= set(half)
