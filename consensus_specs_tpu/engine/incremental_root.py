"""Incremental device-side BeaconState roots for the resident engine.

`engine/state_root.py` recomputes every registry-scale field root per call
(~2N sha for the validator containers + 65k for the randao vector + 8k per
root vector) — correct, but ~10^4x more hashing than an epoch transition
actually dirties (VERDICT r4 weak #4: 2.73 s/root vs 0.2 ms for the host
incremental tree). This module keeps the Merkle TREES resident in HBM and
rehashes only what changed:

  per epoch   balances / participation / inactivity rebuild (they change
              wholesale); ONE randao row and ONE slashings entry path-update
              (their indices are determined by the epoch number:
              specs/phase0/beacon-chain.md process_randao_mixes_reset /
              process_slashings_reset); validator container roots update by
              DIRTY ROW (hysteresis + churn touch few validators — columns
              are diffed on device, K rows re-hashed, K tree paths folded)
  per slot    one state_roots / block_roots leaf path-update (process_slot's
              per-slot `hash_tree_root(state)` obligation costs ~depth
              hashes, not a registry sweep)
  always      the O(1) fields (slot, checkpoints, justification bits)

Bit-equality with `ssz.hash_tree_root(materialize())` is asserted in
tests/test_resident_engine.py. The reference's remerkleable gets the same
effect from persistent structural sharing on the host (SURVEY §2.1
SSZ typing/impl); this is that idea re-expressed as device-resident level
arrays + scatter/gather path folds so the root never leaves HBM either.
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.sha256_jax import merkle_parent_level, sha256_64B_words
from .state_root import (
    DEPTH_VALIDATORS,
    _bswap32,
    _extend,
    _mix_len,
    _u64_chunk_words,
)

U32 = jnp.uint32

# Dirty-row budget for the masked validator update; epochs that touch more
# validators than this (mass ejection scenarios) fall back to the full
# registry sweep.
MAX_DIRTY_VALIDATORS = 1024


# --- resident chunk trees ---------------------------------------------------


def build_tree_levels(chunks: jax.Array) -> tuple:
    """(C, 8) chunk words -> tuple of level arrays, leaves first, root last
    ((1, 8)). C is padded to the next power of two with zero CHUNKS."""
    c = chunks.shape[0]
    depth = max(1, (c - 1)).bit_length() if c > 1 else 0
    full = 1 << depth
    if full != c:
        chunks = jnp.concatenate([chunks, jnp.zeros((full - c, 8), dtype=chunks.dtype)])
    levels = [chunks]
    for _ in range(depth):
        levels.append(merkle_parent_level(levels[-1]))
    return tuple(levels)


def path_update(levels: tuple, idx: jax.Array, new_node: jax.Array) -> tuple:
    """Replace leaf `idx` and refold its root path: depth hashes total."""
    out = [levels[0].at[idx].set(new_node)]
    cur = idx
    for lvl in range(len(levels) - 1):
        parent = cur // 2
        left = out[lvl][2 * parent]
        right = out[lvl][2 * parent + 1]
        h = sha256_64B_words(jnp.concatenate([left, right])[None])[0]
        out.append(levels[lvl + 1].at[parent].set(h))
        cur = parent
    return tuple(out)


def multi_path_update(levels: tuple, idxs: jax.Array, new_nodes: jax.Array) -> tuple:
    """Replace K leaves and refold: K x depth hashes. Duplicate/padded
    indices are harmless (they re-derive the same parent values)."""
    out = [levels[0].at[idxs].set(new_nodes)]
    cur = idxs
    for lvl in range(len(levels) - 1):
        parent = cur // 2
        left = out[lvl][2 * parent]  # (K, 8)
        right = out[lvl][2 * parent + 1]
        h = sha256_64B_words(jnp.concatenate([left, right], axis=1))
        out.append(levels[lvl + 1].at[parent].set(h))
        cur = parent
    return tuple(out)


# --- per-validator container roots -----------------------------------------


def _validator_rows_roots(static01: jax.Array, cols: tuple) -> jax.Array:
    """(K, 16) static words + six (K,) columns -> (K, 8) container roots
    (same 8-leaf layout as state_root._validators_root)."""
    (eff, slashed, elig, act, exit_, wd) = cols
    k = eff.shape[0]
    zeros6 = jnp.zeros((k, 6), dtype=U32)

    def chunk(col):
        lo = _bswap32((col.astype(jnp.uint64) & jnp.uint64(0xFFFFFFFF)).astype(U32))
        hi = _bswap32((col.astype(jnp.uint64) >> jnp.uint64(32)).astype(U32))
        return jnp.concatenate([lo[:, None], hi[:, None], zeros6], axis=1)

    def bchunk(col):
        b = (col.astype(U32) & U32(1)) << 24
        return jnp.concatenate([b[:, None], jnp.zeros((k, 7), dtype=U32)], axis=1)

    h01 = sha256_64B_words(static01)
    h23 = sha256_64B_words(jnp.concatenate([chunk(eff), bchunk(slashed)], axis=1))
    h45 = sha256_64B_words(jnp.concatenate([chunk(elig), chunk(act)], axis=1))
    h67 = sha256_64B_words(jnp.concatenate([chunk(exit_), chunk(wd)], axis=1))
    return sha256_64B_words(jnp.concatenate([
        sha256_64B_words(jnp.concatenate([h01, h23], axis=1)),
        sha256_64B_words(jnp.concatenate([h45, h67], axis=1)),
    ], axis=1))


def _registry_cols(st) -> tuple:
    return (st.effective_balance, st.slashed, st.activation_eligibility_epoch,
            st.activation_epoch, st.exit_epoch, st.withdrawable_epoch)


# --- jitted programs --------------------------------------------------------


@lru_cache(maxsize=None)
def _dirty_scan_fn():
    """Compare the six registry columns against their cached copies:
    -> (count, padded dirty indices, fresh copies of the new columns)."""

    def scan(new_cols, cached_cols):
        n = new_cols[0].shape[0]
        mask = jnp.zeros(n, dtype=bool)
        for a, b in zip(new_cols, cached_cols):
            mask = mask | (a != b)
        count = jnp.sum(mask)
        idxs = jnp.nonzero(mask, size=min(MAX_DIRTY_VALIDATORS, n), fill_value=0)[0]
        copies = tuple(jnp.asarray(a).copy() for a in new_cols)
        return count, idxs, copies

    return jax.jit(scan)


@lru_cache(maxsize=None)
def _masked_validators_update_fn():
    """Recompute K dirty validator container roots, fold their tree paths,
    and return (new levels, new list root with limit-extension + length)."""

    def update(levels, static01, cols, idxs, n):
        rows_static = static01[idxs]
        rows_cols = tuple(c[idxs] for c in cols)
        new_roots = _validator_rows_roots(rows_static, rows_cols)
        new_levels = multi_path_update(levels, idxs, new_roots)
        depth = len(new_levels) - 1
        root = _mix_len(_extend(new_levels[-1][0], depth, DEPTH_VALIDATORS), n)
        return new_levels, root

    return jax.jit(update, static_argnums=(4,), donate_argnums=(0,))


@lru_cache(maxsize=None)
def _full_validators_build_fn():
    def build(static01, cols, n):
        roots = _validator_rows_roots(static01, cols)
        levels = build_tree_levels(roots)
        depth = len(levels) - 1
        root = _mix_len(_extend(levels[-1][0], depth, DEPTH_VALIDATORS), n)
        return levels, root

    return jax.jit(build, static_argnums=(2,))


@lru_cache(maxsize=None)
def _wholesale_roots_fn():
    """Roots of the fields an epoch rewrites wholesale + the O(1) fields
    (single source: state_root.light_field_roots)."""
    from .state_root import light_field_roots

    return jax.jit(light_field_roots)


@lru_cache(maxsize=None)
def _vector_tree_build_fn():
    return jax.jit(build_tree_levels)


@lru_cache(maxsize=None)
def _slashings_tree_build_fn():
    def build(slashings):
        return build_tree_levels(_u64_chunk_words(slashings))

    return jax.jit(build)


@lru_cache(maxsize=None)
def _row_update_fn():
    def update(levels, idx, row):
        return path_update(levels, idx, row)

    return jax.jit(update, donate_argnums=(0,))


@lru_cache(maxsize=None)
def _epoch_rows_update_fn():
    """ONE launch for a whole run of pending epochs: K randao-row paths and
    K slashings-chunk paths fold together (the per-epoch-dispatch loop this
    replaces cost 2 host round trips per epoch). Duplicate
    (wrapped) indices gather identical leaf values, so scatter order is
    irrelevant."""

    def update(randao_levels, slash_levels, mixes, slashings, mix_idxs, slash_chunk_idxs):
        new_randao = multi_path_update(randao_levels, mix_idxs, mixes[mix_idxs])
        all_chunks = _u64_chunk_words(slashings)
        new_slash = multi_path_update(slash_levels, slash_chunk_idxs,
                                      all_chunks[slash_chunk_idxs])
        return new_randao, new_slash

    return jax.jit(update, donate_argnums=(0, 1))


def _root_of(levels: tuple) -> jax.Array:
    return levels[-1][0]


class IncrementalStateRoot:
    """HBM-resident Merkle state for every registry-scale BeaconState field.

    Owned by ResidentEpochEngine; `refresh_after_epochs` follows each run
    of epoch steps, `record_state_root`/`record_block_root` follow each
    per-slot root write (the engine's advance_slot drives them), and
    `device_roots()` yields the field-root dict `assemble_state_root`
    consumes. All cached arrays are COPIES — the engine's step donates its
    input pytree, so holding references into a donated state would read
    deleted buffers.
    """

    def __init__(self, dev, static01: jax.Array):
        n = dev.balances.shape[0]
        self.n = int(n)
        self._static01 = static01
        cols = tuple(jnp.asarray(c).copy() for c in _registry_cols(dev))
        self._cached_cols = cols
        self._val_levels, self._val_root = _full_validators_build_fn()(
            static01, cols, self.n)
        self._randao_levels = _vector_tree_build_fn()(dev.randao_mixes)
        self._block_levels = _vector_tree_build_fn()(dev.block_roots)
        self._state_levels = _vector_tree_build_fn()(dev.state_roots)
        self._slash_levels = _slashings_tree_build_fn()(dev.slashings)
        self._slash_len = int(dev.slashings.shape[0])
        self._light = _wholesale_roots_fn()(dev)

    # -- epoch boundary ------------------------------------------------------

    def refresh_after_epochs(self, dev, last_epoch: int, count: int,
                             epochs_per_historical_vector: int) -> None:
        """Update every cached root for a run of `count` epoch transitions
        ending in epoch `last_epoch`. Each transition writes exactly one
        randao row (process_randao_mixes_reset: row next_epoch % EPV) and
        zeroes one slashings entry (process_slashings_reset: entry
        next_epoch % EPSV) — within an EPV/EPSV window the rows are
        distinct, so path-updating each touched row against the FINAL
        device state is exact. The registry columns are diffed on device
        once for the whole run (cumulative dirty set).

        CONTRACT — epoch-only mutator: between the build (or previous
        refresh) and this call, `dev` may have been advanced ONLY by epoch
        transitions (engine/epoch.py programs), whose write set is exactly
        what is re-derived here, plus the per-slot root writes that went
        through record_state_root/record_block_root. Any other mutation of
        the registry-scale fields (e.g. a future block-processing program
        editing balances mid-epoch, appending validators, or rewriting
        history vectors wholesale) is NOT observed and would silently yield
        a stale root — route such writes through a rebuild (fresh
        IncrementalStateRoot) or a dedicated record_* hook instead. The
        shape guard below makes the registry-growth case fail loudly."""
        if int(dev.balances.shape[0]) != self.n:
            raise ValueError(
                f"IncrementalStateRoot built for {self.n} validators, got a "
                f"state with {int(dev.balances.shape[0])}: registry growth "
                "is outside the epoch-only mutator contract — rebuild the "
                "incremental root cache")
        self._light = _wholesale_roots_fn()(dev)

        count_dirty, idxs, copies = _dirty_scan_fn()(
            _registry_cols(dev), self._cached_cols)
        self._cached_cols = copies
        dirty = int(count_dirty)
        if dirty > 0:
            if dirty <= MAX_DIRTY_VALIDATORS:
                self._val_levels, self._val_root = _masked_validators_update_fn()(
                    self._val_levels, self._static01, copies, idxs, self.n)
            else:
                self._val_levels, self._val_root = _full_validators_build_fn()(
                    self._static01, copies, self.n)

        epochs = range(last_epoch - count + 1, last_epoch + 1)
        mix_rows = np.array([e % epochs_per_historical_vector for e in epochs],
                            dtype=np.int32)
        slash_chunks = np.array([(e % self._slash_len) // 4 for e in epochs],
                                dtype=np.int32)
        # pad K to a power of two (repeat the last index — harmless
        # duplicates) so the jit specializes on O(log) distinct shapes
        k = 1 << (len(mix_rows) - 1).bit_length() if len(mix_rows) > 1 else 1
        pad = k - len(mix_rows)
        if pad:
            mix_rows = np.concatenate([mix_rows, np.repeat(mix_rows[-1:], pad)])
            slash_chunks = np.concatenate(
                [slash_chunks, np.repeat(slash_chunks[-1:], pad)])
        self._randao_levels, self._slash_levels = _epoch_rows_update_fn()(
            self._randao_levels, self._slash_levels, dev.randao_mixes,
            dev.slashings, jnp.asarray(mix_rows), jnp.asarray(slash_chunks))

    # -- slot boundary -------------------------------------------------------

    def record_state_root(self, slot_index: int, root_words: jax.Array) -> None:
        """process_slot writes hash_tree_root(state) into
        state.state_roots[slot % SLOTS_PER_HISTORICAL_ROOT]."""
        self._state_levels = _row_update_fn()(
            self._state_levels, jnp.asarray(slot_index), root_words)

    def record_block_root(self, slot_index: int, root_words: jax.Array) -> None:
        self._block_levels = _row_update_fn()(
            self._block_levels, jnp.asarray(slot_index), root_words)

    # -- assembly ------------------------------------------------------------

    def device_roots(self, slot: int) -> dict:
        """Field-root dict for assemble_state_root. `slot` comes from the
        HOST mirror — it is the one device-owned field that advances
        between epoch steps (per-slot roots), and the host slot is
        canonical for it."""
        roots = dict(self._light)
        roots["slot"] = np.frombuffer(
            int(slot).to_bytes(8, "little") + b"\x00" * 24, dtype=">u4"
        ).astype(np.uint32)
        roots["validators"] = self._val_root
        roots["randao_mixes"] = _root_of(self._randao_levels)
        roots["block_roots"] = _root_of(self._block_levels)
        roots["state_roots"] = _root_of(self._state_levels)
        roots["slashings"] = _root_of(self._slash_levels)
        return roots
