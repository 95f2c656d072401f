"""Device-resident multi-epoch engine: the steady-state epoch pipeline.

`bridge.apply_epoch_via_engine` round-trips the full registry every epoch
(transpose in, device epoch, write back) — correct as a drop-in
`process_epoch`, but at 1M validators the two host crossings dominate the
wall clock by ~100x over the device compute. A node does not need the SSZ
object tree between consecutive epoch transitions; it needs it at sync /
checkpoint / block-proposal boundaries. So keep the `EpochState` resident
on device and cross the host boundary only when something host-visible
happens:

  per epoch (always)          three () bool aux flags + the slot mirror
  per eth1 voting period      clear the host `eth1_data_votes` list (O(1))
  per 256 epochs (mainnet)    32-byte historical-batch root (device merkle)
  per sync-committee period   seed mix row (32 B) + three registry columns
                              for the committee sampler
  on materialize()            the one full write-back, amortized over the
                              epochs since the last one

Reference parity: this replaces the per-epoch cost of
`process_epoch(state)` (specs/altair/beacon-chain.md) for a multi-epoch
run; `materialize()` restores the exact `BeaconState` the sequential
`apply_epoch_via_engine` loop produces — bit-equality is asserted by
tests/test_resident_engine.py against that loop, which is itself
differentially tested against the compiled spec.
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import trace as _obs_trace
from ..robustness import faults as rfaults
from ..robustness.retry import DEVICE_POLICY, call_with_retry, is_retryable
from . import bridge
from .epoch import make_epoch_fn
from .state import DIRTY_TRACKED, EpochConfig


def _step_body(cfg: EpochConfig):
    """The shared un-jitted resident step: `process_epoch` + the
    inter-epoch slot advance. The spec calls `process_epoch` at the last
    slot of each epoch and `process_slots` then advances the slot;
    consecutive transitions are exactly SLOTS_PER_EPOCH apart, so the
    step folds the advance into the same XLA program and the state never
    leaves HBM. Single source for both the per-epoch and the scan jits."""
    epoch_fn = make_epoch_fn(cfg, with_jit=False)
    spe = jnp.uint64(cfg.slots_per_epoch)

    def step(st):
        st, aux = epoch_fn(st)
        return st.replace(slot=st.slot + spe), aux

    return step


@lru_cache(maxsize=None)
def resident_step_fn_for(cfg: EpochConfig):
    """jit one resident step, input donated."""
    return jax.jit(_step_body(cfg), donate_argnums=(0,))


@lru_cache(maxsize=None)
def resident_scan_fn_for(cfg: EpochConfig, k: int):
    """jit a `lax.scan` of k resident steps: ONE device launch and ONE
    aux readout for k epochs.

    Per-epoch dispatch plus the three-bool readout costs a host round trip
    per epoch; the scan form pays it once per SEGMENT. Segments never cross a sync-committee
    period boundary (run_epochs slices them so), which is what makes
    deferred epilogue servicing exact — see ResidentEpochEngine.run_epochs.
    """
    step = _step_body(cfg)

    def scan_k(st):
        return jax.lax.scan(lambda c, _: step(c), st, None, length=k)

    return jax.jit(scan_k, donate_argnums=(0,))


def _start_host_copies(aux) -> None:
    """Queue async D2H copies of every EpochAux leaf right behind the launch
    that produces them, so the later np.asarray readout in _flush_pending
    completes the transfers instead of starting them (overlap with whatever
    the host does in between). No-op on backends without the API.

    Failures here DEGRADE instead of propagating: the async staging is a
    latency optimization, and when it is skipped the flush's np.asarray
    performs the same transfer synchronously. Only retryable (transient /
    link-level) errors are swallowed — a host-code bug still raises."""
    try:
        with _obs_trace.span("engine.host_copy"):
            rfaults.fire("engine.host_copy")
            for leaf in jax.tree_util.tree_leaves(aux):
                if hasattr(leaf, "copy_to_host_async"):
                    leaf.copy_to_host_async()
    except Exception as exc:
        if not is_retryable(exc):
            raise


class ResidentEpochEngine:
    """Runs epochs with the registry resident in device HBM.

    Usage:
        eng = ResidentEpochEngine(spec, state)   # one bridge-in
        for _ in range(k):
            eng.step_epoch()                     # device-only steady state
        eng.materialize()                        # one write-back; `state`
                                                 # now equals the sequential
                                                 # engine loop's result

    Between `step_epoch` calls the host `state` is STALE except for the
    fields the epilogue owns (slot, eth1_data_votes, historical_roots,
    sync committees) — read it only after `materialize()`.
    """

    def __init__(self, spec, state):
        self.spec = spec
        self.state = state
        dev, cfg, cols = bridge.state_to_device_with_columns(spec, state)
        self.cfg = cfg
        self.dev = dev
        self._pre_cols = cols
        # writable copy: the write-back maintains it in place (by gathered
        # row, or wholesale on the full-diff fallback)
        self._pre_mixes = np.array(dev.randao_mixes)
        self._step = resident_step_fn_for(cfg)
        self._inc = None  # incremental root cache, built on first state_root()
        self._pending_epochs = 0  # epoch refreshes owed to the cache
        self._pending_last_epoch = int(state.slot) // cfg.slots_per_epoch
        # Dirty-column accumulator: OR of EpochAux.dirty_cols over every
        # epoch since the last materialize(); lets the write-back skip the
        # D2H transfer of columns no transition touched.
        self._dirty = np.zeros(len(DIRTY_TRACKED), dtype=bool)
        self._epochs_since_sync = 0
        # Deferred segment service (pipelining): the EpochAux of the most
        # recent launch whose host epilogues have not run yet, plus the
        # number of epochs it covers. Flushed before any host-visible read
        # and eagerly when the segment fires a sync-committee rotation.
        self._pending = None
        self._deferred_epochs = 0
        # Device-boundary retry budget (robustness/retry.py): governs the
        # dispatch re-issue and the validated aux re-read. Swappable per
        # engine so tests can zero the backoff.
        self.retry_policy = DEVICE_POLICY

    def _dispatch(self, fn, arg):
        """Issue a (donating) jitted step under the retry policy.

        The injection seam fires BEFORE the call, while the input pytree
        is intact — that is the only point where a retry is safe, because
        the program donates its input and a genuine mid-execution failure
        leaves the buffers deleted. Such a failure's retry raises the
        deleted-buffer XlaRuntimeError and exhausts the budget; callers
        with a fallback (bridge.apply_epoch_via_engine) degrade then."""
        def attempt():
            rfaults.fire("engine.dispatch")
            return fn(arg)

        with _obs_trace.span("engine.dispatch"):
            return call_with_retry(attempt, self.retry_policy)

    def _read_aux(self, aux):
        """Validated host readout of an EpochAux segment.

        Each flag array crosses the corruption seam and then structural
        validation (bool dtype, coherent shapes) — the failure mode is a
        torn/garbled D2H copy, which is retryable because the device
        arrays are intact and np.asarray simply re-reads them. Returns
        (eth1_resets, hist_appends, sync_updates, dirty_cols) with the
        flag arrays (seg,) and dirty_cols (seg, len(DIRTY_TRACKED))."""
        def attempt():
            e = rfaults.corrupt_array(
                "engine.aux_readout", np.asarray(aux.eth1_votes_reset))
            h = rfaults.corrupt_array(
                "engine.aux_readout", np.asarray(aux.historical_append))
            s = rfaults.corrupt_array(
                "engine.aux_readout", np.asarray(aux.sync_committee_update))
            d = rfaults.corrupt_array(
                "engine.aux_readout", np.asarray(aux.dirty_cols))
            e, h, s = (np.atleast_1d(x) for x in (e, h, s))
            d = np.atleast_2d(d)
            for name, arr in (("eth1_votes_reset", e), ("historical_append", h),
                              ("sync_committee_update", s), ("dirty_cols", d)):
                if arr.dtype != np.bool_:
                    raise rfaults.CorruptAuxError(
                        f"aux.{name}: expected bool dtype, got {arr.dtype}")
            if not (e.shape == h.shape == s.shape
                    and d.shape == e.shape + (len(DIRTY_TRACKED),)):
                raise rfaults.CorruptAuxError(
                    "aux flag shapes incoherent: "
                    f"{e.shape}/{h.shape}/{s.shape}/{d.shape}")
            return e, h, s, d

        with _obs_trace.span("engine.aux_readout"):
            return call_with_retry(attempt, self.retry_policy)

    def step_epoch(self, advance_slots: bool = True) -> None:
        """One epoch transition; host work is O(1) except on period
        boundaries (see module docstring). `advance_slots=False` is the
        per-slot drive mode's boundary step (advance_slot owns the +1).

        In the default mode the epilogue service of the PREVIOUS epoch is
        flushed after this epoch's launch, so its flag readout and host
        work overlap this epoch's device compute. The deferral is exact
        for the same reasons segment deferral is (see run_epochs); a
        rotation epoch is serviced eagerly because its sampler must read
        the registry columns before the next launch donates them.
        """
        if not advance_slots:
            # per-slot mode interleaves advance_slot's root-vector writes
            # with epoch steps, so nothing may stay deferred across one.
            self._flush_pending()
            self.dev, aux = self._dispatch(self._step, self.dev)
            e, h, s, d = self._read_aux(aux)
            self._service_segment(e, h, s, dirty_cols=d, advance_slots=False)
            return
        cur = int(self.state.slot) // self.cfg.slots_per_epoch + self._deferred_epochs
        self.dev, aux = self._dispatch(self._step, self.dev)
        _start_host_copies(aux)
        self._flush_pending()  # previous epoch's epilogues overlap this launch
        self._pending = aux
        self._deferred_epochs = 1
        if (cur + 1) % self.cfg.epochs_per_sync_committee_period == 0:
            self._flush_pending()  # this epoch rotates: service it now

    def _flush_pending(self) -> None:
        """Run the deferred epilogue service, if any. Reading the aux
        arrays blocks until their launch (and the async host copies kicked
        off at dispatch) complete."""
        aux = self._pending
        if aux is None:
            return
        self._pending = None
        self._deferred_epochs = 0
        e, h, s, d = self._read_aux(aux)
        self._service_segment(e, h, s, dirty_cols=d)

    def _service_segment(self, eth1_resets, hist_appends, sync_updates,
                         dirty_cols=None, advance_slots: bool = True) -> None:
        """Host epilogues + slot-mirror advance for a segment of epochs,
        given the (seg,) aux flag arrays. Shared by step_epoch (seg=1) and
        run_epochs — the deferral-correctness argument lives on run_epochs."""
        seg = len(eth1_resets)
        with _obs_trace.span("engine.epilogue", epochs=seg):
            if dirty_cols is not None:
                self._dirty |= np.asarray(dirty_cols).any(axis=0)
            else:
                self._dirty[:] = True  # unknown provenance: assume everything moved
            self._epochs_since_sync += seg
            if not advance_slots:
                # per-slot mode: the mirror sits at the epoch's LAST slot and
                # advance_slot increments it after this returns
                assert seg == 1
            if eth1_resets.any():
                self.state.eth1_data_votes = type(self.state.eth1_data_votes)()
            if hist_appends.any():
                root = bridge.sched_historical_batch_root(
                    self.dev.block_roots, self.dev.state_roots)
                for _ in range(int(hist_appends.sum())):
                    self.state.historical_roots.append(self.spec.Root(root))
            if sync_updates.any():
                # segment slicing guarantees the rotation fires only at the
                # segment's LAST epoch, so device columns are current for it.
                # In both modes the mirror sits at the last slot of the epoch
                # preceding the rotation when _rotate runs (its next_epoch =
                # slot//SPE + 1 = the epoch being entered).
                assert sync_updates[-1] and int(sync_updates.sum()) == 1
                if advance_slots:
                    self.state.slot += self.spec.SLOTS_PER_EPOCH * (seg - 1)
                self._rotate_sync_committees_resident()
                if advance_slots:
                    self.state.slot += self.spec.SLOTS_PER_EPOCH
            elif advance_slots:
                self.state.slot += self.spec.SLOTS_PER_EPOCH * seg
            # root-cache refreshes are LAZY: state_root() drains the owed epochs
            # so steps stay pure for callers that never ask for roots. Segments
            # are contiguous, so (last stepped epoch, count) identifies every
            # touched randao/slashings row — the epoch is pinned HERE, as "the
            # epoch just entered": post-advance slot//SPE, or (slot+1)//SPE when
            # advance_slot still owes the +1.
            self._pending_epochs += seg
            slot = int(self.state.slot)
            self._pending_last_epoch = (
                slot if advance_slots else slot + 1) // self.cfg.slots_per_epoch

    def run_epochs(self, k: int) -> None:
        """k epoch transitions in as few device launches as possible.

        Epochs are scanned on device in SEGMENTS that end at (and never
        cross) sync-committee period boundaries, because the rotation
        epilogue must read the registry columns AS OF its firing epoch —
        every other epilogue is exactly servable after the fact:

        - eth1 reset: clearing the host vote list is idempotent and the
          engine model adds no votes between epochs, so servicing the
          resets at segment end equals servicing them inline;
        - historical append: the epoch program never writes block_roots /
          state_roots (those are process_slot effects, host-side), so
          the HistoricalBatch root is invariant across a segment and the
          append(s) can fire late with identical values;
        - sync rotation: NOT deferrable past its epoch (registry churn
          between the boundary and segment end would change the sampled
          committee) — hence the segment slicing, which the host can do
          statically from the period schedule.

        Flag readout is one (seg_len, 3) fetch per segment instead of
        three bools per epoch — and it is PIPELINED: the aux host copies
        are started asynchronously at dispatch, and a segment that does
        not end at a rotation boundary (only ever the final one) stays
        deferred past return, so its epilogue service overlaps whatever
        the caller does next. Rotation segments are serviced before the
        following launch donates the registry columns their sampler reads.
        """
        period = self.cfg.epochs_per_sync_committee_period
        done = 0
        with _obs_trace.span("engine.run_epochs", k=k) as osp:
            segments = 0
            while done < k:
                # epochs remaining in the CURRENT period (next_epoch = cur+1
                # triggers rotation when it hits a multiple of the period);
                # the slot mirror lags by any still-deferred epochs.
                cur = (int(self.state.slot) // self.cfg.slots_per_epoch
                       + self._deferred_epochs)
                to_boundary = period - 1 - (cur % period) + 1  # epochs incl. the one firing rotation
                seg = min(k - done, to_boundary)
                self.dev, auxes = self._dispatch(
                    resident_scan_fn_for(self.cfg, seg), self.dev)
                _start_host_copies(auxes)
                self._flush_pending()  # previous segment overlaps this launch
                self._pending = auxes
                self._deferred_epochs = seg
                if seg == to_boundary:
                    self._flush_pending()  # segment rotates: service it now
                done += seg
                segments += 1
            osp.set(segments=segments)

    def _rotate_sync_committees_resident(self) -> None:
        """`process_sync_committee_updates` against device-current data.

        The host registry is stale here, so the sampler inputs come off the
        device: three (N,) columns (~24 MB at 1M — once per
        EPOCHS_PER_SYNC_COMMITTEE_PERIOD) and the 32-byte seed mix row.
        Pubkeys are immutable per validator index, so they still come from
        the host object tree. Matches bridge._rotate_sync_committees /
        specs/altair/beacon-chain.md get_next_sync_committee.
        """
        spec, state, cfg = self.spec, self.state, self.cfg
        # NOTE: the device slot has already advanced past the transition;
        # the host mirror has not (step_epoch advances it after this call),
        # so current_epoch/next_epoch come from the host slot.
        next_epoch = state.slot // cfg.slots_per_epoch + 1
        act = np.asarray(self.dev.activation_epoch)
        exit_ = np.asarray(self.dev.exit_epoch)
        eff = np.asarray(self.dev.effective_balance)
        active = np.nonzero(
            (act <= np.uint64(next_epoch)) & (np.uint64(next_epoch) < exit_)
        )[0].astype(np.uint64)
        # get_seed over the DEVICE randao mixes (the host rows are stale):
        # hash(domain_type + uint_to_bytes(epoch) + mix)
        mix_slot = (
            int(next_epoch) + cfg.epochs_per_historical_vector - cfg.min_seed_lookahead - 1
        ) % cfg.epochs_per_historical_vector
        mix = bridge._words_to_root(np.asarray(self.dev.randao_mixes[mix_slot]))
        seed = spec.hash(
            bytes(spec.DOMAIN_SYNC_COMMITTEE) + spec.uint_to_bytes(spec.Epoch(next_epoch)) + mix
        )
        bridge.install_next_sync_committee(spec, state, active, eff, bytes(seed))

    def dirty_columns(self) -> dict:
        """{tracked column name: moved since the last materialize} — the
        accumulated dirty-column diff. Read-only: the proof cache's epoch
        advance (proofs/cache.py) consumes this shape; materialize() still
        owns the reset."""
        return {name: bool(f) for name, f in zip(DIRTY_TRACKED, self._dirty)}

    def materialize(self) -> dict:
        """Sync the host `BeaconState` to the device state: the one
        write-back, identical in effect to the per-epoch write-back of the
        sequential loop (diff-based registry update + bulk vectors) — but
        DIRTY-AWARE: only columns some transition since the last sync
        actually mutated cross the host boundary, and the randao mix
        vector is gathered by its schedule-known touched rows (each epoch
        entered writes exactly row epoch % EPOCHS_PER_HISTORICAL_VECTOR)
        instead of wholesale. Transfers of the dirty columns are staged
        asynchronously before the sequential host reconstruction starts.

        Returns the transfer accounting dict from bridge._write_back
        ({"moved_bytes", "full_bytes", "clean_cols"})."""
        self._flush_pending()
        dirty = {name: bool(f) for name, f in zip(DIRTY_TRACKED, self._dirty)}
        epv = self.cfg.epochs_per_historical_vector
        since = self._epochs_since_sync
        if dirty.get("randao_mixes") and 0 < since < epv:
            last = self._pending_last_epoch
            mix_rows = sorted({e % epv for e in range(last - since + 1, last + 1)})
        else:
            mix_rows = None  # wraparound (or nothing ran): full diff path
        # Stage the D2H copies of every column the write-back will fetch,
        # so the transfers run while the host loop reconstructs earlier
        # columns (np.asarray in _write_back then completes, not starts,
        # each copy). randao is excluded when row-gathered.
        try:
            with _obs_trace.span("engine.host_copy"):
                rfaults.fire("engine.host_copy")
                for name, isdirty in dirty.items():
                    if not isdirty or (name == "randao_mixes" and mix_rows is not None):
                        continue
                    arr = getattr(self.dev, name)
                    if hasattr(arr, "copy_to_host_async"):
                        arr.copy_to_host_async()
        except Exception as exc:
            # staging is a latency optimization; _write_back reads sync
            if not is_retryable(exc):
                raise
        with _obs_trace.span("engine.materialize",
                             epochs=since) as sp:
            stats = bridge._write_back(
                self.spec, self.state, self.dev, self._pre_cols, self._pre_mixes,
                dirty=dirty, mix_rows=mix_rows, retry_policy=self.retry_policy)
            sp.set(moved_bytes=stats["moved_bytes"])
        self._dirty[:] = False
        self._epochs_since_sync = 0
        return stats

    def state_root(self) -> bytes:
        """hash_tree_root(BeaconState) WITHOUT materializing.

        INCREMENTAL (engine/incremental_root.py): the first call builds the
        device-resident Merkle level arrays (cost ≈ one full device sweep);
        every epoch step afterwards refreshes only what the transition
        dirtied — the wholesale vectors rebuild, the validator registry
        updates by dirty row, randao/slashings by path — and per-slot root
        obligations (advance_slot) cost one tree path each. Only the
        32-byte field roots cross to the host, where they merge with the
        host-owned field roots (genesis data, eth1, historical accumulator,
        sync committees — all kept current by the step epilogues).
        Bit-equal to materialize()+hash_tree_root
        (tests/test_resident_engine.py)."""
        from .incremental_root import IncrementalStateRoot
        from .state_root import assemble_state_root, validator_static_leaves

        self._flush_pending()  # the deferred epilogue, under its own spans
        with _obs_trace.span("engine.state_root"):
            with _obs_trace.span("engine.root_refresh",
                                 epochs=self._pending_epochs):
                if self._inc is None:
                    if not hasattr(self, "_static_leaves"):
                        self._static_leaves = jnp.asarray(
                            validator_static_leaves(self.state))
                    self._inc = IncrementalStateRoot(self.dev, self._static_leaves)
                elif self._pending_epochs:
                    self._inc.refresh_after_epochs(
                        self.dev,
                        last_epoch=self._pending_last_epoch,
                        count=self._pending_epochs,
                        epochs_per_historical_vector=self.cfg.epochs_per_historical_vector,
                    )
                self._pending_epochs = 0
            with _obs_trace.span("engine.root_readout"):
                roots = jax.device_get(self._inc.device_roots(int(self.state.slot)))
            with _obs_trace.span("engine.root_assemble"):
                return assemble_state_root(self.spec, self.state, roots)

    def advance_slot(self) -> None:
        """`process_slot` (+ the epoch transition at boundaries) against the
        resident state — the per-slot drive mode, exactly
        specs/phase0/beacon-chain.md process_slots' loop body:

          1. previous_state_root = hash_tree_root(state)   (incremental)
          2. state_roots[slot % SPHR] = previous_state_root; fill an empty
             latest_block_header.state_root; block_roots[slot % SPHR] =
             hash_tree_root(latest_block_header)
          3. at (slot+1) % SLOTS_PER_EPOCH == 0: process_epoch (the device
             step, slot mirror untouched)
          4. slot += 1

        History-vector writes land on the host state (canonical), the
        device arrays (the historical-batch epilogue reads them), and the
        incremental root trees (one path each). Interleaves safely with
        step_epoch()/run_epochs() — slot accounting is owned here in this
        mode (step_epoch(advance_slots=False))."""
        spec, state, cfg = self.spec, self.state, self.cfg
        self._flush_pending()
        prev_root = self.state_root()
        idx = int(state.slot) % cfg.slots_per_historical_root
        root_words = jnp.asarray(np.frombuffer(prev_root, dtype=">u4").astype(np.uint32))
        state.state_roots[idx] = spec.Root(prev_root)
        self.dev = self.dev.replace(
            state_roots=self.dev.state_roots.at[idx].set(root_words))
        self._inc.record_state_root(idx, root_words)
        if state.latest_block_header.state_root == spec.Root():
            state.latest_block_header.state_root = spec.Root(prev_root)
        from ..ssz import hash_tree_root as _htr

        block_root = bytes(_htr(state.latest_block_header))
        b_words = jnp.asarray(np.frombuffer(block_root, dtype=">u4").astype(np.uint32))
        state.block_roots[idx] = spec.Root(block_root)
        self.dev = self.dev.replace(
            block_roots=self.dev.block_roots.at[idx].set(b_words))
        self._inc.record_block_root(idx, b_words)
        if (int(state.slot) + 1) % cfg.slots_per_epoch == 0:
            self.step_epoch(advance_slots=False)
        state.slot += 1
