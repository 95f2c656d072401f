"""Device-resident multi-epoch engine vs the sequential bridge loop.

`ResidentEpochEngine` (engine/resident.py) keeps the registry in device
HBM across K epochs and syncs the host BeaconState once at the end; the
sequential loop (`apply_epoch_via_engine` + host slot advance per epoch)
round-trips every epoch and is itself differentially tested against the
compiled spec (tests/test_epoch_engine.py). The two must produce
SSZ-hash-identical states — including across eth1-reset, historical-append
and sync-committee-rotation boundaries, whose epilogues the resident
engine services from device-current data.
"""
import pytest

from consensus_specs_tpu.compiler import get_spec
from consensus_specs_tpu.crypto import bls
from consensus_specs_tpu.engine import bridge
from consensus_specs_tpu.engine.resident import ResidentEpochEngine
from consensus_specs_tpu.ssz import hash_tree_root


@pytest.fixture(scope="module")
def spec():
    return get_spec("altair", "minimal")


def _prepared_state(spec, start_epoch: int, seed: int):
    # shared with test_robustness / test_chaos_epoch via testlib
    from consensus_specs_tpu.testlib.state import prepared_epoch_state

    return prepared_epoch_state(spec, start_epoch, seed)


@pytest.mark.parametrize("k_epochs", [3, 9])
def test_resident_matches_sequential_loop(spec, k_epochs):
    """k=9 from epoch 6 crosses (minimal preset): eth1 reset (period 4),
    historical append (every 8 epochs), and a sync-committee rotation
    (period 8) — every epilogue the resident engine services lazily."""
    was = bls.bls_active
    bls.bls_active = False
    try:
        seq = _prepared_state(spec, start_epoch=6, seed=11)
        res = seq.copy()

        for _ in range(k_epochs):
            bridge.apply_epoch_via_engine(spec, seq)
            seq.slot += spec.SLOTS_PER_EPOCH

        eng = ResidentEpochEngine(spec, res)
        for _ in range(k_epochs):
            eng.step_epoch()
        eng.materialize()

        assert int(res.slot) == int(seq.slot)
        assert bytes(hash_tree_root(res)) == bytes(hash_tree_root(seq))
    finally:
        bls.bls_active = was


def test_resident_state_stale_until_materialize(spec):
    """The documented contract: registry fields lag until materialize()."""
    was = bls.bls_active
    bls.bls_active = False
    try:
        st = _prepared_state(spec, start_epoch=6, seed=3)
        before = [int(b) for b in st.balances]
        eng = ResidentEpochEngine(spec, st)
        eng.step_epoch()
        assert [int(b) for b in st.balances] == before  # untouched host copy
        eng.materialize()
        assert [int(b) for b in st.balances] != before  # rewards applied
    finally:
        bls.bls_active = was


def test_resident_state_root_matches_host_tree(spec):
    """Device-side state root (engine/state_root.py): bit-equal to the
    host SSZ tree, across several epochs and every period epilogue."""
    was = bls.bls_active
    bls.bls_active = False
    try:
        st = _prepared_state(spec, start_epoch=6, seed=5)
        eng = ResidentEpochEngine(spec, st)
        for _ in range(4):
            eng.step_epoch()
            eng.state_root()  # well-defined at every intermediate epoch
        eng_root = eng.state_root()
        eng.materialize()
        host_root = bytes(hash_tree_root(st))
        assert eng_root == host_root
    finally:
        bls.bls_active = was


def test_state_root_and_epilogue_spans(spec):
    """`engine.state_root` holds three children that partition it (refresh,
    readout, assembly); the deferred epilogue it drains first is outside
    it, one `engine.epilogue` for each serviced epoch."""
    from consensus_specs_tpu.obs import trace as obs_trace
    from consensus_specs_tpu.obs.metrics import MetricsRegistry

    was = bls.bls_active
    bls.bls_active = False
    tr = obs_trace.Tracer(registry=MetricsRegistry()).install()
    try:
        eng = ResidentEpochEngine(spec, _prepared_state(spec, start_epoch=6, seed=5))
        eng.state_root()  # the first build
        eng.step_epoch()
        eng.step_epoch()
        eng.state_root()
    finally:
        tr.uninstall()
        bls.bls_active = was
    children = ("engine.root_refresh", "engine.root_readout", "engine.root_assemble")
    roots = tr.spans("engine.state_root")
    assert len(roots) == 2
    for root in roots:
        end = root["t_start"] + root["duration"]
        kids = [s for s in tr.spans() if s["parent"] == "engine.state_root"
                and root["t_start"] <= s["t_start"] <= end]
        assert [k["name"] for k in kids] == list(children)
        assert all(k["t_start"] + k["duration"] <= end and k["depth"] == 1 for k in kids)
        assert sum(k["duration"] for k in kids) <= root["duration"]
    assert [s["attrs"]["epochs"] for s in tr.spans("engine.root_refresh")] == [0, 2]
    epilogues = tr.spans("engine.epilogue")
    assert [s["attrs"]["epochs"] for s in epilogues] == [1, 1]
    assert all(s["parent"] != "engine.state_root" for s in epilogues)


def test_resident_state_root_bellatrix(spec):
    """The generic field-root assembly covers bellatrix's extra
    (host-owned) execution-payload-header field."""
    was = bls.bls_active
    bls.bls_active = False
    try:
        bspec = get_spec("bellatrix", "minimal")
        st = _prepared_state(bspec, start_epoch=6, seed=4)
        eng = ResidentEpochEngine(bspec, st)
        eng.step_epoch()
        root = eng.state_root()
        eng.materialize()
        assert root == bytes(hash_tree_root(st))
    finally:
        bls.bls_active = was


def test_resident_state_root_before_any_step(spec):
    """Root agreement at the bridge-in point (no epoch run yet)."""
    was = bls.bls_active
    bls.bls_active = False
    try:
        st = _prepared_state(spec, start_epoch=6, seed=9)
        expected = bytes(hash_tree_root(st))
        eng = ResidentEpochEngine(spec, st)
        assert eng.state_root() == expected
    finally:
        bls.bls_active = was


@pytest.mark.parametrize("k_epochs", [5, 17])
def test_run_epochs_scan_matches_stepwise(spec, k_epochs):
    """The lax.scan segment runner (run_epochs) is bit-equal to k
    step_epoch calls — k=17 from epoch 6 crosses TWO sync-committee
    rotations plus eth1 resets and historical appends on minimal."""
    was = bls.bls_active
    bls.bls_active = False
    try:
        st_a = _prepared_state(spec, start_epoch=6, seed=21)
        st_b = st_a.copy()

        eng_a = ResidentEpochEngine(spec, st_a)
        for _ in range(k_epochs):
            eng_a.step_epoch()
        eng_a.materialize()

        eng_b = ResidentEpochEngine(spec, st_b)
        eng_b.run_epochs(k_epochs)
        eng_b.materialize()

        assert int(st_a.slot) == int(st_b.slot)
        assert bytes(hash_tree_root(st_a)) == bytes(hash_tree_root(st_b))
    finally:
        bls.bls_active = was


def test_resident_per_slot_roots_incremental(spec):
    """process_slot's per-slot obligation against the resident state
    (engine/incremental_root.py): advance_slot() records state and header
    roots one tree path at a time — including across an epoch boundary,
    where it fires the device epoch step itself — and stays bit-equal to
    the host SSZ tree. Differential oracle: the compiled spec's
    process_slots over the materialized state."""
    was = bls.bls_active
    bls.bls_active = False
    try:
        st = _prepared_state(spec, start_epoch=6, seed=11)
        import copy as _copy

        oracle = _copy.deepcopy(st)
        eng = ResidentEpochEngine(spec, st)
        n_slots = int(spec.SLOTS_PER_EPOCH) + 5  # crosses one boundary
        for _ in range(n_slots):
            eng.advance_slot()
        inc_root = eng.state_root()
        eng.materialize()
        assert inc_root == bytes(hash_tree_root(st))
        # spec-level oracle: identical end state via process_slots
        spec.process_slots(oracle, oracle.slot + n_slots)
        assert bytes(hash_tree_root(oracle)) == inc_root
    finally:
        bls.bls_active = was


def test_resident_incremental_across_scan_segments(spec):
    """run_epochs (scan form) refreshes the incremental cache per segment:
    roots after multi-epoch scans equal the host tree, including across a
    sync-committee rotation boundary."""
    was = bls.bls_active
    bls.bls_active = False
    try:
        st = _prepared_state(spec, start_epoch=6, seed=12)
        eng = ResidentEpochEngine(spec, st)
        eng.state_root()  # build the cache BEFORE any step: scan path must refresh it
        eng.run_epochs(5)
        inc_root = eng.state_root()
        eng.materialize()
        assert inc_root == bytes(hash_tree_root(st))
    finally:
        bls.bls_active = was
