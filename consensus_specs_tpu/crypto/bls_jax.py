"""Device-batched BLS verification: the bridge between the BLS shim and the
TPU pairing kernels.

Reference parity: the role milagro plays behind eth2spec/utils/bls.py
(:17-22 use_milagro — the fast backend CI and all vector generation run on).
Here the fast backend is ops/bls12_jax.py's batched pairing over the RNS
field (ops/fp_rns.py), and the unit of work is a BATCH of signature checks:
one `pairing_check_batch` launch verifies every queued (pubkey, message,
signature) triple of a block/epoch at once (SURVEY.md §7 deferred-batch
stance).

Host side (this module): decompression, hash-to-curve, G1 aggregation for
FastAggregateVerify, padding to bucketed batch shapes (so jit caches stay
small), and the bool readout. Device side: the subgroup checks (pubkeys in
the msm lane's batched [r]-ladder; a flush's signatures, decompressed on the
host without the check, in one batched ψ test at the head of run_checks),
then two Miller loops + shared final exponentiation per item.
"""
from __future__ import annotations

import os
from collections.abc import Mapping
from functools import lru_cache

import numpy as np

from ..obs import metrics as _obs_metrics
from ..obs import trace as _obs_trace
from ..sched import bucketing as _bucketing
from . import bls12_381 as oracle
from .hash_to_curve import hash_to_curve_g2 as _hash_to_curve_g2_uncached
from .bls12_381 import g2_from_bytes as _g2_from_bytes_uncached


# Two pure host functions, both heavily repeated in real workloads:
# messages recur across the aggregates of a slot/epoch (same signing root
# per committee target) and benchmarks replay identical attestation sets.
# g2_from_bytes returns subgroup-checked points only, so the flush, which
# checks its signatures on the device, decodes them without it
# (_signature_and_message). Same caching stance as g1_from_bytes below;
# entries are a few KB -> both caps stay in the tens of MB.
@lru_cache(maxsize=1 << 13)
def hash_to_curve_g2(msg: bytes):
    return _hash_to_curve_g2_uncached(msg)


@lru_cache(maxsize=1 << 13)
def g2_from_bytes(data: bytes):
    return _g2_from_bytes_uncached(data)


# Cache sizing: each entry holds the 48 compressed bytes plus an affine
# point (two ~381-bit ints, ~0.5 KB with dict overhead), so a full cache
# is ~0.5 GB at the 2^20 default — sized for a 1M-validator registry where
# every pubkey recurs each epoch. Override for memory-constrained hosts
# via CONSENSUS_TPU_PUBKEY_CACHE (power-of-two entry count); the cache is
# keyed on raw bytes so shrinking it only costs re-decompression.
_PUBKEY_CACHE_SIZE = int(os.environ.get("CONSENSUS_TPU_PUBKEY_CACHE", 1 << 20))


@lru_cache(maxsize=_PUBKEY_CACHE_SIZE)
def g1_from_bytes(data: bytes):
    """Memoized validated G1 decompression. A node sees the same validator
    pubkeys every epoch, and the r-subgroup check (a 255-bit scalar
    multiplication) dominates decompression cost — so cache by the 48
    compressed bytes, exactly as reference clients cache deserialized
    pubkeys behind milagro. Invalid encodings raise and are NOT cached
    (lru_cache does not memoize raising calls): they are attacker-supplied
    and mostly fail cheaply before the subgroup check."""
    return oracle.g1_from_bytes(data)

# known-valid padding item: e(G1, G2) * e(-G1, G2) == 1
_G1 = oracle.G1_GEN_AFF
_NEG_G1 = (_G1[0], (-_G1[1]) % oracle.P)
_G2 = oracle.G2_GEN_AFF

_MIN_BATCH = 8
# batches at least this big use the shared-final-exponentiation randomized
# check first (one final exp for the whole batch); only a failing batch pays
# the per-item pass for attribution
RLC_MIN_BATCH = 16


def _bucket(n: int) -> int:
    return _bucketing.pow2_bucket(n, _MIN_BATCH)


def _device_check(p1s, q1s, p2s, q2s) -> np.ndarray:
    """e(p1_i, q1_i) * e(p2_i, q2_i) == 1 per item; affine int coords in,
    bool array out. Pads to the next power-of-two bucket."""
    import jax

    from ..ops import bls12_jax as K

    n = len(p1s)
    _, args = _pack_pairing_args(p1s, q1s, p2s, q2s)
    ok = K.pairing_check_batch(*args)
    return np.asarray(jax.device_get(ok))[:n]


class QueuedCheck:
    """One deferred signature check, normalized to the two-pairing form.
    The signature q2 is on the twist but not yet known to lie in G2:
    run_checks checks it on the device before any pairing."""

    __slots__ = ("p1", "q1", "p2", "q2")

    def __init__(self, p1, q1, p2, q2):
        self.p1, self.q1, self.p2, self.q2 = p1, q1, p2, q2


def _signature_and_message(message: bytes, signature: bytes):
    """(H(m)_aff, sig_aff), or None for an invalid encoding (the point at
    infinity is never valid here). The signature is decompressed WITHOUT
    the subgroup check, so it bypasses the validated g2_from_bytes cache:
    run_checks checks the flush's signatures for G2 membership on the
    device, in one launch, in place of a 255-bit host ladder each."""
    try:
        with _obs_trace.span("bls.prep.sig_decode"):
            sig = oracle.g2_from_bytes(bytes(signature), subgroup_check=False)
    except ValueError:
        return None
    if sig is None:
        return None
    with _obs_trace.span("bls.prep.hash_to_curve"):
        hm = hash_to_curve_g2(bytes(message))
    return hm, sig


def _decompress_inputs(pubkey: bytes, message: bytes, signature: bytes):
    """(pk_aff, H(m)_aff, sig_aff) or None if any input is invalid."""
    try:
        with _obs_trace.span("bls.prep.pk_decode"):
            pk = g1_from_bytes(bytes(pubkey))
    except ValueError:
        return None
    if pk is None:
        return None
    hm_sig = _signature_and_message(message, signature)
    if hm_sig is None:
        return None
    return (pk, *hm_sig)


def make_verify_check(pubkey, message, signature) -> QueuedCheck | None:
    """Verify(pk, m, sig) as a QueuedCheck (None = statically invalid)."""
    dec = _decompress_inputs(pubkey, message, signature)
    if dec is None:
        return None
    pk, hm, sig = dec
    return QueuedCheck(pk, hm, _NEG_G1, sig)


# Memoized committee-pubkey aggregation, keyed by sha256 of the
# concatenated compressed keys: only a 32-byte digest plus the affine
# result is retained per entry (keying an lru_cache on the pubkey tuple
# itself would pin ~45 KB of key objects per mainnet sync committee).
# The same committee aggregates on every re-verification of its
# attestations (gossip then block import; benchmark warm-up then measured
# run), and ~128 host point-adds per check otherwise dominate flush prep.
_AGG_CACHE: dict = {}
_AGG_CACHE_MAX = 1 << 12


class _ValidatedPubkeys:
    """Device-validated pubkeys: compressed bytes -> (affine pair, its
    Montgomery rows), filled by the batched device subgroup check in
    _aggregate_pubkeys_device_impl. Kept apart from the g1_from_bytes
    lru_cache because an lru_cache can only be filled by the wrapped call,
    which is exactly the host 255-bit pt_mul this lane exists to avoid.

    Each key takes one slot: its point in `points`, and its x and y rows
    (`F.to_mont` of the coordinates, as the aggregation program reads
    them) in one (`_PK_VALIDATED_MAX`, 2, NLIMBS) int32 table, so a set's
    warm keys are one `np.take`. Bounded FIFO by slot reuse: a new key
    takes the oldest slot, point and rows together. Memory at the cap of
    2^16 keys: the table is 32 MiB (512 B a key), the points ~0.5 KB a
    key more. The rows belong to the field backend they were encoded for;
    a switch of backend empties the store."""

    def __init__(self):
        import threading

        self._lock = threading.Lock()
        self.clear()

    def clear(self) -> None:
        with self._lock:
            self.slot_of: dict = {}
            self.keys: list = []
            self.points: list = []
            self.rows = None  # allocated on the first insert
            self.field = None  # the backend module the rows encode for
            self.next = 0

    def __len__(self) -> int:
        return len(self.slot_of)

    def lookup(self, field, pubkeys: list):
        """(slot per key, None where cold; the warm keys' rows, gathered
        in order as (n_warm, 2, NLIMBS)). Rows come out under the lock that
        guards eviction, so they are the rows of the keys looked up."""
        with self._lock:
            if self.field is not field:
                return [None] * len(pubkeys), None
            slots = [self.slot_of.get(pk) for pk in pubkeys]
            warm = np.fromiter((s for s in slots if s is not None), np.intp)
            return slots, np.take(self.rows, warm, axis=0)

    def insert(self, field, pubkeys: list, points: list, X, Y) -> None:
        """Cache keys that passed the subgroup check, with their rows."""
        with self._lock:
            cap = _PK_VALIDATED_MAX
            if self.field is not field or len(self.keys) != cap:
                self.slot_of, self.next = {}, 0
                self.keys, self.points = [None] * cap, [None] * cap
                self.rows = np.zeros((cap, 2, field.NLIMBS), np.asarray(X).dtype)
                self.field = field
            for pk, pt, x, y in zip(pubkeys, points, X, Y):
                if pk in self.slot_of:
                    continue  # a key listed twice in one set
                s = self.next
                self.next = (s + 1) % len(self.keys)
                old = self.keys[s]
                if old is not None:
                    del self.slot_of[old]
                self.keys[s], self.points[s] = pk, pt
                self.rows[s, 0], self.rows[s, 1] = x, y
                self.slot_of[pk] = s


_PK_VALIDATED_MAX = 1 << 16
_PK_VALIDATED = _ValidatedPubkeys()


def _aggregate_pubkeys_affine(pubkeys_bytes: list):
    """Affine sum of compressed pubkeys (None for an infinity sum);
    raises ValueError on an invalid encoding (never cached)."""
    import hashlib

    key = hashlib.sha256(b"".join(pubkeys_bytes)).digest()
    # LRU, not FIFO: refresh a hit so a hot committee aggregate inserted
    # early outlives cold entries (re-insertion moves it to the dict's
    # end). pop(key, None) keeps this race-safe against a concurrent hit
    # or clear_caches() — a lost entry just recomputes below.
    hit = _AGG_CACHE.pop(key, None)
    if hit is not None:
        _AGG_CACHE[key] = hit
        return hit
    if len(pubkeys_bytes) >= DEVICE_AGGREGATE_MIN:
        marker = _aggregate_pubkeys_sched(pubkeys_bytes)
        if marker is not None:
            if marker[0] == "bad_encoding":
                raise ValueError(marker[1])
            if marker[0] in ("inf_member", "inf"):
                return None  # invalid/degenerate input: never cached
            agg = (marker[1], marker[2])
            if len(_AGG_CACHE) >= _AGG_CACHE_MAX:
                _AGG_CACHE.pop(next(iter(_AGG_CACHE)))
            _AGG_CACHE[key] = agg
            return agg
    acc = None
    for pk in pubkeys_bytes:
        aff = g1_from_bytes(pk)
        if aff is None:
            return None  # infinity pubkey: invalid input, don't cache
        pt = oracle.pt_from_affine(oracle.FP_FIELD, aff)
        acc = pt if acc is None else oracle.pt_add(oracle.FP_FIELD, acc, pt)
    agg = oracle.pt_to_affine(oracle.FP_FIELD, acc)
    if len(_AGG_CACHE) >= _AGG_CACHE_MAX:
        _AGG_CACHE.pop(next(iter(_AGG_CACHE)))
    _AGG_CACHE[key] = agg
    return agg


def _aggregate_pubkeys_sched(pubkeys_bytes: list):
    """Submit one committee aggregate to the sched "msm" work class and
    return its marker tuple, or None when the lane is unavailable (the
    class is not registered on the default scheduler — e.g. a test
    scheduler built from a trimmed class list). Nested submits are safe:
    the scheduler's lock is re-entrant, so this works from inside a BLS
    flush that is itself being served through sched."""
    from .. import sched as _sched

    sch = _sched.default_scheduler()
    if "msm" not in sch.classes:
        return None
    h = sch.submit(_sched.Request(
        work_class="msm", kind="aggregate", payload=tuple(pubkeys_bytes)))
    return h.result()


def _aggregate_pubkeys_device_impl(pubkeys_bytes: list):
    """Device committee aggregation — the "aggregate" kind behind the sched
    msm class. Returns a marker tuple instead of raising, so the scheduler
    seam can carry the outcome through its object-dtype result rows:

        ("point", x, y)        affine aggregate (ints mod p)
        ("inf",)               the sum is the identity
        ("inf_member",)        an infinity pubkey appeared (invalid input)
        ("bad_encoding", msg)  decompression / subgroup rejection

    Keys never seen before decompress WITHOUT the host 255-bit subgroup
    pt_mul (bls12_381.py:590) and are validated in ONE batched device
    ladder ([r]P == inf via g1_subgroup_check_rows) — the firehose cold
    lane's dominant cost (one ~4 ms host check per member, ~2.7 s per
    488-member committee) collapses to a single bucketed kernel launch.
    The sum itself is the all-ones-scalar MSM degenerate case: a plain
    masked reduction tree (g1_aggregate_rows), no windows needed.

    Each key is encoded into Montgomery rows once, when first validated:
    a cold key's rows feed the subgroup check, are cached with its point
    once it passes, and feed the sum; a warm key's rows come from the
    cache (`bls_pubkey_row_hits_total`)."""
    from ..ops import bls12_jax as K

    reg = _obs_metrics.REGISTRY
    field = K.F
    pks = [bytes(pk) for pk in pubkeys_bytes]
    cold_idx: list = []
    cold_affs: list = []
    try:
        with _obs_trace.span("bls.aggregate.decode", keys=len(pks)):
            slots, warm_rows = _PK_VALIDATED.lookup(field, pks)
            for i, s in enumerate(slots):
                if s is not None:
                    continue
                aff = oracle.g1_from_bytes(pks[i], subgroup_check=False)
                if aff is None:
                    return ("inf_member",)
                cold_idx.append(i)
                cold_affs.append(aff)
    except ValueError as e:
        return ("bad_encoding", str(e))
    rows = np.empty((len(pks), 2, field.NLIMBS), np.asarray(field.ONE_MONT).dtype)
    if warm_rows is not None:
        rows[[i for i, s in enumerate(slots) if s is not None]] = warm_rows
    if cold_idx:
        with _obs_trace.span("bls.aggregate.subgroup", keys=len(cold_idx)):
            cx = field.ints_to_mont_batch([a[0] for a in cold_affs])
            cy = field.ints_to_mont_batch([a[1] for a in cold_affs])
            ok = K.g1_subgroup_check_rows(cx, cy)
            if not bool(ok.all()):
                return ("bad_encoding", "G1 point not in r-subgroup")
        _PK_VALIDATED.insert(field, [pks[i] for i in cold_idx], cold_affs, cx, cy)
        rows[cold_idx, 0], rows[cold_idx, 1] = cx, cy
        reg.counter("bls_pubkey_subgroup_device_total").inc(len(cold_idx))
    reg.counter("bls_pubkey_row_hits_total").inc(len(pks) - len(cold_idx))
    with _obs_trace.span("bls.aggregate.device", keys=len(pks)):
        total = K.g1_aggregate_rows(rows[:, 0], rows[:, 1])
    reg.counter("bls_pubkey_aggregate_device_total").inc()
    reg.counter("bls_pubkey_aggregate_device_keys_total").inc(len(pks))
    if total is None:
        return ("inf",)
    return ("point", total[0], total[1])


def make_fast_aggregate_check(pubkeys, message, signature) -> QueuedCheck | None:
    """FastAggregateVerify: aggregate the pubkeys on host, then one check."""
    if len(pubkeys) == 0:
        return None
    try:
        with _obs_trace.span("bls.prep.aggregate", keys=len(pubkeys)):
            agg = _aggregate_pubkeys_affine([bytes(pk) for pk in pubkeys])
    except ValueError:
        return None
    if agg is None:
        return None
    hm_sig = _signature_and_message(message, signature)
    if hm_sig is None:
        return None
    hm, sig = hm_sig
    return QueuedCheck(agg, hm, _NEG_G1, sig)


def random_zbits(n: int):
    """(n, 64) bool device array of host-drawn nonzero 64-bit scalars — the
    randomness input of pairing_check_rlc (single shared packing helper)."""
    import secrets

    import jax.numpy as jnp
    import numpy as np

    zs = [secrets.randbelow(2**64 - 1) + 1 for _ in range(n)]
    return jnp.asarray(
        np.array([[(z >> i) & 1 for i in range(64)] for z in zs], dtype=bool))


def _pack_pairing_args(p1s, q1s, p2s, q2s):
    """Pad to the bucket and encode into pairing_check_* positional args."""
    from ..ops import bls12_jax as K

    n = len(p1s)
    b = _bucket(n)
    pad = b - n
    p1s = list(p1s) + [_G1] * pad
    q1s = list(q1s) + [_G2] * pad
    p2s = list(p2s) + [_NEG_G1] * pad
    q2s = list(q2s) + [_G2] * pad
    enc = K.F.ints_to_mont_batch

    def g1_coords(pts):
        return enc([p[0] for p in pts]), enc([p[1] for p in pts])

    def g2_coords(pts):
        x = (enc([p[0][0] for p in pts]), enc([p[0][1] for p in pts]))
        y = (enc([p[1][0] for p in pts]), enc([p[1][1] for p in pts]))
        return x, y

    px, py = g1_coords(p1s)
    qx, qy = g2_coords(q1s)
    p2x, p2y = g1_coords(p2s)
    q2x, q2y = g2_coords(q2s)
    return b, (qx, qy, px, py, q2x, q2y, p2x, p2y)


# Observability for the most recent randomized flush: which kernel path ran,
# the padded item/distinct counts, and the Miller-loop bill it implies. The
# source of truth is the metrics registry (record_flush below feeds gauges +
# per-path counters); LAST_FLUSH remains as a read-only Mapping VIEW over
# those series so existing consumers (benches/bls_verify_bench.py,
# tests/test_rlc_grouped.py) keep indexing it like the dict it used to be.

_FLUSH_PATHS = ("rlc", "rlc_grouped")


def record_flush(path: str, items: int, distinct: int,
                 miller_loops: int) -> None:
    """Publish one flush's routing decision to the metrics registry."""
    reg = _obs_metrics.REGISTRY
    reg.counter("bls_flush_total", path=path).inc()
    reg.counter("bls_flush_items_total", path=path).inc(items)
    reg.counter("bls_flush_miller_loops_total", path=path).inc(miller_loops)
    reg.gauge("bls_last_flush_items").set(int(items))
    reg.gauge("bls_last_flush_distinct").set(int(distinct))
    reg.gauge("bls_last_flush_miller_loops").set(int(miller_loops))
    for p in _FLUSH_PATHS:
        reg.gauge("bls_last_flush_path", path=p).set(1 if p == path else 0)
    _obs_trace.annotate(flush_path=path, flush_items=int(items),
                        flush_miller_loops=int(miller_loops))


class _LastFlushView(Mapping):
    """Dict-shaped read view of the last flush, backed by the registry.

    Empty before any flush (like the dict it replaces after .clear());
    supports the full Mapping protocol so `view["path"]`, `view.get(...)`
    and `dict(view)` behave exactly as before the migration."""

    def _data(self) -> dict:
        reg = _obs_metrics.REGISTRY
        path = None
        for p in _FLUSH_PATHS:
            if reg.gauge_value("bls_last_flush_path", path=p) == 1:
                path = p
        if path is None:
            return {}
        return {
            "path": path,
            "items": int(reg.gauge_value("bls_last_flush_items")),
            "distinct": int(reg.gauge_value("bls_last_flush_distinct")),
            "miller_loops": int(reg.gauge_value("bls_last_flush_miller_loops")),
        }

    def __getitem__(self, key):
        return self._data()[key]

    def __iter__(self):
        return iter(self._data())

    def __len__(self):
        return len(self._data())

    def __repr__(self):
        return f"LAST_FLUSH({self._data()!r})"


LAST_FLUSH = _LastFlushView()


def _pack_grouped_args(p1s, q1s, q2s):
    """Group checks by distinct q1 (the H(m) point) and pack the segmented
    kernel's arguments: (b_n, b_d, (qx, qy, px, py, q2x, q2y), seg_ids).

    q1 points come out of the hash_to_curve_g2 lru_cache, so equal messages
    share one tuple — but grouping keys on the VALUE (nested int tuples,
    hashable) so identity is an optimization, never a correctness input.

    Padding: distinct count pads to a power of two (one jit cache entry per
    (b_n, b_d) bucket pair, same stance as _bucket) and every pad group is
    seeded with at least one pad item — an empty segment would sum to
    infinity and fail the batch closed (see g1_segment_sum). Pad items are
    identities by construction: e(G1, Q)·e(−G1, Q) == 1 for ANY G2 point Q,
    so a pad item joining group g uses q1_g as its "signature". The item
    bucket is therefore computed over n + pad_groups, which guarantees
    pad_items >= pad_groups. The shape/assignment math lives in
    sched/bucketing.grouped_plan (shared with the scheduler's lanes); this
    function only supplies the BLS pad values."""
    from ..ops import bls12_jax as K

    plan = _bucketing.grouped_plan(q1s, _MIN_BATCH)
    b_n, b_d = plan.b_n, plan.b_d

    reps = [q1s[i] for i in plan.rep_index] + [_G2] * plan.pad_groups
    p1s = list(p1s) + [_G1] * plan.pad_items
    # sig := q1_g makes each pad check an identity for its group
    q2s = list(q2s) + [reps[g] for g in plan.pad_assignments]

    import jax.numpy as jnp
    import numpy as np

    enc = K.F.ints_to_mont_batch
    px, py = enc([p[0] for p in p1s]), enc([p[1] for p in p1s])
    qx = (enc([q[0][0] for q in reps]), enc([q[0][1] for q in reps]))
    qy = (enc([q[1][0] for q in reps]), enc([q[1][1] for q in reps]))
    q2x = (enc([s[0][0] for s in q2s]), enc([s[0][1] for s in q2s]))
    q2y = (enc([s[1][0] for s in q2s]), enc([s[1][1] for s in q2s]))
    seg_ids = jnp.asarray(np.array(plan.seg, dtype=np.int32))
    return b_n, b_d, (qx, qy, px, py, q2x, q2y), seg_ids


def _device_check_all(p1s, q1s, p2s, q2s) -> bool:
    """Single-bool randomized batch check (pairing_check_rlc) with host-drawn
    64-bit scalars; soundness error 2^-64 per flush.

    When messages repeat across the batch (attestation workloads: every
    committee of a slot signs the same root), the flush takes the segmented
    kernel path — D+1 Miller loops for D distinct messages instead of
    N+1. All-distinct batches keep the ungrouped kernel (the segment
    reduce would be pure overhead at D == N)."""
    import jax
    import numpy as np

    from ..ops import bls12_jax as K

    # every queued check's second pairing is e(−G1, sig) (QueuedCheck
    # construction above) — the fixed-base window path applies; the assert
    # pins the invariant so a future check kind with a different base fails
    # loudly instead of silently verifying the wrong equation
    assert all(p2 is _NEG_G1 for p2 in p2s), "RLC fast path requires p2 == -G1"
    n = len(p1s)
    with _obs_trace.span("bls.flush", checks=n):
        if len(set(q1s)) < n:
            with _obs_trace.span("bls.flush.pack", path="rlc_grouped"):
                b_n, b_d, args, seg_ids = _pack_grouped_args(p1s, q1s, q2s)
            with _obs_trace.span("bls.flush.scalars", path="rlc_grouped"):
                z = random_zbits(b_n)
            with _obs_trace.span("bls.flush.device", path="rlc_grouped"):
                ok = K.pairing_check_rlc(*args, None, None, z,
                                         p2_is_neg_g1=True, seg_ids=seg_ids)
                result = bool(np.asarray(jax.device_get(ok)))
            record_flush("rlc_grouped", items=b_n, distinct=b_d,
                         miller_loops=b_d + 1)
        else:
            with _obs_trace.span("bls.flush.pack", path="rlc"):
                b, args = _pack_pairing_args(p1s, q1s, p2s, q2s)
            with _obs_trace.span("bls.flush.scalars", path="rlc"):
                z = random_zbits(b)
            with _obs_trace.span("bls.flush.device", path="rlc"):
                ok = K.pairing_check_rlc(*args, z, p2_is_neg_g1=True)
                result = bool(np.asarray(jax.device_get(ok)))
            record_flush("rlc", items=b, distinct=b, miller_loops=b + 1)
    return result


def _check_signatures_in_g2(checks: list) -> list:
    """Every live check's signature through one batched device ψ test
    (ops/bls12_jax.g2_subgroup_check_device); a check whose signature lies
    outside G2 becomes None, the verdict False."""
    from ..ops import bls12_jax as K

    live = [i for i, c in enumerate(checks) if c is not None]
    if not live:
        return checks
    reg = _obs_metrics.REGISTRY
    with _obs_trace.span("bls.flush.sig_subgroup", sigs=len(live)):
        ok = K.g2_subgroup_check_device([checks[i].q2 for i in live])
    reg.counter("bls_sig_subgroup_device_total").inc(len(live))
    bad = [i for i, good in zip(live, ok) if not good]
    if not bad:
        return checks
    reg.counter("bls_sig_subgroup_rejected_total").inc(len(bad))
    checks = list(checks)
    for i in bad:
        checks[i] = None
    return checks


def run_checks(checks) -> np.ndarray:
    """Execute a list of QueuedCheck | None on device; None -> False. The
    signatures are checked for G2 membership first, in one launch."""
    checks = _check_signatures_in_g2(checks)
    live = [(i, c) for i, c in enumerate(checks) if c is not None]
    out = np.zeros(len(checks), dtype=bool)
    if not live:
        return out
    cols = (
        [c.p1 for _, c in live],
        [c.q1 for _, c in live],
        [c.p2 for _, c in live],
        [c.q2 for _, c in live],
    )
    if len(live) >= RLC_MIN_BATCH and _device_check_all(*cols):
        for i, _ in live:
            out[i] = True
        return out
    # small batch, or the randomized check failed: per-item attribution
    with _obs_trace.span("bls.flush.attribute", checks=len(live)):
        res = _device_check(*cols)
    for (i, _), ok in zip(live, res):
        out[i] = bool(ok)
    return out


def bench_pairing_args(n: int, distinct: int = 8):
    """Device-ready args for `ops.bls12_jax.pairing_check_batch`: `n` valid
    (pubkey, H(m), signature) triples tiled from `distinct` host-signed ones.

    Single source of truth for the benchmark input packing (bench.py and
    benches/bls_verify_bench.py) so the positional pairing argument order
    lives in one place next to the shim's own packing above."""
    import jax
    import numpy as np

    from ..ops import bls12_jax as K
    from .bls_sig import Sign
    from .hash_to_curve import hash_to_curve_g2

    enc = K.F.ints_to_mont_batch
    pks, hms, sigs = [], [], []
    for i in range(distinct):
        sk = 1000 + i
        msg = b"bench message %d" % i
        sigs.append(g2_from_bytes(bytes(Sign(sk, msg))))
        pks.append(
            oracle.pt_to_affine(
                oracle.FP_FIELD, oracle.pt_mul(oracle.FP_FIELD, oracle.G1_GEN, sk)
            )
        )
        hms.append(hash_to_curve_g2(msg))

    def tile(arr):
        reps = (n + distinct - 1) // distinct
        return np.tile(arr, (reps,) + (1,) * (arr.ndim - 1))[:n]

    dev = jax.device_put
    return (
        (dev(tile(enc([h[0][0] for h in hms]))), dev(tile(enc([h[0][1] for h in hms])))),
        (dev(tile(enc([h[1][0] for h in hms]))), dev(tile(enc([h[1][1] for h in hms])))),
        dev(tile(enc([p[0] for p in pks]))),
        dev(tile(enc([p[1] for p in pks]))),
        (dev(tile(enc([s[0][0] for s in sigs]))), dev(tile(enc([s[0][1] for s in sigs])))),
        (dev(tile(enc([s[1][0] for s in sigs]))), dev(tile(enc([s[1][1] for s in sigs])))),
        dev(tile(enc([_NEG_G1[0]] * distinct))),
        dev(tile(enc([_NEG_G1[1]] * distinct))),
    )


def bench_grouped_pairing_args(n: int, distinct: int = 8):
    """Device-ready args for the SEGMENTED `pairing_check_rlc` fast path:
    the same `n` valid triples `bench_pairing_args` tiles (identical sks
    and messages), but packed through `_pack_grouped_args` — returns
    ((qx, qy, px, py, q2x, q2y), seg_ids) so benches and tests compare the
    grouped and ungrouped kernels on the SAME logical inputs."""
    from .bls_sig import Sign

    p1s, q1s, q2s = [], [], []
    for i in range(n):
        sk = 1000 + (i % distinct)
        msg = b"bench message %d" % (i % distinct)
        p1s.append(
            oracle.pt_to_affine(
                oracle.FP_FIELD, oracle.pt_mul(oracle.FP_FIELD, oracle.G1_GEN, sk)
            )
        )
        q1s.append(hash_to_curve_g2(msg))
        q2s.append(g2_from_bytes(bytes(Sign(sk, msg))))
    _, _, args, seg_ids = _pack_grouped_args(p1s, q1s, q2s)
    return args, seg_ids


DEVICE_AGGREGATE_MIN = 32  # below this, host point-adds beat a kernel launch


def aggregate_pubkeys_device(pubkeys) -> bytes:
    """Aggregate compressed G1 pubkeys on device, routed through the sched
    "msm" work class (shape-bucketed dispatch, bounded admission, breaker
    degradation to the host oracle) with batched device subgroup checks for
    cold keys and the g1_aggregate_device reduction tree underneath.

    Raises ValueError on any invalid/infinity input, mirroring the host
    oracle's AggregatePKs contract; an infinity SUM encodes as 0xc0."""
    from .bls12_381 import g1_to_bytes

    if len(pubkeys) == 0:
        raise ValueError("aggregate of empty pubkey list")
    pks = [bytes(pk) for pk in pubkeys]
    marker = _aggregate_pubkeys_sched(pks)
    if marker is None:  # msm lane unavailable: run the device impl inline
        marker = _aggregate_pubkeys_device_impl(pks)
    tag = marker[0]
    if tag == "bad_encoding":
        raise ValueError(marker[1])
    if tag == "inf_member":
        raise ValueError("infinity pubkey in aggregate")
    if tag == "inf":
        return g1_to_bytes(None)  # sum is infinity: canonical 0xc0 encoding
    return g1_to_bytes((marker[1], marker[2]))
