"""Work classes served by the verification scheduler.

A work class owns everything lane-specific the scheduler itself must not
know: how a batch of requests executes on device (`execute`), the
pure-Python degrade path the circuit breaker falls back to
(`execute_degraded`), how a result row converts to the caller-facing value
(`to_result`), the live/padded unit accounting behind the occupancy and
pad-waste metrics (`load`), and — for classes that opt in — the admission
collapse hooks (`collapse_key` / `merge`).

Executors return a numpy array with one row per request (bool verdicts for
BLS/KZG, 32-byte roots for Merkle). The scheduler validates shape and
dtype after the `sched.dispatch` fault seam, so corrupt-kind chaos faults
are caught and retried instead of resolving handles with garbage.

jax-free at module level by charter: jax, the device kernels, and the
heavyweight crypto modules are imported inside the execute bodies only
(the crypto/bls.py deferral pattern), so jax-free shims can import the
scheduler without dragging the device stack in.
"""
from __future__ import annotations

import os

import numpy as np

from ..obs import trace as _obs_trace
from . import bucketing
from .api import Request


class WorkClass:
    """Base class: one verification lane behind the shared dispatch seam."""

    name = "work"
    kinds: tuple = ()
    # per-class queue-depth flush trigger; None defers to the scheduler's
    # default admission policy
    max_depth: int | None = None
    min_bucket = bucketing.MIN_BUCKET

    def execute(self, requests: list) -> np.ndarray:
        """Device path: one row per request."""
        raise NotImplementedError

    def execute_degraded(self, requests: list) -> np.ndarray:
        """Pure-host fallback the breaker degrades to; must agree with
        `execute` bit-for-bit on every valid input."""
        raise NotImplementedError

    def to_result(self, row):
        return bool(row)

    def load(self, requests: list) -> tuple:
        """(live_units, padded_units) for the dispatched batch — feeds the
        sched_batch_occupancy / sched_pad_waste series."""
        n = len(requests)
        return n, bucketing.pow2_bucket(n, self.min_bucket)

    # -- admission collapse (off unless a class overrides) -----------------

    def collapse_key(self, request: Request):
        """Truthy key = this request may merge with queued requests sharing
        the key into ONE device check. None = never collapse."""
        return None

    def merge(self, merged: Request, request: Request) -> Request:
        """Fold `request` into the synthetic collapsed request `merged`;
        raising aborts the collapse (the request queues individually)."""
        raise NotImplementedError

    # Optional batched collapse hook used by Scheduler.submit_many:
    # merge_group(merged, requests) folds a whole same-key group in one
    # aggregation pass. None = the scheduler chains pairwise merge() calls.
    merge_group = None

    # Optional post-dispatch value check: verify_results(requests, results)
    # runs after the scheduler's shape/dtype validation and raises a
    # retryable IntegrityError when a structurally valid batch fails a
    # semantic self-check (the msm class's 2G2T outsourcing equation).
    # None = no check.
    verify_results = None


class BlsWorkClass(WorkClass):
    """BLS signature checks: the deferral queue's device lane.

    Kinds mirror crypto/bls.py's queue entries: "verify" and
    "fast_aggregate" become QueuedChecks for the batched RLC flush;
    "aggregate_verify" (distinct messages per signer) stays on the host
    oracle exactly as the pre-scheduler flush routed it.

    `collapse_same_message=True` enables the Wonderboom admission policy:
    same-message fast_aggregate requests merge into one check over the
    concatenated pubkeys and the aggregated signature (the product of the
    individual verification equations). The collapsed equation is NOT
    sound against adversarially chosen signatures without per-request
    randomization — a forged pair can cancel — so the collapse is opt-in,
    and a failing collapsed check is re-verified per member for sound
    attribution before any handle resolves False.
    """

    name = "bls"
    kinds = ("verify", "fast_aggregate", "aggregate_verify")

    def __init__(self, collapse_same_message: bool = False):
        self.collapse_same_message = collapse_same_message

    def execute(self, requests: list) -> np.ndarray:
        from ..crypto import bls_jax
        from ..crypto import bls_sig

        checks = []
        host: dict = {}
        with _obs_trace.span("bls.prep", checks=len(requests)):
            for i, r in enumerate(requests):
                if r.kind == "verify":
                    checks.append(bls_jax.make_verify_check(*r.payload))
                elif r.kind == "fast_aggregate":
                    checks.append(bls_jax.make_fast_aggregate_check(*r.payload))
                else:  # aggregate_verify: distinct message per signer, host path
                    checks.append(None)
                    host[i] = bool(bls_sig.AggregateVerify(*r.payload))
        dev = bls_jax.run_checks(checks)
        return np.asarray(
            [host[i] if i in host else bool(dev[i])
             for i in range(len(requests))], dtype=bool)

    def execute_degraded(self, requests: list) -> np.ndarray:
        from ..crypto import bls_sig

        dispatch = {
            "verify": bls_sig.Verify,
            "fast_aggregate": bls_sig.FastAggregateVerify,
            "aggregate_verify": bls_sig.AggregateVerify,
        }
        return np.asarray(
            [bool(dispatch[r.kind](*r.payload)) for r in requests],
            dtype=bool)

    def load(self, requests: list) -> tuple:
        n = len(requests)
        msgs = [bytes(r.payload[1]) for r in requests
                if r.kind in ("verify", "fast_aggregate")]
        if len(set(msgs)) < len(msgs):
            # grouped RLC routing: the item bucket covers pad-group seeds
            plan = bucketing.grouped_plan(msgs, self.min_bucket)
            return n, n - plan.n + plan.b_n
        return n, bucketing.pow2_bucket(n, self.min_bucket)

    def collapse_key(self, request: Request):
        if not self.collapse_same_message:
            return None
        if request.kind != "fast_aggregate":
            return None
        return ("fast_aggregate", bytes(request.payload[1]))

    def merge(self, merged: Request, request: Request) -> Request:
        from ..crypto import bls_sig

        pks_a, msg, sig_a = merged.payload
        pks_b, _, sig_b = request.payload
        # Aggregate raises on malformed signature bytes -> the scheduler
        # aborts the collapse and queues the request individually, keeping
        # admission non-raising for garbage inputs.
        agg_sig = bls_sig.Aggregate([bytes(sig_a), bytes(sig_b)])
        return Request(
            work_class=merged.work_class, kind="fast_aggregate",
            payload=(list(pks_a) + list(pks_b), msg, agg_sig),
            group_key=merged.group_key)

    def merge_group(self, merged: Request, requests: list) -> Request:
        """Batched collapse for submit_many: aggregate a committee's worth
        of same-message signatures in ONE Aggregate pass (one point
        decompression per signature) instead of a chain of pairwise merges
        that re-decompresses the running aggregate at every step — the
        admission cost that dominates a streaming attestation workload.
        Raising (malformed bytes anywhere in the group) makes the scheduler
        fall back to pairwise merges, isolating the bad payload."""
        from ..crypto import bls_sig

        pks, msg, sig = merged.payload
        all_pks = list(pks)
        sigs = [bytes(sig)]
        for r in requests:
            pks_r, _, sig_r = r.payload
            all_pks.extend(pks_r)
            sigs.append(bytes(sig_r))
        return Request(
            work_class=merged.work_class, kind="fast_aggregate",
            payload=(all_pks, msg, bls_sig.Aggregate(sigs)),
            group_key=merged.group_key)


class KzgWorkClass(WorkClass):
    """KZG batch lanes: one request = one strict randomized batch check
    (`crypto/kzg_batch` semantics preserved exactly — the request-level
    granularity keeps the all-or-nothing soundness contract intact)."""

    name = "kzg"
    kinds = ("verify_samples", "verify_degree_proofs")

    def execute(self, requests: list) -> np.ndarray:
        from ..crypto import kzg_batch

        out = []
        for r in requests:
            if r.kind == "verify_samples":
                setup, items, use_device = r.payload
                out.append(kzg_batch._verify_samples_impl(
                    setup, items, use_device))
            else:
                setup, items, points_count, use_device = r.payload
                out.append(kzg_batch._verify_degree_proofs_impl(
                    setup, items, points_count, use_device))
        return np.asarray(out, dtype=bool)

    def execute_degraded(self, requests: list) -> np.ndarray:
        from ..crypto import kzg_batch

        out = []
        for r in requests:
            if r.kind == "verify_samples":
                setup, items, _ = r.payload
                out.append(kzg_batch._verify_samples_impl(
                    setup, items, False))
            else:
                setup, items, points_count, _ = r.payload
                out.append(kzg_batch._verify_degree_proofs_impl(
                    setup, items, points_count, False))
        return np.asarray(out, dtype=bool)

    def load(self, requests: list) -> tuple:
        # units are blob/proof items: each request's MSM pads its own item
        # count to a pow2 bucket inside _device_msm
        live = padded = 0
        for r in requests:
            n = len(r.payload[1])
            live += n
            padded += bucketing.pow2_bucket(n, self.min_bucket)
        return live, padded


class MerkleWorkClass(WorkClass):
    """Batched SSZ chunk-tree lanes. Two kinds, both over 32-byte leaves:

    - "tree_root": payload = (chunks,). Trees sharing a leaf count fold in
      one `engine/state_root.tree_root_batch` launch, padded to the pow2
      tree bucket with zero trees (results discarded); host fallback is
      the ssz merkleize oracle.
    - "multiproof": payload = (chunks, gindex) with gindex a generalized
      index over the pow2-padded chunk tree (1 = root, C..2C-1 = leaves).
      Queries sharing a leaf-count bucket fold in one
      `engine/state_root.multiproof_batch` launch; identical trees within
      the batch share ONE device slot (interior hashing paid once), the
      tree axis pads with zero trees and the query axis with root queries
      against tree 0 (both discarded). The result row is the deepest-first
      sibling branch as a tuple of 32-byte values; host fallback is the
      `ssz/proofs.build_chunk_proof` oracle, bit-identical by
      construction.

    A pure tree_root batch keeps the legacy (n, 32) uint8 result array;
    any batch containing a multiproof returns object dtype — branch tuples
    alongside (32,) uint8 root rows (the msm marker-tuple precedent, which
    the scheduler's row validation accepts)."""

    name = "merkle"
    kinds = ("tree_root", "multiproof")

    def execute(self, requests: list) -> np.ndarray:
        if all(r.kind == "tree_root" for r in requests):
            return self._tree_roots_device(requests)
        out = np.empty(len(requests), dtype=object)
        root_idxs = [i for i, r in enumerate(requests)
                     if r.kind == "tree_root"]
        if root_idxs:
            rows = self._tree_roots_device([requests[i] for i in root_idxs])
            for row, i in zip(rows, root_idxs):
                out[i] = row
        self._multiproofs_device(
            requests,
            [i for i, r in enumerate(requests) if r.kind == "multiproof"],
            out)
        return out

    def _tree_roots_device(self, requests: list) -> np.ndarray:
        import jax
        import jax.numpy as jnp

        from ..engine import state_root as SR
        from ..ops.sha256_jax import words_to_bytes

        out = [None] * len(requests)
        by_shape: dict = {}
        for i, r in enumerate(requests):
            chunks = r.payload[0]
            c_full = bucketing.pow2_bucket(max(1, len(chunks)), 1)
            by_shape.setdefault(c_full, []).append(i)
        for c_full, idxs in sorted(by_shape.items()):
            k = len(idxs)
            b_k = bucketing.pow2_bucket(k, 1)
            words = np.zeros((b_k, c_full, 8), dtype=np.uint32)
            for row, i in enumerate(idxs):
                for j, leaf in enumerate(requests[i].payload[0]):
                    words[row, j] = np.frombuffer(
                        bytes(leaf), dtype=">u4").astype(np.uint32)
            roots = np.asarray(jax.device_get(
                SR.tree_root_batch(jnp.asarray(words))))
            for row, i in enumerate(idxs):
                out[i] = np.frombuffer(
                    words_to_bytes(roots[row]), dtype=np.uint8)
        return np.asarray(out, dtype=np.uint8)

    def _multiproofs_device(self, requests: list, idxs: list,
                            out: np.ndarray) -> None:
        """Fill out[i] (a branch tuple) for every multiproof index."""
        from ..engine import state_root as SR
        from ..ops.sha256_jax import words_to_bytes

        by_shape: dict = {}
        for i in idxs:
            chunks, gindex = requests[i].payload
            c_full = bucketing.pow2_bucket(max(1, len(chunks)), 1)
            depth = (c_full - 1).bit_length() if c_full > 1 else 0
            g = int(gindex)
            if g < 1 or g.bit_length() - 1 > depth:
                raise ValueError(
                    f"multiproof gindex {g} outside the depth-{depth} "
                    f"padded chunk tree")
            by_shape.setdefault(c_full, []).append((i, g))
        # content keys memoized by payload identity: a proof-service flush
        # reuses ONE chunks tuple for a whole column's queries, so the
        # O(leaf-count) key build must run once per distinct tuple, not
        # once per request (the payloads stay alive in `requests`, so ids
        # cannot be recycled underneath the memo)
        content_keys: dict = {}

        def key_for(chunks) -> tuple:
            key = content_keys.get(id(chunks))
            if key is None:
                key = content_keys[id(chunks)] = tuple(
                    bytes(c) for c in chunks)
            return key

        for c_full, members in sorted(by_shape.items()):
            slots: dict = {}
            queries = []
            for i, g in members:
                key = key_for(requests[i].payload[0])
                slot = slots.get(key)
                if slot is None:
                    slot = slots[key] = len(slots)
                queries.append((i, slot, g))
            b_k = bucketing.pow2_bucket(len(slots), 1)
            b_q = bucketing.pow2_bucket(len(queries), 1)
            words = np.zeros((b_k, c_full, 8), dtype=np.uint32)
            for key, slot in slots.items():
                for j, leaf in enumerate(key):
                    words[slot, j] = np.frombuffer(
                        leaf, dtype=">u4").astype(np.uint32)
            tree_ids = np.zeros(b_q, dtype=np.int32)
            gidx = np.ones(b_q, dtype=np.int32)  # pad: root query on tree 0
            for row, (i, slot, g) in enumerate(queries):
                tree_ids[row] = slot
                gidx[row] = g
            sib, _nodes, _roots = SR.multiproof_batch(words, tree_ids, gidx)
            for row, (i, slot, g) in enumerate(queries):
                d = g.bit_length() - 1
                out[i] = tuple(
                    words_to_bytes(sib[row, lvl]) for lvl in range(d))

    def execute_degraded(self, requests: list) -> np.ndarray:
        from ..ssz.merkle import merkleize_chunks

        if all(r.kind == "tree_root" for r in requests):
            return np.asarray(
                [np.frombuffer(
                    merkleize_chunks([bytes(c) for c in r.payload[0]]),
                    dtype=np.uint8)
                 for r in requests], dtype=np.uint8)
        from ..ssz.proofs import build_chunk_proof

        out = np.empty(len(requests), dtype=object)
        for i, r in enumerate(requests):
            if r.kind == "tree_root":
                out[i] = np.frombuffer(
                    merkleize_chunks([bytes(c) for c in r.payload[0]]),
                    dtype=np.uint8)
            else:
                chunks, gindex = r.payload
                out[i] = tuple(build_chunk_proof(
                    [bytes(c) for c in chunks], int(gindex)))
        return out

    def to_result(self, row):
        if isinstance(row, tuple):
            return row  # multiproof branch: deepest-first 32-byte siblings
        return np.asarray(row, dtype=np.uint8).tobytes()

    def load(self, requests: list) -> tuple:
        # units are whole trees (tree_root) / queries (multiproof); each
        # (kind, leaf-count) bucket pads independently
        by_shape: dict = {}
        for r in requests:
            c_full = bucketing.pow2_bucket(max(1, len(r.payload[0])), 1)
            key = (r.kind, c_full)
            by_shape[key] = by_shape.get(key, 0) + 1
        live = len(requests)
        padded = sum(bucketing.pow2_bucket(k, 1) for k in by_shape.values())
        return live, padded


class MsmWorkClass(WorkClass):
    """G1 multi-scalar multiplication lanes over the Pippenger kernel
    (ops/bls12_jax.g1_msm_pippenger). Two kinds:

    - "msm": payload = (points, scalars, nbits), points affine int pairs;
      one Σ scalar_i·P_i per request via g1_msm_device.
    - "aggregate": payload = tuple of compressed pubkey bytes — the
      all-ones-scalar degenerate case, routed through crypto/bls_jax's
      batched device subgroup check + g1_aggregate_device reduction tree
      (the firehose cold-lane path).

    Result rows are marker tuples in an object-dtype array — ("point", x,
    y) | ("inf",) | ("inf_member",) | ("bad_encoding", msg) — so hostile
    inputs travel as data instead of exceptions across the dispatch seam.
    Every marker is truthy, which keeps the scheduler's failing-collapse
    re-verify inert (this class never collapses). The bucketer bounds
    compile diversity exactly as for the other lanes: one XLA program per
    (pow2 item bucket, nbits, window).

    With `self_check=True` (or env CONSENSUS_TPU_MSM_SELF_CHECK=1) each
    "msm" row is verified post-dispatch with the 2G2T-style constant-size
    outsourcing equation — see `verify_results` below.
    """

    name = "msm"
    kinds = ("msm", "aggregate")
    min_bucket = 8

    def __init__(self, self_check: bool | None = None):
        if self_check is None:
            self_check = os.environ.get(
                "CONSENSUS_TPU_MSM_SELF_CHECK", "") not in ("", "0")
        self.self_check = bool(self_check)

    def execute(self, requests: list) -> np.ndarray:
        from ..crypto import bls_jax
        from ..ops import bls12_jax as K

        out = np.empty(len(requests), dtype=object)
        for i, r in enumerate(requests):
            if r.kind == "aggregate":
                out[i] = bls_jax._aggregate_pubkeys_device_impl(
                    list(r.payload))
            else:
                points, scalars, nbits = r.payload
                total = K.g1_msm_device(
                    list(points), list(scalars), int(nbits))
                out[i] = (("inf",) if total is None
                          else ("point", total[0], total[1]))
        return out

    def execute_degraded(self, requests: list) -> np.ndarray:
        from ..crypto import kzg_batch

        out = np.empty(len(requests), dtype=object)
        for i, r in enumerate(requests):
            if r.kind == "aggregate":
                out[i] = self._host_aggregate(list(r.payload))
            else:
                points, scalars, _nbits = r.payload
                total = kzg_batch._host_msm(list(points), list(scalars))
                out[i] = (("inf",) if total is None
                          else ("point", total[0], total[1]))
        return out

    @staticmethod
    def _host_aggregate(pubkeys_bytes: list):
        """Host-oracle twin of bls_jax._aggregate_pubkeys_device_impl:
        same marker protocol, validated g1_from_bytes + pt_add loop."""
        from ..crypto import bls12_381 as oracle

        acc = None
        try:
            for pk in pubkeys_bytes:
                aff = oracle.g1_from_bytes(bytes(pk))
                if aff is None:
                    return ("inf_member",)
                pt = oracle.pt_from_affine(oracle.FP_FIELD, aff)
                acc = (pt if acc is None
                       else oracle.pt_add(oracle.FP_FIELD, acc, pt))
        except ValueError as e:
            return ("bad_encoding", str(e))
        aff = oracle.pt_to_affine(oracle.FP_FIELD, acc)
        return ("inf",) if aff is None else ("point", aff[0], aff[1])

    def to_result(self, row):
        return row

    def load(self, requests: list) -> tuple:
        # units are MSM terms: each request pads its own item count to the
        # pow2 bucket inside g1_msm_device / g1_aggregate_device
        live = padded = 0
        for r in requests:
            n = (len(r.payload) if r.kind == "aggregate"
                 else len(r.payload[0]))
            live += n
            padded += bucketing.pow2_bucket(max(1, n), self.min_bucket)
        return live, padded

    def verify_results(self, requests: list, results) -> None:
        """2G2T-style outsourcing check on "msm" rows: draw a random
        64-bit c and require host [c]·R_claimed == device MSM over the
        rerandomized scalars c·s_i mod r — two independent evaluations of
        the same sum bound by a random scalar, so a corrupt-but-well-formed
        row is caught BEFORE any handle resolves (the failure mode the
        scheduler's shape/dtype validation cannot see). This catches
        faults, not an adversarial kernel: a deterministic corruption of
        both evaluations could still agree. "aggregate" rows skip the
        check — a wrong committee aggregate fails the downstream pairing
        check, which already re-attributes per member."""
        if not self.self_check:
            return
        import secrets

        from ..crypto import bls12_381 as oracle
        from ..ops import bls12_jax as K

        for r, row in zip(requests, results):
            if r.kind != "msm":
                continue
            tag = row[0]
            if tag == "point":
                claimed = (int(row[1]), int(row[2]))
            elif tag == "inf":
                claimed = None
            else:
                continue
            points, scalars, _nbits = r.payload
            c = secrets.randbelow(2**64 - 1) + 1
            expect = (None if claimed is None else oracle.pt_to_affine(
                oracle.FP_FIELD,
                oracle.pt_mul(
                    oracle.FP_FIELD,
                    oracle.pt_from_affine(oracle.FP_FIELD, claimed), c)))
            redo = K.g1_msm_device(
                list(points), [c * s % oracle.R for s in scalars], 255)
            if redo != expect:
                from .scheduler import SchedSelfCheckError

                raise SchedSelfCheckError(
                    f"sched.dispatch[{self.name}]: 2G2T self-check "
                    f"mismatch on a {len(scalars)}-term MSM")


class ForkChoiceWorkClass(WorkClass):
    """Batched LMD-GHOST head selection: the fork-choice lane.

    One kind, "head": payload = (StoreSnapshot,) — the gather-form store
    view from forkchoice/mirror. The device path groups snapshots by
    their pow2 (blocks, validators) bucket and answers each group in one
    `engine/fork_choice.ghost_head_batch` launch; the degraded path is
    the spec-shaped host oracle (`forkchoice/reference.host_head`),
    bit-identical per the documented ancestor-equivalence. The result
    row is the head's block index into the snapshot's own table (int32 —
    note index 0, the anchor, is a legitimate falsy head: this class
    never collapses, so the resolver's falsy-collapse reverify path
    cannot misread it)."""

    name = "forkchoice"
    kinds = ("head",)
    min_bucket = 1

    def execute(self, requests: list) -> np.ndarray:
        from ..engine.fork_choice import ghost_head_batch

        return ghost_head_batch([r.payload[0] for r in requests])

    def execute_degraded(self, requests: list) -> np.ndarray:
        from ..forkchoice.reference import host_head

        return np.asarray([host_head(r.payload[0]) for r in requests],
                          dtype=np.int32)

    def to_result(self, row):
        return int(row)

    def load(self, requests: list) -> tuple:
        # units are head queries; each (blocks, validators) bucket pads
        # its query axis independently (engine/fork_choice grouping)
        by_bucket: dict = {}
        for r in requests:
            snap = r.payload[0]
            key = (bucketing.pow2_bucket(max(1, snap.n_blocks), 8),
                   bucketing.pow2_bucket(max(1, snap.n_validators), 64))
            by_bucket[key] = by_bucket.get(key, 0) + 1
        live = len(requests)
        padded = sum(bucketing.pow2_bucket(k, 1) for k in by_bucket.values())
        return live, padded


def default_classes() -> list:
    return [BlsWorkClass(), KzgWorkClass(), MerkleWorkClass(),
            MsmWorkClass(), ForkChoiceWorkClass()]
