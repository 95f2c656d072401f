"""Traffic driver `sync_backfill`: range-sync batches of sync aggregates.

A node syncing the chain verifies each block's SyncAggregate: the
FastAggregateVerify of the committee members its bits name, over the block
root. A range-sync batch of `blocks_per_batch` blocks is verified the way
the spec-facing shim verifies a block's checks under deferred
verification: `bls.use_jax()`, one `bls.deferred_verification()` around the
batch and one `bls.FastAggregateVerify` per block. The batch's verdict is
whether the context exits cleanly or raises `BLSVerificationError`.

Traffic, all from the seed: one period's committee of real keys; per block,
each member takes part with probability `participation`, a distinct
32-byte root, and the signature Sign(sum of the participants' keys, root),
which is the aggregate of their signatures. Every `forged_batch_every`-th
batch of the window, from its `forged_batch_offset`-th, carries
`forged_per_batch` blocks whose signature also includes a member their bits
leave out, as a bad peer's batch would: one in each equal part of the
batch, at an even position in even parts and an odd one in odd parts, so
that either half of the batch, and its even or its odd blocks, hold one.
The first warm-up batch is forged the same way, so the attribution path is
compiled before the window, and its first block carries every member, so
every run validates the period's 512 keys in one program of one shape (the
program caches them for the period). Signatures are made in set-up by a
pool of worker processes that import only benchmark.ref.bls (no JAX); the
window never reuses a block, and a window that runs out of blocks fails
the run.

A batch's result is which of its blocks the program names as failed: none
when the context exits cleanly, else the checks `BLSVerificationError`
lists (what a node needs to know which peer served the bad block). The
check compares, block by block, what the program named with what the
blocks are, and runs the plain reference's FastAggregateVerify (pairing)
on every forged block and on `reference_sample` blocks drawn from the
seed, to confirm what they are.
"""
from __future__ import annotations

import dataclasses
import multiprocessing
import os
import re
import time

import numpy as np

from benchmark.ref import bls as bls_ref

# BLSVerificationError's message: "deferred batch verification failed for checks [3, 17]"
FAILED_CHECKS = re.compile(r"failed for checks \[([0-9, ]*)\]")


@dataclasses.dataclass
class Block:
    pubkeys: list  # the participants' compressed keys
    root: bytes
    signature: bytes
    valid: bool


def _annotate(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


def _signer_pool(traffic: dict):
    procs = max(1, min(int(traffic["signers"]), (os.cpu_count() or 2) - 1))
    return multiprocessing.get_context("spawn").Pool(procs)


def forged_positions(rng, per_batch: int, count: int) -> set:
    """One position in each of `count` equal parts of a batch: even in even
    parts, odd in odd ones."""
    part = per_batch // count
    if per_batch % (2 * count):
        raise ValueError(f"blocks_per_batch {per_batch} does not split into "
                         f"{count} parts of an even length")
    return {q * part + q % 2 + 2 * int(rng.integers(part // 2)) for q in range(count)}


def build_traffic(config: dict, traffic: dict, seed: int, signer=None):
    """(committee keys, warm-up batches, window batches) from the seed."""
    rng = np.random.default_rng([seed, 7])
    size = int(config["sync_committee_size"])
    per_batch = int(traffic["blocks_per_batch"])
    warmup, pool_n = int(traffic["warmup_batches"]), int(traffic["pool_batches"])
    sks = [int.from_bytes(rng.bytes(32), "big") % (bls_ref.R - 1) + 1 for _ in range(size)]
    plan = []  # (bits, root, scalar, valid) per block, batch after batch
    for b in range(warmup + pool_n):
        forged = (b == 0) if b < warmup else (
            (b - warmup) % traffic["forged_batch_every"] == traffic["forged_batch_offset"])
        forged_at = (forged_positions(rng, per_batch, int(traffic["forged_per_batch"]))
                     if forged else set())
        for j in range(per_batch):
            bits = rng.random(size) < traffic["participation"]
            if b == 0 and j == 0:
                bits[:] = True  # the node meets every key of the period once
            root = rng.bytes(32)
            scalar = sum(sks[i] for i in np.nonzero(bits)[0].tolist())
            if j in forged_at:
                out = np.nonzero(~bits)[0]
                extra = int(out[rng.integers(len(out))]) if len(out) else int(rng.integers(size))
                scalar += sks[extra]
            plan.append((bits, root, scalar % bls_ref.R, j not in forged_at))
    own_pool = signer is None
    signer = _signer_pool(traffic) if own_pool else signer
    try:
        keys = signer.map(bls_ref.sk_to_pk, sks, chunksize=16)
        sigs = signer.starmap(bls_ref.sign, [(s, r) for _, r, s, _ in plan], chunksize=8)
    finally:
        if own_pool:
            signer.close()
            signer.join()
    blocks = [Block([keys[i] for i in np.nonzero(bits)[0].tolist()], root, sig, valid)
              for (bits, root, _, valid), sig in zip(plan, sigs)]
    batches = [blocks[i:i + per_batch] for i in range(0, len(blocks), per_batch)]
    return keys, batches[:warmup], batches[warmup:]


def failing(batch) -> frozenset:
    """The positions of the batch's forged blocks."""
    return frozenset(j for j, blk in enumerate(batch) if not blk.valid)


class SyncBackfill:
    def __init__(self, run):
        from consensus_specs_tpu.crypto import bls

        self.run = run
        self.bls = bls
        t0 = time.monotonic()
        self.keys, warm, self.pool = build_traffic(run.config, run.traffic, run.seed)
        t1 = time.monotonic()
        bls.use_jax()
        for batch in warm:
            if self._verify(batch) != failing(batch):
                raise RuntimeError("a warm-up batch got the wrong verdict")
        run.log(f"set-up s: traffic {t1 - t0:.1f}, warm-up {time.monotonic() - t1:.1f}")
        self.named: list = []  # per window batch, the blocks the program named failed
        self.attempted = self.failed = 0

    def _verify(self, batch) -> frozenset | None:
        """The positions of the batch's blocks the program names as failed;
        None where it raised without naming them."""
        bls = self.bls
        try:
            with bls.deferred_verification():
                for blk in batch:
                    bls.FastAggregateVerify(blk.pubkeys, blk.root, blk.signature)
        except bls.BLSVerificationError as exc:
            m = FAILED_CHECKS.search(str(exc))
            return frozenset(int(x) for x in m.group(1).split(",") if x.strip()) if m else None
        return frozenset()

    def window(self, t_end: float) -> None:
        for batch in self.pool:
            if time.monotonic() >= t_end:
                break
            with _annotate("bench.sync.batch"):
                self.named.append(self._verify(batch))
        self.ran_out = time.monotonic() < t_end  # the window is then short of its length
        if self.ran_out:
            self.run.log(f"sync_backfill: the pool of {len(self.pool)} batches ran out "
                         "before the window closed; the traffic needs more pool_batches")
        n = len(self.named)
        sets = sum(len(b) for b in self.pool[:n])
        # blocks whose verdict is wrong: named failed and valid, or forged
        # and not named; a batch that failed without naming is wrong whole
        self.wrong = sum(len(batch) if named is None else len(named ^ failing(batch))
                         for named, batch in zip(self.named, self.pool))
        self.attempted = sets
        self.failed = self.wrong
        self.run.work.update(sets=sets, batches=n,
                             blocks_per_batch=int(self.run.traffic["blocks_per_batch"]))

    def end_to_end(self, window_s: float) -> dict:
        return {"sigsets_per_s": self.attempted / window_s}

    def release(self) -> None:
        self.bls.clear_caches()

    def check(self) -> dict:
        """Each block's verdict against what it is; the reference's
        FastAggregateVerify on every forged block and a seeded sample."""
        done = [blk for batch in self.pool[:len(self.named)] for blk in batch]
        rng = np.random.default_rng([self.run.seed, 8])
        chosen = {i for i, blk in enumerate(done) if not blk.valid}
        k = min(int(self.run.traffic["reference_sample"]), len(done))
        chosen |= set(rng.choice(len(done), k, replace=False).tolist()) if done else set()
        cache: dict = {}
        disagree = sum(
            bls_ref.fast_aggregate_verify(done[i].pubkeys, done[i].root,
                                          done[i].signature, cache) != done[i].valid
            for i in sorted(chosen))
        return {"pool_ran_out": {"value": int(self.ran_out), "limit": 0},
                "block_verdicts_wrong": {"value": self.wrong, "limit": 0},
                "reference_disagrees": {"value": disagree, "limit": 0}}


def setup(run) -> SyncBackfill:
    return SyncBackfill(run)
