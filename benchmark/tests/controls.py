"""Controls and planted faults: what the correctness check must reject.

Each is the timed path of a cell with one thing broken, and drives the
rest of a run through the cell's own driver:

- `ControlEpochs`: the plain reference in the epoch program's place, with
  the rewards, penalties and balances in float32 (the precision a TPU
  program is tempted to use in place of emulated uint64);
- `FrozenStep`: the epoch step returns the state unchanged;
- `AlteredBalance`: one balance altered where the step produces it;
- `ControlSync`: the plain reference in the BLS path's place, breaking the
  guarantee that a verdict covers the block's participants: it verifies
  against the whole committee's aggregate key, the cache a node is
  tempted to keep for a period;
- `AcceptAll`: every batch accepted without verification;
- `FlippedVerdict`: the program's batch verdict inverted where produced;
- `HalfBatch`: half of each batch left out of verification.

    python3 benchmark/tests/controls.py <kind>[,<kind>...] --workload W --seeds N[,N...] --seconds S

runs each of them on each seed at the cell's own size, in one process (the
programs are traced and loaded once), and prints each one's checks as a
JSON line (on the chip for the cells' own sizes;
benchmark/tests/test_controls.py runs them small on the CPU).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.drivers import epoch_loop, sync_backfill  # noqa: E402
from benchmark.ref import bls as bls_ref  # noqa: E402
from benchmark.ref import epoch_altair as ref  # noqa: E402


class ControlEpochs:
    """The reference in the program's place, balances in float32."""

    def __init__(self, run, start):
        self.c = ref.Spec(run.config["constants"])
        self.st = start.copy()
        self.pk_points: dict = {}

    def set_participation(self, flags) -> None:
        self.st.current_epoch_participation = np.asarray(flags)

    def step(self) -> None:
        ref.process_epoch(self.st, self.c, self.pk_points, reward_dtype=np.float32)
        self.st.slot += self.c.SLOTS_PER_EPOCH

    def root(self) -> bytes:
        return ref.state_root(self.st, self.c)

    def outputs(self) -> dict:
        st = self.st
        out = {name: getattr(st, epoch_loop.REF_NAME.get(name, name))
               for name in epoch_loop.COLUMNS}
        out["randao_mixes"] = st.randao_mixes
        out["justification_bits"] = st.justification_bits
        out["checkpoint_epochs"] = np.asarray(
            [st.previous_justified[0], st.current_justified[0], st.finalized[0]], np.uint64)
        out["slot"] = st.slot
        return out


class FrozenStep(epoch_loop.ResidentProgram):
    def step(self) -> None:  # the state comes back unchanged
        pass


class AlteredBalance(epoch_loop.ResidentProgram):
    def step(self) -> None:
        super().step()
        dev = self.engine.dev
        self.engine.dev = dev.replace(balances=dev.balances.at[0].add(1))


class ControlSync(sync_backfill.SyncBackfill):
    """Verifies each block against the whole committee's key."""

    def _verify(self, batch) -> frozenset:
        cache = self.__dict__.setdefault("_pk_cache", {})
        return frozenset(j for j, blk in enumerate(batch) if not bls_ref.fast_aggregate_verify(
            self.keys, blk.root, blk.signature, cache))


class AcceptAll(sync_backfill.SyncBackfill):
    def _verify(self, batch) -> frozenset:
        return frozenset()


class FlippedVerdict(sync_backfill.SyncBackfill):
    """A failed batch reported clean, a clean one as failing its first block."""

    def _verify(self, batch) -> frozenset:
        return frozenset() if super()._verify(batch) else frozenset({0})


class HalfBatch(sync_backfill.SyncBackfill):
    def _verify(self, batch) -> frozenset:
        return super()._verify(batch[: len(batch) // 2])


EPOCH = {"control": ControlEpochs, "frozen_step": FrozenStep, "altered_balance": AlteredBalance}
SYNC = {"control": ControlSync, "accept_all": AcceptAll, "flipped_verdict": FlippedVerdict,
        "half_batch": HalfBatch}


def make_cell(kind: str, run):
    """The cell's driver object with the `kind` control or fault planted."""
    if run.traffic["driver"] == "epoch_loop":
        return epoch_loop.EpochLoop(run, program=EPOCH[kind])
    cell = sync_backfill.SyncBackfill(run)  # warm-up on the sound path
    cell.__class__ = SYNC[kind]
    return cell


def run_control(kind: str, run, seconds: float) -> dict:
    """Set up, a short window at the cell's load, release, check."""
    cell = make_cell(kind, run)
    t0 = time.monotonic()
    run.mark_window(t0)
    cell.window(t0 + seconds)
    run.mark_window(t0, time.monotonic())
    cell.release()
    return cell.check()


def main(argv=None) -> int:
    from benchmark import run as brun
    from benchmark.context import Run

    ap = argparse.ArgumentParser()
    ap.add_argument("kinds")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    for seed in [int(x) for x in args.seeds.split(",")]:
        for kind in args.kinds.split(","):
            _, _, config, traffic = brun.load_cell(args.workload)
            run = Run(seed, config, traffic, peaks={})
            run.install_observers(traced=False)
            t0 = time.monotonic()
            checks = run_control(kind, run, args.seconds)
            correct = all(c["value"] <= c["limit"] for c in checks.values())
            print(json.dumps({"control": kind, "workload": args.workload, "seed": seed,
                              "correct": correct, "checks": checks,
                              "wall_s": round(time.monotonic() - t0, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
