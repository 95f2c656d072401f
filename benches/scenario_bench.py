"""Scenario-engine SLO lane (consensus_specs_tpu/scenarios/).

Measured region: one seeded long-horizon history (reorg storm +
equivocation + drought epochs across a phase0→altair fork transition)
replayed through the chaos-enabled ENGINE lane — the TPU implementation,
epoch transitions routed through engine.bridge with the PR-5 fault seams
armed — then emitted twice as reference-shaped vectors and diffed
byte-for-byte. Reported: replay slots/s (the lane's own histogram input),
deepest reorg survived, vectors emitted, and vectors diffed clean (the
bidirectional-conformance evidence: a nonzero diff count fails the run).

Usage: python benches/scenario_bench.py — one JSON line.
BENCH_SCENARIO_SEED / BENCH_SCENARIO_EPOCHS size the lane (defaults:
seed 1, 8 epochs — bounded for the bench budget; the ≥2,000-slot
soak lives in tests/test_scenarios.py under @slow).
"""
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def run() -> dict:
    from consensus_specs_tpu.scenarios import (
        assert_converged,
        build_history,
        build_script,
        diff_vector_trees,
        emit_history,
        engine_lane,
        oracle_lane,
    )

    seed = int(os.environ.get("BENCH_SCENARIO_SEED", 1))
    epochs = int(os.environ.get("BENCH_SCENARIO_EPOCHS", 8))
    t0 = time.time()
    script = build_script(seed, epochs=epochs)
    history = build_history(script)
    print(f"# scenario host prep (seed {seed}, {epochs} epochs, "
          f"{history.stats['blocks']} blocks): {time.time() - t0:.1f}s",
          file=sys.stderr)

    t0 = time.time()
    engine = engine_lane(history, fault_seed=seed)
    replay_s = time.time() - t0
    # the lane's own elapsed covers just the store-stepping region
    slots_per_s = engine.slots / max(engine.elapsed_s, 1e-9)
    assert_converged([oracle_lane(history), engine])

    out_a = Path(tempfile.mkdtemp(prefix="scenario_bench_a_"))
    out_b = Path(tempfile.mkdtemp(prefix="scenario_bench_b_"))
    try:
        emitted = emit_history(history, out_a, lane_result=engine)
        emit_history(history, out_b, lane_result=engine)
        diffs = diff_vector_trees(out_a, out_b)
        if diffs:
            raise AssertionError(
                f"scenario double-render diverged: {diffs[:4]}")
        diffed = len(emitted)
    finally:
        shutil.rmtree(out_a, ignore_errors=True)
        shutil.rmtree(out_b, ignore_errors=True)

    return {
        "scenario_slots_per_s": round(slots_per_s, 2),
        "scenario_replay_s": round(replay_s, 3),
        "scenario_reorg_depth_max": engine.max_reorg_depth,
        "scenario_reorgs": engine.reorgs,
        "scenario_vectors_emitted": len(emitted),
        "scenario_vectors_diffed": diffed,
        "scenario_slots": engine.slots,
        "scenario_faults_fired": sum(
            (engine.extra.get("faults_fired") or {}).values()),
    }


if __name__ == "__main__":
    print(json.dumps(run()))
