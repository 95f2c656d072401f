"""The correctness check rejects the controls and the planted faults.

Small sizes on the CPU; benchmark/tests/controls.py runs the same at the
cells' own sizes on the chip."""
import json
import os
import subprocess
import sys

import pytest

from benchmark import run as brun
from benchmark.context import Run
from benchmark.tests import controls

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
# the cell's forging (every 8th batch from the window's first, four forged
# blocks in one) at a size a CPU test holds
SMALL_SYNC = {"blocks_per_batch": 8, "pool_batches": 8, "warmup_batches": 2,
              "reference_sample": 2, "signers": 4}


def correct(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def test_epoch_control_fails_the_check():
    _, _, config, traffic = brun.load_cell("epoch.mainnet-1m")
    config["validators"] = 2048
    run = Run(77, config, traffic, peaks={})
    run.install_observers(traced=False)
    checks = controls.run_control("control", run, 1.0)
    assert checks["values_differing"]["value"] > 0


def run_fault(workload, fault, seed, extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_TEST_FAULT=fault, **extra)
    p = subprocess.run([sys.executable, os.path.join(BENCH, "tests", "cpu_run.py"),
                        "--workload", workload, "--seed", str(seed), "--seconds", "3",
                        "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=1500)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", ["frozen_step", "altered_balance"])
def test_epoch_faults_fail_the_check(fault):
    line = run_fault("epoch.mainnet-1m", fault, 4242, {"BENCH_TEST_VALIDATORS": "2048"})
    assert line["correct"] is False


@pytest.mark.parametrize("fault", ["control", "accept_all", "flipped_verdict", "half_batch"])
def test_sync_faults_fail_the_check(fault):
    line = run_fault("sync.mainnet-sync-committee", fault, 31337,
                     {"BENCH_TEST_TRAFFIC": json.dumps(SMALL_SYNC)})
    assert line["correct"] is False
