"""Default-lane generator health probe (VERDICT r3 weak #7).

One case from EVERY vector generator, each in a subprocess under a hard
timeout — so a generator that regresses into compile-bound or hung
territory fails `make test` instead of silently starving
`make generate_tests`. `--smoke 1` (gen_runner.py) stops the run after the
first generated-or-failed case; the assertion requires one case GENERATED
(a generator whose first case errors is as broken as one that hangs).

The subprocesses are pinned to the host CPU backend: generation is a
pure-host lane and must never take the chip.
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
GENERATORS = sorted(p.parent.name for p in (REPO / "generators").glob("*/main.py"))
TIMEOUT_S = int(os.environ.get("GEN_SMOKE_TIMEOUT_S", 420))


def test_all_generators_are_covered():
    assert len(GENERATORS) >= 16, GENERATORS


@pytest.mark.parametrize("name", GENERATORS)
def test_generator_smoke_one_case(name, tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO)  # drop any accelerator plugin site
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, str(REPO / "generators" / name / "main.py"),
         "-o", str(tmp_path), "--smoke", "1"],
        capture_output=True, text=True, timeout=TIMEOUT_S, env=env,
    )
    tail = (res.stdout + res.stderr)[-2000:]
    assert res.returncode == 0, f"{name} rc={res.returncode}\n{tail}"
    assert "generated 1" in res.stdout, f"{name} produced no case\n{tail}"
    # the case completed: no INCOMPLETE sentinel left behind
    assert not list(tmp_path.rglob("INCOMPLETE")), name
