"""Reflection bridge: dual-mode test modules -> vector TestCases.

Reference parity: gen_helpers/gen_from_tests/gen.py (generate_from_tests
:13-56, run_state_test_generators :96-111, combine_mods :114-132): discover
`test_*` functions in a module, re-run each with generator_mode=True pinned
to one (fork, preset), and map module names to runner/handler names. BLS is
forced on for vector generation (reference :75-77) except where a test is
tagged never_bls.
"""
from __future__ import annotations

import importlib
import inspect
from typing import Iterable

from ..crypto import bls
from .gen_typing import TestCase, TestProvider


def generate_from_tests(
    runner_name: str,
    handler_name: str,
    src,
    fork_name: str,
    preset_name: str,
    suite_name: str = "pyspec_tests",
    bls_active: bool = True,
    name_prefix: str = "",
) -> Iterable[TestCase]:
    """name_prefix filters to tests named test_<prefix>* — lets one module
    back multiple handlers (e.g. genesis initialization vs validity)."""
    for name, fn in inspect.getmembers(src, inspect.isfunction):
        if not name.startswith("test_" + name_prefix):
            continue
        run_phases = getattr(fn, "run_phases", None)
        if run_phases is not None and fork_name not in run_phases:
            continue
        allowed = getattr(fn, "allowed_presets", None)
        if allowed is not None and preset_name not in allowed:
            continue
        case_name = name[len("test_") :]

        def case_fn(fn=fn):
            return fn(
                fork=fork_name,
                preset=preset_name,
                generator_mode=True,
                bls_active=bls_active,
            )

        yield TestCase(
            fork_name=fork_name,
            preset_name=preset_name,
            runner_name=runner_name,
            handler_name=handler_name,
            suite_name=suite_name,
            case_name=case_name,
            case_fn=case_fn,
        )


def combine_mods(dict_1: dict, dict_2: dict) -> dict:
    """Merge {handler: [module,...]} maps (fork inheritance of test modules)."""
    out = {k: list(v if isinstance(v, list) else [v]) for k, v in dict_1.items()}
    for k, v in dict_2.items():
        out.setdefault(k, [])
        out[k] += v if isinstance(v, list) else [v]
    return out


def run_state_test_generators(
    runner_name: str,
    all_mods: dict[str, dict[str, object]],
    presets: tuple = ("minimal", "mainnet"),
) -> None:
    """all_mods: {fork: {handler: module-or-dotted-name-or-list}}."""
    from .gen_runner import run_generator

    def make_cases():
        for fork_name, handlers in all_mods.items():
            for handler_name, mods in handlers.items():
                for mod in mods if isinstance(mods, list) else [mods]:
                    prefix = ""
                    if isinstance(mod, tuple):
                        mod, prefix = mod
                    if isinstance(mod, str):
                        mod = importlib.import_module(mod)
                    for preset_name in presets:
                        yield from generate_from_tests(
                            runner_name, handler_name, mod, fork_name, preset_name,
                            name_prefix=prefix,
                        )

    def prepare():
        bls.bls_active = True
        # CONSENSUS_TPU_GEN_BLS=jax: verify through the batched XLA pairing
        # backend instead of the pure-Python oracle — the reference's
        # generators make the same move (milagro on CI, gen.py:75-77),
        # because host-oracle pairings at ~1.5 s each make block-rich
        # suites (sanity, finality) generation-bound. With the persistent
        # compile cache the bucketed flush shapes compile once per machine.
        import os

        if os.environ.get("CONSENSUS_TPU_GEN_BLS") == "jax":
            # force_cpu: generation is a pure-host lane and must never
            # take the chip, whatever JAX_PLATFORMS the caller left set.
            from ..utils.backend import enable_compile_cache, force_cpu

            force_cpu()
            enable_compile_cache()
            bls.use_jax()

    raise SystemExit(
        run_generator(runner_name, [TestProvider(make_cases=make_cases, prepare=prepare)])
    )
