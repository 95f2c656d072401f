"""Backend pinning and the persistent compile cache.

`force_cpu` is the one CPU pin used by every chip-free entry point
(tests/conftest.py, __graft_entry__.dryrun_multichip, the vector
generators). It drops the accelerator PJRT plugin factories before the
first backend init, so a host-only process never loads libtpu: only one
process at a time may hold the chip, and a process that merely wanted the
CPU must not be the one holding it.
"""
from __future__ import annotations

import os

ACCELERATOR_PLUGINS = ("tpu", "cuda", "rocm")

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def enable_compile_cache(default: str = REPO_CACHE_DIR):
    """Turn on JAX's persistent compilation cache and return the jax module.

    The pairing and epoch programs compile for minutes; with the cache only
    the first run on a given machine and code state pays. Where
    `JAX_COMPILATION_CACHE_DIR` is set, the cache lives there and nowhere
    else; otherwise at the fixed `default` (the cache key includes the
    path, so a directory that moves never hits). Entries are keyed by
    platform + HLO hash, so CPU and TPU entries never collide. Safe to
    delete the directory at any time."""
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get(CACHE_ENV) or default)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return jax


def force_cpu(n_devices: int | None = None):
    """Pin this process to the CPU backend; with `n_devices`, provision a
    virtual multi-device CPU mesh (tearing down any already-initialized
    backend — three caches must all clear or the old backend keeps being
    served: _backends, get_backend's lru, and the plugin factory table).

    Safe to call before OR after a backend exists; never probes an
    accelerator. Returns the jax module."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    from jax._src import xla_bridge as xb

    for plugin in ACCELERATOR_PLUGINS:
        xb._backend_factories.pop(plugin, None)
    jax.config.update("jax_platforms", "cpu")
    if n_devices is not None:
        if getattr(xb, "_backends", None):
            xb._clear_backends()
            xb.get_backend.cache_clear()
        jax.config.update("jax_num_cpu_devices", n_devices)
    return jax
