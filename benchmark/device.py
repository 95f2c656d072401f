"""The chip: find it, keep the compile cache, read its peaks and memory."""
from __future__ import annotations

import json
import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def init(chips: int, root: str):
    """Turn the persistent compile cache on and return jax once it has
    found `chips` TPU chips. The cache lives in JAX_COMPILATION_CACHE_DIR
    when that is set, else at the fixed `<checkout>/.jax_cache`, which is
    also where the program keeps it; exporting the path makes the program
    take the same directory."""
    cache = os.environ.get(CACHE_ENV) or os.path.join(root, ".jax_cache")
    os.environ[CACHE_ENV] = cache
    import jax

    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoChip(f"JAX found {platform}, not a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found {len(devices)}")
    return jax


def peaks_for(jax, bench_dir: str) -> dict:
    """This chip's row of peaks.json; an unknown device is an error."""
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        table = json.load(f)
    kind = jax.devices()[0].device_kind
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} has no row in peaks.json")
    return table["devices"][kind]


def describe(jax, chips: int) -> dict:
    """Platform, kind and count as JAX reports them, and the peak bytes in
    use on the fullest chip of those the cell uses."""
    devices = jax.devices()[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peaks) if peaks else None}
