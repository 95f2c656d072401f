"""Run one benchmark cell on the chip and print its result line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from BENCHMARK.json:

- the cell's configuration in `benchmark/configs/<config>.json`;
- its traffic mix in `benchmark/traffic/<traffic>.json`, whose `driver`
  names the general generator in `benchmark/drivers/<driver>.py`;
- each per-layer metric's reader in `benchmark/metrics/<metric>.py`;
- the device's peaks in `benchmark/peaks.json`, keyed by `device_kind`.

A run loads, builds its inputs from the seed, warms up on its own traffic
(set-up), measures for `--seconds` (the window), then checks what the
window produced against the plain reference. With `--trace 1` the window
runs under the JAX profiler and the result carries the per-layer metrics
reduced from the trace, the program's spans and its counters; with
`--trace 0` it carries the end-to-end metrics and the program's tracer is
off. Without a TPU, or with fewer chips than the cell asks for, the run
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import device as bdevice  # noqa: E402
from benchmark.context import Run, log  # noqa: E402

EXIT_NO_CHIP = 3
# A traced run measures at most this long: its per-layer metrics are per
# epoch or per set. On a v5e the profiler stops recording device events
# after a fixed amount: 10 s and 30 s traces of the epoch cell both held
# its first 31 epochs and no more (PR 22). 4 s holds about 17 of them.
TRACE_WINDOW_S = 4.0


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark, cell, config, traffic) for workload `name`."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config = load_json(BENCH_DIR, "configs", cell["config"] + ".json")
    traffic = load_json(BENCH_DIR, "traffic", cell["traffic"] + ".json")
    return bench, cell, config, traffic


def load_reader(metric: str):
    """The `read(run)` function of benchmark/metrics/<metric>.py. Metric
    names hold dots, so the file is loaded by path, not imported."""
    path = os.path.join(BENCH_DIR, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell: dict, kind: str) -> list[dict]:
    """The end_to_end or per_layer metrics this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def host_peak_rss_gib() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench, cell, config, traffic = load_cell(args.workload)
    driver = importlib.import_module("benchmark.drivers." + traffic["driver"])
    try:
        jax = bdevice.init(cell["chips"], ROOT)
    except bdevice.NoChip as exc:
        log(f"no result: {exc}")
        return EXIT_NO_CHIP
    run = Run(args.seed, config, traffic, bdevice.peaks_for(jax, BENCH_DIR))
    run.install_observers(traced=bool(args.trace))

    workload = driver.setup(run)
    setup_s = time.monotonic() - T_START
    log(f"setup_s {setup_s:.3f}; host peak RSS {host_peak_rss_gib():.2f} GiB; "
        f"compile s by program {json.dumps(run.compile_seconds())}")

    compiles_before = run.compiles()
    trace_dir = os.path.join(ROOT, ".bench_trace", args.workload)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # a Python call tracer would bury the host
        options.host_tracer_level = 1  # the harness's annotations, not the runtime's
        options.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.monotonic()
        run.mark_window(t0)
        workload.window(t0 + (min(args.seconds, TRACE_WINDOW_S) if args.trace
                              else args.seconds))
        t1 = time.monotonic()
        run.mark_window(t0, t1)
    if args.trace:
        jax.profiler.stop_trace()
    window_s = t1 - t0
    run.compiles_in_window = run.compiles() - compiles_before
    device = bdevice.describe(jax, cell["chips"])
    log(f"window {window_s:.3f} s, {run.compiles_in_window} compiles in it; "
        f"host peak RSS {host_peak_rss_gib():.2f} GiB")

    if args.trace:
        from benchmark import trace_reduce

        t_reduce = time.monotonic()
        run.trace = trace_reduce.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)  # the reduction is all that is kept
        log(f"trace reduced in {time.monotonic() - t_reduce:.1f} s: "
            f"{sum(run.trace['launches'].values())} program launches")
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        metrics = {}
        for m in cell_metrics(bench, cell, "per_layer"):
            value = load_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(workload.end_to_end(window_s), setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell_metrics(bench, cell, "end_to_end")}
    attempted, failed = workload.attempted, workload.failed

    workload.release()  # free the program's state before the reference runs
    checks = workload.check()
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = run.trace["breakdown"]
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
