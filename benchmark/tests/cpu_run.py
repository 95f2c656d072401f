"""Drive benchmark/run.py end to end on the CPU at a small size (tests only).

    JAX_PLATFORMS=cpu python3 benchmark/tests/cpu_run.py <run.py arguments>

Skips the harness's look for a chip (and takes the v5e peaks row), shrinks
the epoch cell's registry to BENCH_TEST_VALIDATORS and applies the JSON in
BENCH_TEST_TRAFFIC over the traffic mix. BENCH_TEST_FAULT plants one of
benchmark/tests/controls.py's faults in the timed path.
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    from benchmark import device as bdevice
    from benchmark import run as brun

    load_cell = brun.load_cell

    def small(name):
        bench, cell, config, traffic = load_cell(name)
        if "validators" in config:
            config["validators"] = int(os.environ.get("BENCH_TEST_VALIDATORS", "2048"))
        traffic.update(json.loads(os.environ.get("BENCH_TEST_TRAFFIC", "{}")))
        return bench, cell, config, traffic

    def init(chips, root):
        import jax

        jax.config.update("jax_compilation_cache_dir",
                          os.environ.get("BENCH_TEST_CACHE", os.path.join(root, ".jax_cache")))
        return jax

    def peaks_for(jax, bench_dir):
        with open(os.path.join(bench_dir, "peaks.json")) as f:
            return json.load(f)["devices"]["TPU v5 lite"]

    brun.load_cell, bdevice.init, bdevice.peaks_for = small, init, peaks_for
    fault = os.environ.get("BENCH_TEST_FAULT")
    if fault:
        from benchmark.drivers import epoch_loop, sync_backfill
        from benchmark.tests import controls

        epoch_setup, sync_setup = epoch_loop.setup, sync_backfill.setup

        def planted_epoch(run):
            return epoch_loop.EpochLoop(run, program=controls.EPOCH[fault])

        def planted_sync(run):
            cell = sync_setup(run)
            cell.__class__ = controls.SYNC[fault]
            return cell

        epoch_loop.setup, sync_backfill.setup = planted_epoch, planted_sync
    return brun.main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
