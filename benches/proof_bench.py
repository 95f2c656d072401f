"""Light-client read lane (consensus_specs_tpu/proofs/ + the sched
"multiproof" kind).

Measured region: thousands of (column, gindex) branch queries against a
registry-scale synthetic BeaconState served by a ProofService — cache
lookup, miss batching into shape-bucketed device multiproof flushes, and
the store-back — WHILE the write path runs: a resident epoch engine
stepping real epoch transitions over the SAME columns in a background
thread (its dirty-column diffs drive the cache invalidation between
rounds), plus a small attestation-firehose stream keeping the BLS lane
busy. Reported: proofs/s cold (proof-kernel compile included, empty
cache) and warm (best re-issue round: clean columns answer from cache,
dirty columns re-prove on device), the cache hit ratio, p99 request
latency from the lane's OWN histogram (`proof_request_latency_seconds` —
the SLO series, not a stopwatch; the registry resets after an unmeasured
warm-up round so the histogram aggregates steady-state rounds only, with
the cold round's percentiles reported separately), and the warm batched
device path vs the per-query `build_chunk_proof` host loop on identical
cross-checked inputs.

Traffic shape: `BENCH_PROOF_VALIDATORS` validators (default 1_048_576;
bench.py clamps the cpu-debug lane), six registry columns registered
(balances / effective_balance / inactivity_scores move every epoch;
activation_epoch / activation_eligibility_epoch / exit_epoch stay clean
absent activations and ejections), `BENCH_PROOF_QUERIES` distinct leaf
queries spread round-robin across the columns so every flush batches a
mixed-column device multiproof.

Usage: python benches/proof_bench.py — one JSON line, persisted to
BENCH_LOCAL.json. BENCH_PROOF_VALIDATORS / BENCH_PROOF_QUERIES /
BENCH_PROOF_ROUNDS / BENCH_PROOF_FLUSH / BENCH_PROOF_FIREHOSE_COMMITTEES
size the lane (committees=0 disables the firehose stream).
"""
import json
import os
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

COLUMNS = ("balances", "effective_balance", "inactivity_scores",
           "activation_epoch", "activation_eligibility_epoch", "exit_epoch")

MAX_WRITE_EPOCHS = 120  # stay clear of the sync-committee rotation
#                         boundary (synthetic pubkeys are not G1 points)


def default_counts() -> dict:
    return {
        "validators": int(os.environ.get("BENCH_PROOF_VALIDATORS", 1_048_576)),
        "queries": int(os.environ.get("BENCH_PROOF_QUERIES", 2048)),
        "rounds": int(os.environ.get("BENCH_PROOF_ROUNDS", 3)),
        "flush": int(os.environ.get("BENCH_PROOF_FLUSH", 512)),
        "firehose_committees": int(
            os.environ.get("BENCH_PROOF_FIREHOSE_COMMITTEES", 2)),
        "firehose_size": int(os.environ.get("BENCH_PROOF_FIREHOSE_SIZE", 32)),
        "firehose_atts": int(os.environ.get("BENCH_PROOF_FIREHOSE_ATTS", 2)),
    }


def _build_queries(counts: dict, n_chunks: int):
    """Round-robin column-interleaved distinct leaf queries, so every
    flush-sized slice spans all columns (mixed-column device batches)."""
    import numpy as np

    from consensus_specs_tpu.proofs import leaf_gindex

    rng = np.random.RandomState(2302)
    per_col = max(1, counts["queries"] // len(COLUMNS))
    picks = {
        name: rng.choice(n_chunks, size=min(per_col, n_chunks),
                         replace=False)
        for name in COLUMNS}
    queries = []
    for i in range(per_col):
        for name in COLUMNS:
            if i < len(picks[name]):
                queries.append(
                    (name, leaf_gindex(int(picks[name][i]), n_chunks)))
    return queries


def _start_firehose_thread(counts: dict, stop: threading.Event):
    """Small steady attestation stream on its own scheduler: keeps the
    BLS device lane busy while the read lane runs. Returns (thread,
    stats) or (None, stats) when disabled."""
    stats = {"rounds": 0, "atts": 0}
    if counts["firehose_committees"] <= 0:
        return None, stats
    import benches.firehose_bench as fb
    from consensus_specs_tpu.firehose import AttestationFirehose, FirehoseConfig
    from consensus_specs_tpu.obs import metrics as obs_metrics
    from consensus_specs_tpu.sched import BlsWorkClass, Scheduler

    fh_counts = {"committees": counts["firehose_committees"],
                 "committee_size": counts["firehose_size"],
                 "atts_per_committee": counts["firehose_atts"], "rounds": 1}
    payloads, pk_table, messages = fb.build_traffic(fh_counts)
    classify = fb.make_classifier(pk_table, messages)
    cfg = FirehoseConfig(batch_attestations=len(payloads),
                         max_pending=len(payloads), flush_deadline_s=30.0)
    reg = obs_metrics.MetricsRegistry()

    def one_round():
        sch = Scheduler(classes=[BlsWorkClass(collapse_same_message=True)],
                        registry=reg)
        fh = AttestationFirehose(classify, scheduler=sch, registry=reg,
                                 config=cfg, threaded=True)
        with fh:
            fh.offer_many(payloads)
            fh.drain(timeout_s=900.0)
        res = fh.results()
        assert len(res) == len(payloads) and all(res.values())
        stats["rounds"] += 1
        stats["atts"] += len(payloads)

    # pay the pairing-bucket compile and the cold crypto caches BEFORE the
    # measured region: the steady stream is the write-path load, not a
    # compile benchmark
    one_round()

    def loop():
        while not stop.is_set():
            one_round()

    t = threading.Thread(target=loop, name="proof-bench-firehose",
                         daemon=True)
    t.start()
    return t, stats


def run(counts: dict | None = None) -> dict:
    import numpy as np

    from consensus_specs_tpu.compiler import get_spec
    from consensus_specs_tpu.engine.resident import ResidentEpochEngine
    from consensus_specs_tpu.obs import metrics as obs_metrics
    from consensus_specs_tpu.proofs import ProofService, u64_column_chunks
    from consensus_specs_tpu.sched import MerkleWorkClass, Scheduler
    from consensus_specs_tpu.ssz.proofs import build_chunk_proof
    from consensus_specs_tpu.testlib.big_state import synthetic_beacon_state

    if counts is None:
        counts = default_counts()
    n_validators = counts["validators"]
    spec = get_spec("altair", "mainnet")
    # same slot choice as epoch_e2e_bench: off the sync-committee-period
    # and eth1-reset boundaries, so the synthetic registry's fake pubkeys
    # never reach a rotation
    slot = int(spec.SLOTS_PER_EPOCH) * 101 - 1

    t0 = time.time()
    state = synthetic_beacon_state(spec, n_validators, slot=slot)
    eng = ResidentEpochEngine(spec, state)
    print(f"# proof state build ({n_validators} validators): "
          f"{time.time() - t0:.1f}s", file=sys.stderr)

    t0 = time.time()
    eng.step_epoch()  # epoch-program compile, outside every measured region
    np.asarray(eng.dev.balances)
    print(f"# proof write-path warmup (epoch compile): "
          f"{time.time() - t0:.1f}s", file=sys.stderr)

    # one lock serializes engine steps (which DONATE the column buffers)
    # against provider column reads; the proof kernel dispatch itself runs
    # outside it, contending with the write path only for the device
    write_lock = threading.Lock()
    write_stats = {"epochs": 1}
    stop = threading.Event()

    def write_loop():
        while not stop.is_set() and write_stats["epochs"] < MAX_WRITE_EPOCHS:
            with write_lock:
                eng.step_epoch()
            np.asarray(eng.dev.balances)  # keep the device queue honest
            write_stats["epochs"] += 1

    reg = obs_metrics.MetricsRegistry()
    sched = Scheduler(classes=[MerkleWorkClass()], registry=reg)
    svc = ProofService(scheduler=sched, registry=reg)

    def make_provider(name):
        def provider():
            with write_lock:
                return u64_column_chunks(np.asarray(getattr(eng.dev, name)))
        return provider

    for name in COLUMNS:
        svc.register_column(name, make_provider(name))
    n_chunks = len(u64_column_chunks(np.asarray(eng.dev.balances)))
    queries = _build_queries(counts, n_chunks)
    flush = counts["flush"]

    fh_thread, fh_stats = _start_firehose_thread(counts, stop)
    writer = threading.Thread(target=write_loop, name="proof-bench-writer",
                              daemon=True)
    writer.start()

    def one_round() -> float:
        t = time.time()
        for i in range(0, len(queries), flush):
            svc.prove_many(queries[i:i + flush])
        return time.time() - t

    # cold: empty cache, multiproof-kernel compile included, write path hot
    cold_dt = one_round()
    hist = reg.histogram("proof_request_latency_seconds")
    cold_p99, cold_p50 = hist.p99(), hist.p50()
    print(f"# proof cold round (compile included): {cold_dt:.1f}s "
          f"({len(queries)} queries)", file=sys.stderr)

    # warm rounds: dirty-column diff invalidates between rounds — clean
    # columns answer from cache, dirty columns re-prove on device. One
    # UNMEASURED warm-up round pays the dirty-only flush's XLA bucket
    # (fewer trees than a cold flush -> a new shape), then the registry
    # resets so the histogram aggregates only the measured rounds — the
    # same steady-state framing as the firehose soak lane.
    def _hm():
        return (sum(reg.counters_matching("proof_cache_hits_total").values()),
                sum(reg.counters_matching(
                    "proof_cache_misses_total").values()))

    svc.note_epoch(eng.dirty_columns())
    warmup_dt = one_round()
    print(f"# proof warm-up round (dirty-bucket compile): {warmup_dt:.2f}s",
          file=sys.stderr)
    reg.reset()

    warm_h0, warm_m0 = _hm()
    best = float("inf")
    dirty_seen: dict = {}
    for r in range(counts["rounds"]):
        dirty = eng.dirty_columns()
        for k, v in dirty.items():
            dirty_seen[k] = dirty_seen.get(k, False) or v
        svc.note_epoch(dirty)
        dt = one_round()
        print(f"# proof warm round {r}: {dt:.2f}s "
              f"(dirty: {sorted(k for k in COLUMNS if dirty[k])})",
              file=sys.stderr)
        best = min(best, dt)
    warm_h1, warm_m1 = _hm()
    warm_ratio = (warm_h1 - warm_h0) / max(
        (warm_h1 - warm_h0) + (warm_m1 - warm_m0), 1)

    stop.set()
    writer.join(timeout=600.0)
    if fh_thread is not None:
        fh_thread.join(timeout=900.0)

    # batched device path vs the per-query host loop, on ONE frozen
    # snapshot of every column (identical inputs, results cross-checked
    # byte-for-byte). Same flush size and column mix as the lane rounds,
    # so the warm XLA buckets are reused; fresh empty cache so every query
    # really rides the device.
    with write_lock:
        frozen = {name: tuple(
            u64_column_chunks(np.asarray(getattr(eng.dev, name))))
            for name in COLUMNS}
    svc2 = ProofService(scheduler=sched,
                        registry=obs_metrics.MetricsRegistry())
    for name in COLUMNS:
        svc2.register_column(name, lambda name=name: frozen[name])
    t0 = time.time()
    device_branches = []
    for i in range(0, len(queries), flush):
        device_branches.extend(svc2.prove_many(queries[i:i + flush]))
    device_dt = time.time() - t0
    t0 = time.time()
    host_branches = [tuple(build_chunk_proof(frozen[name], g))
                     for name, g in queries]
    host_dt = time.time() - t0
    assert device_branches == host_branches, (
        "device multiproof batch diverged from the build_chunk_proof host "
        "loop on identical inputs")
    speedup = host_dt / max(device_dt, 1e-9)
    print(f"# proof device batch {device_dt:.2f}s vs host loop "
          f"{host_dt:.2f}s ({speedup:.1f}x, cross-checked)", file=sys.stderr)

    hist = reg.histogram("proof_request_latency_seconds")
    inval = reg.counters_matching("proof_cache_invalidated_total")
    return {
        "proof_proofs_per_s_cold": round(len(queries) / cold_dt, 1),
        "proof_proofs_per_s_warm": round(len(queries) / best, 1),
        "proof_cache_hit_ratio": round(
            reg.gauge_value("proof_cache_hit_ratio"), 4),
        "proof_cache_hit_ratio_warm": round(warm_ratio, 4),
        "proof_p99_request_s": round(hist.p99(), 4),
        "proof_p50_request_s": round(hist.p50(), 4),
        "proof_p99_request_cold_s": round(cold_p99, 4),
        "proof_p50_request_cold_s": round(cold_p50, 4),
        "proof_vs_host_speedup": round(speedup, 2),
        "proof_queries": len(queries),
        "proof_chunks_per_column": n_chunks,
        "proof_columns": len(COLUMNS),
        "proof_dirty_columns_seen": sorted(
            k for k, v in dirty_seen.items() if v),
        "proof_cache_invalidations": {
            k: int(v) for k, v in sorted(inval.items())},
        "proof_write_epochs": write_stats["epochs"],
        "proof_firehose_rounds": fh_stats["rounds"],
        "proof_firehose_atts": fh_stats["atts"],
        "proof_counts": {k: counts[k] for k in (
            "validators", "queries", "rounds", "flush")},
    }


def main():
    from consensus_specs_tpu.utils.backend import enable_compile_cache, force_cpu

    force_cpu()
    enable_compile_cache()
    import bench

    r = run()
    record = {
        "metric": "proof_proofs_per_s_warm",
        "value": r["proof_proofs_per_s_warm"],
        "unit": "proofs/sec",
        "vs_baseline": None,
        "extra": r,
    }
    bench.persist_local(record)
    print(json.dumps(record))


if __name__ == "__main__":
    main()
