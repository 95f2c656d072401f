"""Headline benchmark — BOTH BASELINE.md north stars, one JSON line.

1. `bls_verify_throughput` (the headline metric/value): aggregate BLS
   signature verifications per second on one chip — batched
   e(pk_i, H(m_i))·e(-G1, sig_i) == 1 checks through the RNS pairing kernels
   (ops/bls12_jax.py over ops/fp_rns.py). Target >= 100k/s (BASELINE.json);
   `vs_baseline` is measured/target.
2. `extra.process_epoch_s` (+ `extra.epoch_validators` for the size it ran
   at): mainnet-preset altair `process_epoch` device wall-clock (target
   < 2 s at 1M validators; the `process_epoch_1m_s` alias is emitted only
   when the run really is >=1M). `extra.epoch_vs_baseline` = 2.0/measured.

The reference publishes no numbers (BASELINE.json `published: {}`), so both
baselines are the BASELINE.json targets. Host prep (decompression,
hash-to-curve) is excluded from the BLS timed region: pubkeys live
decompressed in the registry and messages hash once per slot, so the pairing
is the marginal per-verification cost.

Prints ONE JSON line on stdout (progress notes on stderr) and exits 0
only when every lane ran on a TPU. Without a TPU it exits non-zero before
measuring anything, and an exception in any lane exits non-zero with its
traceback: there is no CPU fallback and no zero-value record. The record
names the device as JAX reports it (`platform`, `device_kind`, device
count). Every successful measurement is also persisted to BENCH_LOCAL.json
(timestamp + git SHA).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

N_VALIDATORS = int(os.environ.get("BENCH_VALIDATORS", 1_048_576))
N_BLS = int(os.environ.get("BENCH_BLS_N", 2048))
BLS_TARGET = 100_000.0
EPOCH_TARGET_S = 2.0


def bench_epoch() -> float:
    import jax

    from consensus_specs_tpu.compiler import get_spec
    from consensus_specs_tpu.engine.epoch import make_epoch_fn
    from consensus_specs_tpu.engine.state import EpochConfig
    from consensus_specs_tpu.engine.synthetic import synthetic_epoch_state

    cfg = EpochConfig.from_spec(get_spec("altair", "mainnet"))
    state = synthetic_epoch_state(cfg, n=N_VALIDATORS)
    fn = make_epoch_fn(cfg)

    t0 = time.time()
    out, _ = fn(state)
    jax.block_until_ready(out.balances)
    print(f"# epoch compile+first: {time.time() - t0:.1f}s", file=sys.stderr)

    times = []
    for _ in range(5):
        refreshed = jax.tree.map(lambda x: x.copy(), out)
        t0 = time.time()
        out2, _ = fn(refreshed)
        jax.block_until_ready(out2.balances)
        times.append(time.time() - t0)
        out = out2
    return sorted(times)[len(times) // 2]


def bench_bls() -> tuple[float, float, float, dict, dict]:
    """(per-item verifies/sec, RLC verifies/sec, compile_s, rlc stage
    breakdown, flush extras) at batch N_BLS. `flush extras` carries the
    grouped D+1-Miller-loop kernel comparison and the end-to-end
    deferred-flush lane (host prep included) from benches/bls_verify_bench —
    the e2e number is REQUIRED alongside the kernel-only figure (r5 VERDICT:
    kernel-only throughput without host-prep accounting is the evidence
    gap)."""
    import time as _time

    import jax
    import numpy as np

    from consensus_specs_tpu.crypto.bls_jax import bench_pairing_args
    from consensus_specs_tpu.ops import bls12_jax as K

    args = bench_pairing_args(N_BLS)
    t0 = _time.time()
    ok = K.pairing_check_batch(*args)
    ok.block_until_ready()
    compile_s = _time.time() - t0
    assert bool(np.asarray(ok).all()), "batched verification rejected valid signatures"
    print(f"# bls compile+first: {compile_s:.1f}s", file=sys.stderr)

    times = []
    for _ in range(3):
        t0 = _time.time()
        K.pairing_check_batch(*args).block_until_ready()
        times.append(_time.time() - t0)
    per_item = N_BLS / min(times)

    # randomized batch check (shared final exponentiation) — the deferred
    # flush's large-batch path
    from consensus_specs_tpu.crypto.bls_jax import random_zbits

    zbits = random_zbits(N_BLS)
    ok = K.pairing_check_rlc(*args, zbits, p2_is_neg_g1=True)
    ok.block_until_ready()
    assert bool(np.asarray(ok))
    rlc_times = []
    for _ in range(3):
        t0 = _time.time()
        K.pairing_check_rlc(*args, zbits, p2_is_neg_g1=True).block_until_ready()
        rlc_times.append(_time.time() - t0)

    stages = {}
    if os.environ.get("BENCH_BLS_STAGES", "1") != "0":
        from benches.bls_verify_bench import rlc_stage_breakdown

        stages = rlc_stage_breakdown(args, zbits)
        print(f"# rlc stage breakdown: {stages}", file=sys.stderr)

    flush_extra = {}
    if os.environ.get("BENCH_BLS_GROUPED", "1") != "0":
        from benches.bls_verify_bench import grouped_vs_ungrouped

        flush_extra.update(grouped_vs_ungrouped())
        print(f"# rlc grouped vs ungrouped: {flush_extra}", file=sys.stderr)
    if os.environ.get("BENCH_BLS_E2E", "1") != "0":
        from benches.bls_verify_bench import GROUPED_N, e2e_flush_lane

        e2e = e2e_flush_lane(min(N_BLS, GROUPED_N))
        print(f"# bls e2e flush lane: {e2e}", file=sys.stderr)
        flush_extra.update(e2e)
    return per_item, N_BLS / min(rlc_times), compile_s, stages, flush_extra


def run_benches() -> dict:
    import contextlib

    import jax

    from consensus_specs_tpu.obs import metrics as obs_metrics
    from consensus_specs_tpu.obs import recompile as obs_recompile
    from consensus_specs_tpu.obs import trace as obs_trace

    # Observability ON for the bench run: spans over every instrumented seam
    # plus the per-kernel recompile tracker, all feeding the process
    # registry. The snapshot is persisted next to BENCH_LOCAL.json
    # (persist_local) and a compact digest rides in extra["obs"] — a bench
    # record that recompiled a kernel 14 times says so.
    tracer = obs_trace.Tracer(registry=obs_metrics.REGISTRY,
                              max_spans=65536).install()
    compile_tracker = obs_recompile.CompileTracker(
        registry=obs_metrics.REGISTRY).install()
    profile_dir = os.environ.get("BENCH_PROFILE_DIR")
    ctx = jax.profiler.trace(profile_dir) if profile_dir else contextlib.nullcontext()
    with ctx:
        with obs_trace.span("bench_bls"):
            vps, rlc_vps, compile_s, rlc_stages, bls_flush = bench_bls()
        with obs_trace.span("bench_epoch"):
            epoch_s = bench_epoch()
        with obs_trace.span("bench_attestations"):
            import benches.attestation_bench as att_bench

            att = att_bench.run()
        with obs_trace.span("bench_state_root"):
            import benches.state_root_bench as sr_bench

            sr = sr_bench.run(int(os.environ.get("BENCH_SR_VALIDATORS", N_VALIDATORS)))
        with obs_trace.span("bench_epoch_e2e"):
            import benches.epoch_e2e_bench as e2e_bench

            e2e = e2e_bench.run(int(os.environ.get("BENCH_E2E_VALIDATORS", N_VALIDATORS)))
        with obs_trace.span("bench_kzg"):
            import benches.kzg_bench as kzg_bench

            kzg_r = kzg_bench.run()
        with obs_trace.span("bench_msm"):
            import benches.msm_bench as msm_bench

            msm_r = msm_bench.run()
        with obs_trace.span("bench_sync_aggregate"):
            import benches.sync_aggregate_bench as sync_bench

            sync_r = sync_bench.run()
        with obs_trace.span("bench_sched"):
            import benches.sched_bench as sched_bench

            sched_r = sched_bench.run()
        with obs_trace.span("bench_firehose"):
            import benches.firehose_bench as firehose_bench

            fh_r = firehose_bench.run()
        with obs_trace.span("bench_scenario"):
            import benches.scenario_bench as scenario_bench

            scen_r = scenario_bench.run()
        with obs_trace.span("bench_proofs"):
            import benches.proof_bench as proof_bench

            proof_r = proof_bench.run()
        with obs_trace.span("bench_forkchoice"):
            import benches.forkchoice_bench as forkchoice_bench

            fc_r = forkchoice_bench.run()
        with obs_trace.span("bench_frontdoor"):
            import benches.frontdoor_bench as frontdoor_bench

            fd_r = frontdoor_bench.run()
    if profile_dir:
        print(f"# device trace written to {profile_dir}", file=sys.stderr)
    stage_s = {key.split('"')[1]: round(h["sum"], 6)
               for key, h in obs_metrics.REGISTRY.snapshot()["histograms"].items()
               if key.startswith('span_seconds{span="bench_')}
    print(f"# stage timings: {stage_s}", file=sys.stderr)
    tracer.uninstall()
    compile_tracker.uninstall()
    obs_digest = {
        "spans": len(tracer.finished) + tracer.dropped,
        "spans_dropped": tracer.dropped,
        "compile_total": compile_tracker.kernels(),
        "compile_distinct_shapes": {
            k: compile_tracker.distinct_shapes(k)
            for k in compile_tracker.kernels()},
        "flushes": obs_metrics.REGISTRY.counters_matching("bls_flush_total"),
    }
    print(f"# obs: {obs_digest}", file=sys.stderr)
    return {
        "metric": "bls_verify_throughput",
        "value": round(vps, 1),
        "unit": "verifications/sec/chip",
        "vs_baseline": round(vps / BLS_TARGET, 4),
        "extra": {
            "bls_batch": N_BLS,
            "bls_verify_throughput_rlc": round(rlc_vps, 1),
            "bls_compile_s": round(compile_s, 1),
            "bls_rlc_stage_s": rlc_stages,
            # grouped D+1 flush + end-to-end lane (host prep included):
            # bls_verify_throughput_e2e / rlc_distinct_messages / rlc_*
            **bls_flush,
            # keyed by the ACTUAL registry size measured — the 1M alias is
            # added only when the run really is 1M (VERDICT r4 weak #3)
            "process_epoch_s": round(epoch_s, 4),
            "epoch_validators": N_VALIDATORS,
            "epoch_vs_baseline": round(EPOCH_TARGET_S / epoch_s, 2),
            # cold = caches cleared (comparable with r1-r3 recordings);
            # warm = marginal re-verification rate with caches hot
            "attestations_per_sec": round(att["attestations_per_sec_cold"], 1),
            "attestation_epoch_s": round(att["cold_epoch_s"], 4),
            "attestations_per_sec_warm": round(att["attestations_per_sec_warm"], 1),
            "attestation_warm_epoch_s": round(att["warm_epoch_s"], 4),
            "attestations_per_epoch": att["attestations_per_epoch"],
            "attestation_validators": att["validators"],
            "attestation_committees_per_slot": att["committees_per_slot"],
            # BASELINE config 4 honest end-to-end — HEADLINE is the resident
            # pipeline's amortized per-epoch cost; the sequential lane (full
            # bridge round trip every epoch) rides along for the stage
            # breakdown, and write_back_bytes carries the measured dirty vs
            # full-materialize D2H accounting from the same run
            "epoch_e2e_s": e2e["e2e_epoch_s"],
            "epoch_e2e_sequential_s": e2e["sequential_epoch_s"],
            "epoch_e2e_stages_s": e2e["stages_s"],
            "epoch_e2e_write_back_bytes": e2e["write_back_bytes"],
            "epoch_e2e_validators": e2e["validators"],
            # steady-state device-resident loop (engine/resident.py): the
            # registry never leaves HBM; materialize + root amortized
            "epoch_resident_s": e2e["resident_epoch_s"],
            "epoch_resident_scan_s": e2e["resident_scan_epoch_s"],
            "epoch_resident_state_root_s": e2e["resident_state_root_s"],
            "epoch_resident_state_root_slot_s": e2e["resident_state_root_slot_s"],
            "epoch_resident_amortized_s": e2e["resident_amortized_epoch_s"],
            "epoch_resident_epochs": e2e["resident_epochs"],
            "epoch_resident_vs_baseline": round(
                EPOCH_TARGET_S / max(e2e["resident_amortized_epoch_s"], 1e-9), 2),
            # BASELINE config 5: batched KZG sample verification per block
            "kzg_blobs_per_s": kzg_r["blobs_per_s"],
            "kzg_batch_verify_s": kzg_r["batch_verify_s"],
            "kzg_blobs": kzg_r["blobs"],
            # Pippenger bucket-MSM kernel vs the per-item ladder it replaced
            # (same points/scalars, cross-checked before timing); the sweep
            # grid rides in msm_sweep
            "msm_items_per_s": msm_r["msm_items_per_s"],
            "msm_vs_ladder_speedup": msm_r["msm_vs_ladder_speedup"],
            "msm_n": msm_r["msm_n"],
            "msm_window": msm_r["msm_window"],
            "msm_nbits": msm_r["msm_nbits"],
            "msm_sweep": msm_r["msm_sweep"],
            # BASELINE config 3: per-block sync-aggregate obligation — one
            # 512-member FastAggregateVerify per block, flushed as a stream
            "sync_aggregate_blocks_per_s": sync_r["blocks_per_s_cold"],
            "sync_aggregate_blocks_per_s_warm": sync_r["blocks_per_s_warm"],
            "sync_aggregate_blocks": sync_r["blocks"],
            "sync_aggregate_committee_size": sync_r["committee_size"],
            # unified verification scheduler mixed lane: per-class items/s
            # through the shared dispatch seam, steady-state p99
            # submit->result latency, and the bucketing occupancy floor
            # (>= 0.75 by construction; a bucketing regression shows here)
            "sched_bls_items_per_s": sched_r["sched_bls_items_per_s"],
            "sched_kzg_items_per_s": sched_r["sched_kzg_items_per_s"],
            "sched_merkle_items_per_s": sched_r["sched_merkle_items_per_s"],
            "sched_p99_latency_s": sched_r["sched_p99_latency_s"],
            "sched_occupancy_min": sched_r["sched_occupancy_min"],
            "sched_compile_s": sched_r["sched_compile_s"],
            # attestation firehose soak: streaming gossip->aggregate->flush
            # throughput at 64 committees/slot sized for a 1M-validator
            # registry, p99 ingest->verified from the pipeline's own
            # histogram, and the committee-collapse ratio (atts per
            # device pairing check)
            "firehose_atts_per_s_cold": fh_r["firehose_atts_per_s_cold"],
            "firehose_atts_per_s_steady": fh_r["firehose_atts_per_s_steady"],
            "firehose_p99_ingest_to_verified_s":
                fh_r["firehose_p99_ingest_to_verified_s"],
            "firehose_collapse_ratio": fh_r["firehose_collapse_ratio"],
            "firehose_queue_depth_peak": fh_r["firehose_queue_depth_peak"],
            # scenario-engine SLO lane: chaos-enabled engine replay of a
            # seeded long-horizon history (storms/equivocations/fork
            # transition), plus the emit->diff double render — the
            # bidirectional conformance loop measured end to end
            "scenario_slots_per_s": scen_r["scenario_slots_per_s"],
            "scenario_reorg_depth_max": scen_r["scenario_reorg_depth_max"],
            "scenario_vectors_emitted": scen_r["scenario_vectors_emitted"],
            "scenario_vectors_diffed": scen_r["scenario_vectors_diffed"],
            "scenario_slots": scen_r["scenario_slots"],
            "scenario_faults_fired": scen_r["scenario_faults_fired"],
            # light-client read lane: batched device multiproofs + the
            # dirty-column proof cache serving thousands of branch queries
            # while the epoch+firehose write path runs; p99 from the
            # lane's own histogram and the cross-checked device-vs-host
            # speedup on identical inputs
            "proof_proofs_per_s_cold": proof_r["proof_proofs_per_s_cold"],
            "proof_proofs_per_s_warm": proof_r["proof_proofs_per_s_warm"],
            "proof_cache_hit_ratio": proof_r["proof_cache_hit_ratio"],
            "proof_p99_request_s": proof_r["proof_p99_request_s"],
            "proof_vs_host_speedup": proof_r["proof_vs_host_speedup"],
            "proof_queries": proof_r["proof_queries"],
            "proof_write_epochs": proof_r["proof_write_epochs"],
            # fork-choice head lane: reorg-storm soak over a contested
            # tree at registry scale, every verified batch folded through
            # the service's firehose seam; head lag (verified -> head
            # reflecting it) from the lane's own histogram, device batch
            # cross-checked bit-identical against the host oracle
            "forkchoice_heads_per_s": fc_r["forkchoice_heads_per_s"],
            "forkchoice_head_lag_p99_s": fc_r["forkchoice_head_lag_p99_s"],
            "forkchoice_head_flips": fc_r["forkchoice_head_flips"],
            "forkchoice_vs_host_speedup":
                fc_r["forkchoice_vs_host_speedup"],
            "forkchoice_blocks": fc_r["forkchoice_blocks"],
            "forkchoice_validators": fc_r["forkchoice_validators"],
            # front-door admission plane: the three seeded traffic
            # profiles replayed un-paced on the real clock; the
            # hostile-tenant lane's worst HONEST p99 (from the door's own
            # per-tenant histogram) is the SLO series, and the
            # attestation-shed count sums every round of every profile —
            # the writes-never-shed invariant, gated at zero
            "frontdoor_requests_per_s": fd_r["frontdoor_requests_per_s"],
            "frontdoor_hostile_honest_p99_s":
                fd_r["frontdoor_hostile_honest_p99_s"],
            "frontdoor_attestation_sheds":
                fd_r["frontdoor_attestation_sheds"],
            "frontdoor_mallory_quota_refusals":
                fd_r["frontdoor_mallory_quota_refusals"],
            "frontdoor_profiles": fd_r["frontdoor_profiles"],
            # per-slot state root at registry scale (incremental Merkle)
            "state_root_slot_s": sr["slot_root_s"],
            "state_root_block_s": sr["block_root_s"],
            "state_root_cold_s": sr["cold_root_s"],
            # trace/recompile digest; the full canonical snapshot is
            # BENCH_OBS.json (persist_local)
            "obs": obs_digest,
            "device": device_record(),
        },
    }


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10, cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip()
    except Exception:
        return "unknown"


def device_record() -> dict:
    """The device as JAX reports it: platform, kind and count."""
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def persist_local(record: dict) -> None:
    """Append the measurement to BENCH_LOCAL.json (timestamp + git SHA) so
    every chip number keeps its provenance."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "BENCH_LOCAL.json")
    entry = {
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_sha": _git_sha(),
        **record,
    }
    try:
        history = []
        if os.path.exists(path):
            with open(path) as f:
                history = json.load(f)
            if not isinstance(history, list):
                history = [history]
        history.append(entry)
        with open(path, "w") as f:
            json.dump(history, f, indent=1)
    except Exception as exc:  # never let provenance writing kill the bench
        print(f"# BENCH_LOCAL.json write failed: {exc}", file=sys.stderr)
    try:
        # The full canonical obs snapshot rides alongside the scoreboard
        # history: every counter/histogram the instrumented seams recorded
        # during this run, in the byte-stable exporter format.
        from consensus_specs_tpu.obs import export as obs_export

        obs_export.write_snapshot(
            os.path.join(os.path.dirname(path), "BENCH_OBS.json"),
            meta={"lane": "bench", "git_sha": entry["git_sha"]})
    except Exception as exc:
        print(f"# BENCH_OBS.json write failed: {exc}", file=sys.stderr)


def main() -> int:
    from consensus_specs_tpu.utils.backend import enable_compile_cache

    jax = enable_compile_cache()
    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"# bench.py measures the TPU; JAX found {platform!r}",
              file=sys.stderr)
        return 1
    record = run_benches()
    if N_VALIDATORS >= 1_048_576:
        record["extra"]["process_epoch_1m_s"] = record["extra"]["process_epoch_s"]
    _gate_slos(record)
    persist_local(record)
    print(json.dumps(record))
    return 0


def _gate_slos(record: dict) -> None:
    """Evaluate slo.json against this run BEFORE persisting, so the record
    carries its own verdict (extra["slo"]) and a regression is visible in
    the history, not just in CI. Non-fatal by design: `make slo` /
    tools/slo_check.py is the enforcing gate (rc != 0)."""
    root = os.path.dirname(os.path.abspath(__file__))
    spec_path = os.path.join(root, "slo.json")
    try:
        from consensus_specs_tpu.obs import export as obs_export
        from consensus_specs_tpu.obs import slo as obs_slo

        specs = obs_slo.load_spec_file(spec_path)
        snap = obs_export.snapshot_dict(meta={"lane": "bench"})
        history = []
        local = os.path.join(root, "BENCH_LOCAL.json")
        if os.path.exists(local):
            with open(local) as f:
                history = json.load(f)
            if not isinstance(history, list):
                history = [history]
        # run_benches() uninstalled its tracer, so disabled-mode overhead
        # is measurable in-process here
        results = obs_slo.evaluate(specs, [snap], history + [record])
        record.setdefault("extra", {})["slo"] = obs_slo.summarize(results)
        for r in results:
            if not r.ok:
                print(f"# SLO VIOLATION {r.name}: {r.detail}",
                      file=sys.stderr)
    except Exception as exc:
        print(f"# slo evaluation failed: {exc}", file=sys.stderr)


if __name__ == "__main__":
    sys.exit(main())
