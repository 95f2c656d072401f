"""Attestation firehose soak lane (consensus_specs_tpu/firehose/).

Measured region: gossip-shaped micro-batches of synthetic aggregate
attestations offered through the full streaming service — ingest
(message-id dedup + classify), committee-keyed collapse at scheduler
admission, and the double-buffered device flush — until every verdict
lands. Reported: attestations/s cold (all crypto caches cleared, compile
included) and steady-state (best re-sighting round: the same payload set
re-offered to a FRESH firehose, so dedup restarts while the process-level
crypto caches stay hot — the same warm framing the attestation lane's
`attestations_per_sec_warm` uses), plus p99/p50 ingest→verified latency
from the firehose's OWN histogram (the SLO series, not a stopwatch), the
measured collapse ratio (attestations per device check), and the
backpressure high-water mark.

Traffic shape: `BENCH_FIREHOSE_COMMITTEES` committees per slot (default
64, the mainnet MAX_COMMITTEES_PER_SLOT) sized for a 1M-validator
registry — 1M / (32 slots × 64 committees) ≈ 488 members — each producing
`BENCH_FIREHOSE_ATTS` aggregates over disjoint member subsets. One member
key set is rotated per committee (distinct subset tuples, so pubkey
aggregation is NOT cross-committee cached) and signatures use the
aggregate identity Sign(Σsk, m) == Aggregate(Sign(sk_i, m)), keeping host
prep tractable; prep happens before any timed region.

Usage: python benches/firehose_bench.py — one JSON line, persisted to
BENCH_LOCAL.json. BENCH_FIREHOSE_COMMITTEES / BENCH_FIREHOSE_SIZE /
BENCH_FIREHOSE_ATTS / BENCH_FIREHOSE_ROUNDS size the lane.
"""
import json
import os
import struct
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

MICRO_BATCH = 64  # payloads per offer_many call: gossip-drain granularity


def default_counts() -> dict:
    return {
        "committees": int(os.environ.get("BENCH_FIREHOSE_COMMITTEES", 64)),
        # 1_000_000 validators / 32 slots / 64 committees
        "committee_size": int(os.environ.get("BENCH_FIREHOSE_SIZE", 488)),
        "atts_per_committee": int(os.environ.get("BENCH_FIREHOSE_ATTS", 8)),
        "rounds": int(os.environ.get("BENCH_FIREHOSE_ROUNDS", 3)),
    }


def build_traffic(counts: dict, forge=()):
    """(payloads, pk_table, messages): c-major payload stream of
    struct('<II')-framed (committee, aggregate_index) headers + the 96-byte
    aggregate signature; pk_table[(c, s)] is that aggregate's pubkey tuple.

    `forge`: (c, s) aggregates whose signers sign the NEXT committee's
    message instead — a valid signature over another message, which the
    verifier must reject."""
    from consensus_specs_tpu.crypto import bls_sig

    C = counts["committees"]
    size = counts["committee_size"]
    aps = counts["atts_per_committee"]
    sks = [100003 + i for i in range(size)]
    pks = [bls_sig.SkToPk(sk) for sk in sks]
    messages = [(b"firehose slot root %04d" % c).ljust(32, b"\x00")
                for c in range(C)]
    payloads = []
    pk_table = {}
    step = max(1, size // aps)
    for c in range(C):
        rot = c % size
        order_pks = pks[rot:] + pks[:rot]
        order_sks = sks[rot:] + sks[:rot]
        for s in range(aps):
            lo = s * step
            hi = size if s == aps - 1 else min(size, lo + step)
            pk_table[(c, s)] = tuple(order_pks[lo:hi])
            signed = messages[(c + 1) % C] if (c, s) in forge else messages[c]
            sig = bls_sig.Sign(sum(order_sks[lo:hi]), signed)
            payloads.append(struct.pack("<II", c, s) + bytes(sig))
    return payloads, pk_table, messages


def make_classifier(pk_table: dict, messages: list):
    from consensus_specs_tpu.firehose import AttestationItem, ClassifyError
    from consensus_specs_tpu.parallel.gossip_driver import message_id

    def classify(raw: bytes) -> AttestationItem:
        try:
            c, s = struct.unpack_from("<II", raw)
            msg = messages[c]
            return AttestationItem(
                msg_id=message_id(bytes(raw)),
                key=(0, c, msg[:8]),
                pubkeys=pk_table[(c, s)],
                message=msg,
                signature=bytes(raw[8:]),
                ssz=bytes(raw))
        except Exception as exc:
            raise ClassifyError(f"bench frame: {exc}") from exc

    return classify


def run(counts: dict | None = None) -> dict:
    from consensus_specs_tpu.crypto import bls
    from consensus_specs_tpu.firehose import AttestationFirehose, FirehoseConfig
    from consensus_specs_tpu.obs import metrics as obs_metrics
    from consensus_specs_tpu.sched import BlsWorkClass, Scheduler

    if counts is None:
        counts = default_counts()
    t0 = time.time()
    payloads, pk_table, messages = build_traffic(counts)
    classify = make_classifier(pk_table, messages)
    n_atts = len(payloads)
    print(f"# firehose host prep ({n_atts} aggregate attestations over "
          f"{counts['committees']} committees of {counts['committee_size']}): "
          f"{time.time() - t0:.1f}s", file=sys.stderr)

    # seal exactly once per round, at the full stream: every dispatch is
    # the same 64-committee batch in ONE pow2 pairing bucket. Sealing
    # earlier lets the producer/worker race smear one round's work across
    # two bucket sizes — each a separate minutes-long XLA compile on CPU —
    # and the admission/dispatch overlap it would buy is noise here (warm
    # admission is ~2 orders of magnitude cheaper than the pairing batch)
    cfg = FirehoseConfig(batch_attestations=n_atts, max_pending=n_atts,
                         flush_deadline_s=30.0)

    def round_run(reg) -> float:
        sch = Scheduler(classes=[BlsWorkClass(collapse_same_message=True)],
                        max_depth=1 << 30, registry=reg)
        fh = AttestationFirehose(classify, scheduler=sch, registry=reg,
                                 config=cfg, threaded=True)
        t = time.time()
        with fh:
            for i in range(0, n_atts, MICRO_BATCH):
                fh.offer_many(payloads[i:i + MICRO_BATCH])
            # the cold round pays ~2.7s of host pubkey aggregation per
            # 488-member committee — well past drain()'s default deadline
            fh.drain(timeout_s=900.0)
        dt = time.time() - t
        res = fh.results()
        assert len(res) == n_atts, f"lost verdicts: {len(res)}/{n_atts}"
        assert all(res.values()), "firehose rejected valid attestations"
        assert sch.breaker("bls").state == "closed", "bench lane degraded"
        return dt

    # cold: every crypto cache (pubkey/signature decompression, committee
    # aggregation, hash-to-curve, sign) empty, device compile included.
    # First-sighting committee aggregation must route through the device
    # MSM lane (batched subgroup checks + g1_aggregate_device via the sched
    # "msm" class) — the counters live on the process registry, so snapshot
    # around the round and FAIL the bench if the cold lane fell back to the
    # host pt_add loop.
    glob = obs_metrics.REGISTRY
    agg_dev_before = glob.counter_value("bls_pubkey_aggregate_device_total")
    sub_dev_before = glob.counter_value("bls_pubkey_subgroup_device_total")
    bls.clear_caches()
    cold_dt = round_run(obs_metrics.MetricsRegistry())
    agg_dev_cold = (glob.counter_value("bls_pubkey_aggregate_device_total")
                    - agg_dev_before)
    sub_dev_cold = (glob.counter_value("bls_pubkey_subgroup_device_total")
                    - sub_dev_before)
    assert agg_dev_cold > 0, (
        "cold-lane committee aggregation did not route through the device "
        "MSM path (bls_pubkey_aggregate_device_total never ticked)")
    print(f"# firehose cold round (compile included): {cold_dt:.1f}s — "
          f"{agg_dev_cold} device aggregations, {sub_dev_cold} device "
          f"subgroup checks", file=sys.stderr)

    # steady state: re-sighting rounds — fresh firehose (dedup reset), hot
    # process caches; the histogram below aggregates only these rounds
    reg = obs_metrics.MetricsRegistry()
    best = float("inf")
    for _ in range(counts["rounds"]):
        best = min(best, round_run(reg))

    hist = reg.histogram("firehose_ingest_to_verified_seconds")
    submitted = reg.counter_value("firehose_submitted_total")
    dispatched = reg.counter_value("sched_items_total", work_class="bls")
    return {
        "firehose_atts_per_s_cold": round(n_atts / cold_dt, 1),
        "firehose_atts_per_s_steady": round(n_atts / best, 1),
        # cold-lane device routing evidence: committee aggregations and
        # cold pubkey subgroup checks served by the MSM lane this run
        "firehose_agg_device_committees": int(agg_dev_cold),
        "firehose_subgroup_device_keys": int(sub_dev_cold),
        "firehose_p99_ingest_to_verified_s": round(hist.p99(), 4),
        "firehose_p50_ingest_to_verified_s": round(hist.p50(), 4),
        # attestations per device pairing check, measured across the steady
        # rounds (submitted members / dispatched collapsed entries)
        "firehose_collapse_ratio": round(submitted / max(dispatched, 1), 2),
        "firehose_queue_depth_peak": reg.gauge_value(
            "firehose_queue_depth_peak"),
        "firehose_deferrals": reg.counter_value("firehose_deferrals_total"),
        "firehose_counts": {k: counts[k] for k in (
            "committees", "committee_size", "atts_per_committee", "rounds")},
    }


def main():
    # standalone entry: mirror bench.py's lane setup (the persistent
    # compile cache keeps the pairing-kernel buckets from recompiling —
    # a single RLC bucket costs minutes of XLA time on CPU)
    from consensus_specs_tpu.utils.backend import enable_compile_cache, force_cpu

    force_cpu()
    enable_compile_cache()
    import bench

    r = run()
    record = {
        "metric": "firehose_atts_per_s_steady",
        "value": r["firehose_atts_per_s_steady"],
        "unit": "attestations/sec",
        "vs_baseline": None,
        "extra": r,
    }
    bench.persist_local(record)
    print(json.dumps(record))


if __name__ == "__main__":
    main()
