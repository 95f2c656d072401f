"""Bring-up smoke test: the system's main path once, on one TPU v5e chip.

Run from the repo root: `python chip_smoke.py`. It imports
consensus_specs_tpu the way a user does, fails (non-zero exit, no result
line) unless `jax.devices()[0]` is a TPU, and runs four phases in order:

  a  the resident epoch engine at BASELINE config 4 (mainnet preset,
     altair, 1,048,576 validators): bridge-in, `step_epoch` x3,
     `run_epochs(2)`, the device state root, `materialize()`, and the
     device root checked against the host SSZ tree;
  b  epoch math against the spec: one epoch through
     `bridge.apply_epoch_via_engine` on a 16,384-validator registry with
     scrambled balances, participation, inactivity scores, slashings and
     ejections, compared bit-exactly with the compiled spec's
     `process_epoch` (the check that u64 emulation on the TPU gives the
     spec's answers);
  c  the attestation BLS flush at mainnet width: one slot of firehose
     traffic (64 committees of 488 members, 2 aggregates of 244 each, two
     of them forged) through `bls.use_jax()` -> `AttestationFirehose` ->
     the default sched scheduler -> the grouped RLC kernel, cross-checked
     against the pure-Python oracle; then bench.py's batch-2048 pairing
     check through `pairing_check_batch` and the RLC kernel, valid and
     tampered;
  d  no silent degradation: zero degraded dispatches, empty breaker logs,
     zero host-path BLS dispatches.

Each phase prints one JSON progress line: wall seconds, the compiles that
obs.recompile's CompileTracker counted and their backend seconds (per
program where one took a second or more), the device's `peak_bytes_in_use`
where the backend reports it, and the host's peak RSS. The last line is
`{"ok": true, "device": {...}}`. Any failed check or exception exits
non-zero.

XLA compiles dominate a cold run (some 1,600 s of them one after another
on the chip's host), so phase c's BLS programs are compiled ahead: one
worker thread compiles bench.py's batch through the RLC kernel, then the
flush's pairing programs on a synthetic slot of phase c's shapes, while
the main thread runs phases a and b; the main thread then compiles the
device pubkey aggregation on phase c's own traffic, and
`pairing_check_batch`. The worker's compiles therefore show in those
phases' windows; phase c starts once the warm-up is done, and clears
every host crypto cache first, so it still runs the cold path.
The persistent compile cache lives where JAX_COMPILATION_CACHE_DIR says,
else at <repo>/.jax_cache (utils.backend.enable_compile_cache).

`--chips 4` runs only the four-chip paths and what they are compared
with: the 1M-registry epoch sharded over a 4-device mesh against the
one-chip program, and the mesh-sharded grouped RLC flush on phase c's
traffic against the one-chip kernel. `--rehearse` runs the same phases at
small sizes on whatever JAX finds (the CPU in a sandbox): the only way
past the TPU check, and its last line names the platform it ran on.
"""
from __future__ import annotations

import argparse
import contextlib
import ctypes
import json
import random
import resource
import sys
import time
from concurrent.futures import ThreadPoolExecutor

FULL = dict(validators=1 << 20, spec_validators=16_384, committees=64,
            committee_size=488, aggregates=2, batch=2048)
REHEARSE = dict(validators=4096, spec_validators=2048, committees=8,
                committee_size=64, aggregates=2, batch=16)
RESIDENT_STEPS = 3
SCAN_EPOCHS = 2
# (committee, aggregate) pairs whose signers sign the next committee's
# message: valid signatures over another message
FORGED = ((1, 0), (2, 1))
SEED = 21


class SmokeFailure(Exception):
    """A result the chip gave differs from what it must be."""


def check(ok, what) -> None:
    """Like `assert`, but kept under `python -O`."""
    if not ok:
        raise SmokeFailure(what)


class Phases:
    """Runs named phases in order and prints one JSON line per phase."""

    def __init__(self, jax, tracker):
        self.jax = jax
        self.tracker = tracker

    def run(self, name, fn, *args):
        n0 = sum(self.tracker.kernels().values())
        s0 = self.tracker.kernel_seconds()
        t0 = time.time()
        details = fn(*args)
        seconds = time.time() - t0
        release_host_memory()
        spent = {k: v - s0.get(k, 0.0)
                 for k, v in self.tracker.kernel_seconds().items()}
        stats = self.jax.devices()[0].memory_stats() or {}
        print(json.dumps({
            "phase": name, "seconds": round(seconds, 3),
            "compiles": sum(self.tracker.kernels().values()) - n0,
            "compile_s": round(sum(spent.values()), 3),
            # programs whose compiles finished in this window, any thread
            "slow_compiles_s": {k: round(v, 1) for k, v in spent.items()
                                if v >= 1.0},
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "host_peak_rss_gb": round(resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss / 2**20, 2),
            **details}), flush=True)


def release_host_memory() -> None:
    """Hand the memory that finished compiles freed back to the OS. glibc
    keeps it in per-thread arenas otherwise: a deviceless compile of the
    grouped RLC program still held 16 GB after it returned (4.7 GB after
    malloc_trim), and two threads compiling in turn ran the 40 GiB
    one-chip host out of memory (PR 21)."""
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):  # not glibc: nothing to trim
        pass


def timed(fn, *args) -> float:
    """Seconds `fn(*args)` took; frees host memory afterwards."""
    t0 = time.time()
    fn(*args)
    release_host_memory()
    return round(time.time() - t0, 3)


def _mainnet_spec():
    from consensus_specs_tpu.compiler import get_spec

    return get_spec("altair", "mainnet")


def _epoch_end_slot(spec) -> int:
    # Last slot of epoch 100: (epoch + 1) stays clear of the sync-committee
    # period boundary, so rotation (which needs real G1 pubkeys) never runs
    # on the synthetic registry — the benches/epoch_e2e_bench.py choice.
    return int(spec.SLOTS_PER_EPOCH) * 101 - 1


# --- phase a: the resident epoch engine at 1M ---------------------------------


def phase_resident(spec, n):
    import jax

    from consensus_specs_tpu.engine.resident import ResidentEpochEngine
    from consensus_specs_tpu.ssz import hash_tree_root
    from consensus_specs_tpu.testlib.big_state import synthetic_beacon_state

    state = synthetic_beacon_state(spec, n, slot=_epoch_end_slot(spec))
    eng = ResidentEpochEngine(spec, state)
    for _ in range(RESIDENT_STEPS):
        eng.step_epoch()
    eng.run_epochs(SCAN_EPOCHS)
    jax.block_until_ready(eng.dev.balances)
    device_root = eng.state_root()
    moved = eng.materialize()
    host_root = bytes(hash_tree_root(state))
    check(device_root == host_root,
          f"device root {device_root.hex()} != host root {host_root.hex()}")
    epochs = RESIDENT_STEPS + SCAN_EPOCHS
    end_slot = _epoch_end_slot(spec) + epochs * int(spec.SLOTS_PER_EPOCH)
    check(int(state.slot) == end_slot, f"slot {state.slot} != {end_slot}")
    return {"validators": n, "epochs": epochs,
            "root": "0x" + host_root.hex(),
            "materialize_moved_bytes": moved["moved_bytes"]}


# --- phase b: epoch math against the spec -------------------------------------


def scrambled_state(spec, n, seed):
    """A `synthetic_beacon_state` with the per-validator columns scrambled
    (the testlib `prepared_epoch_state` recipe at registry scale): random
    balances and inactivity scores, participation biased so the target
    vote justifies, ~1% slashed at the slashings-penalty epoch, ~1% below
    the ejection balance, and a filled slashings vector."""
    from consensus_specs_tpu.testlib.big_state import synthetic_beacon_state

    state = synthetic_beacon_state(spec, n, slot=_epoch_end_slot(spec))
    rng = random.Random(seed)
    epoch = int(spec.get_current_epoch(state))
    half = int(spec.EPOCHS_PER_SLASHINGS_VECTOR) // 2
    gwei = int(spec.EFFECTIVE_BALANCE_INCREMENT)

    def flags():  # source and target set with p=0.85, head with p=0.5
        r = rng.random
        return int(r() < 0.85) | int(r() < 0.85) << 1 | int(r() < 0.5) << 2

    balances = [rng.randrange(16 * gwei, 40 * gwei) for _ in range(n)]
    for i in rng.sample(range(n), max(1, n // 100)):
        state.validators[i].slashed = True
        state.validators[i].withdrawable_epoch = epoch + half
    for i in rng.sample(range(n), max(1, n // 100)):
        balances[i] = rng.randrange(8 * gwei, 16 * gwei)
        state.validators[i].effective_balance = 16 * gwei
    state.balances = type(state.balances).from_values(balances)
    part = type(state.previous_epoch_participation)
    state.previous_epoch_participation = part.from_values(
        [flags() for _ in range(n)])
    state.current_epoch_participation = part.from_values(
        [flags() for _ in range(n)])
    state.inactivity_scores = type(state.inactivity_scores).from_values(
        [rng.randrange(0, 100) for _ in range(n)])
    for i in range(len(state.slashings)):
        state.slashings[i] = rng.randrange(0, 64 * gwei)
    return state


@contextlib.contextmanager
def reference_caches(spec):
    """The compiled spec recomputes `get_total_active_balance` and
    `get_unslashed_participating_indices` on every call, which makes
    `process_epoch` quadratic in the registry (45 s at 4,096 validators on
    a sandbox CPU, some 12 minutes at 16,384). Memoize both for the
    reference run, keyed as the reference pyspec's own `cache_this` keys
    them: on the hash tree roots of what they read and the current epoch,
    so a cached value is always the value the function would compute."""
    from consensus_specs_tpu.ssz import hash_tree_root

    def reads(state):
        return (int(spec.get_current_epoch(state)),
                hash_tree_root(state.validators),
                hash_tree_root(state.previous_epoch_participation),
                hash_tree_root(state.current_epoch_participation))

    saved = {}
    for name in ("get_total_active_balance",
                 "get_unslashed_participating_indices"):
        fn = saved[name] = getattr(spec, name)

        def memoized(state, *args, fn=fn, memo={}):
            key = (reads(state), *map(int, args))
            if key not in memo:
                memo[key] = fn(state, *args)
            return memo[key]

        setattr(spec, name, memoized)
    try:
        yield
    finally:
        for name, fn in saved.items():
            setattr(spec, name, fn)


def phase_spec_epoch(spec, n):
    from consensus_specs_tpu.engine import bridge
    from consensus_specs_tpu.ssz import hash_tree_root

    state = scrambled_state(spec, n, SEED)
    ref = state.copy()
    bridge.apply_epoch_via_engine(spec, state)
    t0 = time.time()
    with reference_caches(spec):
        spec.process_epoch(ref)
    spec_s = time.time() - t0
    check(list(state.balances) == list(ref.balances), "balances diverge")
    check(list(state.inactivity_scores) == list(ref.inactivity_scores),
          "inactivity scores diverge")
    root, ref_root = bytes(hash_tree_root(state)), bytes(hash_tree_root(ref))
    check(root == ref_root, f"state root {root.hex()} != spec {ref_root.hex()}")
    return {"validators": n, "root": "0x" + root.hex(),
            "spec_process_epoch_s": round(spec_s, 3)}


# --- phase c: the attestation BLS flush ---------------------------------------


def firehose_counts(sizes):
    return {"committees": sizes["committees"],
            "committee_size": sizes["committee_size"],
            "atts_per_committee": sizes["aggregates"]}


def warm_flush_kernels(sizes):
    """Compile the flush's pairing programs at phase c's shapes (as many
    items as the slot has aggregates, one distinct message per committee)
    by verifying a synthetic slot: the grouped RLC kernel, and, since one
    item carries another committee's signature, the per-item pass that
    attributes it. No pubkey aggregation, so this needs no other program
    first."""
    import numpy as np

    from consensus_specs_tpu.crypto import bls_jax, bls_sig

    sk, committees = 7, sizes["committees"]
    pk = bls_sig.SkToPk(sk)
    messages = [b"warm slot root %04d" % c for c in range(committees)]
    sigs = [bls_sig.Sign(sk, m) for m in messages]
    n = committees * sizes["aggregates"]
    checks = [bls_jax.make_verify_check(
        pk, messages[i % committees], sigs[(i + (i == 0)) % committees])
        for i in range(n)]
    ok = bls_jax.run_checks(checks)
    check(list(np.flatnonzero(~ok)) == [0], "warm-up slot verdicts")


def warm_aggregation(sizes):
    """Compile the device pubkey aggregation (batched subgroup check and
    reduction tree) for phase c's traffic, in the order the flush meets
    its cold keys."""
    from benches.firehose_bench import build_traffic
    from consensus_specs_tpu.crypto import bls_jax

    payloads, pk_table, messages = build_traffic(
        firehose_counts(sizes), forge=FORGED)
    for key, raw in zip(sorted(pk_table), payloads):
        bls_jax.make_fast_aggregate_check(
            list(pk_table[key]), messages[key[0]], raw[8:])


def warm_batch(sizes, kernel):
    """Compile bench.py's batch through `pairing_check_batch` ("batch") or
    the RLC kernel ("rlc")."""
    import jax

    from consensus_specs_tpu.crypto import bls_jax
    from consensus_specs_tpu.ops import bls12_jax as K

    args = bls_jax.bench_pairing_args(sizes["batch"])
    if kernel == "rlc":
        out = K.pairing_check_rlc(*args, bls_jax.random_zbits(sizes["batch"]),
                                  p2_is_neg_g1=True)
    else:
        out = K.pairing_check_batch(*args)
    jax.block_until_ready(out)


def phase_bls(sizes):
    import struct

    import jax
    import numpy as np

    from benches.firehose_bench import build_traffic, make_classifier
    from consensus_specs_tpu import sched
    from consensus_specs_tpu.crypto import bls, bls_jax, bls_sig
    from consensus_specs_tpu.firehose import AttestationFirehose, FirehoseConfig
    from consensus_specs_tpu.ops import bls12_jax as K
    from consensus_specs_tpu.parallel.gossip_driver import message_id

    t0 = time.time()
    payloads, pk_table, messages = build_traffic(
        firehose_counts(sizes), forge=FORGED)
    prep_s = time.time() - t0
    bls.clear_caches()  # the cold path: nothing decompressed or aggregated
    bls.use_jax()
    n = len(payloads)
    fh = AttestationFirehose(
        make_classifier(pk_table, messages),
        scheduler=sched.default_scheduler(),
        config=FirehoseConfig(batch_attestations=n, max_pending=n,
                              flush_deadline_s=30.0))
    with fh:
        fh.offer_many(payloads)
        fh.drain(timeout_s=900.0)
    results = fh.results()
    verdicts = {struct.unpack_from("<II", raw): results[message_id(raw)]
                for raw in payloads}
    forged = set(FORGED)
    check(len(verdicts) == n, f"lost verdicts: {len(verdicts)}/{n}")
    check(all(ok for key, ok in verdicts.items() if key not in forged),
          "an honest aggregate was rejected")
    check(not any(verdicts[key] for key in forged), "a forgery was accepted")

    honest = [key for key in verdicts if key not in forged][:2]
    for key in list(FORGED) + honest:
        c, s = key
        sig = payloads[c * sizes["aggregates"] + s][8:]
        oracle = bls_sig.FastAggregateVerify(list(pk_table[key]), messages[c], sig)
        check(oracle == verdicts[key], f"oracle disagrees on {key}")

    # bench.py's headline batch, valid then with two signatures swapped
    args = bls_jax.bench_pairing_args(sizes["batch"])
    zbits = bls_jax.random_zbits(sizes["batch"])
    per_item = np.asarray(K.pairing_check_batch(*args))
    rlc = bool(K.pairing_check_rlc(*args, zbits, p2_is_neg_g1=True))
    check(per_item.all() and rlc, "valid batch rejected")
    qx, qy, px, py, q2x, q2y, p2x, p2y = args
    swap = np.arange(sizes["batch"])
    swap[[0, 1]] = swap[[1, 0]]
    q2x_bad, q2y_bad = (tuple(jax.numpy.asarray(np.asarray(c)[swap]) for c in q)
                        for q in (q2x, q2y))
    bad = (qx, qy, px, py, q2x_bad, q2y_bad, p2x, p2y)
    per_item_bad = np.asarray(K.pairing_check_batch(*bad))
    rlc_bad = bool(K.pairing_check_rlc(*bad, zbits, p2_is_neg_g1=True))
    check(list(np.flatnonzero(~per_item_bad)) == [0, 1],
          f"tampered batch: rejected items {np.flatnonzero(~per_item_bad)}")
    check(not rlc_bad, "RLC accepted a tampered batch")
    return {"aggregates": n, "forged_rejected": len(forged),
            "oracle_checked": len(forged) + len(honest),
            "traffic_prep_s": round(prep_s, 3),
            "batch": sizes["batch"]}


# --- phase d: no silent degradation -------------------------------------------


def phase_degradation(registry):
    from consensus_specs_tpu import sched
    from consensus_specs_tpu.engine import bridge

    sch = sched.default_scheduler()
    degraded = registry.counters_matching("sched_degraded_total")
    epoch_degraded = registry.counter_value("epoch_degraded_total")
    breaker_events = {
        brk.name: list(brk.events)
        for brk in [bridge.device_breaker()]
        + [sch.breaker(name) for name in sch.classes]}
    host_bls = registry.counter_value(
        "sched_dispatch_total", work_class="bls", path="host")
    device_bls = registry.counter_value(
        "sched_dispatch_total", work_class="bls", path="device")
    check(not any(degraded.values()), f"degraded dispatches: {degraded}")
    check(epoch_degraded == 0, f"{epoch_degraded} degraded epochs")
    check(not any(breaker_events.values()), f"breaker events: {breaker_events}")
    check(host_bls == 0, f"{host_bls} host-path BLS dispatches")
    check(device_bls > 0, "no BLS dispatch reached the device")
    return {"device_bls_dispatches": device_bls,
            "retries": registry.counters_matching("retries_total")}


# --- four chips -----------------------------------------------------------------


def _check_spans(x, n_devices):
    check(len(x.sharding.device_set) == n_devices, x.sharding)
    shard_devices = {s.device for s in x.addressable_shards}
    check(len(shard_devices) == n_devices, shard_devices)


def four_chip_paths(spec, sizes):
    """The validator-sharded epoch and the mesh-sharded grouped RLC flush,
    each against its one-chip program on the same input. The four
    programs compile concurrently, the epoch pair while the main thread
    packs the slot."""
    import jax
    import numpy as np

    from benches.firehose_bench import build_traffic
    from consensus_specs_tpu.crypto import bls_jax
    from consensus_specs_tpu.engine.epoch import make_epoch_fn
    from consensus_specs_tpu.engine.state import EpochConfig
    from consensus_specs_tpu.engine.synthetic import synthetic_epoch_state
    from consensus_specs_tpu.ops import bls12_jax as K
    from consensus_specs_tpu.parallel.collectives import (
        pairing_check_rlc_grouped_mesh,
    )
    from consensus_specs_tpu.parallel.mesh import (
        epoch_state_shardings,
        make_mesh,
        shard_epoch_state,
    )

    check(len(jax.devices()) >= 4, f"need 4 devices, have {jax.devices()}")
    mesh = make_mesh(jax.devices()[:4])
    cfg = EpochConfig.from_spec(spec)
    state = synthetic_epoch_state(cfg, n=sizes["validators"], seed=SEED)
    sharded = shard_epoch_state(state, mesh)
    _check_spans(sharded.balances, 4)
    fn = make_epoch_fn(cfg, with_jit=False)
    shardings = epoch_state_shardings(mesh)
    one_chip = jax.jit(fn)
    on_mesh = jax.jit(fn, in_shardings=(shardings,),
                      out_shardings=(shardings, None))

    def packed(forge):
        payloads, pk_table, messages = build_traffic(
            firehose_counts(sizes), forge=forge)
        checks = [bls_jax.make_fast_aggregate_check(
            list(pk_table[(c, s)]), messages[c], raw[8:])
            for (c, s), raw in zip(sorted(pk_table), payloads)]
        _, _, args, seg_ids = bls_jax._pack_grouped_args(
            [q.p1 for q in checks], [q.q1 for q in checks],
            [q.q2 for q in checks])
        return args, seg_ids

    def rlc_one_chip(args, seg_ids):
        return bool(K.pairing_check_rlc(*args, None, None, zbits,
                                        p2_is_neg_g1=True, seg_ids=seg_ids))

    def rlc_mesh(args, seg_ids):
        out = pairing_check_rlc_grouped_mesh(mesh, *args, zbits, seg_ids)
        _check_spans(out, 4)
        return bool(out)

    t0 = time.time()
    with ThreadPoolExecutor(4) as pool:
        epoch_jobs = [pool.submit(one_chip, state),
                      pool.submit(on_mesh, sharded)]
        traffic = {"forged": packed(FORGED), "honest": packed(())}
        prep_s = time.time() - t0
        zbits = bls_jax.random_zbits(traffic["honest"][1].shape[0])
        rlc_jobs = [pool.submit(rlc_one_chip, *traffic["forged"]),
                    pool.submit(rlc_mesh, *traffic["forged"])]
        (out1, aux1), (out4, aux4) = (j.result() for j in epoch_jobs)
        single_forged, mesh_forged = (j.result() for j in rlc_jobs)
    first_s = time.time() - t0
    _check_spans(out4.balances, 4)
    for name in out1.__dataclass_fields__:
        check(np.array_equal(np.asarray(getattr(out1, name)),
                             np.asarray(getattr(out4, name))),
              f"sharded epoch diverges in {name}")
    for name in aux1.__dataclass_fields__:
        check(np.array_equal(np.asarray(getattr(aux1, name)),
                             np.asarray(getattr(aux4, name))),
              f"sharded epoch aux diverges in {name}")
    check(not single_forged and not mesh_forged,
          f"forged slot accepted: one chip {single_forged}, mesh {mesh_forged}")
    honest = rlc_one_chip(*traffic["honest"]), rlc_mesh(*traffic["honest"])
    check(all(honest), f"honest slot rejected (one chip, mesh): {honest}")
    return {"validators": sizes["validators"],
            "aggregates": int(traffic["honest"][1].shape[0]),
            "traffic_prep_s": round(prep_s, 3),
            "compile_and_first_run_s": round(first_s, 3)}


# --- entry point ----------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="small sizes on whatever JAX finds (no TPU check)")
    opts = ap.parse_args(argv)

    from consensus_specs_tpu.obs import metrics as obs_metrics
    from consensus_specs_tpu.obs import recompile as obs_recompile
    from consensus_specs_tpu.utils.backend import enable_compile_cache

    jax = enable_compile_cache()
    device = jax.devices()[0]
    if device.platform != "tpu" and not opts.rehearse:
        print(f"chip_smoke: no TPU (JAX found {device.platform!r})",
              file=sys.stderr)
        return 1
    sizes = REHEARSE if opts.rehearse else FULL
    # Import the main path before the warm-up thread starts: the imports
    # switch jax_enable_x64 on, and two threads must not race them.
    import benches.firehose_bench  # noqa: F401
    from consensus_specs_tpu.crypto import bls_jax  # noqa: F401
    from consensus_specs_tpu.engine import resident  # noqa: F401
    tracker = obs_recompile.CompileTracker(registry=obs_metrics.REGISTRY)
    phases = Phases(jax, tracker.install())
    spec = _mainnet_spec()
    if opts.chips == 4:
        phases.run("four_chips", four_chip_paths, spec, sizes)
    else:
        with ThreadPoolExecutor(1) as pool:
            # one pairing compile at a time on the worker: each holds
            # 10-20 GB of host memory while it runs
            rlc = pool.submit(timed, warm_batch, sizes, "rlc")
            flush = pool.submit(timed, warm_flush_kernels, sizes)
            phases.run("a_resident_epochs", phase_resident, spec,
                       sizes["validators"])
            phases.run("b_spec_epoch", phase_spec_epoch, spec,
                       sizes["spec_validators"])
            warm = {"aggregation": timed(warm_aggregation, sizes),
                    "pairing_check_batch": timed(warm_batch, sizes, "batch")}
            warm.update(pairing_check_rlc=rlc.result(), flush=flush.result())
            print(json.dumps({"warmup_s": warm}), flush=True)
        phases.run("c_bls_flush", phase_bls, sizes)
        phases.run("d_no_degradation", phase_degradation, obs_metrics.REGISTRY)
    tracker.uninstall()
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
