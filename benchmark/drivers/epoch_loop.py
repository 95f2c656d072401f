"""Traffic driver `epoch_loop`: epoch boundaries back to back on one node.

Each epoch of the loop is what a beacon node owes at an epoch boundary
once the epoch's blocks are in: the epoch transition, then the post-epoch
state root. On-device block processing does not exist yet, so before each
transition the epoch's participation flags are set from the seed, as the
epoch's attestations would have set them (each flag drawn per validator
with the traffic's probabilities). Without them the chain would leak from
its fifth empty epoch, a path no healthy node runs.

The program sees only what a node holds: a spec `BeaconState` built from
the seed (the configuration's `state` recipe), a `ResidentEpochEngine` over
it, and the participation columns. Set-up builds the state, the engine and
its first state root, then runs `warmup_epochs` loop epochs from flag
stream 0, which the window (stream 1) never reuses; the start epoch puts a
sync-committee rotation and a historical-roots append inside the warm-up.

The check replays every epoch the program ran, warm-up included, with the
same flags through the plain NumPy reference (benchmark/ref/epoch_altair),
and compares every column the epoch program writes and the state root
after the last epoch, and after `root_samples` epochs of the window drawn
from the seed.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark.ref import bls as bls_ref
from benchmark.ref import epoch_altair as ref

COLUMNS = ("balances", "effective_balance", "activation_eligibility_epoch",
           "activation_epoch", "exit_epoch", "withdrawable_epoch", "slashed",
           "prev_participation", "curr_participation", "inactivity_scores",
           "slashings")
REF_NAME = {"prev_participation": "previous_epoch_participation",
            "curr_participation": "current_epoch_participation"}


def _annotate(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


# --- the state, from the seed ---------------------------------------------------

def build_state(config: dict, start_epoch: int, seed: int) -> ref.EpochRefState:
    """The configuration's registry at the last slot of `start_epoch`."""
    c = ref.Spec(config["constants"])
    recipe = config["state"]
    n = int(config["validators"])
    rng = np.random.default_rng([seed, 1])
    far = np.uint64(2**64 - 1)

    sks = [int.from_bytes(rng.bytes(32), "big") % (bls_ref.R - 1) + 1
           for _ in range(recipe["key_pool"])]
    pool = np.frombuffer(b"".join(bls_ref.sk_to_pk(k) for k in sks),
                         np.uint8).reshape(-1, 48)
    creds = np.frombuffer(b"".join(b"\x00" + ref._sha(row.tobytes())[1:] for row in pool),
                          np.uint8).reshape(-1, 32)
    key_of = rng.integers(0, len(pool), n)

    balances = rng.integers(*recipe["balance_gwei"], n, dtype=np.uint64)
    eff = np.full(n, c.MAX_EFFECTIVE_BALANCE, np.uint64)
    withdrawable = np.full(n, far, np.uint64)
    slashed = np.zeros(n, bool)
    hit = rng.choice(n, max(1, int(n * recipe["slashed_share"])), replace=False)
    slashed[hit] = True
    withdrawable[hit] = start_epoch + c.EPOCHS_PER_SLASHINGS_VECTOR // 2
    low = rng.choice(n, max(1, int(n * recipe["ejected_share"])), replace=False)
    balances[low] = rng.integers(*recipe["ejected_balance_gwei"], len(low), dtype=np.uint64)
    eff[low] = c.EJECTION_BALANCE
    # an activation queue, as mainnet keeps one: eligible since before the
    # finalized checkpoint, activated at the churn limit each epoch
    pending = rng.choice(n, int(n * recipe["pending_share"]), replace=False)
    balances[pending] = c.MAX_EFFECTIVE_BALANCE
    eff[pending] = c.MAX_EFFECTIVE_BALANCE
    slashed[pending] = False
    withdrawable[pending] = far
    eligibility = np.zeros(n, np.uint64)
    eligibility[pending] = start_epoch - 8
    activation = np.zeros(n, np.uint64)
    activation[pending] = far

    def flags(p):
        u = rng.random((3, n))
        return ((u[0] < p[0]).astype(np.uint8) | (u[1] < p[1]).astype(np.uint8) << 1
                | (u[2] < p[2]).astype(np.uint8) << 2)

    def roots(k):
        return np.frombuffer(rng.bytes(32 * k), np.uint8).reshape(k, 32).copy()

    def committee():
        idx = rng.integers(0, n, c.SYNC_COMMITTEE_SIZE)
        agg = bls_ref.sk_to_pk(sum(sks[key_of[i]] for i in idx) % bls_ref.R)
        return pool[key_of[idx]].copy(), agg

    version = bytes.fromhex
    return ref.EpochRefState(
        genesis_time=1_606_824_023,
        genesis_validators_root=rng.bytes(32),
        slot=(start_epoch + 1) * c.SLOTS_PER_EPOCH - 1,
        fork=(version(c.GENESIS_FORK_VERSION[2:]), version(c.ALTAIR_FORK_VERSION[2:]), 0),
        latest_block_header=((start_epoch + 1) * c.SLOTS_PER_EPOCH - 2, int(rng.integers(0, n)),
                             rng.bytes(32), bytes(32), rng.bytes(32)),
        eth1_data=(rng.bytes(32), n, rng.bytes(32)),
        eth1_data_votes=[],
        eth1_deposit_index=n,
        historical_roots=[],
        justification_bits=np.zeros(4, bool),
        previous_justified=(start_epoch - 2, rng.bytes(32)),
        current_justified=(start_epoch - 1, rng.bytes(32)),
        finalized=(start_epoch - 2, rng.bytes(32)),
        current_sync_committee=committee(),
        next_sync_committee=committee(),
        pubkeys=pool[key_of],
        withdrawal_credentials=creds[key_of],
        effective_balance=eff,
        slashed=slashed,
        activation_eligibility_epoch=eligibility,
        activation_epoch=activation,
        exit_epoch=np.full(n, far, np.uint64),
        withdrawable_epoch=withdrawable,
        balances=balances,
        previous_epoch_participation=flags(recipe["participation_at_start"]),
        current_epoch_participation=flags(recipe["participation_at_start"]),
        inactivity_scores=rng.integers(0, recipe["inactivity_score_below"], n, dtype=np.uint64),
        block_roots=roots(c.SLOTS_PER_HISTORICAL_ROOT),
        state_roots=roots(c.SLOTS_PER_HISTORICAL_ROOT),
        randao_mixes=roots(c.EPOCHS_PER_HISTORICAL_VECTOR),
        slashings=rng.integers(0, recipe["slashings_below_gwei"], c.EPOCHS_PER_SLASHINGS_VECTOR,
                               dtype=np.uint64),
    )


def to_spec_state(spec, st: ref.EpochRefState):
    """The program's `BeaconState` holding the same values."""
    V = spec.Validator
    pk, wc = st.pubkeys.tobytes(), st.withdrawal_credentials.tobytes()
    validators = [
        V(pubkey=pk[48 * i:48 * i + 48], withdrawal_credentials=wc[32 * i:32 * i + 32],
          effective_balance=eb, slashed=sl, activation_eligibility_epoch=ae,
          activation_epoch=ac, exit_epoch=ex, withdrawable_epoch=wd)
        for i, (eb, sl, ae, ac, ex, wd) in enumerate(zip(
            st.effective_balance.tolist(), st.slashed.tolist(),
            st.activation_eligibility_epoch.tolist(), st.activation_epoch.tolist(),
            st.exit_epoch.tolist(), st.withdrawable_epoch.tolist()))]
    pv, cv, fe = st.fork
    hs, hp, hpr, hsr, hbr = st.latest_block_header

    def checkpoint(cp):
        return spec.Checkpoint(epoch=cp[0], root=cp[1])

    def committee(sc):
        keys, agg = sc
        return spec.SyncCommittee(pubkeys=[k.tobytes() for k in keys], aggregate_pubkey=agg)

    state = spec.BeaconState(
        genesis_time=st.genesis_time,
        genesis_validators_root=st.genesis_validators_root,
        slot=st.slot,
        fork=spec.Fork(previous_version=pv, current_version=cv, epoch=fe),
        latest_block_header=spec.BeaconBlockHeader(
            slot=hs, proposer_index=hp, parent_root=hpr, state_root=hsr, body_root=hbr),
        eth1_data=spec.Eth1Data(deposit_root=st.eth1_data[0], deposit_count=st.eth1_data[1],
                                block_hash=st.eth1_data[2]),
        eth1_deposit_index=st.eth1_deposit_index,
        historical_roots=st.historical_roots,
        validators=validators,
        justification_bits=[bool(b) for b in st.justification_bits],
        previous_justified_checkpoint=checkpoint(st.previous_justified),
        current_justified_checkpoint=checkpoint(st.current_justified),
        finalized_checkpoint=checkpoint(st.finalized),
        current_sync_committee=committee(st.current_sync_committee),
        next_sync_committee=committee(st.next_sync_committee),
    )
    state.balances = type(state.balances).from_values(st.balances.tolist())
    part = type(state.previous_epoch_participation)
    state.previous_epoch_participation = part.from_values(st.previous_epoch_participation.tolist())
    state.current_epoch_participation = part.from_values(st.current_epoch_participation.tolist())
    state.inactivity_scores = type(state.inactivity_scores).from_values(st.inactivity_scores.tolist())
    state.slashings = type(state.slashings).from_values(st.slashings.tolist())
    for name in ("block_roots", "state_roots", "randao_mixes"):
        vec = getattr(state, name)
        for i, row in enumerate(getattr(st, name)):
            vec[i] = row.tobytes()
    return state


def participation_fn(n: int, probs: dict):
    """A jitted (key, stream, epoch) -> (n,) uint8 flags: source, target and
    head each set with their probability, independently per validator."""
    import jax
    import jax.numpy as jnp

    p = jnp.asarray([probs["source"], probs["target"], probs["head"]], jnp.float32)

    @jax.jit
    def flags(key, stream, epoch):
        k = jax.random.fold_in(jax.random.fold_in(key, stream), epoch)
        u = jax.random.uniform(k, (3, n), jnp.float32)
        bits = (u < p[:, None]).astype(jnp.uint8)
        return bits[0] | (bits[1] << 1) | (bits[2] << 2)

    return flags


# --- the cell ---------------------------------------------------------------------

class ResidentProgram:
    """The timed path: the program's ResidentEpochEngine over the state."""

    def __init__(self, run, start: ref.EpochRefState):
        from consensus_specs_tpu.compiler import get_spec
        from consensus_specs_tpu.engine.resident import ResidentEpochEngine

        spec = get_spec(run.config["fork"], run.config["preset"])
        self.engine = ResidentEpochEngine(spec, to_spec_state(spec, start))

    def set_participation(self, flags) -> None:
        eng = self.engine
        eng.dev = eng.dev.replace(curr_participation=flags)

    def step(self) -> None:
        self.engine.step_epoch()

    def root(self) -> bytes:
        return self.engine.state_root()

    def outputs(self) -> dict:
        """What the program holds after its last epoch, then drops it."""
        dev = self.engine.dev
        out = {name: np.asarray(getattr(dev, name)) for name in COLUMNS}
        out["randao_mixes"] = np.ascontiguousarray(dev.randao_mixes, dtype=">u4").view(np.uint8)
        out["justification_bits"] = np.asarray(dev.justification_bits)
        out["checkpoint_epochs"] = np.asarray(
            [dev.prev_justified_epoch, dev.curr_justified_epoch, dev.finalized_epoch], np.uint64)
        out["slot"] = int(self.engine.state.slot)
        del self.engine, dev
        return out


class EpochLoop:
    WARMUP, WINDOW = 0, 1

    def __init__(self, run, program=ResidentProgram):
        import jax

        self.run = run
        self.c = ref.Spec(run.config["constants"])
        traffic = run.traffic
        t0 = time.monotonic()
        self.start = build_state(run.config, traffic["start_epoch"], run.seed)
        t1 = time.monotonic()
        self.program = program(run, self.start)
        t2 = time.monotonic()
        self.key = jax.random.fold_in(jax.random.key(run.seed % 2**32), run.seed // 2**32)
        self.flags = participation_fn(len(self.start.balances), traffic["participation"])
        self.applied: list = []  # (stream, epoch index) in the order run
        self.roots: list = []  # state root after each of them
        self.program.root()  # builds the device root trees
        t3 = time.monotonic()
        for k in range(traffic["warmup_epochs"]):
            self._epoch(self.WARMUP, k)
        run.log(f"set-up s: state {t1 - t0:.1f}, program {t2 - t1:.1f}, "
                f"first root {t3 - t2:.1f}, warm-up {time.monotonic() - t3:.1f}")
        self.window_epochs = 0
        self.attempted = self.failed = 0

    def _epoch(self, stream: int, k: int) -> None:
        with _annotate("bench.epoch.participation"):
            self.program.set_participation(self.flags(self.key, stream, k))
        with _annotate("bench.epoch.step_epoch"):
            self.program.step()
        with _annotate("bench.epoch.state_root"):
            self.roots.append(self.program.root())
        self.applied.append((stream, k))

    def window(self, t_end: float) -> None:
        k, times = 0, []
        while time.monotonic() < t_end:
            t0 = time.monotonic()
            self._epoch(self.WINDOW, k)
            times.append(time.monotonic() - t0)
            k += 1
        self.window_epochs = self.attempted = k
        self.run.work["epochs"] = k
        q = np.percentile(times, [0, 50, 100])
        self.run.log(f"window epoch s: min {q[0]:.4f}, median {q[1]:.4f}, max {q[2]:.4f}")

    def end_to_end(self, window_s: float) -> dict:
        return {"epoch_s": window_s / self.window_epochs}

    def release(self) -> None:
        """Read what the program produced, then drop its state."""
        import jax

        self.out = self.program.outputs()
        self.flag_rows = {sk: np.asarray(self.flags(self.key, *sk)) for sk in self.applied}
        del self.program
        jax.clear_caches()

    def check(self) -> dict:
        """Replay every epoch through the reference; compare."""
        c, st = self.c, self.start.copy()
        rng = np.random.default_rng([self.run.seed, 2])
        first = len(self.applied) - self.window_epochs
        samples = set(rng.integers(first, len(self.applied),
                                   self.run.traffic["root_samples"]).tolist())
        samples.add(len(self.applied) - 1)
        pk_points: dict = {}
        root_mismatches = 0
        for i, sk in enumerate(self.applied):
            st.current_epoch_participation = self.flag_rows[sk]
            ref.process_epoch(st, c, pk_points)
            st.slot += c.SLOTS_PER_EPOCH
            if i in samples:
                root_mismatches += int(ref.state_root(st, c) != self.roots[i])
        mismatched = 0
        for name in COLUMNS:
            mismatched += int((self.out[name] != getattr(st, REF_NAME.get(name, name))).sum())
        mismatched += int((self.out["randao_mixes"].reshape(st.randao_mixes.shape)
                           != st.randao_mixes).any(axis=1).sum())
        mismatched += int((self.out["justification_bits"] != st.justification_bits).sum())
        ref_epochs = np.asarray([st.previous_justified[0], st.current_justified[0],
                                 st.finalized[0]], np.uint64)
        mismatched += int((self.out["checkpoint_epochs"] != ref_epochs).sum())
        mismatched += int(self.out["slot"] != st.slot)
        return {"values_differing": {"value": mismatched, "limit": 0},
                "state_roots_differing": {"value": root_mismatches, "limit": 0}}


def setup(run) -> EpochLoop:
    return EpochLoop(run)
