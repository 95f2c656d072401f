"""Plain altair `process_epoch` and `hash_tree_root(BeaconState)` in NumPy.

Written for the benchmark from specs/altair/beacon-chain.md (and the
phase0 functions it inherits), imported by nothing in the program. The
state is `EpochRefState`: one NumPy column per validator field, the small
vectors as arrays, and the host-side fields as plain values. Every
function follows the spec's order and integer arithmetic (uint64, floor
division); the loops the spec writes per validator are whole-column
operations, except the exit queue, which stays a loop because each exit
moves the queue for the next.

`reward_dtype` exists for the control only: the control computes the
rewards, the penalties and the balances they update in float32, the
precision a TPU program is tempted to use in place of emulated uint64,
and must come out wrong.
"""
from __future__ import annotations

import dataclasses
import hashlib

import numpy as np

from . import bls as bls_ref
from . import ssz

FAR_FUTURE = np.uint64(2**64 - 1)
U64 = np.uint64


@dataclasses.dataclass
class EpochRefState:
    # host-side scalars and containers
    genesis_time: int
    genesis_validators_root: bytes
    slot: int
    fork: tuple  # (previous_version 4 B, current_version 4 B, epoch)
    latest_block_header: tuple  # (slot, proposer_index, parent_root, state_root, body_root)
    eth1_data: tuple  # (deposit_root, deposit_count, block_hash)
    eth1_data_votes: list
    eth1_deposit_index: int
    historical_roots: list
    justification_bits: np.ndarray  # (4,) bool
    previous_justified: tuple  # (epoch, root)
    current_justified: tuple
    finalized: tuple
    current_sync_committee: tuple  # ((512, 48) uint8 pubkeys, 48-byte aggregate)
    next_sync_committee: tuple
    # per-validator columns
    pubkeys: np.ndarray  # (n, 48) uint8
    withdrawal_credentials: np.ndarray  # (n, 32) uint8
    effective_balance: np.ndarray
    slashed: np.ndarray
    activation_eligibility_epoch: np.ndarray
    activation_epoch: np.ndarray
    exit_epoch: np.ndarray
    withdrawable_epoch: np.ndarray
    balances: np.ndarray
    previous_epoch_participation: np.ndarray  # uint8
    current_epoch_participation: np.ndarray
    inactivity_scores: np.ndarray
    # vectors
    block_roots: np.ndarray  # (8192, 32) uint8
    state_roots: np.ndarray
    randao_mixes: np.ndarray  # (65536, 32) uint8
    slashings: np.ndarray  # (8192,) uint64

    def copy(self) -> "EpochRefState":
        out = {}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            out[f.name] = v.copy() if isinstance(v, (np.ndarray, list)) else v
        return EpochRefState(**out)


class Spec:
    """The constants of one configuration file (its `constants` object)."""

    def __init__(self, constants: dict):
        for k, v in constants.items():
            setattr(self, k, v)


def _sha(data: bytes) -> bytes:
    return hashlib.sha256(data).digest()


# --- helpers (phase0 / altair beacon-chain.md) ----------------------------------

def current_epoch(st, c) -> int:
    return st.slot // c.SLOTS_PER_EPOCH


def previous_epoch(st, c) -> int:
    e = current_epoch(st, c)
    return e - 1 if e > 0 else 0


def active_mask(st, epoch: int) -> np.ndarray:
    e = U64(epoch)
    return (st.activation_epoch <= e) & (e < st.exit_epoch)


class Masks:
    """The per-epoch masks and totals process_epoch reads, computed once.
    Within one process_epoch they cannot change: activations and exits the
    registry update schedules take effect at current_epoch + 5 or later, and
    effective balances move only after the last reader (process_slashings)."""

    def __init__(self, st, c):
        cur, prev = current_epoch(st, c), previous_epoch(st, c)
        self.active_cur = active_mask(st, cur)
        self.active_prev = self.active_cur if prev == cur else active_mask(st, prev)
        self.total_active = total_balance(st, c, self.active_cur)
        unslashed = ~st.slashed
        self.prev_flags = [self.active_prev & unslashed
                           & ((st.previous_epoch_participation >> np.uint8(f)) & np.uint8(1) == 1)
                           for f in range(len(c.PARTICIPATION_FLAG_WEIGHTS))]
        self.cur_target = (self.active_cur & unslashed
                           & ((st.current_epoch_participation
                               >> np.uint8(c.TIMELY_TARGET_FLAG_INDEX)) & np.uint8(1) == 1))
        self.eligible = self.active_prev | (st.slashed & (U64(prev + 1) < st.withdrawable_epoch))


def total_balance(st, c, mask) -> int:
    return max(c.EFFECTIVE_BALANCE_INCREMENT, int(st.effective_balance.sum(where=mask, dtype=np.uint64)))


def block_root(st, c, epoch: int) -> bytes:
    slot = epoch * c.SLOTS_PER_EPOCH
    assert slot < st.slot <= slot + c.SLOTS_PER_HISTORICAL_ROOT
    return st.block_roots[slot % c.SLOTS_PER_HISTORICAL_ROOT].tobytes()


def is_in_inactivity_leak(st, c) -> bool:
    return previous_epoch(st, c) - st.finalized[0] > c.MIN_EPOCHS_TO_INACTIVITY_PENALTY


def integer_squareroot(n: int) -> int:
    x, y = n, (n + 1) // 2
    while y < x:
        x, y = y, (y + n // y) // 2
    return x


def activation_exit_epoch(epoch: int, c) -> int:
    return epoch + 1 + c.MAX_SEED_LOOKAHEAD


def decrease(balances: np.ndarray, delta: np.ndarray) -> np.ndarray:
    """decrease_balance: saturates at 0."""
    return balances - np.minimum(delta, balances)


# --- process_epoch --------------------------------------------------------------

def justification_and_finalization(st, c, m: Masks) -> None:
    cur = current_epoch(st, c)
    if cur <= 1:
        return
    prev = previous_epoch(st, c)
    total = m.total_active
    prev_target = total_balance(st, c, m.prev_flags[c.TIMELY_TARGET_FLAG_INDEX])
    cur_target = total_balance(st, c, m.cur_target)
    old_prev, old_cur = st.previous_justified, st.current_justified
    st.previous_justified = st.current_justified
    bits = st.justification_bits
    bits[1:] = bits[:-1].copy()
    bits[0] = False
    if prev_target * 3 >= total * 2:
        st.current_justified = (prev, block_root(st, c, prev))
        bits[1] = True
    if cur_target * 3 >= total * 2:
        st.current_justified = (cur, block_root(st, c, cur))
        bits[0] = True
    if bits[1:4].all() and old_prev[0] + 3 == cur:
        st.finalized = old_prev
    if bits[1:3].all() and old_prev[0] + 2 == cur:
        st.finalized = old_prev
    if bits[0:3].all() and old_cur[0] + 2 == cur:
        st.finalized = old_cur
    if bits[0:2].all() and old_cur[0] + 1 == cur:
        st.finalized = old_cur


def inactivity_updates(st, c, m: Masks) -> None:
    if current_epoch(st, c) == 0:
        return
    part = m.prev_flags[c.TIMELY_TARGET_FLAG_INDEX]
    s = st.inactivity_scores
    s = s - (m.eligible & part) * np.minimum(U64(1), s)
    s = s + (m.eligible & ~part) * U64(c.INACTIVITY_SCORE_BIAS)
    if not is_in_inactivity_leak(st, c):
        s = s - m.eligible * np.minimum(U64(c.INACTIVITY_SCORE_RECOVERY_RATE), s)
    st.inactivity_scores = s


def rewards_and_penalties(st, c, m: Masks, reward_dtype=np.uint64) -> None:
    """A flag's reward and penalty depend on the validator only through its
    effective balance in increments, so each is a table over increments
    (0..MAX_EFFECTIVE_BALANCE / increment), computed in `reward_dtype`."""
    if current_epoch(st, c) == 0:
        return
    total = m.total_active
    per_increment = c.EFFECTIVE_BALANCE_INCREMENT * c.BASE_REWARD_FACTOR // integer_squareroot(total)
    active_increments = total // c.EFFECTIVE_BALANCE_INCREMENT
    dt = reward_dtype
    incr = (st.effective_balance // U64(c.EFFECTIVE_BALANCE_INCREMENT)).astype(np.intp)
    base = np.arange(c.MAX_EFFECTIVE_BALANCE // c.EFFECTIVE_BALANCE_INCREMENT + 1).astype(dt) * dt(per_increment)
    leak = is_in_inactivity_leak(st, c)
    elig = m.eligible
    b = st.balances.astype(dt)
    for flag, weight in enumerate(c.PARTICIPATION_FLAG_WEIGHTS):
        part = m.prev_flags[flag]
        if not leak:
            part_increments = total_balance(st, c, part) // c.EFFECTIVE_BALANCE_INCREMENT
            reward = (base * dt(weight) * dt(part_increments)
                      // dt(active_increments * c.WEIGHT_DENOMINATOR))
            b = b + (elig & part) * reward[incr]
        if flag != c.TIMELY_HEAD_FLAG_INDEX:
            penalty = base * dt(weight) // dt(c.WEIGHT_DENOMINATOR)
            b = decrease(b, (elig & ~part) * penalty[incr])
    missed = elig & ~m.prev_flags[c.TIMELY_TARGET_FLAG_INDEX]
    num = st.effective_balance.astype(dt) * st.inactivity_scores.astype(dt)
    den = dt(c.INACTIVITY_SCORE_BIAS * c.INACTIVITY_PENALTY_QUOTIENT_ALTAIR)
    st.balances = decrease(b, missed * (num // den)).astype(np.uint64)


def initiate_exits(st, c, m: Masks, indices) -> None:
    """initiate_validator_exit for each index in order: each exit joins the
    queue after the ones before it."""
    indices = [i for i in indices if st.exit_epoch[i] == FAR_FUTURE]
    if not indices:
        return
    cur = current_epoch(st, c)
    churn = max(c.MIN_PER_EPOCH_CHURN_LIMIT, int(m.active_cur.sum()) // c.CHURN_LIMIT_QUOTIENT)
    epochs, counts = np.unique(st.exit_epoch[st.exit_epoch != FAR_FUTURE], return_counts=True)
    count = dict(zip(epochs.tolist(), counts.tolist()))
    queue = max(list(count) + [activation_exit_epoch(cur, c)])
    for i in indices:
        if count.get(queue, 0) >= churn:
            queue += 1
        st.exit_epoch[i] = U64(queue)
        st.withdrawable_epoch[i] = U64(queue + c.MIN_VALIDATOR_WITHDRAWABILITY_DELAY)
        count[queue] = count.get(queue, 0) + 1


def registry_updates(st, c, m: Masks) -> None:
    cur = current_epoch(st, c)
    queue_eligible = ((st.activation_eligibility_epoch == FAR_FUTURE)
                      & (st.effective_balance == U64(c.MAX_EFFECTIVE_BALANCE)))
    st.activation_eligibility_epoch[queue_eligible] = U64(cur + 1)
    eject = m.active_cur & (st.effective_balance <= U64(c.EJECTION_BALANCE))
    initiate_exits(st, c, m, np.nonzero(eject)[0].tolist())
    activation = np.nonzero((st.activation_eligibility_epoch <= U64(st.finalized[0]))
                            & (st.activation_epoch == FAR_FUTURE))[0]
    order = np.lexsort((activation, st.activation_eligibility_epoch[activation]))
    churn = max(c.MIN_PER_EPOCH_CHURN_LIMIT, int(m.active_cur.sum()) // c.CHURN_LIMIT_QUOTIENT)
    st.activation_epoch[activation[order][:churn]] = U64(activation_exit_epoch(cur, c))


def slashings(st, c, m: Masks) -> None:
    epoch = current_epoch(st, c)
    total = m.total_active
    adjusted = min(int(st.slashings.sum(dtype=np.uint64)) * c.PROPORTIONAL_SLASHING_MULTIPLIER_ALTAIR, total)
    hit = st.slashed & (U64(epoch + c.EPOCHS_PER_SLASHINGS_VECTOR // 2) == st.withdrawable_epoch)
    if not hit.any():
        return
    inc = c.EFFECTIVE_BALANCE_INCREMENT
    penalty = np.zeros_like(st.balances)
    idx = np.nonzero(hit)[0]
    penalty[idx] = [int(e) // inc * adjusted // total * inc
                    for e in st.effective_balance[idx].tolist()]
    st.balances = decrease(st.balances, penalty)


def effective_balance_updates(st, c) -> None:
    hyst = c.EFFECTIVE_BALANCE_INCREMENT // c.HYSTERESIS_QUOTIENT
    down = U64(hyst * c.HYSTERESIS_DOWNWARD_MULTIPLIER)
    up = U64(hyst * c.HYSTERESIS_UPWARD_MULTIPLIER)
    b, eb = st.balances, st.effective_balance
    move = (b + down < eb) | (eb + up < b)
    new = np.minimum(b - b % U64(c.EFFECTIVE_BALANCE_INCREMENT), U64(c.MAX_EFFECTIVE_BALANCE))
    st.effective_balance = np.where(move, new, eb)


def seed(st, c, epoch: int, domain: bytes) -> bytes:
    mix = st.randao_mixes[(epoch + c.EPOCHS_PER_HISTORICAL_VECTOR - c.MIN_SEED_LOOKAHEAD - 1)
                          % c.EPOCHS_PER_HISTORICAL_VECTOR].tobytes()
    return _sha(domain + epoch.to_bytes(8, "little") + mix)


def shuffled_index(index: int, count: int, seed_: bytes, rounds: int) -> int:
    for r in range(rounds):
        pivot = int.from_bytes(_sha(seed_ + bytes([r]))[:8], "little") % count
        flip = (pivot + count - index) % count
        position = max(index, flip)
        source = _sha(seed_ + bytes([r]) + (position // 256).to_bytes(4, "little"))
        if (source[(position % 256) // 8] >> (position % 8)) & 1:
            index = flip
    return index


def next_sync_committee_indices(st, c) -> list:
    epoch = current_epoch(st, c) + 1
    active = np.nonzero(active_mask(st, epoch))[0]
    count = len(active)
    s = seed(st, c, epoch, bytes.fromhex(c.DOMAIN_SYNC_COMMITTEE[2:]))
    out, i = [], 0
    while len(out) < c.SYNC_COMMITTEE_SIZE:
        cand = int(active[shuffled_index(i % count, count, s, c.SHUFFLE_ROUND_COUNT)])
        random_byte = _sha(s + (i // 32).to_bytes(8, "little"))[i % 32]
        if int(st.effective_balance[cand]) * 255 >= c.MAX_EFFECTIVE_BALANCE * random_byte:
            out.append(cand)
        i += 1
    return out


def next_sync_committee(st, c, pk_points: dict) -> tuple:
    """get_next_sync_committee; `pk_points` caches validated G1 points."""
    keys = st.pubkeys[next_sync_committee_indices(st, c)]
    points = []
    for row in keys:
        raw = row.tobytes()
        if raw not in pk_points:
            pk_points[raw] = bls_ref.key_validate(raw)
        if pk_points[raw] is None:
            raise ValueError("sync committee member with an invalid key")
        points.append(pk_points[raw])
    return keys.copy(), bls_ref.aggregate_pubkeys(points)


def process_epoch(st, c, pk_points: dict, reward_dtype=np.uint64) -> None:
    """specs/altair/beacon-chain.md process_epoch, in the spec's order."""
    m = Masks(st, c)
    justification_and_finalization(st, c, m)
    inactivity_updates(st, c, m)
    rewards_and_penalties(st, c, m, reward_dtype)
    registry_updates(st, c, m)
    slashings(st, c, m)
    nxt = current_epoch(st, c) + 1
    if nxt % c.EPOCHS_PER_ETH1_VOTING_PERIOD == 0:  # eth1 data reset
        st.eth1_data_votes = []
    effective_balance_updates(st, c)
    st.slashings[nxt % c.EPOCHS_PER_SLASHINGS_VECTOR] = 0  # slashings reset
    cur = current_epoch(st, c)
    st.randao_mixes[nxt % c.EPOCHS_PER_HISTORICAL_VECTOR] = \
        st.randao_mixes[cur % c.EPOCHS_PER_HISTORICAL_VECTOR]
    if nxt % (c.SLOTS_PER_HISTORICAL_ROOT // c.SLOTS_PER_EPOCH) == 0:
        st.historical_roots.append(_sha(ssz.merkleize(st.block_roots)
                                        + ssz.merkleize(st.state_roots)))
    st.previous_epoch_participation = st.current_epoch_participation
    st.current_epoch_participation = np.zeros_like(st.current_epoch_participation)
    if nxt % c.EPOCHS_PER_SYNC_COMMITTEE_PERIOD == 0:
        st.current_sync_committee = st.next_sync_committee
        st.next_sync_committee = next_sync_committee(st, c, pk_points)


# --- hash_tree_root(BeaconState) -------------------------------------------------

def _checkpoint_root(cp) -> bytes:
    return ssz.container_root([ssz.uint_chunk(cp[0]), cp[1]])


def _sync_committee_root(sc) -> bytes:
    keys, agg = sc
    pk_roots = ssz.bytes48_roots(keys)
    agg_root = ssz.bytes48_roots(np.frombuffer(agg, np.uint8)[None])[0].tobytes()
    return ssz.container_root([ssz.merkleize(pk_roots), agg_root])


def state_root(st, c) -> bytes:
    """hash_tree_root of the altair BeaconState, field by field."""
    pv, cv, fe = st.fork
    hs, hp, hpr, hsr, hbr = st.latest_block_header
    dr, dc, bh = st.eth1_data
    pk_roots = ssz.bytes48_roots(st.pubkeys)
    val_roots = ssz.validator_roots(
        pk_roots, st.withdrawal_credentials, st.effective_balance, st.slashed,
        st.activation_eligibility_epoch, st.activation_epoch, st.exit_epoch,
        st.withdrawable_epoch)
    vote_roots = [ssz.container_root([v[0], ssz.uint_chunk(v[1]), v[2]])
                  for v in st.eth1_data_votes]
    limit_votes = c.EPOCHS_PER_ETH1_VOTING_PERIOD * c.SLOTS_PER_EPOCH
    votes = np.frombuffer(b"".join(vote_roots), np.uint8).reshape(-1, 32)
    hist = np.frombuffer(b"".join(st.historical_roots), np.uint8).reshape(-1, 32)
    fields = [
        ssz.uint_chunk(st.genesis_time),
        st.genesis_validators_root,
        ssz.uint_chunk(st.slot),
        ssz.container_root([pv + bytes(28), cv + bytes(28), ssz.uint_chunk(fe)]),
        ssz.container_root([ssz.uint_chunk(hs), ssz.uint_chunk(hp), hpr, hsr, hbr]),
        ssz.merkleize(st.block_roots),
        ssz.merkleize(st.state_roots),
        ssz.mix_in_length(ssz.merkleize(hist, c.HISTORICAL_ROOTS_LIMIT), len(hist)),
        ssz.container_root([dr, ssz.uint_chunk(dc), bh]),
        ssz.mix_in_length(ssz.merkleize(votes, limit_votes), len(votes)),
        ssz.uint_chunk(st.eth1_deposit_index),
        ssz.mix_in_length(ssz.merkleize(val_roots, c.VALIDATOR_REGISTRY_LIMIT), len(val_roots)),
        ssz.list_root_basic(st.balances.astype("<u8"), c.VALIDATOR_REGISTRY_LIMIT),
        ssz.merkleize(st.randao_mixes),
        ssz.vector_root_basic(st.slashings.astype("<u8")),
        ssz.list_root_basic(st.previous_epoch_participation.astype(np.uint8), c.VALIDATOR_REGISTRY_LIMIT),
        ssz.list_root_basic(st.current_epoch_participation.astype(np.uint8), c.VALIDATOR_REGISTRY_LIMIT),
        ssz.bitvector_chunk(st.justification_bits),
        _checkpoint_root(st.previous_justified),
        _checkpoint_root(st.current_justified),
        _checkpoint_root(st.finalized),
        ssz.list_root_basic(st.inactivity_scores.astype("<u8"), c.VALIDATOR_REGISTRY_LIMIT),
        _sync_committee_root(st.current_sync_committee),
        _sync_committee_root(st.next_sync_committee),
    ]
    return ssz.container_root(fields)
