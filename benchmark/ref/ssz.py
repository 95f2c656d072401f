"""Plain SSZ merkleization (ssz/simple-serialize.md) with hashlib and NumPy.

Independent of the program: nodes are (n, 32) uint8 arrays, each tree
level is one pass of `hashlib.sha256` over pairs, and padding uses the
zero-subtree roots. Only what the altair `BeaconState` needs is here.
"""
from __future__ import annotations

import hashlib

import numpy as np

_sha = hashlib.sha256
ZERO_HASHES = [bytes(32)]
for _ in range(64):
    ZERO_HASHES.append(_sha(ZERO_HASHES[-1] + ZERO_HASHES[-1]).digest())


def hash_pairs(nodes: np.ndarray) -> np.ndarray:
    """(2k, 32) -> (k, 32): sha256 of each adjacent pair."""
    buf = memoryview(np.ascontiguousarray(nodes, dtype=np.uint8).tobytes())
    out = b"".join([_sha(buf[i:i + 64]).digest() for i in range(0, len(buf), 64)])
    return np.frombuffer(out, dtype=np.uint8).reshape(-1, 32)


def merkleize(chunks: np.ndarray, limit: int | None = None) -> bytes:
    """Root of `chunks` ((n, 32) uint8) padded with zero chunks to the next
    power of two of `limit` (or of n)."""
    n = len(chunks)
    size = max(n, 1) if limit is None else max(limit, 1)
    depth = (size - 1).bit_length()
    if n == 0:
        return ZERO_HASHES[depth]
    level = np.asarray(chunks, dtype=np.uint8).reshape(n, 32)
    for d in range(depth):
        if len(level) % 2:
            level = np.concatenate([level, np.frombuffer(ZERO_HASHES[d], np.uint8)[None]])
        level = hash_pairs(level)
    return level[0].tobytes()


def mix_in_length(root: bytes, length: int) -> bytes:
    return _sha(root + length.to_bytes(32, "little")).digest()


def pack(values: np.ndarray) -> np.ndarray:
    """Little-endian basic values packed into (ceil, 32) chunks."""
    raw = np.ascontiguousarray(values).view(np.uint8).reshape(-1)
    pad = (-len(raw)) % 32
    if pad:
        raw = np.concatenate([raw, np.zeros(pad, np.uint8)])
    return raw.reshape(-1, 32)


def uint_chunk(value: int) -> bytes:
    return int(value).to_bytes(32, "little")


def list_root_basic(values: np.ndarray, limit: int) -> bytes:
    """List[uintN, limit] root; `values` is a little-endian NumPy array."""
    per_chunk = 32 // values.dtype.itemsize
    chunk_limit = (limit + per_chunk - 1) // per_chunk
    return mix_in_length(merkleize(pack(values), chunk_limit), len(values))


def vector_root_basic(values: np.ndarray) -> bytes:
    return merkleize(pack(values))


def container_root(field_roots: list) -> bytes:
    return merkleize(np.frombuffer(b"".join(field_roots), np.uint8).reshape(-1, 32))


def bytes48_roots(keys: np.ndarray) -> np.ndarray:
    """(n, 48) uint8 -> (n, 32) roots of Bytes48 (two chunks each)."""
    padded = np.zeros((len(keys), 64), np.uint8)
    padded[:, :48] = keys
    return hash_pairs(padded.reshape(-1, 32))


def validator_roots(pubkey_roots: np.ndarray, credentials: np.ndarray,
                    effective_balance, slashed, eligibility, activation,
                    exit_, withdrawable) -> np.ndarray:
    """(n, 32) roots of n Validator containers (8 fields, depth 3). Rows
    with the same eight leaves share one root, so each distinct row is
    hashed once."""
    n = len(effective_balance)
    leaves = np.zeros((n, 8, 32), np.uint8)
    leaves[:, 0] = pubkey_roots
    leaves[:, 1] = credentials
    for j, col in ((2, effective_balance), (4, eligibility), (5, activation),
                   (6, exit_), (7, withdrawable)):
        leaves[:, j, :8] = np.asarray(col, "<u8").view(np.uint8).reshape(n, 8)
    leaves[:, 3, 0] = np.asarray(slashed, bool)
    rows = leaves.reshape(n, 256)
    keys = np.ascontiguousarray(rows).view(np.dtype((np.void, 256))).reshape(n)
    uniq, inverse = np.unique(keys, return_inverse=True)
    level = np.frombuffer(uniq.tobytes(), np.uint8).reshape(-1, 32)
    for _ in range(3):
        level = hash_pairs(level)
    return level[inverse.reshape(-1)]


def bitvector_chunk(bits) -> bytes:
    value = sum(int(bool(b)) << i for i, b in enumerate(bits))
    return value.to_bytes(32, "little")
