"""Multi-host "gossip" load driver — the DCN side of the distributed story.

The reference specifies its network layer as prose and never executes it
(SURVEY.md §5: "distributed communication backend: none implemented"). This
framework keeps the vectors-as-test-bus stance for conformance but ships the
piece the reference leaves to clients: a host-side driver that plays the
gossip layer's role for multi-host load runs. Each node owns a TCP listener
socket and may run as a thread (how the in-repo tests drive it, all nodes in
one process) or as its own OS process via `run_node_process`/`spawn_cluster`
(one per host/slice in a real deployment; exercised by the
`test_gossip_driver` process-cluster test). A node:

  1. produces its share of signed attestation messages for the slot,
  2. floods them to every peer over TCP (localhost stands in for DCN),
     framed exactly like the wire contract in specs/phase0/p2p-interface.md:
     snappy BLOCK compression and the 20-byte
     SHA256(MESSAGE_DOMAIN_VALID_SNAPPY ‖ ssz) message-id for dedup,
  3. collects the slot's messages from peers, deduplicates by message-id,
  4. verifies the whole collected batch in ONE deferred-BLS flush
     (crypto/bls.deferred_verification — the same bulk path
     state_transition uses, which on device is one pairing_check_batch).

The intra-host/ICI half of the distributed design lives in parallel/mesh.py
(sharded epoch engine + GSPMD collectives); this driver is the inter-host
half. Convergence invariant checked by the tests: after each slot barrier,
every node holds the identical message set.
"""
from __future__ import annotations

import hashlib
import socket
import struct
import threading
from dataclasses import dataclass, field

from ..obs import metrics as _obs_metrics
from ..obs import trace as _obs_trace
from ..robustness import faults as rfaults

MESSAGE_DOMAIN_INVALID_SNAPPY = b"\x00\x00\x00\x00"
MESSAGE_DOMAIN_VALID_SNAPPY = b"\x01\x00\x00\x00"
_LEN = struct.Struct("<I")
# GOSSIP_MAX_SIZE (specs/phase0/p2p-interface.md): the largest uncompressed
# payload a gossip message may declare — passed to snappy.decompress so a
# crafted preamble is rejected at the protocol bound, not the 1 GiB backstop.
MAX_MESSAGE_SIZE = 1 << 20
# Wire-frame bound: a frame carries one snappy-compressed message, and snappy
# BLOCK compression expands incompressible input by at most ~1/6 + constant,
# so any frame larger than this cannot decompress to <= MAX_MESSAGE_SIZE. A
# bigger declared length is a framing attack or a desynced stream — without
# the bound, one crafted 4-byte header makes _recv_exact buffer (up to) 4 GiB
# from a hostile peer before decode even runs.
MAX_WIRE_FRAME = MAX_MESSAGE_SIZE + MAX_MESSAGE_SIZE // 6 + 64
# rx socket timeout: a peer that stops sending mid-frame cannot pin the rx
# thread (and whatever waits on its stats) forever.
RECV_TIMEOUT = 30.0


class FrameError(ValueError):
    """Framing-level violation (oversized declared length). Once the length
    prefix cannot be trusted there is no way to find the next frame boundary
    — the connection must be dropped, not resynced."""


def message_id(ssz_bytes: bytes) -> bytes:
    """20-byte phase0 gossip message-id (specs/phase0/p2p-interface.md):
    domain ‖ decompressed data, no topic binding."""
    return hashlib.sha256(MESSAGE_DOMAIN_VALID_SNAPPY + ssz_bytes).digest()[:20]


def message_id_v2(topic: bytes, data: bytes) -> bytes:
    """Topic-aware altair message-id (specs/altair/p2p-interface.md):
    the topic (length-prefixed, little-endian uint64) is mixed into the
    hash, so identical payloads on two topics get distinct ids — the
    cross-topic seen-cache poisoning phase0's derivation admits is closed.
    `data` is the raw wire payload; the VALID domain + decompressed bytes
    are hashed when it is valid snappy, the INVALID domain + raw bytes
    otherwise."""
    from ..native.snappy import decompress

    prefix = len(topic).to_bytes(8, "little") + topic
    try:
        payload = decompress(data, max_len=MAX_MESSAGE_SIZE)
        domain = MESSAGE_DOMAIN_VALID_SNAPPY
    except (ValueError, IndexError):
        # The wire-format failures snappy.decompress raises (ValueError from
        # the native path, IndexError from the pure-Python fallback on
        # truncated input); anything else (MemoryError, a broken native
        # import) must propagate — it is an environment fault, not an
        # invalid message.
        payload = data
        domain = MESSAGE_DOMAIN_INVALID_SNAPPY
    return hashlib.sha256(domain + prefix + payload).digest()[:20]


def encode_message(ssz_bytes: bytes) -> bytes:
    from ..native.snappy import compress

    return compress(ssz_bytes)


def decode_message(wire: bytes) -> bytes:
    from ..native.snappy import decompress

    return decompress(wire, max_len=MAX_MESSAGE_SIZE)


# --- framing over a stream socket -------------------------------------------


def send_frame(sock: socket.socket, payload: bytes) -> None:
    sock.sendall(_LEN.pack(len(payload)) + payload)


def recv_frame(sock: socket.socket,
               max_frame: int = MAX_WIRE_FRAME) -> bytes | None:
    header = _recv_exact(sock, _LEN.size)
    if header is None:
        return None
    (n,) = _LEN.unpack(header)
    if n > max_frame:
        raise FrameError(
            f"declared frame length {n} exceeds the {max_frame}-byte wire "
            "bound")
    return _recv_exact(sock, n)


def _recv_exact(sock: socket.socket, n: int) -> bytes | None:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            return None
        buf += chunk
    return buf


# --- node -------------------------------------------------------------------


@dataclass
class NodeStats:
    """Per-node gossip accounting. Every increment goes through `count`,
    which mirrors the tick into the process-wide metrics registry
    (`gossip_<field>_total{node=...}`) — the registry snapshot is the
    cross-node view, this dataclass stays the cheap per-node one."""

    node_id: int = -1
    produced: int = 0
    received: int = 0
    duplicates: int = 0
    verified_batches: int = 0
    partial_drains: int = 0  # drain_ready() calls that returned messages
    malformed: int = 0  # frames/messages quarantined instead of delivered
    message_ids: set = field(default_factory=set)
    # (reason, payload head) of recent malformed frames — enough to
    # attribute a misbehaving peer in a postmortem, bounded memory.
    quarantined: list = field(default_factory=list)

    def count(self, stat: str, n: int = 1) -> None:
        setattr(self, stat, getattr(self, stat) + n)
        _obs_metrics.REGISTRY.counter(
            f"gossip_{stat}_total", node=self.node_id).inc(n)


class GossipNode:
    """One gossip participant: a listener plus dial-out links to peers."""

    def __init__(self, node_id: int, listen_port: int, peer_ports: list[int]):
        self.node_id = node_id
        self.listen_port = listen_port
        self.peer_ports = peer_ports
        self.stats = NodeStats(node_id=node_id)
        self.inbox: list[bytes] = []  # decompressed ssz payloads
        self._lock = threading.Lock()
        self._server = socket.create_server(("127.0.0.1", listen_port))
        self._server.settimeout(10.0)
        self._accepted: list[socket.socket] = []
        self._links: list[socket.socket] = []
        self._rx_threads: list[threading.Thread] = []
        self._stop = threading.Event()

    # -- wiring ---------------------------------------------------------------

    def accept_peers(self, count: int) -> None:
        for _ in range(count):
            conn, _ = self._server.accept()
            self._accepted.append(conn)
            t = threading.Thread(target=self._rx_loop, args=(conn,), daemon=True)
            t.start()
            self._rx_threads.append(t)

    def dial_peers(self) -> None:
        for port in self.peer_ports:
            s = socket.create_connection(("127.0.0.1", port), timeout=10.0)
            self._links.append(s)

    def _quarantine(self, reason: str, wire: bytes) -> None:
        """Count + quarantine a malformed frame instead of letting it raise
        out of the rx loop (one bad peer must not kill message collection
        for every well-behaved one)."""
        with self._lock:
            self.stats.count("malformed")
            self.stats.quarantined.append((reason, bytes(wire[:64])))
            del self.stats.quarantined[:-32]  # keep the most recent 32

    def _rx_loop(self, conn: socket.socket) -> None:
        conn.settimeout(RECV_TIMEOUT)
        while not self._stop.is_set():
            try:
                wire = recv_frame(conn)
            except FrameError as exc:
                # length prefix can't be trusted -> the stream has no
                # recoverable frame boundary: quarantine and drop the link
                self._quarantine(f"frame: {exc}", b"")
                break
            except (TimeoutError, OSError):
                break
            if wire is None:
                break
            with _obs_trace.span("gossip.rx", node=self.node_id,
                                 wire_bytes=len(wire)):
                wire = rfaults.mangle_bytes("gossip.recv_frame", wire)
                try:
                    with _obs_trace.span("gossip.decode", node=self.node_id):
                        ssz = decode_message(wire)
                except (ValueError, IndexError) as exc:
                    # truncated/garbled snappy payload: the FRAME was still
                    # length-delimited, so the stream is in sync — quarantine
                    # the message, keep the connection
                    self._quarantine(
                        f"decode: {type(exc).__name__}: {exc}", wire)
                    continue
                mid = message_id(ssz)
                with self._lock:
                    if mid in self.stats.message_ids:
                        self.stats.count("duplicates")
                        continue
                    self.stats.message_ids.add(mid)
                    self.stats.count("received")
                    self.inbox.append(ssz)

    # -- slot actions ---------------------------------------------------------

    def publish(self, ssz_payloads: list[bytes]) -> None:
        """Flood locally produced messages to every peer."""
        with self._lock:
            for ssz in ssz_payloads:
                mid = message_id(ssz)
                if mid not in self.stats.message_ids:
                    self.stats.message_ids.add(mid)
                    self.inbox.append(ssz)
                    self.stats.count("produced")
        for ssz in ssz_payloads:
            wire = encode_message(ssz)
            for link in self._links:
                send_frame(link, wire)

    def drain_ready(self, max_messages: int | None = None) -> list[bytes]:
        """Non-blocking partial drain for streaming consumers (the
        attestation firehose): pop up to `max_messages` verified-candidate
        payloads that already cleared framing, decode, and message-id
        dedup — WITHOUT waiting for the slot barrier and without
        verifying. Interleaves freely with `drain_and_verify`, which keeps
        its exact batch semantics over whatever remains buffered: every
        message is returned by exactly one drain call, whichever kind
        claims it first."""
        with self._lock:
            if max_messages is None:
                batch, self.inbox = self.inbox, []
            else:
                batch = self.inbox[:max_messages]
                del self.inbox[:max_messages]
            if batch:
                self.stats.count("partial_drains")
        return batch

    def drain_and_verify(self, verify_fn) -> int:
        """Verify everything collected so far in one deferred-BLS flush."""
        from ..crypto import bls

        with self._lock:
            batch = list(self.inbox)
            self.inbox.clear()
        if batch:
            with _obs_trace.span("gossip.drain_and_verify",
                                 node=self.node_id, batch=len(batch)):
                with bls.deferred_verification():
                    for ssz in batch:
                        verify_fn(ssz)
            self.stats.count("verified_batches")
        return len(batch)

    def close(self) -> None:
        self._stop.set()
        for s in self._links + self._accepted:
            try:
                s.close()
            except OSError:
                pass
        self._server.close()


# --- full-mesh topology helper ----------------------------------------------


def connect_full_mesh(nodes: list[GossipNode]) -> None:
    """Dial every node to every other; each accepts n-1 inbound links."""
    n = len(nodes)
    acceptors = [
        threading.Thread(target=node.accept_peers, args=(n - 1,)) for node in nodes
    ]
    for t in acceptors:
        t.start()
    for node in nodes:
        node.dial_peers()
    for t in acceptors:
        t.join(timeout=15.0)


# --- one-OS-process-per-node cluster -----------------------------------------


def run_node_process(node_id: int, ports: list[int], messages_per_node: int,
                     barrier, out_queue) -> None:
    """Entry point for one cluster member running in its OWN OS process.

    Wires into the full mesh (two barrier phases: listeners up, mesh dialed),
    floods its share of deterministic payloads, waits for convergence, and
    reports (node_id, message_count, duplicates, sha256-of-sorted-ids) so the
    parent can assert every process converged to the identical message set."""
    import time
    import traceback

    try:
        n = len(ports)
        peers = [p for i, p in enumerate(ports) if i != node_id]
        node = GossipNode(node_id, ports[node_id], peers)
        barrier.wait(timeout=30.0)  # every process has a listening socket
        acceptor = threading.Thread(target=node.accept_peers, args=(n - 1,), daemon=True)
        acceptor.start()
        node.dial_peers()
        acceptor.join(timeout=15.0)
        barrier.wait(timeout=30.0)  # full mesh wired
        payloads = [
            b"node %03d attestation %06d " % (node_id, j) + b"." * 40
            for j in range(messages_per_node)
        ]
        node.publish(payloads)
        want = n * messages_per_node
        deadline = time.time() + 30.0
        while time.time() < deadline:
            with node._lock:
                have = len(node.stats.message_ids)
            if have >= want:
                break
            time.sleep(0.02)
        with node._lock:
            ids = sorted(node.stats.message_ids)
            dups = node.stats.duplicates
        digest = hashlib.sha256(b"".join(ids)).hexdigest()
        out_queue.put((node_id, len(ids), dups, digest))
        node.close()
    except BaseException:  # always report: a silent child hangs the parent
        out_queue.put((node_id, -1, -1, traceback.format_exc()))
        raise


def spawn_cluster(n_nodes: int, messages_per_node: int = 8,
                  base_port: int | None = None) -> list[tuple]:
    """Run one gossip round with one OS process per node (localhost TCP
    standing in for DCN). Returns the per-node reports sorted by node id;
    convergence holds iff every report carries the same count and digest.

    Host-only: each `spawn` child imports just this module, which never
    imports JAX, so no child asks for the chip that the parent may hold
    (one process per chip)."""
    import multiprocessing as mp
    import os

    if base_port is None:
        base_port = 20000 + (os.getpid() * 7) % 20000
    ports = [base_port + i for i in range(n_nodes)]
    ctx = mp.get_context("spawn")
    barrier = ctx.Barrier(n_nodes)
    out_queue = ctx.Queue()
    procs = [
        ctx.Process(target=run_node_process,
                    args=(i, ports, messages_per_node, barrier, out_queue))
        for i in range(n_nodes)
    ]
    for p in procs:
        p.start()
    try:
        reports = [out_queue.get(timeout=120.0) for _ in range(n_nodes)]
    finally:
        for p in procs:
            p.join(timeout=30.0)
            if p.is_alive():  # report collected or failed; never leak children
                p.terminate()
    failed = [r for r in reports if r[1] < 0]
    if failed:
        raise RuntimeError(
            f"gossip cluster: {len(failed)} node(s) crashed:\n" +
            "\n".join(r[3] for r in failed))
    return sorted(reports)
