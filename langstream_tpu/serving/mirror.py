"""Multi-host SPMD serving: the dispatch mirror.

A model sharded across hosts (tp spanning a multi-host TPU slice) needs
EVERY process of the replica to enter the same jit programs in the same
order — XLA collectives ride inside those programs. Only host 0 sees
request traffic (gateway/runner/HTTP run there), and its engine makes
timing-dependent host decisions (admission grouping, bucket choice,
chunk size). Followers therefore cannot recompute the schedule; they
must REPLAY it.

The contract (reference has no analogue — it never spans a model across
processes; this is the TPU-native design for BASELINE #5-style serving
at >8-chip scale):

- host 0 runs the normal :class:`DecodeEngine` with ``engine.mirror``
  set to a :class:`DispatchMirror`. Every device dispatch publishes a
  compact record (kind, static meta, host numpy args) BEFORE the local
  dispatch; records form one FIFO stream.
- each follower host builds the identical engine (same config, same
  seed/params/mesh — weights load deterministically) and replays the
  stream with :class:`FollowerExecutor`: same jits, same static shapes,
  same host args, its own shard of cache/params/counts.
- pipelined decode chains from ON-DEVICE carries on host 0; the
  ``decode_chained`` record carries no arrays — the follower chains
  from its OWN previous decode outputs, which hold identical values by
  SPMD determinism.
- ``kv_layout: paged`` replays too: paged dispatch records carry each
  row's block-table slice (small int32 host metadata — pool data never
  crosses the wire), and copy-on-write block copies publish their own
  ``block_copy`` records, so the follower applies the identical pool
  mutations to its kv-head shard without running the block
  allocator/prefix-cache/LRU bookkeeping itself — those are host-0
  decisions already baked into the tables it receives.
- ``prefill_mode: mixed`` replays as ``mixed`` records: one per fused
  prefill+decode step, carrying the per-row token counts (offsets /
  num_tokens / write/decode/completes masks) plus the tables and
  sampling arrays — the follower enters the same ``_get_mixed(width)``
  jit with identical args, so the chunked-prefill schedule host 0
  chose is baked into the stream like every other timing decision.
- the mixed-step carry chains too: a ``mixed_chained`` record carries
  ONLY the window-delta metadata (token windows + per-row counts +
  masks) — the follower reuses tables/sampling arrays and the previous
  step's sampled tokens from its own mixed carry, which hold identical
  values by SPMD determinism (the ``decode_chained`` contract, plus the
  small host-predictable delta the mixed step inherently needs).

Transport is a length-prefixed JSON-header + raw-array-bytes frame
stream over TCP (deliberately NOT pickle — nothing executable crosses
the wire): host 0 listens,
followers connect before serving starts (`expected` blocks until all
joined, because a follower joining mid-stream would miss cache state).
jax.distributed.initialize (runtime/multihost.py) must already be up so
the global mesh exists on every process.
"""

from __future__ import annotations

import hashlib
import json
import logging
import queue
import socket
import struct
import threading
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

logger = logging.getLogger(__name__)


def config_fingerprint(config: Dict[str, Any]) -> bytes:
    """16-byte digest of the serving config. Leader and followers must
    run the SAME model/engine configuration — mismatched shapes would
    not fail loudly (each process jit-compiles its own variants) but
    would silently diverge. The handshake compares digests."""
    canonical = json.dumps(config, sort_keys=True, default=str)
    return hashlib.sha256(canonical.encode()).digest()[:16]


_MAGIC = b"LSM1"
_FINGERPRINT_LEN = 16
_ANY_FINGERPRINT = bytes(_FINGERPRINT_LEN)  # all-zero = skip the check
_HEADER = struct.Struct("!I")  # payload length
# record payloads are NOT pickle: followers deserialize data from the
# network, so the wire format is a JSON header (kind, meta, array
# dtypes/shapes) plus raw array bytes — nothing executable
_ALLOWED_DTYPES = frozenset(
    ("int32", "uint32", "float32", "bool", "int64", "float64")
)


def _encode_record(kind: str, meta: Dict[str, Any], arrays: list) -> bytes:
    specs = []
    buffers: List[bytes] = []
    for array in arrays:
        # np.asarray, NOT ascontiguousarray: the latter promotes 0-d
        # scalars to shape (1,), and the copy-record jit needs true
        # scalars for lax.dynamic_slice indices
        array = np.asarray(array)
        specs.append({"dtype": array.dtype.name, "shape": list(array.shape)})
        buffers.append(array.tobytes())  # tobytes is C-order regardless
    header = json.dumps(
        {"kind": kind, "meta": meta, "arrays": specs}
    ).encode()
    return b"".join(
        [_HEADER.pack(len(header)), header, *buffers]
    )


def _send_record(sock: socket.socket, payload: bytes) -> None:
    sock.sendall(_HEADER.pack(len(payload)) + payload)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    chunks: List[bytes] = []
    while n:
        part = sock.recv(n)
        if not part:
            raise ConnectionError("mirror stream closed")
        chunks.append(part)
        n -= len(part)
    return b"".join(chunks)


def _recv_record(sock: socket.socket) -> Tuple[str, Dict[str, Any], list]:
    (length,) = _HEADER.unpack(_recv_exact(sock, _HEADER.size))
    payload = _recv_exact(sock, length)
    (header_len,) = _HEADER.unpack(payload[: _HEADER.size])
    cursor = _HEADER.size + header_len
    header = json.loads(payload[_HEADER.size: cursor])
    arrays = []
    for spec in header["arrays"]:
        dtype = spec["dtype"]
        if dtype not in _ALLOWED_DTYPES:
            raise ValueError(f"mirror: disallowed dtype {dtype!r}")
        shape = tuple(int(d) for d in spec["shape"])
        count = int(np.prod(shape, dtype=np.int64)) if shape else 1
        size = count * np.dtype(dtype).itemsize
        arrays.append(
            np.frombuffer(
                payload[cursor: cursor + size], dtype=dtype
            ).reshape(shape)
        )
        cursor += size
    if cursor != len(payload):
        raise ValueError("mirror: record length mismatch")
    return header["kind"], header["meta"], arrays


class DispatchMirror:
    """Host-0 side: accept follower connections, then fan every
    published dispatch record out to all of them in order.

    ``publish`` only enqueues (the engine thread never blocks on the
    network); a single writer thread preserves FIFO order. A follower
    that drops its connection mid-serve is fatal for the replica — the
    next collective would deadlock anyway — so the error is raised into
    the engine thread via the queue. The queue is bounded: a follower
    that falls persistently behind the leader's dispatch rate (records
    are small, so the bound is generous) is the same fatal condition as
    a dropped follower — without it the leader accumulates encoded
    records without limit and the engine gets no backpressure signal
    until memory pressure."""

    # dispatch records are ~100 bytes + small host arrays; 65536 queued
    # records is minutes of serving headroom, yet bounds leader memory
    QUEUE_MAXSIZE = 65536
    # how long publish() may block on a full queue before declaring the
    # follower link dead
    PUBLISH_TIMEOUT_S = 60.0

    def __init__(
        self,
        host: str = "0.0.0.0",
        port: int = 0,
        fingerprint: bytes = _ANY_FINGERPRINT,
    ) -> None:
        self._server = socket.create_server((host, port))
        self.port = self._server.getsockname()[1]
        self._fingerprint = fingerprint
        self._followers: List[socket.socket] = []
        self._queue: "queue.Queue[Optional[bytes]]" = queue.Queue(
            maxsize=self.QUEUE_MAXSIZE
        )
        self._writer: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        self._closed = False

    def wait_for_followers(self, expected: int, timeout: float = 300.0) -> None:
        """Block until ``expected`` followers complete the handshake,
        then start the writer. Must run before any traffic is served."""
        self._server.settimeout(timeout)
        while len(self._followers) < expected:
            conn, addr = self._server.accept()
            # bound the handshake read too — a connection that sends no
            # bytes (port scanner, health probe) must not hang startup
            conn.settimeout(10.0)
            try:
                magic = _recv_exact(conn, len(_MAGIC))
                theirs = _recv_exact(conn, _FINGERPRINT_LEN)
            except (socket.timeout, ConnectionError, OSError):
                conn.close()
                logger.warning("mirror: handshake timeout from %s", addr)
                continue
            if magic != _MAGIC:
                conn.close()
                logger.warning("mirror: bad handshake from %s", addr)
                continue
            if (
                self._fingerprint != _ANY_FINGERPRINT
                and theirs != _ANY_FINGERPRINT
                and theirs != self._fingerprint
            ):
                conn.close()
                logger.error(
                    "mirror: follower %s runs a DIFFERENT serving config "
                    "(fingerprint mismatch) — rejected; replay on "
                    "mismatched shapes would silently diverge", addr,
                )
                continue
            conn.settimeout(None)
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self._followers.append(conn)
            logger.info(
                "mirror: follower %d/%d connected from %s",
                len(self._followers), expected, addr,
            )
        self._writer = threading.Thread(
            target=self._write_loop, name="mirror-writer", daemon=True
        )
        self._writer.start()

    def publish(self, kind: str, meta: Dict[str, Any], arrays: list) -> None:
        if self._error is not None:
            raise RuntimeError("mirror writer failed") from self._error
        try:
            self._queue.put(
                _encode_record(kind, meta, arrays),
                timeout=self.PUBLISH_TIMEOUT_S,
            )
        except queue.Full:
            # lint: allow(cross-thread-mutation) -- benign latched
            #   error: each writer performs a single None→exception
            #   transition on a word-sized slot; a reader seeing a stale
            #   None enqueues at most one extra record before failing
            self._error = RuntimeError(
                f"mirror publish queue full for {self.PUBLISH_TIMEOUT_S:.0f}s"
                " — follower cannot keep up with the dispatch rate"
            )
            raise RuntimeError("mirror writer failed") from self._error

    def _write_loop(self) -> None:
        while True:
            payload = self._queue.get()
            if payload is None:
                return
            for follower in self._followers:
                try:
                    _send_record(follower, payload)
                except OSError as error:
                    self._error = error
                    logger.error("mirror: follower write failed: %s", error)
                    return

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        try:
            self._queue.put_nowait(None)
        except queue.Full:
            pass  # writer is wedged; the bounded join below handles it
        if self._writer is not None:
            self._writer.join(timeout=10)
        for follower in self._followers:
            try:
                follower.close()
            except OSError:
                pass
        self._server.close()


class FollowerExecutor:
    """Follower side: replay host 0's dispatch stream on this process's
    shard of the global mesh.

    The engine passed in must be constructed with the same config as
    host 0's and must NOT be started — the executor owns its cache and
    counts. Outputs other than cache/counts are dropped (host 0 emits
    the tokens); the previous decode outputs are retained so
    ``decode_chained`` records can chain exactly like host 0 does."""

    def __init__(self, engine) -> None:
        self.engine = engine
        self._sock: Optional[socket.socket] = None
        # previous decode output, for chained chunks:
        # (final_tokens, final_lengths, active_arg, tables, sampling)
        # — tables is None on dense engines
        self._carry: Optional[Tuple[Any, Any, Any, Any, tuple]] = None
        # previous mixed-step output, for mixed_chained records:
        # (sampled, tables, sampling) — the device-resident operands a
        # chained mixed record deliberately does not carry
        self._mixed_carry: Optional[Tuple[Any, Any, tuple]] = None
        self.records = 0

    def connect(
        self,
        host: str,
        port: int,
        timeout: float = 300.0,
        fingerprint: bytes = _ANY_FINGERPRINT,
    ) -> None:
        self._sock = socket.create_connection((host, port), timeout=timeout)
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._sock.sendall(_MAGIC + fingerprint)

    def run(self) -> int:
        """Replay records until a ``stop`` record or stream close.
        Returns the number of records executed."""
        assert self._sock is not None, "connect() first"
        try:
            while True:
                try:
                    kind, meta, arrays = _recv_record(self._sock)
                except ConnectionError:
                    logger.info("mirror: stream closed, follower exiting")
                    return self.records
                if kind == "stop":
                    return self.records
                # chaos (LANGSTREAM_FAULTS=mirror_follower@step=N): a
                # follower dying mid-replay — the leader-side handling
                # of a dropped follower is part of the fault surface
                from langstream_tpu.runtime import faults

                faults.check("mirror_follower")
                self._execute(kind, meta, arrays)
                self.records += 1
        finally:
            self._sock.close()

    def _execute(self, kind: str, meta: Dict[str, Any], arrays: list) -> None:
        engine = self.engine
        # paged dispatches carry one extra operand — the block-table
        # rows — in dispatch-arg position (after slot_ids / active);
        # engine.paged tells the replay how to split the record back
        # into the jit's exact argument tuple
        extra = 1 if engine.paged else 0
        # leader dispatches run under the engine mesh (sharding
        # constraints/shard_map resolve against the ambient mesh);
        # replay must too or tp>1 followers diverge
        with engine.mesh:
            if kind == "prefill":
                run = engine._get_prefill(meta["bucket"])
                engine.cache, engine._counts = run(
                    engine.params, engine.cache, *arrays[:3 + extra],
                    engine._counts, *arrays[3 + extra:],
                )[:2]
            elif kind == "prefill_offset":
                run = engine._get_prefill_offset(meta["bucket"])
                engine.cache, engine._counts = run(
                    engine.params, engine.cache, *arrays[:4 + extra],
                    engine._counts, *arrays[4 + extra:],
                )[:2]
            elif kind == "copy":
                run = engine._get_copy_prefix(meta["bucket"])
                (engine.cache,) = run(engine.params, engine.cache, *arrays)
            elif kind == "block_copy":
                # the paged COW primitive: duplicate pool block src->dst
                # on this process's kv-head shard
                run = engine._get_block_copy()
                (engine.cache,) = run(engine.params, engine.cache, *arrays)
            elif kind == "mixed":
                # mixed prefill+decode step (prefill_mode: mixed): the
                # record carries per-row token counts + the mask trio +
                # the full block tables + carry operands in dispatch-arg
                # position; the sampled tokens become this process's
                # mixed carry (identical to host 0's by SPMD
                # determinism) so mixed_chained records can chain
                run = engine._get_mixed(meta["width"])
                engine.cache, engine._counts, sampled, _, _ = run(
                    engine.params, engine.cache, *arrays[:7],
                    engine._counts, *arrays[7:],
                )
                # arrays: 0-5 window/count metadata, 6 tables,
                # 7 prev_sampled, 8 chain_mask, 9.. sampling arrays
                self._mixed_carry = (
                    sampled, arrays[6], tuple(arrays[9:])
                )
            elif kind == "mixed_chained":
                assert self._mixed_carry is not None, \
                    "chained mixed step before any mixed step"
                prev_sampled, tables, sampling = self._mixed_carry
                run = engine._get_mixed(meta["width"])
                engine.cache, engine._counts, sampled, _, _ = run(
                    engine.params, engine.cache, *arrays[:6], tables,
                    engine._counts, prev_sampled, arrays[6], *sampling,
                )
                self._mixed_carry = (sampled, tables, sampling)
            elif kind == "decode":
                tokens, lengths, active = arrays[:3]
                tables = arrays[3] if extra else None
                self._decode(
                    meta["steps"], tokens, lengths, active, tables,
                    tuple(arrays[3 + extra:]),
                )
            elif kind == "decode_chained":
                assert self._carry is not None, \
                    "chained decode before any decode"
                tokens, lengths, active, tables, sampling = self._carry
                self._decode(
                    meta["steps"], tokens, lengths, active, tables, sampling
                )
            else:
                raise ValueError(f"unknown mirror record kind {kind!r}")

    def _decode(self, steps, tokens, lengths, active, tables, sampling) -> None:
        engine = self.engine
        run = engine._get_decode(steps)
        paged_args = (tables,) if tables is not None else ()
        (
            engine.cache, engine._counts, _, _, _,
            final_tokens, final_lengths, _,
        ) = run(
            engine.params, engine.cache, tokens, lengths, active, active,
            *paged_args, engine._counts, *sampling,
        )
        self._carry = (final_tokens, final_lengths, active, tables, sampling)
