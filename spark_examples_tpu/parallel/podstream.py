"""Host-side pod-sparse window exchange + the depth-D slot pipeline.

The pod-sparse protocol's per-window agreement (header, payload-confirm,
carrier payload) historically rode device collectives
(``multihost_utils.process_allgather``): every exchange was a device
program enqueued BEHIND the previous window's scatter on each device's
serial execution stream, so collective latency serialized against
scatter compute — the phase-barrier shape PR 9 removed from cold ingest,
now inside the pod stream (MULTICHIP_r06 pins the cost: ~8× off
single-controller at the same N). This module moves the agreement onto a
**persistent host-side TCP mesh between the pod's processes**: pure
socket IO, no device programs and no jaxlib calls, so a sync thread can
run window w+1's whole exchange while window w's scatter executes on
device. Three pieces:

- :class:`_PodSocketMesh` — the per-process singleton full mesh of
  peer sockets. Peer addresses bootstrap ONCE through the
  jax.distributed coordination-service KV store (the only jaxlib-client
  touch, made from the main thread before any pipelined work); after
  that every protocol byte flows over the sockets. This matters beyond
  latency: the coordination client is shared with jax internals — the
  gloo CPU-collective rendezvous and the compilation cache use it from
  XLA's own threads — and concurrent client calls from a second Python
  thread segfault jaxlib. The socket mesh keeps the sync thread off the
  client entirely. Sends run on tiny per-peer sender threads so a slow
  peer can never produce a mutual send-block deadlock; receives run on
  the sync thread in deterministic per-peer frame order (TCP preserves
  each peer's post order, and the protocol makes every receive's
  (stream, step, kind) predictable).
- :class:`PodWindowExchange` — one stream's framed post/gather API over
  the mesh (headers, confirms, payloads). Streams are opened in
  identical program order on every process — the same assumption every
  collective already makes — so a module-level counter names them
  consistently; frames carry (stream, step, kind) and a mismatch is a
  loud protocol error, never silent reordering.
- :class:`SlotPipeline` — the depth-D bounded pipeline: a daemon thread
  repeatedly calls a ``produce`` callback (one protocol step per call)
  and stages results into a bounded queue; the consumer iterates staged
  slots. A producer exception is re-raised in the consumer AT ITS SLOT
  POSITION — every process sees the same agreed stream order, so the
  raise lands on the same step everywhere (the all-raise-together
  discipline of the lockstep protocol, preserved per in-flight slot).

Synchronized-failure cleanliness: every failure the protocol raises
(producer −2 headers, payload-confirm −2, route/dtype divergence) is
detected from identical gathered data AFTER the same phase on every
process, so all peers stop at the same point in the frame sequence and
no socket is left holding half-read frames — the next stream starts on
clean pipes.

Peer DEATH (kill -9, OOM, host loss) is the one failure that cannot be
agreed from gathered data — the peer stops posting mid-sequence. The
hardened receive path converts it into the same shape: EOF/ECONNRESET
on an established socket raises :class:`PodPeerDeadError`, the gather
paths synthesize the dead peer's −2 header/confirm (the exact
producer-failure encoding every process already raises on together),
and the mesh tears its sockets down so every survivor detects within
one receive instead of one phase apart — all survivors raise at the
same slot. The mesh is poisoned afterwards: a pod minus a member is
fail-stop + relaunch, never a silent continue.
"""

from __future__ import annotations

import itertools
import queue
import socket
import struct
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np

__all__ = [
    "POD_EXCHANGE_TIMEOUT_S",
    "PodPeerDeadError",
    "PodWindowExchange",
    "SlotPipeline",
    "coordination_client",
]

# Blocking-receive deadline for one protocol phase: generous (a peer may
# legitimately be deep in host ingest for its next window), but finite —
# a dead peer turns into a loud RuntimeError instead of the native
# collective's silent forever-hang. --collective-timeout's watchdog
# remains the tighter fail-stop story when configured.
POD_EXCHANGE_TIMEOUT_S = 1800.0

_STREAM_IDS = itertools.count()

# Frame kinds on the wire. _KIND_CHECK carries the optional
# collective-congruence digest (utils/collectivecheck): it is only ever
# posted when EVERY live process advertised the check in its step
# header, so a mixed-enablement pod never desyncs on unexpected frames.
_KIND_HEADER = 0
_KIND_CONFIRM = 1
_KIND_PAYLOAD = 2
_KIND_CHECK = 3

# stream (q), step (q), kind (B), byte length (q) — little-endian.
_FRAME = struct.Struct("<qqBq")


class PodPeerDeadError(RuntimeError):
    """An established peer socket died mid-protocol (EOF/ECONNRESET —
    the peer process was killed, OOMed, or its host vanished).

    Distinct from the generic protocol-desync/timeout RuntimeErrors so
    the gather paths can CONVERT it into the synchronized −2 failure
    shape every process already handles (producer-error semantics:
    raise together at the same slot) instead of each survivor hanging
    out its own receive deadline one phase apart. ``peer`` is the dead
    process index when known.
    """

    def __init__(self, message: str, peer: Optional[int] = None) -> None:
        super().__init__(message)
        self.peer = peer


def coordination_client() -> Any:
    """The jax.distributed coordination-service client, or ``None``.

    Present on every process of a multi-process jax run (it is what
    ``jax.distributed.initialize`` connects); ``None`` single-process.
    Used here ONLY for the one-time peer-address bootstrap, from the
    main thread — see the module docstring for why per-step traffic
    must stay off this client.
    """
    try:
        from jax._src import distributed

        return distributed.global_state.client
    except Exception:  # pragma: no cover — jax internals drift
        return None


def _local_ip() -> str:
    """The IP this host uses to reach the coordinator (UDP-connect
    trick — no packet is sent; IPv6 coordinator addresses are bracket-
    stripped and probed over AF_INET6). Falls back to the hostname's
    resolved address, then loopback (correct only for the
    single-machine pod-sim — a multi-host mesh that lands there fails
    the dial with connection-refused, surfaced loudly by setup)."""
    try:
        from jax._src import distributed

        coord = str(distributed.global_state.coordinator_address)
        host = coord.rsplit(":", 1)[0]
        family = socket.AF_INET
        if host.startswith("[") and host.endswith("]"):
            host, family = host[1:-1], socket.AF_INET6
        elif ":" in host:
            family = socket.AF_INET6
        s = socket.socket(family, socket.SOCK_DGRAM)
        try:
            s.connect((host, 1))
            return s.getsockname()[0]
        finally:
            s.close()
    except Exception:
        pass
    try:
        return socket.gethostbyname(socket.gethostname())
    except Exception:
        return "127.0.0.1"


class _PeerSender:
    """One peer's outbound frame queue + daemon sender thread.

    Sends must never run on the sync thread: with every process pushing
    payload frames to every peer before reading any, two full TCP
    buffers would deadlock the pod. The queue is unbounded but its depth
    is governed by the pipeline depth (a handful of frames)."""

    def __init__(self, sock: socket.socket, peer: int) -> None:
        self._sock = sock
        self._q: "queue.Queue[Optional[bytes]]" = queue.Queue()
        self._thread = threading.Thread(
            target=self._run,
            name=f"pod-exchange-send-{peer}",
            daemon=True,
        )
        self._thread.start()

    def send(self, frame: bytes) -> None:
        self._q.put(frame)

    def _run(self) -> None:
        while True:
            frame = self._q.get()
            if frame is None:
                return
            try:
                self._sock.sendall(frame)
            except OSError:
                # Peer gone (it raised and tore down, or died): the
                # receive side surfaces the loud error; sending more
                # is pointless but must not kill this process.
                return


class _PodSocketMesh:
    """Per-process full mesh of peer connections (module singleton).

    Connection setup: every process binds an ephemeral listening socket,
    publishes ``pod_exchange/addr/<pid>`` through the coordination KV
    store (the one-time bootstrap), then connects to every LOWER pid and
    accepts one connection from every HIGHER pid (identified by a hello
    byte) — one socket per unordered pair, used bidirectionally for the
    life of the process.
    """

    _instance: Optional["_PodSocketMesh"] = None
    _instance_lock = threading.Lock()

    def __init__(self, pid: int, world: int, timeout_s: float) -> None:
        self._pid = pid
        self._world = world
        self._timeout_s = timeout_s
        self._socks: Dict[int, socket.socket] = {}
        self._senders: Dict[int, _PeerSender] = {}
        self.poisoned = False
        self.poison_reason = ""
        self._connect(timeout_s)

    def poison(self) -> None:
        """Mark the mesh unusable: an ABANDONED stream (consumer died
        one-sided, e.g. an XLA error mid-dispatch) may have left its
        sync thread blocked mid-read and unread frames on the pipes —
        a later stream reusing these sockets would desync on garbage.
        Synchronized protocol failures (all peers raising at the same
        frame boundary) do NOT poison: the pipes are provably clean
        there and back-to-back streams are supported (the chaos suite
        runs exactly that). After poisoning, the pod's recovery
        contract is what it always was for one-sided death: fail-stop
        + relaunch (docs/ARCHITECTURE.md §5)."""
        self.poisoned = True
        self.poison_reason = (
            "an abandoned stream (one-sided consumer failure); the "
            "socket pipes may hold half-read frames"
        )

    @classmethod
    def instance(cls, timeout_s: float) -> Optional["_PodSocketMesh"]:
        with cls._instance_lock:
            if cls._instance is not None:
                if cls._instance.poisoned:
                    raise RuntimeError(
                        "pod exchange mesh was poisoned by "
                        + (
                            cls._instance.poison_reason
                            or "an abandoned stream"
                        )
                        + " — pod recovery is fail-stop + relaunch "
                        "(docs/ARCHITECTURE.md §5)"
                    )
                return cls._instance
            client = coordination_client()
            if client is None:
                return None
            import jax

            cls._instance = cls(
                jax.process_index(), jax.process_count(), timeout_s
            )
            return cls._instance

    def _connect(self, timeout_s: float) -> None:
        client = coordination_client()
        # The listener's family must match the ADVERTISED address: an
        # IPv6 fabric publishes a v6 address, and peers dialing it
        # against a v4-only listener would get connection-refused.
        ip = _local_ip()
        v6 = ":" in ip
        listener = socket.socket(
            socket.AF_INET6 if v6 else socket.AF_INET,
            socket.SOCK_STREAM,
        )
        listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        listener.bind(("::" if v6 else "0.0.0.0", 0))
        listener.listen(self._world)
        port = listener.getsockname()[1]
        addr = f"[{ip}]:{port}" if v6 else f"{ip}:{port}"
        client.key_value_set_bytes(
            f"pod_exchange/addr/{self._pid}", addr.encode()
        )
        timeout_ms = int(timeout_s * 1000)
        peers: Dict[int, Tuple[str, int]] = {}
        for p in range(self._world):
            if p == self._pid:
                continue
            raw = client.blocking_key_value_get_bytes(
                f"pod_exchange/addr/{p}", timeout_ms
            ).decode()
            host, pstr = raw.rsplit(":", 1)
            peers[p] = (host.strip("[]"), int(pstr))

        dial_exc: List[BaseException] = []

        def _dial() -> None:
            # Outbound side on a helper thread (pure sockets, no
            # jaxlib) so accept and connect cannot deadlock each other;
            # its exception is re-raised by the main thread below — a
            # refused/filtered peer must surface as ITS error, not as a
            # generic timeout after 30 minutes in accept().
            try:
                for p in sorted(peers):
                    if p >= self._pid:
                        continue
                    s = socket.create_connection(
                        peers[p], timeout=timeout_s
                    )
                    s.sendall(struct.pack("<q", self._pid))
                    self._socks[p] = s
            except BaseException as e:  # noqa: BLE001 — re-raised below
                dial_exc.append(e)

        dialer = threading.Thread(target=_dial, daemon=True)
        dialer.start()
        listener.settimeout(timeout_s)
        try:
            for _ in range(self._world - 1 - self._pid):
                conn, _ = listener.accept()
                # Accepted sockets are blocking regardless of the
                # listener's timeout; bound the hello read or a
                # half-open inbound connection hangs setup forever.
                conn.settimeout(timeout_s)
                (peer,) = struct.unpack(
                    "<q", self._recv_exact_raw(conn, 8)
                )
                self._socks[int(peer)] = conn
        finally:
            listener.close()
        dialer.join(timeout=timeout_s)
        if dial_exc:
            raise RuntimeError(
                "pod exchange mesh setup failed dialing a lower-pid "
                "peer (firewalled/NATed address, or the peer died "
                "before accepting?)"
            ) from dial_exc[0]
        missing = [
            p
            for p in range(self._world)
            if p != self._pid and p not in self._socks
        ]
        if missing:
            raise RuntimeError(
                f"pod exchange mesh setup failed: no connection to "
                f"process(es) {missing}"
            )
        for p, s in self._socks.items():
            s.settimeout(timeout_s)
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self._senders[p] = _PeerSender(s, p)

    @staticmethod
    def _recv_exact_raw(sock: socket.socket, n: int) -> bytes:
        buf = bytearray()
        while len(buf) < n:
            chunk = sock.recv(n - len(buf))
            if not chunk:
                raise PodPeerDeadError(
                    "pod exchange peer closed its connection "
                    "mid-protocol (peer process died?)"
                )
            buf.extend(chunk)
        return bytes(buf)

    def _peer_died(self, peer: int) -> None:
        """Peer-death cascade: poison the mesh (a member is gone — the
        pod's recovery contract is fail-stop + relaunch) and close every
        socket, so survivors blocked reading THIS process unblock with
        EOF immediately and convert the same way. Without the cascade,
        survivor A can detect the death one phase ahead of survivor B,
        stop posting, and leave B hanging out the full receive deadline
        waiting on A — the staggered-raise shape the −2 protocol
        exists to prevent."""
        self.poisoned = True
        self.poison_reason = (
            f"the death of pod process {peer} mid-protocol (mesh "
            "sockets torn down for the synchronized raise)"
        )
        for s in self._socks.values():
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                s.close()
            except OSError:
                pass

    def post(
        self, peer: int, stream: int, step: int, kind: int, body: bytes
    ) -> None:
        self._senders[peer].send(
            _FRAME.pack(stream, step, kind, len(body)) + body
        )

    def recv(
        self, peer: int, stream: int, step: int, kind: int
    ) -> bytes:
        """The next frame from ``peer`` — which the protocol guarantees
        is (stream, step, kind); anything else is version skew or a
        protocol bug and raises loudly."""
        sock = self._socks[peer]
        try:
            raw = self._recv_exact_raw(sock, _FRAME.size)
            got_stream, got_step, got_kind, length = _FRAME.unpack(raw)
            if (got_stream, got_step, got_kind) != (stream, step, kind):
                raise RuntimeError(
                    "pod exchange protocol desync with peer "
                    f"{peer}: expected (stream={stream}, step={step}, "
                    f"kind={kind}), got (stream={got_stream}, "
                    f"step={got_step}, kind={got_kind}) — "
                    "version-skewed pod or out-of-order stream "
                    "construction"
                )
            # Body read under the SAME attributed handler: a peer dying
            # mid-frame must surface with peer/stream/step context, not
            # as an anonymous socket.timeout half an hour later.
            return self._recv_exact_raw(sock, length) if length else b""
        except socket.timeout as e:
            raise RuntimeError(
                f"pod exchange timed out waiting for peer {peer} "
                f"(stream {stream} step {step} kind {kind}) after "
                f"{self._timeout_s:.0f}s; a lockstep collective would "
                "have hung here forever — check the peer's log"
            ) from e
        except (PodPeerDeadError, OSError) as e:
            # EOF or ECONNRESET/EPIPE on an ESTABLISHED socket: the
            # peer process died. Attribute it, cascade the teardown
            # (every survivor must detect within one recv, not one
            # phase later), and let the gather paths convert it into
            # the synchronized −2 failure shape.
            self._peer_died(peer)
            raise PodPeerDeadError(
                f"pod exchange peer {peer} died mid-protocol "
                f"(stream {stream} step {step} kind {kind}): {e}",
                peer=peer,
            ) from e


class PodWindowExchange:
    """One stream's post/gather API over the process socket mesh.

    Values are raw little-endian numpy bytes (headers int64, payloads
    int32 carrier matrices); shapes are derivable from the agreed
    header geometry, so no metadata rides the wire beyond the frame
    header.
    """

    def __init__(self, mesh: _PodSocketMesh, pid: int, world: int) -> None:
        self._mesh = mesh
        self._pid = pid
        self._world = world
        self._stream = next(_STREAM_IDS)
        # Own posted values, folded into gathers so no loopback socket
        # is needed (the allgather semantics include the local row).
        self._own_header = np.zeros(0, np.int64)
        self._own_confirm = np.int64(0)
        self._own_check = np.int64(0)
        # Wall-clock of this step's own header post: paired with each
        # peer header's arrival time in gather_headers to mint the
        # pod.exchange_ts instants merge_pod_trace.py estimates
        # per-peer clock offsets from (NTP midpoint method).
        self._last_send_unix = 0.0

    @property
    def stream(self) -> int:
        """This stream's process-lifetime-unique id (identical on every
        process — streams open in agreed program order). Rides the
        telemetry spans so trace analysis can scope per-stream (step
        numbers restart per stream)."""
        return self._stream

    @classmethod
    def open(
        cls, timeout_s: float = POD_EXCHANGE_TIMEOUT_S
    ) -> Optional["PodWindowExchange"]:
        """Exchange for this process, or ``None`` without a
        coordination client (single-process). Call from the MAIN
        thread: first use bootstraps the socket mesh through the
        coordination client, which must never race jax's own use of it
        (module docstring)."""
        import jax

        mesh = _PodSocketMesh.instance(timeout_s)
        if mesh is None:
            return None
        return cls(mesh, jax.process_index(), jax.process_count())

    def _post_all(self, step: int, kind: int, body: bytes) -> None:
        for p in range(self._world):
            if p != self._pid:
                self._mesh.post(p, self._stream, step, kind, body)

    def post_header(self, step: int, fields: np.ndarray) -> None:
        self._own_header = np.asarray(fields, np.int64)
        self._last_send_unix = time.time()
        self._post_all(step, _KIND_HEADER, self._own_header.tobytes())

    def gather_headers(self, step: int, n_fields: int) -> np.ndarray:
        """(world, n_fields) int64 — every process's step header (own
        row included, like the allgather it replaces).

        A peer that DIED (EOF/ECONNRESET on its established socket)
        contributes a synthesized all-−2 row: field 0 = −2 is exactly
        the producer-failure shape the consumer already raises on
        everywhere together, so peer death fails the whole pod at this
        slot instead of stranding survivors in later phases. The mesh
        teardown inside the failed recv cascades the detection to every
        survivor within one receive."""
        rows: List[Optional[np.ndarray]] = [None] * self._world
        recv_unix: Dict[int, float] = {}
        for p in range(self._world):
            if p == self._pid:
                continue
            try:
                rows[p] = np.frombuffer(
                    self._mesh.recv(
                        p, self._stream, step, _KIND_HEADER
                    ),
                    dtype=np.int64,
                )
            except PodPeerDeadError as e:
                print(f"WARNING: {e}; converting to the synchronized "
                      "-2 failure shape.", flush=True)
                rows[p] = np.full(n_fields, -2, np.int64)
                continue
            recv_unix[p] = time.time()
        # One instant per peer AFTER the loop — the recv path itself
        # stays untouched. send_unix is when WE posted this step's
        # header, recv_unix when the peer's arrived: the (send, recv)
        # pair this process contributes to the midpoint offset estimate
        # (the peer's mirror-image instant completes the round trip).
        from spark_examples_tpu import obs

        for p, rts in recv_unix.items():
            obs.instant(
                "pod.exchange_ts",
                scope="t",
                me=self._pid,
                peer=p,
                step=step,
                stream=self._stream,
                send_unix=self._last_send_unix,
                recv_unix=rts,
            )
        return np.stack(
            [
                r if r is not None else self._own_header
                for r in rows
            ]
        ).reshape(self._world, n_fields)

    def post_confirm(self, step: int, ok: bool) -> None:
        self._own_confirm = np.int64(0 if ok else -2)
        self._post_all(
            step,
            _KIND_CONFIRM,
            np.array([self._own_confirm], np.int64).tobytes(),
        )

    def gather_confirms(self, step: int) -> np.ndarray:
        """(world,) int64 — 0 ok / −2 payload-construction failure (a
        DEAD peer reads as −2 too: same synchronized fail-everywhere
        raise, see :meth:`gather_headers`)."""
        vals = np.empty(self._world, np.int64)
        for p in range(self._world):
            if p == self._pid:
                vals[p] = self._own_confirm
                continue
            try:
                vals[p] = np.frombuffer(
                    self._mesh.recv(
                        p, self._stream, step, _KIND_CONFIRM
                    ),
                    dtype=np.int64,
                )[0]
            except PodPeerDeadError as e:
                print(f"WARNING: {e}; converting to the synchronized "
                      "-2 failure shape.", flush=True)
                vals[p] = -2
        return vals

    def post_check(self, step: int, digest: int) -> None:
        """Post this process's collective-congruence digest for one
        step (non-negative int64 — see utils/collectivecheck). Only
        call when the gathered headers agreed every live process has
        the check enabled."""
        self._own_check = np.int64(digest)
        self._post_all(
            step,
            _KIND_CHECK,
            np.array([self._own_check], np.int64).tobytes(),
        )

    def gather_checks(self, step: int) -> np.ndarray:
        """(world,) int64 — every process's step digest (own value
        included, like the header/confirm gathers)."""
        vals = np.empty(self._world, np.int64)
        for p in range(self._world):
            if p == self._pid:
                vals[p] = self._own_check
                continue
            vals[p] = np.frombuffer(
                self._mesh.recv(p, self._stream, step, _KIND_CHECK),
                dtype=np.int64,
            )[0]
        return vals

    def post_payload(self, step: int, mat: np.ndarray) -> None:
        self._post_all(
            step, _KIND_PAYLOAD, np.ascontiguousarray(mat).tobytes()
        )

    def get_payload(
        self,
        step: int,
        peer: int,
        shape: Tuple[int, ...],
        dtype: Any = np.int32,
    ) -> np.ndarray:
        raw = np.frombuffer(
            self._mesh.recv(peer, self._stream, step, _KIND_PAYLOAD),
            dtype=dtype,
        )
        return raw.reshape(shape)

    def close(self) -> None:
        """Stream teardown: nothing to reclaim — sockets persist for
        the process lifetime and every frame of a completed stream has
        been consumed (synchronized failures stop all peers at the
        same frame boundary)."""

    def poison(self) -> None:
        """Abandoned-stream teardown: see :meth:`_PodSocketMesh.poison`."""
        self._mesh.poison()


@dataclass
class PodSlot:
    """One agreed protocol step, staged for the consumer."""

    step: int
    route: str  # "scatter" | "dense"
    gathered: Optional[np.ndarray]  # scatter: global carrier matrix
    local: Optional[np.ndarray]  # dense: this process's packed panel
    nnz: int
    variants: int
    windows: int  # local windows coalesced into this step's gang
    # scatter: every carrier count of ``gathered`` fits int8
    counts_fit_int8: bool = False


_DONE = object()


class SlotPipeline:
    """Depth-D staged pipeline between the sync thread and the consumer.

    ``produce()`` returns a :class:`PodSlot`, ``None`` when the stream
    has drained, or raises (protocol failures — already synchronized
    across processes by the exchange). Results stage into a bounded
    queue of ``depth`` slots; the consumer's iterator yields them in
    order and re-raises the producer's exception at its slot position.
    ``depth == 0`` degrades to inline lockstep (no thread): one protocol
    step per consumer pull — the ablation/debug mode.
    """

    def __init__(
        self, produce: Callable[[], Optional[PodSlot]], depth: int
    ) -> None:
        if depth < 0:
            raise ValueError(f"pipeline depth must be >= 0, got {depth}")
        self._produce = produce
        self._depth = depth
        self._q: queue.Queue = queue.Queue(maxsize=max(depth, 1))
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def _put(self, item: Any) -> bool:
        # Bounded put that gives up when the consumer abandoned the
        # stream — a blocked q.put with no reader would leak the thread
        # (same discipline as arrays/feed.device_prefetch).
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _run(self) -> None:
        try:
            while not self._stop.is_set():
                slot = self._produce()
                if slot is None:
                    self._put(_DONE)
                    return
                if not self._put(slot):
                    return
        except BaseException as e:  # noqa: BLE001 — re-raised in consumer
            self._put(e)

    def __iter__(self) -> Iterator[PodSlot]:
        if self._depth == 0:
            while True:
                slot = self._produce()
                if slot is None:
                    return
                yield slot
        self._thread = threading.Thread(
            target=self._run, name="pod-sparse-sync", daemon=True
        )
        self._thread.start()
        try:
            while True:
                item = self._q.get()
                if item is _DONE:
                    return
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            # Consumer abandoned the iterator (close/GeneratorExit or an
            # exception in its loop body): release the sync thread.
            self._stop.set()