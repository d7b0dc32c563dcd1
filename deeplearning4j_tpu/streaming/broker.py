"""Message broker SPI + in-memory and TCP implementations.

Parity: the Kafka producer/consumer pair in
``dl4j-streaming/.../kafka/NDArrayKafkaClient.java`` (+
``NDArrayPublisher``/``NDArrayConsumer``). The SPI keeps the pipeline
layer transport-agnostic; ``InMemoryBroker`` is the test/dev transport,
``TcpBroker(Server)`` is a dependency-free network transport with
length-prefixed frames and per-topic FIFO queues (at-most-once, one
consumer group — the subset of Kafka semantics the reference pipelines
actually use).

The server runs a ``selectors``-based reactor by default: one event
loop owns every connection, the topic queues, and the long-poll parking
lot, so the data plane needs no server-side locks at all and scales to
thousands of idle long-pollers without a thread each. The pre-reactor
thread-per-connection server is kept behind ``reactor=False``.
"""

from __future__ import annotations

import collections
import logging
import queue
import random
import selectors
import socket
import socketserver
import struct
import threading
import time
from typing import Deque, Dict, Optional, Tuple

from deeplearning4j_tpu.monitor import record_fault

logger = logging.getLogger("deeplearning4j_tpu")

_MAX_FRAME = 1 << 30

#: Wire-v4 ping prologue. ``ping()`` rides the v4 binary header: the 'G'
#: payload opens with this magic + the speaker's wire version, and the
#: server echoes its own. Mirrored from ``serving.wire`` (which imports
#: the serving package and therefore, transitively, this module — the
#: constants live here to keep the layering acyclic; a lint pins them
#: equal to ``wire.WIRE_MAGIC``/``wire.WIRE_VERSION``).
PING_MAGIC = b"\xd4\x0a"
PING_VERSION = 4


class BrokerUnavailable(ConnectionError):
    """The broker could not be reached within the bounded reconnect
    budget. Distinct from ``consume`` returning ``None`` — that is a
    genuine long-poll timeout (broker healthy, topic empty); this means
    the transport itself is down and the caller should fail over or
    surface the outage instead of treating it as an idle stream."""


class MessageBroker:
    """Transport SPI: byte payloads on named topics.

    Liveness: ``ping()`` performs one cheap round-trip against the
    transport (raises on a dead one) and every successful operation
    refreshes ``last_seen`` (``time.monotonic()``), so a health plane
    can read connection liveness directly instead of inferring death
    from consume timeouts."""

    #: monotonic timestamp of the last successful broker round-trip
    #: (None until the first one).
    last_seen: Optional[float] = None

    def publish(self, topic: str, payload: bytes) -> None:
        raise NotImplementedError

    def consume(self, topic: str, timeout: Optional[float] = None) -> Optional[bytes]:
        """Pop the next payload, blocking up to ``timeout`` seconds.
        Returns None on timeout."""
        raise NotImplementedError

    def ping(self) -> float:
        """One liveness round-trip; returns the RTT in seconds and
        refreshes ``last_seen``. Raises (e.g.
        :class:`BrokerUnavailable`) when the transport is dead."""
        t0 = time.monotonic()
        self.last_seen = time.monotonic()
        return time.monotonic() - t0

    def close(self) -> None:
        pass


class InMemoryBroker(MessageBroker):
    """Per-topic FIFO queues in-process."""

    def __init__(self):
        self._topics: Dict[str, "queue.Queue[bytes]"] = {}
        self._lock = threading.Lock()
        self.last_seen: Optional[float] = None

    def ping(self) -> float:
        t0 = time.monotonic()
        with self._lock:
            pass  # in-process: the lock round-trip IS the transport
        self.last_seen = time.monotonic()
        return self.last_seen - t0

    def _q(self, topic: str) -> "queue.Queue[bytes]":
        with self._lock:
            if topic not in self._topics:
                self._topics[topic] = queue.Queue()
            return self._topics[topic]

    def publish(self, topic: str, payload: bytes) -> None:
        self._q(topic).put(bytes(payload))
        self.last_seen = time.monotonic()

    def consume(self, topic: str, timeout: Optional[float] = None) -> Optional[bytes]:
        try:
            msg = self._q(topic).get(timeout=timeout)
        except queue.Empty:
            msg = None
        self.last_seen = time.monotonic()
        return msg


# --- TCP transport ----------------------------------------------------------
# Frame: 1-byte op ('P' publish / 'C' consume / 'G' ping) + u16 topic len +
#        topic utf-8 + u32 payload len + payload.
# Reply: 1-byte status (1 = payload follows / 0 = none-or-ack) + u32 len +
#        payload. The status byte keeps zero-length payloads distinguishable
#        from a consume poll timeout. 'G' frames carry an empty topic; their
#        payload opens with PING_MAGIC + the client's wire version and the
#        server echoes PING_MAGIC + its own version (status 1) — a liveness
#        round-trip that doubles as wire-version discovery and refreshes the
#        server's per-peer last_seen table. Pre-v4 peers send/ack empty 'G'
#        frames; both sides treat a missing magic as "wire v3 peer".

def _send_frame(sock: socket.socket, op: bytes, topic: str, payload: bytes) -> None:
    t = topic.encode()
    sock.sendall(op + struct.pack(">HI", len(t), len(payload)) + t + payload)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    chunks = []
    while n:
        chunk = sock.recv(min(n, 1 << 20))
        if not chunk:
            raise ConnectionError("peer closed mid-frame")
        chunks.append(chunk)
        n -= len(chunk)
    return b"".join(chunks)


def _ping_reply() -> Tuple[bytes, bytes]:
    return b"\x01", PING_MAGIC + bytes([PING_VERSION])


class _BrokerHandler(socketserver.BaseRequestHandler):
    """Thread-per-connection handler (legacy ``reactor=False`` path)."""

    def handle(self):
        broker: InMemoryBroker = self.server._broker  # type: ignore[attr-defined]
        timeout = self.server._poll_timeout  # type: ignore[attr-defined]
        peers = self.server._peers  # type: ignore[attr-defined]
        peer = "%s:%s" % self.client_address[:2]
        try:
            while True:
                try:
                    op = _recv_exact(self.request, 1)
                except ConnectionError:
                    return
                tlen, plen = struct.unpack(">HI", _recv_exact(self.request, 6))
                if plen > _MAX_FRAME:
                    return
                topic = _recv_exact(self.request, tlen).decode()
                payload = _recv_exact(self.request, plen)
                if op == b"P":
                    broker.publish(topic, payload)
                    status, reply = b"\x00", b""
                elif op == b"C":
                    msg = broker.consume(topic, timeout=timeout)
                    status = b"\x00" if msg is None else b"\x01"
                    reply = msg or b""
                elif op == b"G":
                    if payload.startswith(PING_MAGIC):
                        status, reply = _ping_reply()
                    else:
                        status, reply = b"\x00", b""
                else:
                    return
                peers[peer] = time.monotonic()
                self.request.sendall(
                    status + struct.pack(">I", len(reply)) + reply)
        finally:
            peers.pop(peer, None)


class _Conn:
    """Reactor-side connection state. ``rbuf`` is the one preallocated
    recv buffer for the connection's lifetime (grown geometrically,
    never reallocated per frame); ``rlen`` is the filled prefix."""

    __slots__ = ("sock", "peer", "rbuf", "rlen", "out", "waiting")

    def __init__(self, sock: socket.socket, peer: str):
        self.sock = sock
        self.peer = peer
        self.rbuf = bytearray(64 << 10)
        self.rlen = 0
        self.out = bytearray()
        # (topic, deadline) while parked on an empty-topic long poll.
        self.waiting: Optional[Tuple[str, float]] = None


class _Reactor:
    """Single-threaded ``selectors`` event loop owning every broker
    connection, the topic queues, and the long-poll parking lot.

    All state below is loop-confined: only the reactor thread touches
    ``_topics``/``_parked``/connection objects, so the server side of
    the data plane holds zero locks (``peers()``/``address`` read
    snapshot-safe primitives under the GIL). Long polls park the
    connection instead of blocking a thread: a publish fulfils the
    oldest parked waiter inline, and the loop tick expires the rest."""

    def __init__(self, host: str, port: int, poll_timeout: float):
        self._poll_timeout = float(poll_timeout)
        self._listen = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listen.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listen.bind((host, port))
        self._listen.listen(1024)
        self._listen.setblocking(False)
        self.address = self._listen.getsockname()[:2]
        self._sel = selectors.DefaultSelector()
        self._sel.register(self._listen, selectors.EVENT_READ, None)
        self._wake_r, self._wake_w = socket.socketpair()
        self._wake_r.setblocking(False)
        self._sel.register(self._wake_r, selectors.EVENT_READ, None)
        self._topics: Dict[str, Deque[bytes]] = {}
        self._parked: Dict[str, Deque[_Conn]] = {}
        self.peers: Dict[str, float] = {}
        self._stopping = False

    # ------------------------------------------------------------ loop

    def run(self) -> None:
        try:
            while not self._stopping:
                timeout = self._poll_timeout
                if any(self._parked.values()):
                    now = time.monotonic()
                    soonest = min(c.waiting[1]
                                  for dq in self._parked.values() for c in dq)
                    timeout = min(timeout, max(0.0, soonest - now))
                for key, mask in self._sel.select(timeout):
                    if key.fileobj is self._listen:
                        self._accept()
                    elif key.fileobj is self._wake_r:
                        try:
                            self._wake_r.recv(4096)
                        except OSError:
                            pass
                    else:
                        conn: _Conn = key.data
                        if mask & selectors.EVENT_WRITE:
                            self._flush(conn)
                        if mask & selectors.EVENT_READ and conn.sock.fileno() >= 0:
                            self._readable(conn)
                self._expire_parked()
        finally:
            for key in list(self._sel.get_map().values()):
                if isinstance(key.data, _Conn):
                    self._close_conn(key.data)
            self._sel.close()
            for s in (self._listen, self._wake_r, self._wake_w):
                try:
                    s.close()
                except OSError:
                    pass

    def wake(self) -> None:
        try:
            self._wake_w.send(b"\x00")
        except OSError:
            pass

    def stop(self) -> None:
        self._stopping = True
        self.wake()

    # ------------------------------------------------------ connections

    def _accept(self) -> None:
        while True:
            try:
                sock, addr = self._listen.accept()
            except (BlockingIOError, OSError):
                return
            sock.setblocking(False)
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            conn = _Conn(sock, "%s:%s" % addr[:2])
            self._sel.register(sock, selectors.EVENT_READ, conn)

    def _close_conn(self, conn: _Conn) -> None:
        if conn.waiting is not None:
            topic = conn.waiting[0]
            dq = self._parked.get(topic)
            if dq is not None:
                try:
                    dq.remove(conn)
                except ValueError:
                    pass
            conn.waiting = None
        try:
            self._sel.unregister(conn.sock)
        except (KeyError, ValueError):
            pass
        try:
            conn.sock.close()
        except OSError:
            pass
        self.peers.pop(conn.peer, None)

    def _set_interest(self, conn: _Conn, write: bool) -> None:
        mask = selectors.EVENT_READ
        if write:
            mask |= selectors.EVENT_WRITE
        try:
            self._sel.modify(conn.sock, mask, conn)
        except (KeyError, ValueError, OSError):
            pass

    # ------------------------------------------------------------- read

    def _readable(self, conn: _Conn) -> None:
        if conn.rlen == len(conn.rbuf):
            conn.rbuf.extend(bytes(len(conn.rbuf)))  # grow 2x, keep prefix
        try:
            with memoryview(conn.rbuf) as mv:
                got = conn.sock.recv_into(mv[conn.rlen:])
        except BlockingIOError:
            return
        except OSError:
            self._close_conn(conn)
            return
        if got == 0:
            self._close_conn(conn)
            return
        conn.rlen += got
        self._process(conn)

    def _process(self, conn: _Conn) -> None:
        consumed = 0
        # A parked connection stops parsing: its client is mid-long-poll
        # and serialized, so anything else in the buffer waits its turn.
        while conn.waiting is None:
            avail = conn.rlen - consumed
            if avail < 7:
                break
            tlen, plen = struct.unpack_from(">HI", conn.rbuf, consumed + 1)
            if plen > _MAX_FRAME:
                self._close_conn(conn)
                return
            total = 7 + tlen + plen
            if avail < total:
                need = consumed + total
                while len(conn.rbuf) < need:
                    conn.rbuf.extend(bytes(len(conn.rbuf)))
                break
            op = conn.rbuf[consumed]
            topic = bytes(conn.rbuf[consumed + 7:consumed + 7 + tlen]).decode()
            payload = bytes(conn.rbuf[consumed + 7 + tlen:consumed + total])
            consumed += total
            self.peers[conn.peer] = time.monotonic()
            if op == ord("P"):
                self._publish(topic, payload)
                self._reply(conn, b"\x00", b"")
            elif op == ord("C"):
                dq = self._topics.get(topic)
                if dq:
                    self._reply(conn, b"\x01", dq.popleft())
                else:
                    conn.waiting = (topic,
                                    time.monotonic() + self._poll_timeout)
                    self._parked.setdefault(
                        topic, collections.deque()).append(conn)
            elif op == ord("G"):
                if payload.startswith(PING_MAGIC):
                    self._reply(conn, *_ping_reply())
                else:
                    self._reply(conn, b"\x00", b"")
            else:
                self._close_conn(conn)
                return
        if consumed:
            remaining = conn.rlen - consumed
            if remaining:
                conn.rbuf[0:remaining] = conn.rbuf[consumed:conn.rlen]
            conn.rlen = remaining

    # ------------------------------------------------------- topics/poll

    def _publish(self, topic: str, payload: bytes) -> None:
        dq = self._parked.get(topic)
        while dq:
            waiter = dq.popleft()
            if waiter.waiting is None:
                continue
            waiter.waiting = None
            self._reply(waiter, b"\x01", payload)
            self._process(waiter)  # parse frames queued behind the poll
            return
        self._topics.setdefault(topic, collections.deque()).append(payload)

    def _expire_parked(self) -> None:
        now = time.monotonic()
        for topic in list(self._parked):
            dq = self._parked[topic]
            while dq and dq[0].waiting is not None and dq[0].waiting[1] <= now:
                waiter = dq.popleft()
                waiter.waiting = None
                self._reply(waiter, b"\x00", b"")
                self._process(waiter)
            while dq and dq[0].waiting is None:
                dq.popleft()
            if not dq:
                del self._parked[topic]

    # ------------------------------------------------------------ write

    def _reply(self, conn: _Conn, status: bytes, payload: bytes) -> None:
        conn.out += status + struct.pack(">I", len(payload)) + payload
        self._flush(conn)

    def _flush(self, conn: _Conn) -> None:
        try:
            while conn.out:
                sent = conn.sock.send(conn.out)
                if sent == 0:
                    break
                del conn.out[:sent]
        except BlockingIOError:
            pass
        except OSError:
            self._close_conn(conn)
            return
        self._set_interest(conn, write=bool(conn.out))


class TcpBrokerServer:
    """Broker daemon: any number of TCP clients publish/consume.
    ``port=0`` auto-picks. ``reactor=True`` (default) serves every
    connection from one ``selectors`` event loop — long polls park the
    connection instead of pinning a thread, and the topic state needs no
    locks because only the loop touches it. ``reactor=False`` keeps the
    pre-v4 thread-per-connection ``socketserver`` implementation (topics
    in an ``InMemoryBroker``) as a measured baseline."""

    def __init__(self, port: int = 0, host: str = "127.0.0.1",
                 poll_timeout: float = 0.25, reactor: bool = True):
        self.reactor = bool(reactor)
        self._thread: Optional[threading.Thread] = None
        if self.reactor:
            self._core: Optional[_Reactor] = _Reactor(host, port, poll_timeout)
            self._srv = None
        else:
            self._core = None
            self._srv = socketserver.ThreadingTCPServer(
                (host, port), _BrokerHandler)
            self._srv.daemon_threads = True
            self._srv._broker = InMemoryBroker()  # type: ignore[attr-defined]
            self._srv._poll_timeout = poll_timeout  # type: ignore[attr-defined]
            self._srv._peers = {}  # type: ignore[attr-defined]

    @property
    def address(self):
        if self._core is not None:
            return self._core.address
        return self._srv.server_address[:2]

    def peers(self) -> Dict[str, float]:
        """Connected clients → monotonic ``last_seen`` of their most
        recent completed frame (a peer that vanished without a clean
        close disappears once the loop — or its handler thread on the
        legacy path — notices the dead socket)."""
        if self._core is not None:
            return dict(self._core.peers)
        return dict(self._srv._peers)  # type: ignore[attr-defined]

    def start(self) -> "TcpBrokerServer":
        target = self._core.run if self._core is not None \
            else self._srv.serve_forever
        self._thread = threading.Thread(target=target,
                                        name="dl4j-tpu-broker", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        if self._core is not None:
            self._core.stop()
        else:
            self._srv.shutdown()
            self._srv.server_close()
        if self._thread:
            self._thread.join(timeout=5)


class TcpBroker(MessageBroker):
    """Client half: a ``MessageBroker`` over one TCP connection to a
    :class:`TcpBrokerServer`. Consume long-polls: the server replies
    empty after its poll timeout and the client retries until the
    caller's ``timeout`` budget runs out.

    Transport resilience: a dropped connection (broker restart, network
    blip) triggers reconnect-and-resend with jittered exponential
    backoff, bounded by ``max_retries``; when the budget is exhausted
    every operation raises :class:`BrokerUnavailable` — so ``consume``
    returning ``None`` ALWAYS means "topic idle", never "transport
    dead". The jitter RNG is seeded (deterministic fleets don't
    thundering-herd a restarting broker on the same schedule). Retried
    publishes are at-least-once: the op may have been applied just
    before the connection died.

    Socket hygiene: ``TCP_NODELAY`` is set (Nagle would stall the small
    per-burst chunk frames behind unacked data), and replies land in one
    preallocated per-connection recv buffer instead of per-frame
    ``bytes`` concatenation. Transport-fault metrics are recorded after
    ``_lock`` is released (``record_fault`` takes registry locks; the
    hot path must not nest them under the connection lock)."""

    def __init__(self, host: str, port: int, connect_timeout: float = 5.0,
                 max_retries: int = 4, backoff_base_s: float = 0.05,
                 backoff_max_s: float = 2.0, seed: int = 0):
        self._host = host
        self._port = port
        self._connect_timeout = connect_timeout
        self.max_retries = max(0, int(max_retries))
        self._backoff_base = float(backoff_base_s)
        self._backoff_max = float(backoff_max_s)
        self._rng = random.Random(seed)
        self._lock = threading.Lock()
        self._sock: Optional[socket.socket] = None
        self._rbuf = bytearray(64 << 10)
        self._closed = False
        self._fault_backlog = 0
        #: wire version advertised by the server on the last ``ping()``
        #: (None until one completes; 3 when the peer predates v4).
        self.peer_wire: Optional[int] = None
        self.last_seen: Optional[float] = None
        try:
            with self._lock:
                self._ensure_connected(initial=True)
        finally:
            self._drain_faults()

    # ----------------------------------------------------- connection

    def _connect_once(self) -> None:
        self._sock = socket.create_connection(
            (self._host, self._port), timeout=self._connect_timeout)
        self._sock.settimeout(None)  # long-poll replies block
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

    def _drop(self) -> None:
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
            self._sock = None

    def _backoff(self, attempt: int) -> float:
        delay = min(self._backoff_max, self._backoff_base * (2 ** attempt))
        return delay * (0.5 + self._rng.random() / 2)  # jitter: [0.5, 1.0)x

    def _note_fault(self) -> None:
        # Deferred: counted under _lock, recorded by _drain_faults()
        # outside it, so registry locks never nest under the conn lock.
        self._fault_backlog += 1

    def _drain_faults(self) -> None:
        n, self._fault_backlog = self._fault_backlog, 0
        for _ in range(n):
            record_fault("transport")

    def _ensure_connected(self, initial: bool = False) -> None:
        if self._closed:
            raise BrokerUnavailable("broker client is closed")
        if self._sock is not None:
            return
        last: Optional[Exception] = None
        for attempt in range(1 + self.max_retries):
            if attempt > 0 or not initial:
                time.sleep(self._backoff(attempt))
            try:
                self._connect_once()
                if last is not None:
                    logger.info("TcpBroker: reconnected to %s:%s after %d "
                                "attempt(s)", self._host, self._port, attempt)
                return
            except OSError as e:
                last = e
                self._note_fault()
                logger.warning(
                    "TcpBroker: connect to %s:%s failed (%s: %s), attempt "
                    "%d/%d", self._host, self._port, type(e).__name__, e,
                    attempt + 1, 1 + self.max_retries)
        raise BrokerUnavailable(
            f"broker {self._host}:{self._port} unreachable after "
            f"{1 + self.max_retries} attempts") from last

    # ------------------------------------------------------ transport

    def _recv_into(self, n: int) -> memoryview:
        """Read exactly ``n`` bytes into the connection's preallocated
        recv buffer (grown geometrically when a reply outsizes it) and
        return a view of the filled prefix. The view is only valid
        until the next ``_recv_into`` call."""
        if len(self._rbuf) < n:
            self._rbuf = bytearray(max(n, 2 * len(self._rbuf)))
        got = 0
        with memoryview(self._rbuf) as mv:
            while got < n:
                r = self._sock.recv_into(mv[got:n])
                if not r:
                    raise ConnectionError("peer closed mid-frame")
                got += r
        return memoryview(self._rbuf)[:n]

    def _roundtrip(self, op: bytes, topic: str, payload: bytes):
        try:
            with self._lock:
                return self._roundtrip_locked(op, topic, payload)
        finally:
            self._drain_faults()

    def _roundtrip_locked(self, op: bytes, topic: str, payload: bytes):
        last: Optional[Exception] = None
        for attempt in range(1 + self.max_retries):
            try:
                self._ensure_connected()
                _send_frame(self._sock, op, topic, payload)
                with self._recv_into(5) as head:
                    ok = head[0] == 1
                    (rlen,) = struct.unpack_from(">I", head, 1)
                with self._recv_into(rlen) as body:
                    reply = bytes(body)
                self.last_seen = time.monotonic()
                return ok, reply
            except BrokerUnavailable:
                raise
            except (OSError, ConnectionError, struct.error) as e:
                last = e
                self._note_fault()
                logger.warning(
                    "TcpBroker: %s on %s failed mid-roundtrip (%s: %s) — "
                    "reconnecting", op, topic, type(e).__name__, e)
                self._drop()
        raise BrokerUnavailable(
            f"broker {self._host}:{self._port} lost mid-operation and "
            f"unreachable after {1 + self.max_retries} attempts") from last

    def publish(self, topic: str, payload: bytes) -> None:
        self._roundtrip(b"P", topic, payload)

    def ping(self) -> float:
        """One 'G' liveness round-trip; returns the RTT in seconds and
        refreshes ``last_seen``. The ping rides the wire-v4 header
        (PING_MAGIC + version) and records the server's echoed version
        in ``peer_wire`` (3 when the peer predates v4). Raises
        :class:`BrokerUnavailable` when the reconnect budget is
        exhausted — a clean positive death signal, so health planes
        never have to infer a dead transport from consume timeouts."""
        t0 = time.monotonic()
        ok, reply = self._roundtrip(
            b"G", "", PING_MAGIC + bytes([PING_VERSION]))
        if ok and reply[:2] == PING_MAGIC and len(reply) >= 3:
            self.peer_wire = reply[2]
        else:
            self.peer_wire = 3
        return time.monotonic() - t0

    def consume(self, topic: str, timeout: Optional[float] = None) -> Optional[bytes]:
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            found, reply = self._roundtrip(b"C", topic, b"")
            if found:
                return reply
            if deadline is not None and time.monotonic() >= deadline:
                return None  # genuine poll timeout — broker is healthy

    def close(self) -> None:
        with self._lock:
            self._closed = True
            self._drop()
