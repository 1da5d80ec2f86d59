"""TCP bus backend: a socket broker + remote client behind the EventBus
seam — the second BusBackend implementation the pluggable-bus contract
demands (SURVEY.md §5 distributed backend: "Kafka-shaped bus for
host-side transport"; the reference's Kafka is exactly this role [U];
reference mount empty, see provenance banner).

Topology: ``BusBrokerServer`` wraps a real in-proc ``EventBus`` (so all
log/cursor/backpressure semantics are literally the same code) behind a
length-prefixed asyncio TCP protocol; ``RemoteEventBus`` implements the
EventBus surface over one multiplexed connection, so a
``SiteWhereInstance`` runs unchanged against either backend.

Wire format: 4-byte big-endian length + pickle, deserialized through
the RESTRICTED unpickler (``runtime.safepickle``): only stdlib
containers, numpy reconstruction, and ``sitewhere_tpu.*`` classes load —
a compromised peer or tampered frame cannot smuggle an
arbitrary-constructor gadget. Payloads are arbitrary framework objects
(columnar ``MeasurementBatch`` on the hot path) exactly as in-proc.
Batches inside the pickle stream ride the raw-buffer wire codec
(``core.batch``): numeric columns as dtype-tagged raw buffers, token
columns as (vocab, int32 inverse) — so the consumer decodes a batch with
one buffer copy, inherits the group indexes for free, and never pays
per-row pickle ops (docs/PERFORMANCE.md "Raw-buffer wire codec").

Protocol: requests ``(req_id, op, args)``; responses ``(req_id, ok,
value)``. ``req_id is None`` marks fire-and-forget (no response) — used
by the sync-callable API points (subscribe/seek/publish_nowait/...)
whose in-proc counterparts are synchronous: the frame is written
immediately on the socket, so ordering against later awaited calls on
the same connection is preserved.
"""

from __future__ import annotations

import asyncio
import itertools
import json
import logging
import os
import pickle
import random
import struct
from collections import deque
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

from sitewhere_tpu.runtime import safepickle
from sitewhere_tpu.runtime.bus import EventBus, FaultPlan, TopicNaming
from sitewhere_tpu.runtime.dlog import LeaseJournal
from sitewhere_tpu.runtime.hostlease import LeaseTable
from sitewhere_tpu.runtime.lifecycle import LifecycleComponent, cancel_and_wait
from sitewhere_tpu.runtime.metrics import MetricsRegistry

logger = logging.getLogger("sitewhere.netbus")

# server-side cap on one blocking consume poll (seconds): a vanished
# client must not pin a poll forever. Clients preserve longer timeouts
# by re-issuing capped polls (RemoteEventBus.consume); a caller going
# through ``BusBrokerServer`` directly has its longer timeout TRUNCATED
# to this — logged + counted (netbus_consume_timeout_clamped_total)
# instead of silently, since a single poll returning early looks
# exactly like an empty topic to the caller.
CONSUME_TIMEOUT_CAP_S = 30.0

_LEN = struct.Struct(">I")
MAX_FRAME = 256 * 1024 * 1024


class FrameTooLargeError(ValueError):
    """A frame that would exceed MAX_FRAME, rejected on the WRITE path.

    The read path always enforced the cap; without the write-path check an
    oversized payload reached the peer, which dropped the whole connection
    — poisoning every topic multiplexed on it. Rejecting at the producer
    turns that into a per-call error naming the offending topic."""


def _dump(obj: Any, topic: Optional[str] = None) -> Tuple[bytes, bytes]:
    """Serialize one frame as ``(length-header, payload)``.

    ``MeasurementBatch`` payloads ride the raw-buffer wire codec
    (``core.batch.MeasurementBatch.__reduce__``): numeric columns are
    dtype-tagged raw buffers inside the pickle stream instead of
    per-element pickle ops. The two parts go out via ``writelines`` so a
    large payload is never re-copied into one contiguous
    header+payload bytes object."""
    data = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    if len(data) > MAX_FRAME:
        where = f" for topic '{topic}'" if topic else ""
        raise FrameTooLargeError(
            f"refusing to send a {len(data)}-byte frame{where}: exceeds "
            f"MAX_FRAME ({MAX_FRAME} bytes); the peer would drop the "
            f"connection"
        )
    return _LEN.pack(len(data)), data


class BrokerNotPrimaryError(RuntimeError):
    """A data-plane op reached a warm STANDBY broker. Standbys serve
    only the replication/handshake plane until promoted; a failover-
    aware client treats this (and the handshake's role field) as "try
    the next endpoint", never as a caller-visible failure."""


class BrokerGenerationFencedError(RuntimeError):
    """An append reached a broker whose generation was superseded (a
    standby promoted past it). The payload is still caller-side, so the
    awaited paths ERROR — the client fails over and retries against the
    live primary; nothing is double-served from the zombie."""


class BrokerGeneration:
    """Durable broker generation + fenced flag — the host-epoch fencing
    pattern one level up (docs/ROBUSTNESS.md "Broker fault domain").

    Promotion bumps the generation DURABLY (tmp + fsync + atomic
    replace, the same commit-point pattern as the journals); every
    client handshake (``hello``) carries the highest generation its
    sender has seen, so a zombie primary learns it was superseded from
    the FIRST informed peer and fences itself durably — its appends
    divert from that instant, and stay diverted across its own
    restarts. With no path the state is process-local (in-proc test
    brokers, memory buses)."""

    def __init__(self, path: Optional[Path] = None) -> None:
        self.path = path
        self.generation = 1
        self.fenced_by: Optional[int] = None
        # highest peer generation observed (hellos + replication polls);
        # promotion bumps past it so "newer generation wins" stays
        # decidable even when the old primary was never reachable
        self.seen = 0
        if path is not None and path.exists():
            try:
                st = json.loads(path.read_text())
                self.generation = int(st.get("generation", 1))
                fb = st.get("fenced_by")
                self.fenced_by = int(fb) if fb is not None else None
            except (ValueError, OSError):
                logger.warning("unreadable broker generation file %s — "
                               "starting at generation 1", path)

    @property
    def fenced(self) -> bool:
        return self.fenced_by is not None

    def _persist(self) -> None:
        if self.path is None:
            return
        tmp = self.path.with_suffix(".tmp")
        with open(tmp, "w") as f:
            json.dump({"generation": self.generation,
                       "fenced_by": self.fenced_by}, f)
            f.flush()
            os.fsync(f.fileno())
        tmp.replace(self.path)

    def bump_to(self, generation: int) -> None:
        self.generation = int(generation)
        self.fenced_by = None
        self._persist()

    def fence(self, peer_generation: int) -> None:
        self.seen = max(self.seen, int(peer_generation))
        self.fenced_by = int(peer_generation)
        self._persist()


class _ReplRing:
    """Bounded in-memory replication ring: every mutation the primary
    applies (WAL appends, journaled cursor commits, lease ops, control
    ops) is appended as a seq-numbered record; the warm standby drains
    it via the ``repl_poll`` long-poll. Bounded like every other queue
    in the system (tools/check_queues.py): when a standby lags more
    than ``capacity`` records, the OLDEST are evicted (counted
    ``netbus_repl_evicted_total``) and the poller is told to RESYNC
    from a full snapshot — bounded broker memory beats an unbounded
    backlog held hostage by a slow standby."""

    def __init__(
        self,
        capacity: int = 8192,
        metrics: Optional[MetricsRegistry] = None,
    ) -> None:
        self.capacity = int(capacity)
        self.metrics = metrics or MetricsRegistry()
        self._buf: deque = deque()
        self.base_seq = 0   # seq of _buf[0]
        self.head_seq = 0   # next seq to assign
        self.data_event = asyncio.Event()

    def append(self, rec: tuple) -> int:
        seq = self.head_seq
        self.head_seq += 1
        self._buf.append(rec)
        if len(self._buf) > self.capacity:
            self._buf.popleft()
            self.base_seq += 1
            self.metrics.counter("netbus_repl_evicted_total").inc()
        self.metrics.gauge("netbus_repl_ring_depth").set(len(self._buf))
        self.data_event.set()
        return seq

    def read(
        self, from_seq: int, max_records: int = 1024
    ) -> Tuple[List[tuple], int, bool]:
        """→ (records, next_seq, resync). ``resync`` means ``from_seq``
        was already evicted: the poller must snapshot instead."""
        if from_seq < self.base_seq:
            return [], self.head_seq, True
        start = from_seq - self.base_seq
        recs = list(itertools.islice(self._buf, start, start + max_records))
        return recs, from_seq + len(recs), False


def _publish_topic(op: str, args: tuple) -> Optional[str]:
    """The topic a payload-bearing op targets (for write-path errors)."""
    if op in ("publish", "publish_nowait", "publish_fenced") and args:
        return str(args[0])
    return None


async def _read_frame(reader: asyncio.StreamReader) -> Any:
    head = await reader.readexactly(_LEN.size)
    (n,) = _LEN.unpack(head)
    if n > MAX_FRAME:
        raise ValueError(f"frame too large: {n}")
    return safepickle.loads(await reader.readexactly(n))


class _ConnCtx:
    """Per-connection broker state: the reply writer + its lock, the
    pending consume polls by req_id (cancellable — by the client via
    ``consume_cancel``, or by a lease fence revoking the host's group
    membership), and the host ids whose lease ops arrived on this
    connection (a serving host multiplexes its lease client and its
    consumers over ONE socket, which is what makes fence-time poll
    revocation possible)."""

    __slots__ = ("writer", "write_lock", "consumes", "hosts")

    def __init__(self, writer: asyncio.StreamWriter) -> None:
        self.writer = writer
        self.write_lock = asyncio.Lock()
        self.consumes: Dict[Any, asyncio.Task] = {}
        self.hosts: set = set()


class BusBrokerServer(LifecycleComponent):
    """Socket broker fronting an in-proc EventBus."""

    def __init__(
        self,
        naming: Optional[TopicNaming] = None,
        retention: int = 65536,
        host: str = "127.0.0.1",
        port: int = 0,
        bus: Optional[EventBus] = None,
        metrics: Optional[MetricsRegistry] = None,
        role: str = "primary",
        lease_grace_s: float = 10.0,
        repl_capacity: int = 8192,
    ) -> None:
        super().__init__("bus-broker")
        # pluggable backing bus: pass a dlog.DurableEventBus for a broker
        # whose logs + cursors survive kill -9 (round-4 verdict item 4)
        self.bus = bus if bus is not None else EventBus(naming, retention)
        self.metrics = metrics or MetricsRegistry()
        # broker fault domain (docs/ROBUSTNESS.md "Broker fault
        # domain"): role gates the data plane (standbys only serve the
        # replication/handshake plane until promoted); the durable
        # generation fences a superseded primary's appends; the repl
        # ring feeds the warm standby's WAL/cursor/lease tail
        self.role = role
        self.lease_grace_s = float(lease_grace_s)
        root = getattr(self.bus, "root", None)
        self.generation = BrokerGeneration(
            Path(root) / "generation.json" if root is not None else None)
        lease_journal = None
        if root is not None:
            lease_dir = Path(root) / "leases"
            lease_dir.mkdir(parents=True, exist_ok=True)
            lease_journal = LeaseJournal(lease_dir / "leases.log")
        self.repl_ring = _ReplRing(capacity=repl_capacity,
                                   metrics=self.metrics)
        if hasattr(self.bus, "set_repl_listener"):
            # WAL-level tap: fires synchronously inside append AFTER the
            # flush, so ring order == offset order per partition and a
            # replicated record is never ahead of the primary's own
            # durability point
            self.bus.set_repl_listener(
                lambda t, p, off, payload: self.repl_ring.append(
                    ("wal", t, p, off, payload)))
            # journal-level cursor tap (NOT eager in-memory cursors):
            # replicating only journaled commits preserves at-least-once
            # across failover — the standby's cursors trail, never lead
            self.bus.set_cursor_listener(
                lambda t, g, cur: self.repl_ring.append(("cur", t, g, cur)))
        # host fault domain (docs/ROBUSTNESS.md "Host fault domains"):
        # the broker is the authority on which process holds which
        # slice-set lease, at which epoch — the single place a zombie
        # host's stale-epoch writes can be fenced atomically with the
        # publish they ride on. The journal makes epoch high-water +
        # fences survive broker restart (a restart must not un-fence).
        self.leases = LeaseTable(metrics=self.metrics, journal=lease_journal)
        self._host_conns: Dict[str, set] = {}  # host id → {_ConnCtx}
        self._clamp_logged: set = set()
        self.host = host
        self.port = port
        self.bound_port: Optional[int] = None
        self._server: Optional[asyncio.base_events.Server] = None
        self._conn_tasks: set = set()

    async def on_start(self) -> None:
        self._server = await asyncio.start_server(
            self._serve_conn, self.host, self.port
        )
        self.bound_port = self._server.sockets[0].getsockname()[1]

    async def on_stop(self) -> None:
        if self._server is not None:
            self._server.close()
            # connections first: since Python 3.12.1 wait_closed() waits
            # for every accepted connection to drop, so stopping with a
            # client still attached would never return
            for t in list(self._conn_tasks):
                await cancel_and_wait(t)
            await self._server.wait_closed()
            self._server = None

    async def _serve_conn(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        task = asyncio.current_task()
        self._conn_tasks.add(task)
        conn = _ConnCtx(writer)
        pending: set = set()
        try:
            while True:
                try:
                    req_id, op, args = await _read_frame(reader)
                except (asyncio.IncompleteReadError, ConnectionResetError):
                    return
                except (safepickle.UnpicklingError, ValueError) as exc:
                    # hostile/corrupt frame (gadget class, oversize, bad
                    # shape): drop THIS connection, quietly — the broker
                    # and every other client stay up
                    self._record_error("frame", exc)
                    return
                if op == "consume_cancel":
                    # the client-side consumer task was cancelled (tenant
                    # teardown, handoff): kill its pending long-poll NOW,
                    # before a future publish gets delivered into the void
                    # — the in-proc poll commits the group cursor at
                    # delivery, so a stale poll that outlives its caller
                    # silently eats the next item. Cancelling while the
                    # poll waits is loss-free: nothing is taken until
                    # delivery.
                    t = conn.consumes.get(args[0]) if args else None
                    if t is not None:
                        t.cancel()
                    self.metrics.counter("netbus_consume_cancels_total").inc()
                    continue
                # each request runs in its own task so a long-poll can't
                # block other ops multiplexed on this connection
                t = asyncio.create_task(
                    self._handle(req_id, op, args, conn)
                )
                pending.add(t)
                t.add_done_callback(pending.discard)
                if op == "consume" and req_id is not None:
                    conn.consumes[req_id] = t
                    t.add_done_callback(
                        lambda _t, r=req_id: conn.consumes.pop(r, None)
                    )
        finally:
            for t in list(pending):
                await cancel_and_wait(t)
            for h in conn.hosts:
                conns = self._host_conns.get(h)
                if conns is not None:
                    conns.discard(conn)
                    if not conns:
                        self._host_conns.pop(h, None)
            writer.close()
            self._conn_tasks.discard(task)

    async def _handle(self, req_id, op, args, conn: _ConnCtx) -> None:
        writer, write_lock = conn.writer, conn.write_lock
        try:
            value = await self._dispatch(op, args, conn,
                                         noreply=req_id is None)
            ok = True
        except asyncio.CancelledError:
            raise
        except Exception as exc:  # noqa: BLE001 - errors cross the wire
            value = f"{type(exc).__name__}: {exc}"
            ok = False
            self._record_error(op, exc)
        if req_id is None:
            return
        try:
            frame = _dump((req_id, ok, value))
        except FrameTooLargeError as exc:
            # an oversized RESPONSE (e.g. a giant consume batch) must not
            # poison the connection either — surface it as a call error
            frame = _dump((req_id, False, f"{type(exc).__name__}: {exc}"))
            self._record_error(op, exc)
        try:
            async with write_lock:
                writer.writelines(frame)
                await writer.drain()
        except asyncio.CancelledError:
            if op == "consume" and ok and isinstance(value, list) and value:
                # a consume_cancel (or connection teardown) raced an
                # in-flight delivery: the cursor is already past these
                # items and the reply will never land — at-most-once
                # loses them. Count loudly; the wide stale-poll window
                # is closed by consume_cancel, this is the residual
                # delivery-already-taken instant.
                self.metrics.counter(
                    "netbus_cancelled_delivery_dropped_total"
                ).inc(len(value))
                logger.warning(
                    "consume delivery of %d item(s) dropped by "
                    "cancellation before the reply was written",
                    len(value),
                )
            raise

    def _bind_host_conn(self, host_id: str, conn: Optional[_ConnCtx]) -> None:
        """Remember which connection a host's lease ops ride on — the
        same multiplexed socket carries its consumers, so a fence can
        find (and revoke) the host's parked polls."""
        if conn is None:
            return
        conn.hosts.add(host_id)
        self._host_conns.setdefault(host_id, set()).add(conn)

    def _revoke_host_polls(self, host_id: str) -> None:
        """Fence-time group-membership revocation: cancel every parked
        consume poll on the fenced host's connection(s) and reply ``[]``
        so the client's consumer (if it ever thaws) sees an empty poll,
        not a hang. Cancelling a parked poll is loss-free — the in-proc
        poll takes nothing until delivery. The replies skip ``drain()``
        on purpose: a frozen host isn't reading, and the fence dispatch
        must not block on its socket buffer."""
        for conn in self._host_conns.get(host_id, ()):
            for req_id, t in list(conn.consumes.items()):
                if t.done():
                    continue
                t.cancel()
                self.metrics.counter(
                    "netbus_fence_revoked_polls_total", host=host_id
                ).inc()
                try:
                    conn.writer.writelines(_dump((req_id, True, [])))
                except (ConnectionError, OSError, RuntimeError):
                    pass  # connection already tearing down

    # ops a warm standby still serves: the observability + replication
    # + handshake plane. Everything else raises BrokerNotPrimaryError so
    # a failover-aware client rotates to the real primary.
    STANDBY_OPS = frozenset({
        "metrics_snapshot", "topics", "lags", "peek", "lease_table",
        "snapshot_offsets", "snapshot_state",
    })
    # append ops diverted once this broker's generation is fenced
    APPEND_OPS = frozenset({"publish", "publish_nowait", "publish_fenced"})
    # control-plane mutations streamed to the standby after they apply.
    # "seek" is absent on purpose: on a durable bus its journaled cursor
    # write already reaches the ring via the cursor listener.
    REPLICATED_CTL_OPS = frozenset({
        "subscribe", "unsubscribe", "drop_topics", "undrop",
        "restore_offsets", "restore_state",
    })

    async def _dispatch(
        self, op: str, args: tuple, conn: Optional[_ConnCtx] = None,
        noreply: bool = False,
    ) -> Any:
        # -- broker fault domain (docs/ROBUSTNESS.md "Broker fault
        # domain"): handshake/replication plane first, then role + the
        # generation fence gate the data plane ------------------------
        if op == "hello":
            return self._hello(int(args[0]) if args else 0)
        if op == "repl_poll":
            return await self._repl_poll(*args)
        if op == "repl_snapshot":
            return self._repl_snapshot()
        if op == "promote":
            return self.promote(str(args[0]) if args else "op")
        if self.role != "primary" and op not in self.STANDBY_OPS:
            raise BrokerNotPrimaryError(
                f"standby broker (generation "
                f"{self.generation.generation}) does not serve '{op}'"
            )
        if self.generation.fenced and op in self.APPEND_OPS:
            return self._divert_fenced_append(op, args, noreply)
        value = await self._dispatch_op(op, args, conn)
        # stream the mutation to the standby tail AFTER it applied —
        # never replicate an op that errored. WAL appends + journaled
        # cursors ride their own listeners; this covers the lease and
        # control planes.
        if op.startswith("lease_") and op != "lease_table":
            self.repl_ring.append(("lease", op, args))
        elif op in self.REPLICATED_CTL_OPS:
            self.repl_ring.append(("ctl", op, args))
        return value

    def _hello(self, client_generation: int) -> Dict[str, Any]:
        """Generation-gossip handshake, answered inline by clients
        before their reply loop starts. A peer asserting a NEWER
        generation than ours proves a standby promoted past us while we
        were dead or partitioned: self-fence durably, right here, so
        every later append diverts instead of double-serving."""
        g = self.generation
        if client_generation > g.generation and not g.fenced:
            self._commit_fence_generation(client_generation)
        g.seen = max(g.seen, client_generation)
        return {"generation": g.generation, "role": self.role,
                "fenced": g.fenced}

    def _commit_fence_generation(self, peer_generation: int) -> None:
        """Zombie self-fencing commit point (sync — registered in
        tools/registries.py COMMIT_SECTIONS): the durable fence and its
        counter land together; appends divert from the next dispatch."""
        self.generation.fence(peer_generation)
        self.metrics.counter("broker_generation_fenced_total").inc()
        logger.warning(
            "broker generation %d fenced by peer generation %d — "
            "appends divert to the broker-fenced dead-letter topic",
            self.generation.generation, peer_generation,
        )

    def promote(self, reason: str = "manual") -> Dict[str, Any]:
        """Standby → primary takeover (idempotent on a live primary).
        The new generation is strictly above everything this broker has
        ever seen — its own, any peer's hello, and whoever fenced it —
        so the superseded primary loses every future generation
        comparison, even if it never heard about intermediate hops."""
        g = self.generation
        if self.role == "primary" and not g.fenced:
            return {"generation": g.generation, "role": self.role,
                    "promoted": False}
        new_gen = max(g.generation, g.seen, g.fenced_by or 0) + 1
        self._commit_promotion(new_gen, reason)
        return {"generation": g.generation, "role": self.role,
                "promoted": True}

    def _commit_promotion(self, new_generation: int, reason: str) -> None:
        """Promotion commit point (sync — registered commit section):
        the durable generation bump, the role flip, and the lease
        grace-window extension land together, so host leases inherited
        from the dead primary's table aren't expired by the standby's
        clock before their owners have had ``lease_grace_s`` to
        re-handshake (ISSUE 18: failover must not mass-expire hosts)."""
        self.generation.bump_to(new_generation)
        self.role = "primary"
        extended = self.leases.extend_all(self.lease_grace_s)
        self.metrics.counter("broker_promotions_total").inc()
        logger.warning(
            "promoted to primary at generation %d (%s); extended %d "
            "lease(s) by %.1fs grace",
            new_generation, reason, extended, self.lease_grace_s,
        )

    async def _repl_poll(
        self,
        from_seq: int,
        max_records: int = 1024,
        timeout_s: float = 5.0,
    ) -> Dict[str, Any]:
        """Standby's long-poll against the replication ring. Empty polls
        park on the ring's data event (capped like consume polls); a
        ``from_seq`` older than the ring's base means the standby lagged
        past an eviction → tell it to resync from a full snapshot."""
        ring = self.repl_ring
        loop = asyncio.get_running_loop()
        deadline = loop.time() + max(
            0.0, min(float(timeout_s), CONSUME_TIMEOUT_CAP_S))
        while True:
            # clear BEFORE reading: an append racing the read re-sets
            # the event, so the wait below can't miss it
            ring.data_event.clear()
            recs, nxt, resync = ring.read(int(from_seq), int(max_records))
            if resync:
                self.metrics.counter("netbus_repl_resync_served_total").inc()
                return {"resync": True, "head": ring.head_seq,
                        "generation": self.generation.generation}
            if recs:
                break
            remaining = deadline - loop.time()
            if remaining <= 0:
                break
            try:
                await asyncio.wait_for(ring.data_event.wait(), remaining)
            except asyncio.TimeoutError:
                break
        # primary-side view of standby lag (the standby exports its own)
        self.metrics.gauge("netbus_replication_lag").set(
            ring.head_seq - nxt)
        return {"records": recs, "next": nxt, "head": ring.head_seq,
                "generation": self.generation.generation}

    def _repl_snapshot(self) -> Dict[str, Any]:
        """Full-state resync source for a fresh (or lagged-out) standby.
        ``seq`` is the ring head at capture: every mutation after it is
        in the ring, every one before it is in the snapshot, and the
        overlap a concurrent append could create is absorbed by
        ``replica_append`` idempotence."""
        bus = self.bus
        return {
            "seq": self.repl_ring.head_seq,
            "state": bus.snapshot_state(),
            "offsets": bus.snapshot_offsets(),
            "leases": self.leases.export(),
            "generation": self.generation.generation,
        }

    def _divert_fenced_append(
        self, op: str, args: tuple, noreply: bool
    ) -> Any:
        """A superseded (fenced) broker must not double-serve appends.
        Awaited ops ERROR — the payload is still caller-side, so the
        failover-aware client retries against the promoted primary.
        Fire-and-forget frames have no reply channel to error through:
        divert them to the broker-fenced dead-letter topic for audit
        instead of silently dropping. Both paths count
        ``netbus_fenced_appends_total`` by op."""
        self.metrics.counter("netbus_fenced_appends_total", op=op).inc()
        if not noreply:
            raise BrokerGenerationFencedError(
                f"broker generation {self.generation.generation} fenced "
                f"by generation {self.generation.fenced_by}; retry "
                f"against the promoted primary"
            )
        naming = getattr(self.bus, "naming", None) or TopicNaming()
        self.bus.publish_nowait(
            naming.global_topic("broker-fenced"),
            {
                "topic": _publish_topic(op, args),
                "payload": args[1] if len(args) > 1 else None,
                "op": op,
                "generation": self.generation.generation,
                "fenced_by": self.generation.fenced_by,
            },
        )
        return None

    async def _dispatch_op(
        self, op: str, args: tuple, conn: Optional[_ConnCtx] = None
    ) -> Any:
        bus = self.bus
        if op == "publish":
            return await bus.publish(*args)
        if op == "publish_nowait":
            return bus.publish_nowait(*args)
        if op == "consume":
            # cap server-side waits at CONSUME_TIMEOUT_CAP_S so a
            # vanished client can't pin a poll forever; RemoteEventBus
            # preserves longer timeouts by re-issuing capped polls. A
            # direct caller's longer timeout is TRUNCATED here — logged
            # once per (topic, group) + counted, never silent: a clamped
            # poll returning [] is indistinguishable from an empty topic
            # on the caller's side. A dropped (tombstoned) topic returns
            # None so the client can stop re-issuing instead of
            # hot-looping on instant empty replies.
            topic, group, max_items, timeout_s, *rest = args
            partition = rest[0] if rest else None
            if bus.topic(topic).dropped:
                return None
            if timeout_s is not None and timeout_s > CONSUME_TIMEOUT_CAP_S:
                self.metrics.counter(
                    "netbus_consume_timeout_clamped_total"
                ).inc()
                key = (topic, group)
                if key not in self._clamp_logged:
                    self._clamp_logged.add(key)
                    logger.warning(
                        "consume timeout %.1fs clamped to %.1fs for "
                        "topic=%s group=%s (re-issue polls client-side "
                        "for longer waits)",
                        timeout_s, CONSUME_TIMEOUT_CAP_S, topic, group,
                    )
                timeout_s = CONSUME_TIMEOUT_CAP_S
            elif timeout_s is None:
                timeout_s = CONSUME_TIMEOUT_CAP_S
            return await bus.consume(
                topic, group, max_items, timeout_s, partition
            )
        if op == "subscribe":
            return bus.subscribe(*args)
        if op == "unsubscribe":
            return bus.unsubscribe(*args)
        if op == "seek":
            return bus.seek(*args)
        if op == "topics":
            return bus.topics()
        if op == "drop_topics":
            return bus.drop_topics(*args)
        if op == "undrop":
            return bus.undrop(*args)
        if op == "snapshot_offsets":
            return bus.snapshot_offsets()
        if op == "restore_offsets":
            return bus.restore_offsets(*args)
        if op == "snapshot_state":
            return bus.snapshot_state()
        if op == "restore_state":
            return bus.restore_state(*args)
        if op == "peek":
            return bus.peek(*args)
        if op == "lags":
            return bus.lags()
        if op == "inject_faults":
            drop_p, dup_p, delay_s, topic, *rest = args
            fail_p = rest[0] if rest else 0.0
            return bus.inject_faults(
                topic,
                FaultPlan(
                    drop_p=drop_p, dup_p=dup_p, delay_s=delay_s, fail_p=fail_p
                ),
            )
        if op == "clear_faults":
            return bus.clear_faults(*args)
        # -- host lease control plane (runtime.hostlease) ----------------
        if op == "lease_acquire":
            host_id, slices, ttl_s, min_epoch = args
            self._bind_host_conn(str(host_id), conn)
            return self.leases.acquire(
                host_id, slices, ttl_s, min_epoch=min_epoch
            )
        if op == "lease_renew":
            host_id, epoch, ttl_s, health = args
            self._bind_host_conn(str(host_id), conn)
            return self.leases.renew(host_id, epoch, ttl_s, health)
        if op == "lease_release":
            return self.leases.release(*args)
        if op == "lease_fence":
            high = self.leases.fence(*args)
            # the lease is also the consumer-group SESSION: fencing a
            # host revokes its parked consume polls, Kafka-rebalance
            # style. Without this a hung-but-connected host (SIGSTOP)
            # keeps its long-polls parked at the broker, and every
            # publish after adoption is delivered into its frozen socket
            # buffer — the cursor advances and the adopter starves.
            self._revoke_host_polls(str(args[0]) if args else "")
            return high
        if op == "lease_table":
            return self.leases.table()
        if op == "metrics_snapshot":
            # chaos harnesses + operators read broker-side counters
            # (fenced publishes, lease churn) without a scrape endpoint
            return self.metrics.snapshot()
        if op == "publish_fenced":
            # the zombie-fencing commit point: the lease check and the
            # publish happen in ONE broker-side dispatch, so "lease lost
            # after the check" cannot interleave with the append. A
            # stale-epoch publish is rejected, counted, and DLQ'd —
            # never silently double-served, never silently dropped.
            topic, payload, key, host_id, epoch = args
            if self.leases.check(host_id, epoch):
                return {
                    "fenced": False,
                    "offset": await bus.publish(topic, payload, key),
                }
            self.metrics.counter(
                "host_fenced_publishes_total", host=str(host_id)
            ).inc()
            naming = getattr(bus, "naming", None) or TopicNaming()
            off = bus.publish_nowait(
                naming.host_fenced(str(host_id)),
                {"topic": topic, "host": host_id, "epoch": epoch,
                 "payload": payload},
            )
            return {"fenced": True, "offset": off}
        raise ValueError(f"unknown op '{op}'")


class RemoteEventBus:
    """EventBus surface over a broker connection. Drop-in for
    SiteWhereInstance(bus=...): same methods, same semantics (the broker
    runs the very same EventBus code)."""

    # bound on fire-and-forget frames buffered while disconnected: past
    # it the OLDEST buffered frame is dropped and counted
    # (netbus_frames_lost_total by op) — bounded memory, loud loss
    NOWAIT_BUFFER_MAX = 512

    def __init__(
        self,
        host: Optional[str] = None,
        port: Optional[int] = None,
        naming: Optional[TopicNaming] = None,
        retention: int = 65536,
        reconnect_window_s: float = 20.0,
        metrics: Optional[MetricsRegistry] = None,
        endpoints: Optional[List[Tuple[str, int]]] = None,
        generation: int = 0,
    ) -> None:
        self.naming = naming or TopicNaming()
        self.retention = retention
        # broker fault domain: the client holds a LIST of endpoints
        # (primary first, warm standbys after) and rotates through it on
        # connect errors and on not-primary/fenced rejections — failover
        # is a client-side concern, the brokers never redirect. A single
        # host+port is the degenerate one-endpoint list (and the
        # rollback knob: one endpoint ⇒ exactly the old behavior).
        if endpoints:
            self.endpoints: List[Tuple[str, int]] = [
                (str(h), int(p)) for h, p in endpoints
            ]
        else:
            if host is None or port is None:
                raise ValueError(
                    "RemoteEventBus needs host+port or endpoints=[...]")
            self.endpoints = [(str(host), int(port))]
        self._ep_idx = 0
        # highest broker generation this client has observed; asserted
        # in every hello so a zombie primary learns it was superseded
        # from ANY client that saw the promotion
        self.generation_seen = int(generation)
        self.metrics = metrics or MetricsRegistry()
        self._rng = random.Random()
        self._pending_nowait: deque = deque()
        # how long awaited calls retry against a down broker before the
        # error propagates (0 = fail fast). A durable broker restarted on
        # the same port within the window is transparent to the pipeline:
        # its logs + group cursors come back from disk, so re-issued polls
        # resume exactly where the dead broker left off.
        self.reconnect_window_s = reconnect_window_s
        self._reader: Optional[asyncio.StreamReader] = None
        self._writer: Optional[asyncio.StreamWriter] = None
        self._reply_task: Optional[asyncio.Task] = None
        self._futures: Dict[int, asyncio.Future] = {}
        self._ids = itertools.count(1)
        self._subs: set = set()  # (topic, group, at) replayed on reconnect
        self._closed = False
        self._conn_lock: Optional[asyncio.Lock] = None

    # the current endpoint, kept as properties so every log line and
    # error message names where the client actually points right now
    @property
    def host(self) -> str:
        return self.endpoints[self._ep_idx][0]

    @property
    def port(self) -> int:
        return self.endpoints[self._ep_idx][1]

    def _rotate_endpoint(self) -> None:
        if len(self.endpoints) > 1:
            self._ep_idx = (self._ep_idx + 1) % len(self.endpoints)

    # -- connection -------------------------------------------------------
    async def connect(self) -> "RemoteEventBus":
        # initial connect rides the same rotate/backoff loop as
        # reconnects, so a client started against a just-killed primary
        # finds the promoted standby within the window
        self._conn_lock = asyncio.Lock()
        await self._ensure_connected()
        return self

    async def _connect_once(self) -> None:
        host, port = self.endpoints[self._ep_idx]
        reader, writer = await asyncio.open_connection(host, port)
        # generation-gossip handshake, answered inline BEFORE the reply
        # loop starts: rejects standbys and fenced zombies (raising
        # ConnectionError — an OSError — so the rotate/backoff loop
        # moves on), and tells a superseded primary about the newest
        # generation we saw (it self-fences durably on receipt).
        try:
            writer.writelines(_dump((0, "hello", (self.generation_seen,))))
            await writer.drain()
            _rid, ok, value = await asyncio.wait_for(
                _read_frame(reader), CONSUME_TIMEOUT_CAP_S
            )
        except (asyncio.TimeoutError, asyncio.IncompleteReadError,
                ConnectionResetError, ValueError,
                safepickle.UnpicklingError):
            writer.close()
            raise ConnectionError(
                f"broker handshake failed at {host}:{port}")
        if not ok or not isinstance(value, dict):
            # pre-fault-domain broker ("unknown op 'hello'"): treat as a
            # plain primary — single-endpoint deployments stay compatible
            value = {"generation": 0, "role": "primary", "fenced": False}
        if value.get("fenced") or value.get("role") != "primary":
            writer.close()
            why = "fenced" if value.get("fenced") else str(value.get("role"))
            self.metrics.counter(
                "netbus_endpoint_rejected_total", role=why
            ).inc()
            self.generation_seen = max(
                self.generation_seen, int(value.get("generation", 0)))
            raise ConnectionError(f"broker at {host}:{port} is {why}")
        self.generation_seen = max(
            self.generation_seen, int(value.get("generation", 0)))
        self._reader, self._writer = reader, writer
        self._reply_task = asyncio.create_task(
            self._reply_loop(), name="netbus-replies"
        )
        # re-register group cursors: a durable broker already has them on
        # disk (subscribe is then a no-op), a fresh one needs them back
        for topic, group, at in self._subs:
            self._writer.writelines(
                _dump((None, "subscribe", (topic, group, at)))
            )
        self._flush_pending_nowait()

    def _flush_pending_nowait(self) -> None:
        """Replay fire-and-forget frames buffered during the outage, in
        order, ahead of any new traffic on the fresh connection."""
        while self._pending_nowait:
            _op, frame = self._pending_nowait.popleft()
            self._writer.writelines(frame)
        self.metrics.gauge("netbus_nowait_buffered").set(0)

    # reconnect backoff: first retry after RECONNECT_BASE_S, doubling to
    # RECONNECT_MAX_S, each delay jittered ±RECONNECT_JITTER — a fleet of
    # clients must not hammer a dead (or just-restarted) broker in
    # lockstep for the whole reconnect_window_s
    RECONNECT_BASE_S = 0.05
    RECONNECT_MAX_S = 2.0
    RECONNECT_JITTER = 0.25

    def _backoff(self, attempt: int) -> float:
        d = min(
            self.RECONNECT_BASE_S * (2 ** max(attempt - 1, 0)),
            self.RECONNECT_MAX_S,
        )
        return max(
            0.0, d * (1.0 + self.RECONNECT_JITTER * (2 * self._rng.random() - 1))
        )

    async def _ensure_connected(self) -> None:
        if self._closed:
            raise ConnectionError("bus client closed")
        if self._writer is not None:
            return
        assert self._conn_lock is not None, "RemoteEventBus not connected"
        async with self._conn_lock:
            if self._writer is not None or self._closed:
                return
            loop = asyncio.get_running_loop()
            deadline = loop.time() + self.reconnect_window_s
            attempt = 0
            while True:
                attempt += 1
                try:
                    await self._connect_once()
                    self.metrics.counter(
                        "netbus_reconnects_total", outcome="ok"
                    ).inc()
                    return
                except OSError:
                    self.metrics.counter(
                        "netbus_reconnects_total", outcome="error"
                    ).inc()
                    # rotate: the next attempt tries the next endpoint —
                    # with a standby configured, this IS client failover
                    self._rotate_endpoint()
                    if loop.time() >= deadline:
                        self.metrics.counter(
                            "netbus_reconnects_total", outcome="exhausted"
                        ).inc()
                        eps = ", ".join(
                            f"{h}:{p}" for h, p in self.endpoints)
                        raise ConnectionError(
                            f"bus broker unreachable at {eps}"
                        )
                    # jittered exponential backoff: no hot spinning
                    # against a dead broker inside the window
                    await asyncio.sleep(self._backoff(attempt))

    def _mark_disconnected(self) -> None:
        if self._writer is not None:
            self._writer.close()
            self._writer = None
        self._reader = None
        for fut in self._futures.values():
            if not fut.done():
                fut.set_exception(ConnectionError("bus connection lost"))
        self._futures.clear()

    async def close(self) -> None:
        self._closed = True
        await cancel_and_wait(self._reply_task)
        self._reply_task = None
        if self._writer is not None:
            self._writer.close()
            self._writer = None
        # frames buffered for a reconnect that will never come are LOST
        # — count them by op on the way out, never silently
        while self._pending_nowait:
            op, _f = self._pending_nowait.popleft()
            self.metrics.counter("netbus_frames_lost_total", op=op).inc()
        self.metrics.gauge("netbus_nowait_buffered").set(0)
        for fut in self._futures.values():
            if not fut.done():
                fut.set_exception(ConnectionError("bus connection closed"))
        self._futures.clear()

    async def _reply_loop(self) -> None:
        assert self._reader is not None
        while True:
            try:
                req_id, ok, value = await _read_frame(self._reader)
            except (asyncio.IncompleteReadError, ConnectionResetError,
                    OSError):
                self._mark_disconnected()
                return
            except (safepickle.UnpicklingError, ValueError):
                # hostile/corrupt broker frame: treat like a dead link —
                # disconnect and let the reconnect path take over
                self._mark_disconnected()
                return
            fut = self._futures.pop(req_id, None)
            if fut is not None and not fut.done():
                if ok:
                    fut.set_result(value)
                else:
                    fut.set_exception(RuntimeError(value))
            elif ok and isinstance(value, list) and value:
                # a delivery beat our consume_cancel to the wire: the
                # broker committed the cursor, but no caller is awaiting.
                # Loud, not silent — this is the residual at-most-once
                # window the cancel op shrinks from seconds to an RTT.
                logger.warning(
                    "discarding %d item(s) delivered to a cancelled "
                    "consume (req_id=%s)", len(value), req_id,
                )

    async def _call(self, op: str, *args) -> Any:
        loop = asyncio.get_running_loop()
        deadline = loop.time() + max(self.reconnect_window_s, 0.0)
        attempt = 0
        while True:
            attempt += 1
            await self._ensure_connected()
            req_id = next(self._ids)
            # write-path frame cap: an oversized publish fails THIS call
            # (naming the topic) instead of poisoning the peer connection;
            # serialized before the future registers so nothing leaks
            frame = _dump((req_id, op, args), _publish_topic(op, args))
            fut: asyncio.Future = loop.create_future()
            self._futures[req_id] = fut
            try:
                self._writer.writelines(frame)
                await self._writer.drain()
                return await fut
            except asyncio.CancelledError:
                # our caller's task was cancelled (component terminate,
                # tenant handoff) while this call was in flight. For a
                # consume that leaves a live long-poll on the broker:
                # the next publish would be delivered against THIS dead
                # future and discarded — a silent row loss. Tell the
                # broker to cancel the poll (loss-free while it waits).
                self._futures.pop(req_id, None)
                if op == "consume" and self._writer is not None:
                    try:
                        self._send_nowait("consume_cancel", req_id)
                    except Exception:  # noqa: BLE001 - teardown path
                        pass
                raise
            except ConnectionError:
                # broker died mid-call. Retrying may re-apply a mutation
                # whose first attempt landed before the crash (at-least-
                # once, like any acked-after-commit bus); polls are safe
                # to re-issue by construction.
                self._futures.pop(req_id, None)
                if self._closed or loop.time() >= deadline:
                    raise
                await asyncio.sleep(self._backoff(attempt))
            except RuntimeError as exc:
                msg = str(exc)
                if not msg.startswith(("BrokerNotPrimaryError",
                                       "BrokerGenerationFencedError")):
                    raise
                # the endpoint answered, but as a standby or a fenced
                # zombie (a promotion happened mid-connection): the op
                # did NOT apply there, so rotating and retrying against
                # the real primary is duplicate-free — this is the
                # client half of fenced failover.
                self.metrics.counter(
                    "netbus_failovers_total", cause=msg.split(":", 1)[0]
                ).inc()
                self._futures.pop(req_id, None)
                self._mark_disconnected()
                self._rotate_endpoint()
                if self._closed or loop.time() >= deadline:
                    raise ConnectionError(msg)
                await asyncio.sleep(self._backoff(attempt))

    def _send_nowait(self, op: str, *args) -> None:
        """Fire-and-forget for the sync API points; StreamWriter.write is
        synchronous, so ordering vs later calls is preserved. During a
        broker outage these frames are BUFFERED (bounded at
        NOWAIT_BUFFER_MAX) and flushed in order on reconnect — a
        reconnect window no longer silently eats publish_nowait/seek
        frames. Overflow drops the OLDEST frame, counted
        netbus_frames_lost_total by op; subscriptions replay from
        ``_subs`` instead, so they are never buffered or lost."""
        if op == "subscribe":
            self._subs.add(args)
        frame = _dump((None, op, args), _publish_topic(op, args))
        if self._writer is None:
            if op == "subscribe":
                return
            if len(self._pending_nowait) >= self.NOWAIT_BUFFER_MAX:
                old_op, _f = self._pending_nowait.popleft()
                self.metrics.counter(
                    "netbus_frames_lost_total", op=old_op
                ).inc()
            self._pending_nowait.append((op, frame))
            self.metrics.gauge("netbus_nowait_buffered").set(
                len(self._pending_nowait))
            return
        self._writer.writelines(frame)

    # -- EventBus surface -------------------------------------------------
    async def publish(self, topic: str, payload: Any, key: Any = None) -> int:
        return await self._call("publish", topic, payload, key)

    def publish_nowait(self, topic: str, payload: Any, key: Any = None) -> int:
        self._send_nowait("publish_nowait", topic, payload, key)
        return -1  # offset unknowable without a round trip

    async def consume(
        self,
        topic: str,
        group: str,
        max_items: int = 256,
        timeout_s: Optional[float] = None,
        partition: Optional[int] = None,
    ) -> List[Any]:
        # the broker clamps one server-side poll at CONSUME_TIMEOUT_CAP_S
        # (30 s — longer per-poll timeouts are truncated broker-side,
        # counted in netbus_consume_timeout_clamped_total); preserve the
        # in-proc semantics for ANY timeout by re-issuing capped polls
        # against a client-side deadline (None = wait forever)
        loop = asyncio.get_running_loop()
        deadline = None if timeout_s is None else loop.time() + timeout_s
        while True:
            remaining = (
                None if deadline is None else max(0.0, deadline - loop.time())
            )
            # always poll at least once: timeout 0 means "non-blocking
            # fetch of whatever is available", exactly like the in-proc bus
            items = await self._call(
                "consume", topic, group, max_items, remaining, partition
            )
            if items is None:
                return []  # topic dropped (tenant teardown) — stop polling
            if items:
                return items
            if remaining is not None and remaining <= CONSUME_TIMEOUT_CAP_S:
                return items  # the broker honored the full remaining wait

    def subscribe(self, topic: str, group: str, at: str = "earliest") -> None:
        self._send_nowait("subscribe", topic, group, at)

    def unsubscribe(self, topic: str, group: str) -> None:
        self._subs = {s for s in self._subs if s[:2] != (topic, group)}
        self._send_nowait("unsubscribe", topic, group)

    def seek(self, topic: str, group: str, offset: int) -> None:
        self._send_nowait("seek", topic, group, offset)

    def drop_topics(self, prefix: str) -> List[str]:
        self._send_nowait("drop_topics", prefix)
        return []

    def undrop(self, prefix: str) -> None:
        self._send_nowait("undrop", prefix)

    async def topics(self) -> List[str]:
        return await self._call("topics")

    async def peek(self, topic: str, max_items: int = 100) -> dict:
        return await self._call("peek", topic, max_items)

    async def lags(self) -> Dict[str, dict]:
        """Per-topic depth + consumer lag from the broker (the remote
        half of the ``bus_consumer_lag`` gauge collection). Payload trace
        contexts (``core.trace.TraceContext``) cross this wire inside
        their payload frames — the restricted unpickler admits core
        classes, so traces survive a netbus hop with no extra protocol."""
        return await self._call("lags")

    def inject_faults(self, topic: str, plan: FaultPlan) -> None:
        # the plan's rng doesn't pickle usefully; send the knobs
        self._send_nowait(
            "inject_faults", plan.drop_p, plan.dup_p, plan.delay_s, topic,
            plan.fail_p,
        )

    def clear_faults(self, topic: str) -> None:
        self._send_nowait("clear_faults", topic)

    # -- host lease control plane ----------------------------------------
    # Lease ops ride ``_call``, i.e. the SAME jittered-backoff reconnect
    # path every awaited op gets: a renewal issued mid-reconnect retries
    # against the window and lands carrying its original epoch — the
    # epoch is an argument, not connection state, so a broker bounce
    # never resets it (tests/test_netbus.py reconnect-during-renewal).
    async def lease_acquire(
        self,
        host_id: str,
        slices: tuple = (),
        ttl_s: Optional[float] = None,
        min_epoch: int = 0,
    ) -> dict:
        return await self._call(
            "lease_acquire", host_id, tuple(slices), ttl_s, int(min_epoch)
        )

    async def lease_renew(
        self,
        host_id: str,
        epoch: int,
        ttl_s: Optional[float] = None,
        health: Optional[dict] = None,
    ) -> dict:
        try:
            return await self._call(
                "lease_renew", host_id, int(epoch), ttl_s,
                dict(health or {}),
            )
        except (ConnectionError, RuntimeError):
            # the broker stayed unreachable past the reconnect window
            # (or rejected the frame): the caller keeps its epoch and
            # retries next tick — counted, never silent, because a host
            # quietly failing renewals is exactly how a lease expires
            # out from under live traffic
            self.metrics.counter(
                "netbus_lease_renew_failures_total", host=str(host_id)
            ).inc()
            raise

    async def lease_release(self, host_id: str, epoch: int) -> bool:
        return await self._call("lease_release", host_id, int(epoch))

    async def lease_fence(self, host_id: str) -> int:
        return await self._call("lease_fence", host_id)

    async def lease_table(self) -> dict:
        return await self._call("lease_table")

    async def metrics_snapshot(self) -> dict:
        return await self._call("metrics_snapshot")

    async def publish_fenced(
        self, topic: str, payload: Any, host_id: str, epoch: int,
        key: Any = None,
    ) -> dict:
        return await self._call(
            "publish_fenced", topic, payload, key, host_id, int(epoch)
        )

    def publish_fenced_nowait(
        self, topic: str, payload: Any, host_id: str, epoch: int,
        key: Any = None,
    ) -> int:
        self._send_nowait(
            "publish_fenced", topic, payload, key, host_id, int(epoch)
        )
        return -1  # offset unknowable without a round trip

    # checkpoint seam — async here (network), awaited by CheckpointManager
    # callers that support remote buses
    async def snapshot_state(self) -> Dict[str, dict]:
        return await self._call("snapshot_state")

    async def restore_state(self, state: Dict[str, dict]) -> None:
        await self._call("restore_state", state)

    async def snapshot_offsets(self) -> Dict[str, Dict[str, int]]:
        return await self._call("snapshot_offsets")

    async def restore_offsets(self, snap: Dict[str, Dict[str, int]]) -> None:
        await self._call("restore_offsets", snap)


class StandbyReplicator(LifecycleComponent):
    """Warm-standby tail (ISSUE 18 tentpole): colocated with a STANDBY
    ``BusBrokerServer``, it drains the primary's replication ring via
    ``repl_poll`` long-polls and applies each record — WAL appends at
    the primary's offsets, journaled cursor commits, lease-table and
    control-plane ops — to the standby's own (durable) bus. When the
    primary stays unreachable past ``failover_after_s`` it PROMOTES its
    broker (durable generation bump + lease grace window), then flips
    into a fence-peer loop: hello-gossip the old endpoints forever so a
    zombie primary — even one restarted from its old data dir hours
    later — fences itself durably on first contact and diverts appends
    instead of double-serving them."""

    POLL_TIMEOUT_S = 5.0   # server-side long-poll per repl_poll
    RETRY_S = 0.25
    FENCE_PERIOD_S = 1.0
    HELLO_TIMEOUT_S = 5.0

    def __init__(
        self,
        broker: BusBrokerServer,
        primary_endpoints: List[Tuple[str, int]],
        failover_after_s: float = 5.0,
        metrics: Optional[MetricsRegistry] = None,
        faultplan: Any = None,
        promote_on_loss: bool = True,
        on_promote: Any = None,
    ) -> None:
        super().__init__("netbus-standby")
        self.broker = broker
        self.primary_endpoints = [
            (str(h), int(p)) for h, p in primary_endpoints
        ]
        self.failover_after_s = float(failover_after_s)
        # hard client-side cap per replication call: a SIGSTOP'd primary
        # hangs TCP without an RST, so every await on it must time out
        self.call_timeout_s = self.POLL_TIMEOUT_S + max(
            2.0, self.failover_after_s)
        self.metrics = metrics or broker.metrics
        self.faultplan = faultplan
        self.promote_on_loss = promote_on_loss
        self.on_promote = on_promote
        self.applied_seq = 0
        self._synced = False
        self._client: Optional[RemoteEventBus] = None
        self._task: Optional[asyncio.Task] = None
        self._fenced_peers: set = set()

    async def on_start(self) -> None:
        self._task = asyncio.create_task(
            self._tail_loop(), name="netbus-standby-tail"
        )

    async def on_stop(self) -> None:
        if self._task is not None:
            await cancel_and_wait(self._task)
            self._task = None
        await self._drop_client()

    async def _drop_client(self) -> None:
        if self._client is not None:
            c, self._client = self._client, None
            try:
                await c.close()
            except Exception:  # noqa: BLE001 - teardown path
                pass

    async def _client_or_connect(self) -> RemoteEventBus:
        if self._client is None:
            c = RemoteEventBus(
                endpoints=self.primary_endpoints,
                naming=getattr(self.broker.bus, "naming", None),
                reconnect_window_s=0.0,  # fail fast; WE own retry cadence
                metrics=self.metrics,
            )
            try:
                await asyncio.wait_for(c.connect(), self.call_timeout_s)
            except BaseException:
                await c.close()
                raise
            self._client = c
        return self._client

    async def _tail_loop(self) -> None:
        loop = asyncio.get_running_loop()
        last_contact = loop.time()
        while True:
            if self.broker.role == "primary":
                await self._fence_peer_loop()
                return
            if self.faultplan is not None:
                f = self.faultplan.match("standby", "repl")
                if f is not None and f.kind == "repl_stall":
                    # chaos knob: stall the tail so replication lag
                    # grows measurably (faultplan "repl_stall")
                    await asyncio.sleep(f.delay_s)
            try:
                await self._poll_once()
                last_contact = loop.time()
            except asyncio.CancelledError:
                raise
            except (ConnectionError, OSError, RuntimeError,
                    asyncio.TimeoutError) as exc:
                await self._drop_client()
                down_s = loop.time() - last_contact
                if self.promote_on_loss and down_s >= self.failover_after_s:
                    info = self.broker.promote(
                        f"primary unreachable {down_s:.1f}s"
                    )
                    if self.on_promote is not None:
                        self.on_promote(info)
                    continue  # next pass enters the fence-peer loop
                logger.debug("standby poll failed (%r); retrying", exc)
                await asyncio.sleep(self.RETRY_S)

    async def _poll_once(self) -> None:
        client = await self._client_or_connect()
        if not self._synced:
            snap = await asyncio.wait_for(
                client._call("repl_snapshot"), self.call_timeout_s
            )
            self._commit_snapshot(snap)
            return
        reply = await asyncio.wait_for(
            client._call(
                "repl_poll", self.applied_seq, 1024, self.POLL_TIMEOUT_S
            ),
            self.call_timeout_s,
        )
        g = self.broker.generation
        g.seen = max(g.seen, int(reply.get("generation", 0)))
        if reply.get("resync"):
            # we lagged past a ring eviction — rebuild from a snapshot
            self._synced = False
            return
        recs = reply.get("records") or []
        if recs:
            self._commit_records(recs, int(reply["next"]))
        self.metrics.gauge("netbus_replication_lag").set(
            max(0, int(reply.get("head", self.applied_seq))
                - self.applied_seq)
        )

    def _commit_snapshot(self, snap: dict) -> None:
        """Resync commit point (sync — registered commit section): logs,
        cursors, lease table, and the applied-seq watermark move to the
        snapshot as ONE unit, so a cancel mid-resync can't leave the
        watermark claiming state that never landed."""
        broker = self.broker
        broker.bus.restore_state(snap.get("state") or {})
        broker.bus.restore_offsets(snap.get("offsets") or {})
        broker.leases.load(snap.get("leases") or {})
        broker.generation.seen = max(
            broker.generation.seen, int(snap.get("generation", 0)))
        self.applied_seq = int(snap.get("seq", 0))
        self._synced = True
        self.metrics.counter("netbus_repl_resyncs_total").inc()

    def _commit_records(self, recs: List[tuple], next_seq: int) -> None:
        """Batch-apply commit point (sync — registered commit section):
        records apply in ring order and the watermark moves with them —
        never past a record that didn't apply."""
        for rec in recs:
            self._apply_record(rec)
        self.applied_seq = next_seq
        self.metrics.counter("netbus_repl_records_total").inc(len(recs))

    def _apply_record(self, rec: tuple) -> None:
        kind = rec[0]
        broker = self.broker
        if kind == "wal":
            _k, topic, part, offset, payload = rec
            broker.bus.apply_replica_append(topic, part, offset, payload)
        elif kind == "cur":
            _k, topic, group, cursor = rec
            broker.bus.seek(topic, group, cursor)
        elif kind == "lease":
            _k, op, args = rec
            getattr(broker.leases, op[len("lease_"):])(*args)
        elif kind == "ctl":
            _k, op, args = rec
            getattr(broker.bus, op)(*args)
        else:
            logger.warning("unknown replication record kind %r", kind)

    async def _fence_peer_loop(self) -> None:
        """Post-promotion: hello-gossip the old primary endpoints until
        each acknowledges our generation, and keep listening after that
        — a zombie restarted from its old data dir hours later is
        fenced on its FIRST hello, not its first double-served append."""
        while True:
            for ep in self.primary_endpoints:
                try:
                    reply = await self._hello_endpoint(ep)
                except (OSError, asyncio.TimeoutError, ValueError,
                        asyncio.IncompleteReadError,
                        safepickle.UnpicklingError):
                    # down or unreachable: fine — if it ever comes
                    # back we fence it then
                    self._fenced_peers.discard(ep)
                    continue
                if not isinstance(reply, dict):
                    continue
                # symmetric gossip: THEIR generation may outrank ours
                # (a later promotion elsewhere) — same rule applies
                self.broker._hello(int(reply.get("generation", 0)))
                if reply.get("fenced") and ep not in self._fenced_peers:
                    self._fenced_peers.add(ep)
                    self.metrics.counter("broker_peer_fences_total").inc()
                    logger.info(
                        "old primary %s:%d fenced at generation %d",
                        ep[0], ep[1], self.broker.generation.generation,
                    )
            await asyncio.sleep(self.FENCE_PERIOD_S)

    async def _hello_endpoint(self, ep: Tuple[str, int]) -> Any:
        reader, writer = await asyncio.wait_for(
            asyncio.open_connection(*ep), self.HELLO_TIMEOUT_S
        )
        try:
            writer.writelines(_dump(
                (0, "hello", (self.broker.generation.generation,))
            ))
            await writer.drain()
            _rid, ok, value = await asyncio.wait_for(
                _read_frame(reader), self.HELLO_TIMEOUT_S
            )
            return value if ok else None
        finally:
            writer.close()


# ------------------------------------------------------------------ main
def main(argv: Optional[List[str]] = None) -> None:
    """Standalone broker process: ``python -m sitewhere_tpu.runtime.netbus
    --port P [--data-dir D]``. With --data-dir the broker is DURABLE
    (segmented on-disk logs + cursor journal, dlog.DurableEventBus): kill
    it -9, restart it on the same dir, and consumers resume from their
    persisted offsets with no event loss."""
    import argparse
    import sys

    ap = argparse.ArgumentParser()
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--instance-id", default="sw")
    ap.add_argument("--retention", type=int, default=65536)
    ap.add_argument("--data-dir", default="",
                    help="enable durability under this directory")
    ap.add_argument("--partitions", default="{}",
                    help='JSON topic-suffix → count, e.g. '
                         '{"inbound-events": 4}')
    ap.add_argument("--standby-of", default="",
                    help='run as a warm STANDBY tailing this primary: '
                         '"host:port[,host:port...]"')
    ap.add_argument("--failover-after", type=float, default=5.0,
                    help="seconds of primary unreachability before the "
                         "standby promotes itself")
    ap.add_argument("--lease-grace", type=float, default=10.0,
                    help="post-promotion grace extension for inherited "
                         "host leases")
    args = ap.parse_args(argv)
    naming = TopicNaming(args.instance_id)
    parts = {k: int(v) for k, v in json.loads(args.partitions).items()}
    if args.data_dir:
        from sitewhere_tpu.runtime.dlog import DurableEventBus

        bus = DurableEventBus(
            args.data_dir, naming, args.retention, partitions=parts
        )
    else:
        bus = EventBus(naming, args.retention, partitions=parts)

    async def run() -> None:
        role = "standby" if args.standby_of else "primary"
        broker = BusBrokerServer(
            host=args.host, port=args.port, bus=bus, role=role,
            lease_grace_s=args.lease_grace,
        )
        await broker.initialize()
        await broker.start()
        replicator = None
        if args.standby_of:
            eps = []
            for spec in args.standby_of.split(","):
                h, _, p = spec.strip().rpartition(":")
                eps.append((h or "127.0.0.1", int(p)))

            def _on_promote(info: dict) -> None:
                # parents (chaos harnesses, supervisors) watch stdout
                # for the promotion event
                print(json.dumps({"promoted": True, **info}), flush=True)

            replicator = StandbyReplicator(
                broker, eps, failover_after_s=args.failover_after,
                on_promote=_on_promote,
            )
            await replicator.initialize()
            await replicator.start()
        # READY line: parents parse the bound port from stdout
        print(json.dumps({"ready": True, "port": broker.bound_port,
                          "role": role,
                          "generation": broker.generation.generation}),
              flush=True)
        try:
            await asyncio.Event().wait()  # serve until killed
        finally:
            if replicator is not None:
                await replicator.terminate()
            await broker.terminate()

    try:
        asyncio.run(run())
    except KeyboardInterrupt:
        sys.exit(0)


if __name__ == "__main__":
    main()
