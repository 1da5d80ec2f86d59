"""AMQP 0-9-1 over asyncio: minimal broker + client, actual wire protocol.

Capability parity with the reference's AMQP/RabbitMQ transport (RabbitMQ
+ ActiveMQ receivers in service-event-sources — SURVEY.md §2.2 [U];
reference mount empty, see provenance banner). This image ships no AMQP
stack (no pika), so the wire protocol is implemented here: the AMQP
protocol header, frame format (type/channel/size/payload/0xCE),
connection negotiation (Start/Tune/Open), channel open, queue declare,
basic publish/consume/deliver/ack, and content header+body frames.

Scope: the default direct exchange (routing key == queue name), one
consumer per queue delivery (round-robin), auto-ack and explicit-ack
modes. Exchanges/bindings/transactions/flow control are out of scope —
the reference's ingest usage is the simple queue produce/consume
pattern this covers.
"""

from __future__ import annotations

import asyncio
import itertools
import struct
from collections import deque
from typing import Awaitable, Callable, Dict, List, Optional, Tuple

from sitewhere_tpu.runtime.lifecycle import LifecycleComponent, cancel_and_wait

PROTO_HEADER = b"AMQP\x00\x00\x09\x01"
FRAME_METHOD, FRAME_HEADER, FRAME_BODY, FRAME_HEARTBEAT = 1, 2, 3, 8
FRAME_END = 0xCE
FRAME_MAX = 131072           # negotiated in Tune/Tune-Ok by both ends
_BODY_CHUNK = FRAME_MAX - 8  # frame_max includes the 8-byte envelope


def body_frames(channel: int, body: bytes) -> bytes:
    """Content body split into negotiated-frame_max-sized frames —
    oversized single frames are a frame_error to conformant peers."""
    return b"".join(
        body_frame(channel, body[i:i + _BODY_CHUNK])
        for i in range(0, len(body), _BODY_CHUNK)
    )

# (class, method) ids
CONN_START, CONN_START_OK = (10, 10), (10, 11)
CONN_TUNE, CONN_TUNE_OK = (10, 30), (10, 31)
CONN_OPEN, CONN_OPEN_OK = (10, 40), (10, 41)
CONN_CLOSE, CONN_CLOSE_OK = (10, 50), (10, 51)
CH_OPEN, CH_OPEN_OK = (20, 10), (20, 11)
Q_DECLARE, Q_DECLARE_OK = (50, 10), (50, 11)
BASIC_CONSUME, BASIC_CONSUME_OK = (60, 20), (60, 21)
BASIC_PUBLISH, BASIC_DELIVER, BASIC_ACK = (60, 40), (60, 60), (60, 80)

Handler = Callable[[bytes, str], Awaitable[None]]


# ---------------------------------------------------------------- codec
def shortstr(s: str) -> bytes:
    b = s.encode()
    return bytes([len(b)]) + b


def longstr(b: bytes) -> bytes:
    return len(b).to_bytes(4, "big") + b


class _R:
    def __init__(self, data: bytes) -> None:
        self.d, self.o = data, 0

    def u8(self):
        v = self.d[self.o]; self.o += 1; return v

    def u16(self):
        v = int.from_bytes(self.d[self.o:self.o + 2], "big"); self.o += 2; return v

    def u32(self):
        v = int.from_bytes(self.d[self.o:self.o + 4], "big"); self.o += 4; return v

    def u64(self):
        v = int.from_bytes(self.d[self.o:self.o + 8], "big"); self.o += 8; return v

    def sstr(self):
        n = self.u8(); v = self.d[self.o:self.o + n].decode(); self.o += n; return v

    def lstr(self):
        n = self.u32(); v = self.d[self.o:self.o + n]; self.o += n; return v

    def table(self):
        return self.lstr()  # opaque: we never need the contents


def method_frame(channel: int, cm: Tuple[int, int], args: bytes = b"") -> bytes:
    payload = struct.pack(">HH", *cm) + args
    return (
        struct.pack(">BHI", FRAME_METHOD, channel, len(payload))
        + payload + bytes([FRAME_END])
    )


def header_frame(channel: int, body_size: int) -> bytes:
    payload = struct.pack(">HHQH", 60, 0, body_size, 0)  # no properties
    return (
        struct.pack(">BHI", FRAME_HEADER, channel, len(payload))
        + payload + bytes([FRAME_END])
    )


def body_frame(channel: int, body: bytes) -> bytes:
    return (
        struct.pack(">BHI", FRAME_BODY, channel, len(body))
        + body + bytes([FRAME_END])
    )


async def read_frame(reader) -> Tuple[int, int, bytes]:
    head = await reader.readexactly(7)
    ftype, channel, size = struct.unpack(">BHI", head)
    payload = await reader.readexactly(size)
    (end,) = await reader.readexactly(1)
    if end != FRAME_END:
        raise ValueError("bad AMQP frame end octet")
    return ftype, channel, payload


# ---------------------------------------------------------------- broker
class _Queue:
    def __init__(self, name: str) -> None:
        self.name = name
        self.messages: deque = deque()
        # consumers: (channel, consumer_tag, writer, lock, no_ack)
        self.consumers: List[tuple] = []
        self._rr = 0
        self.delivery_tags = itertools.count(1)


class AmqpBroker(LifecycleComponent):
    """Minimal conformant AMQP 0-9-1 broker (default direct exchange)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0) -> None:
        super().__init__("amqp-broker")
        self.host, self.port = host, port
        self.bound_port: Optional[int] = None
        self._server = None
        self._conns: set = set()
        self.queues: Dict[str, _Queue] = {}

    async def on_start(self) -> None:
        self._server = await asyncio.start_server(self._serve, self.host, self.port)
        self.bound_port = self._server.sockets[0].getsockname()[1]

    async def on_stop(self) -> None:
        if self._server is not None:
            self._server.close()
            # connections first: since Python 3.12.1 wait_closed() waits
            # for every accepted connection to drop, so stopping with a
            # client still attached would never return
            for t in list(self._conns):
                await cancel_and_wait(t)
            await self._server.wait_closed()
            self._server = None

    def _queue(self, name: str) -> _Queue:
        q = self.queues.get(name)
        if q is None:
            q = self.queues[name] = _Queue(name)
        return q

    async def _serve(self, reader, writer) -> None:
        task = asyncio.current_task()
        self._conns.add(task)
        lock = asyncio.Lock()
        my_consumers: List[Tuple[str, tuple]] = []
        # in-flight content: channel → [exchange, routing_key, size, chunks]
        pending: Dict[int, list] = {}
        try:
            if await reader.readexactly(8) != PROTO_HEADER:
                writer.write(PROTO_HEADER)  # spec: answer with ours + close
                await writer.drain()
                return
            async with lock:
                # Start with empty server-properties/mechanisms tables
                writer.write(method_frame(0, CONN_START, bytes([0, 9])
                             + longstr(b"") + longstr(b"PLAIN") + longstr(b"en_US")))
                await writer.drain()
            while True:
                ftype, channel, payload = await read_frame(reader)
                if ftype == FRAME_HEARTBEAT:
                    continue
                if ftype == FRAME_HEADER:
                    entry = pending.get(channel)
                    if entry is None:
                        continue  # header with no in-flight publish: drop
                    r = _R(payload)
                    r.u16(); r.u16()
                    entry[2] = r.u64()
                    if entry[2] == 0:
                        del pending[channel]
                        await self._route(entry[0], entry[1], b"")
                    continue
                if ftype == FRAME_BODY:
                    entry = pending.get(channel)
                    if entry is None:
                        continue
                    entry[3].append(payload)
                    if sum(len(c) for c in entry[3]) >= entry[2]:
                        del pending[channel]
                        await self._route(entry[0], entry[1], b"".join(entry[3]))
                    continue
                r = _R(payload)
                cm = (r.u16(), r.u16())
                if cm == CONN_START_OK:
                    r.table(); r.sstr(); r.lstr(); r.sstr()
                    async with lock:
                        writer.write(method_frame(
                            0, CONN_TUNE, struct.pack(">HIH", 0, FRAME_MAX, 0)
                        ))
                        await writer.drain()
                elif cm == CONN_TUNE_OK:
                    pass
                elif cm == CONN_OPEN:
                    async with lock:
                        writer.write(method_frame(0, CONN_OPEN_OK, shortstr("")))
                        await writer.drain()
                elif cm == CONN_CLOSE:
                    async with lock:
                        writer.write(method_frame(0, CONN_CLOSE_OK))
                        await writer.drain()
                    return
                elif cm == CH_OPEN:
                    async with lock:
                        writer.write(method_frame(channel, CH_OPEN_OK, longstr(b"")))
                        await writer.drain()
                elif cm == Q_DECLARE:
                    r.u16()
                    name = r.sstr()
                    self._queue(name)
                    async with lock:
                        writer.write(method_frame(
                            channel, Q_DECLARE_OK,
                            shortstr(name) + struct.pack(">II", 0, 0),
                        ))
                        await writer.drain()
                elif cm == BASIC_CONSUME:
                    r.u16()
                    qname = r.sstr()
                    tag = r.sstr() or f"ctag-{len(my_consumers)}"
                    flags = r.u8()
                    no_ack = bool(flags & 0x02)
                    entry = (channel, tag, writer, lock, no_ack)
                    self._queue(qname).consumers.append(entry)
                    my_consumers.append((qname, entry))
                    async with lock:
                        writer.write(method_frame(
                            channel, BASIC_CONSUME_OK, shortstr(tag)
                        ))
                        await writer.drain()
                    await self._drain_queue(qname)
                elif cm == BASIC_PUBLISH:
                    r.u16()
                    exchange = r.sstr()
                    routing_key = r.sstr()
                    pending[channel] = [exchange, routing_key, 0, []]
                elif cm == BASIC_ACK:
                    pass  # at-most-once redelivery is out of scope
        except (asyncio.IncompleteReadError, ConnectionResetError, ValueError):
            return
        finally:
            for qname, entry in my_consumers:
                q = self.queues.get(qname)
                if q is not None and entry in q.consumers:
                    q.consumers.remove(entry)
            self._conns.discard(task)
            writer.close()

    MAX_QUEUE_DEPTH = 65536

    async def _route(self, exchange: str, routing_key: str, body: bytes) -> None:
        # default direct exchange: routing key names the queue. Unroutable
        # messages DROP (default-exchange semantics — auto-creating a
        # queue per typo would buffer garbage forever), and queue depth is
        # bounded (oldest sheds first)
        q = self.queues.get(routing_key)
        if q is None:
            self.messages_unroutable = getattr(self, "messages_unroutable", 0) + 1
            return
        q.messages.append(body)
        while len(q.messages) > self.MAX_QUEUE_DEPTH:
            q.messages.popleft()
        await self._drain_queue(routing_key)

    async def _drain_queue(self, qname: str) -> None:
        q = self.queues.get(qname)
        if q is None:
            return
        while q.messages and q.consumers:
            body = q.messages.popleft()
            q._rr = (q._rr + 1) % len(q.consumers)
            channel, tag, writer, lock, _no_ack = q.consumers[q._rr]
            tagno = next(q.delivery_tags)
            args = (
                shortstr(tag) + struct.pack(">QB", tagno, 0)
                + shortstr("") + shortstr(q.name)
            )
            try:
                async with lock:
                    writer.write(method_frame(channel, BASIC_DELIVER, args))
                    writer.write(header_frame(channel, len(body)))
                    writer.write(body_frames(channel, body))
                    await writer.drain()
            except (ConnectionResetError, RuntimeError):
                q.messages.appendleft(body)
                return


# ---------------------------------------------------------------- client
class AmqpClient:
    """Minimal AMQP 0-9-1 client: declare, publish, consume."""

    def __init__(self, host: str, port: int) -> None:
        self.host, self.port = host, port
        self._reader = None
        self._writer = None
        self._task = None
        self._handlers: Dict[str, Handler] = {}  # queue → handler
        self._replies: deque = deque()  # futures awaiting any method reply
        self._channel = 1
        self._deliver: Optional[list] = None

    async def connect(self) -> "AmqpClient":
        self._reader, self._writer = await asyncio.open_connection(
            self.host, self.port
        )
        self._writer.write(PROTO_HEADER)
        await self._writer.drain()
        self._task = asyncio.create_task(self._read_loop(), name="amqp-client")
        try:
            await self._rpc(None)                    # await Start
            self._writer.write(method_frame(0, CONN_START_OK,
                               longstr(b"") + shortstr("PLAIN")
                               + longstr(b"\x00guest\x00guest") + shortstr("en_US")))
            await self._rpc(None)                    # await Tune
            self._writer.write(method_frame(0, CONN_TUNE_OK,
                               struct.pack(">HIH", 0, FRAME_MAX, 0)))
            self._writer.write(method_frame(0, CONN_OPEN, shortstr("/")
                               + shortstr("") + bytes([0])))
            await self._rpc(None)                    # await Open-Ok
            self._writer.write(method_frame(self._channel, CH_OPEN, shortstr("")))
            await self._rpc(None)                    # await Channel.Open-Ok
        except BaseException:
            # a failed handshake must not leak the read-loop task/socket
            await self.close()
            raise
        return self

    async def close(self) -> None:
        await cancel_and_wait(self._task)
        self._task = None
        if self._writer is not None:
            self._writer.close()
            self._writer = None

    async def _rpc(self, frame: Optional[bytes]):
        fut = asyncio.get_running_loop().create_future()
        self._replies.append(fut)
        if frame is not None:
            self._writer.write(frame)
            await self._writer.drain()
        return await asyncio.wait_for(fut, 10.0)

    async def _read_loop(self) -> None:
        try:
            while True:
                ftype, channel, payload = await read_frame(self._reader)
                if ftype == FRAME_METHOD:
                    r = _R(payload)
                    cm = (r.u16(), r.u16())
                    if cm == BASIC_DELIVER:
                        r.sstr(); r.u64(); r.u8(); r.sstr()
                        qname = r.sstr()
                        self._deliver = [qname, 0, []]
                        continue
                    if self._replies:
                        fut = self._replies.popleft()
                        if not fut.done():
                            fut.set_result((cm, payload))
                elif ftype == FRAME_HEADER and self._deliver is not None:
                    r = _R(payload)
                    r.u16(); r.u16()
                    self._deliver[1] = r.u64()
                    if self._deliver[1] == 0:
                        await self._dispatch(self._deliver[0], b"")
                        self._deliver = None
                elif ftype == FRAME_BODY and self._deliver is not None:
                    self._deliver[2].append(payload)
                    if sum(len(c) for c in self._deliver[2]) >= self._deliver[1]:
                        qname, _, chunks = self._deliver
                        self._deliver = None
                        await self._dispatch(qname, b"".join(chunks))
        except (asyncio.IncompleteReadError, ConnectionResetError):
            for fut in self._replies:
                if not fut.done():
                    fut.set_exception(ConnectionError("amqp connection lost"))
            self._replies.clear()
        except asyncio.CancelledError:
            raise
        except Exception:  # noqa: BLE001 - a handler error must not leave
            # the client deaf with hanging rpcs
            for fut in self._replies:
                if not fut.done():
                    fut.set_exception(ConnectionError("amqp client error"))
            self._replies.clear()

    async def _dispatch(self, qname: str, body: bytes) -> None:
        handler = self._handlers.get(qname)
        if handler is not None:
            try:
                await handler(body, qname)
            except Exception:  # noqa: BLE001
                pass

    async def queue_declare(self, name: str) -> None:
        await self._rpc(method_frame(
            self._channel, Q_DECLARE,
            struct.pack(">H", 0) + shortstr(name) + bytes([0]) + longstr(b""),
        ))

    async def consume(self, queue: str, handler: Handler) -> None:
        self._handlers[queue] = handler
        await self._rpc(method_frame(
            self._channel, BASIC_CONSUME,
            struct.pack(">H", 0) + shortstr(queue) + shortstr("")
            + bytes([0x02])  # no-ack
            + longstr(b""),
        ))

    async def publish(self, routing_key: str, body: bytes) -> None:
        self._writer.write(method_frame(
            self._channel, BASIC_PUBLISH,
            struct.pack(">H", 0) + shortstr("") + shortstr(routing_key)
            + bytes([0]),
        ))
        self._writer.write(header_frame(self._channel, len(body)))
        if body:
            self._writer.write(body_frames(self._channel, body))
        await self._writer.drain()
