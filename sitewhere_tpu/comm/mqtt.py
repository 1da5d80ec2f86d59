"""MQTT 3.1.1 over asyncio: broker + client, actual wire protocol.

Capability parity with the reference's MQTT transport (Paho/fuse client
against HiveMQ/ActiveMQ brokers — SURVEY.md §2.2 event-sources [U];
reference mount empty, see provenance banner). This image ships no MQTT
stack at all, so both ends are implemented here against the MQTT 3.1.1
spec: CONNECT/CONNACK, PUBLISH (publisher QoS 0/1 — QoS 1 gets a
PUBACK), SUBSCRIBE/SUBACK, UNSUBSCRIBE/UNSUBACK, PINGREQ/PINGRESP,
DISCONNECT, standard fixed header with varint remaining-length, UTF-8
topics, and ``+``/``#`` filter matching. A conformant external client
(e.g. paho) can talk to the broker; the client can talk to an external
broker.

Scope notes: subscriber-side delivery is QoS 0 (SUBACK grants 0
accordingly); QoS 2, retained messages, sessions, and wills are not
implemented (the platform's ingest/command paths use QoS 0/1
fire-and-acknowledge semantics).
"""

from __future__ import annotations

import asyncio
from typing import Awaitable, Callable, Dict, List, Optional, Tuple

from sitewhere_tpu.runtime.lifecycle import LifecycleComponent, cancel_and_wait

# packet types (MQTT 3.1.1 §2.2.1)
CONNECT, CONNACK, PUBLISH, PUBACK = 1, 2, 3, 4
SUBSCRIBE, SUBACK, UNSUBSCRIBE, UNSUBACK = 8, 9, 10, 11
PINGREQ, PINGRESP, DISCONNECT = 12, 13, 14

Handler = Callable[[str, bytes], Awaitable[None]]


# ---------------------------------------------------------------- codec
def encode_varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n % 128
        n //= 128
        out.append(b | 0x80 if n else b)
        if not n:
            return bytes(out)


async def read_varint(reader: asyncio.StreamReader) -> int:
    mult, value = 1, 0
    for _ in range(4):
        (b,) = await reader.readexactly(1)
        value += (b & 0x7F) * mult
        if not b & 0x80:
            return value
        mult *= 128
    raise ValueError("malformed varint remaining length")


def _utf8(s: str) -> bytes:
    b = s.encode()
    return len(b).to_bytes(2, "big") + b


def packet(ptype: int, flags: int, body: bytes) -> bytes:
    return bytes([(ptype << 4) | flags]) + encode_varint(len(body)) + body


async def read_packet(reader: asyncio.StreamReader) -> Tuple[int, int, bytes]:
    (h,) = await reader.readexactly(1)
    n = await read_varint(reader)
    body = await reader.readexactly(n) if n else b""
    return h >> 4, h & 0x0F, body


class _Body:
    """Cursor over a packet body."""

    def __init__(self, data: bytes) -> None:
        self.data, self.off = data, 0

    def u8(self) -> int:
        v = self.data[self.off]
        self.off += 1
        return v

    def u16(self) -> int:
        v = int.from_bytes(self.data[self.off:self.off + 2], "big")
        self.off += 2
        return v

    def utf8(self) -> str:
        n = self.u16()
        v = self.data[self.off:self.off + n].decode()
        self.off += n
        return v

    def rest(self) -> bytes:
        return self.data[self.off:]


def topic_matches(pattern: str, topic: str) -> bool:
    """MQTT filter matching: ``+`` one level, ``#`` trailing multi-level."""
    p_parts = pattern.split("/")
    t_parts = topic.split("/")
    for i, p in enumerate(p_parts):
        if p == "#":
            return True
        if i >= len(t_parts):
            return False
        if p != "+" and p != t_parts[i]:
            return False
    return len(p_parts) == len(t_parts)


# ---------------------------------------------------------------- broker
class MqttBroker(LifecycleComponent):
    """Minimal conformant MQTT 3.1.1 broker over asyncio TCP."""

    def __init__(
        self,
        host: str = "127.0.0.1",
        port: int = 0,
        authenticator: Optional[Callable[[str, str, str], bool]] = None,
    ) -> None:
        super().__init__("mqtt-broker")
        self.host, self.port = host, port
        # (client_id, username, password) → accept?  With no authenticator
        # the broker is OPEN — acceptable only inside the deployment trust
        # boundary. The instance's embedded broker (InstanceConfig.
        # mqtt_broker_port) passes authenticate_device here so MQTT ingest
        # enforces the same tenant auth as the CoAP/HTTP/WS paths.
        self.authenticator = authenticator
        self.bound_port: Optional[int] = None
        self._server = None
        self._conns: set = set()
        # live connections: id → (subscription filters, writer, write lock)
        self._entries: Dict[int, tuple] = {}
        self.messages_routed = 0
        self.messages_shed = 0  # dropped for slow consumers (buffer cap)

    MAX_BUFFERED = 1 << 20  # 1 MiB of un-flushed bytes per subscriber

    async def on_start(self) -> None:
        self._server = await asyncio.start_server(
            self._serve, self.host, self.port
        )
        self.bound_port = self._server.sockets[0].getsockname()[1]

    async def on_stop(self) -> None:
        if self._server is not None:
            self._server.close()
            # connections first: since Python 3.12.1 wait_closed() waits
            # for every accepted connection to drop, so stopping with a
            # client still attached would never return
            for task in list(self._conns):
                await cancel_and_wait(task)
            await self._server.wait_closed()
            self._server = None

    async def _serve(self, reader, writer) -> None:
        task = asyncio.current_task()
        self._conns.add(task)
        subs: List[str] = []
        lock = asyncio.Lock()
        # registered on first SUBSCRIBE: (filters, writer, lock)
        entry = (subs, writer, lock)
        try:
            ptype, _, body = await read_packet(reader)
            if ptype != CONNECT:
                return
            b = _Body(body)
            proto = b.utf8()
            level = b.u8()
            if proto not in ("MQTT", "MQIsdp") or level not in (3, 4):
                writer.write(packet(CONNACK, 0, bytes([0, 0x01])))  # bad proto
                await writer.drain()
                return
            cflags = b.u8()  # connect flags (sessions/wills unsupported)
            b.u16()  # keepalive (no server-side expiry enforcement)
            client_id = b.utf8()
            if cflags & 0x04:  # will flag: skip will topic + message
                b.utf8()
                n = b.u16()
                b.off += n
            username = b.utf8() if cflags & 0x80 else ""
            password = ""
            if cflags & 0x40:
                n = b.u16()
                password = b.data[b.off:b.off + n].decode("utf-8", "replace")
                b.off += n
            if self.authenticator is not None and not self.authenticator(
                client_id, username, password
            ):
                # rc=4 bad user name or password (MQTT 3.1.1 §3.2.2.3)
                writer.write(packet(CONNACK, 0, bytes([0, 0x04])))
                await writer.drain()
                return
            writer.write(packet(CONNACK, 0, bytes([0, 0x00])))  # accepted
            await writer.drain()
            self._entries[id(entry)] = entry
            while True:
                ptype, flags, body = await read_packet(reader)
                if ptype == PUBLISH:
                    await self._on_publish(flags, body, writer, lock)
                elif ptype == SUBSCRIBE:
                    b = _Body(body)
                    pid = b.u16()
                    codes = bytearray()
                    while b.off < len(b.data):
                        filt = b.utf8()
                        b.u8()  # requested qos
                        subs.append(filt)
                        # fan-out delivery is QoS 0, so GRANT QoS 0 — a
                        # conformant subscriber must not be promised
                        # at-least-once the broker won't provide
                        codes.append(0)
                    async with lock:
                        writer.write(packet(
                            SUBACK, 0, pid.to_bytes(2, "big") + bytes(codes)
                        ))
                        await writer.drain()
                elif ptype == UNSUBSCRIBE:
                    b = _Body(body)
                    pid = b.u16()
                    while b.off < len(b.data):
                        filt = b.utf8()
                        if filt in subs:
                            subs.remove(filt)
                    async with lock:
                        writer.write(packet(UNSUBACK, 0, pid.to_bytes(2, "big")))
                        await writer.drain()
                elif ptype == PINGREQ:
                    async with lock:
                        writer.write(packet(PINGRESP, 0, b""))
                        await writer.drain()
                elif ptype == DISCONNECT:
                    return
        except (asyncio.IncompleteReadError, ConnectionResetError):
            return
        except (ValueError, IndexError, UnicodeDecodeError):
            # malformed packet from an untrusted peer (bad varint, body
            # truncated mid-field, invalid UTF-8 string): drop the
            # connection instead of killing the serve task with an
            # unhandled error
            return
        finally:
            self._conns.discard(task)
            self._entries.pop(id(entry), None)
            writer.close()

    async def _on_publish(self, flags, body, src_writer, src_lock) -> None:
        qos = (flags >> 1) & 0x3
        b = _Body(body)
        topic = b.utf8()
        pid = b.u16() if qos else 0
        payload = b.rest()
        if qos == 1:
            async with src_lock:
                src_writer.write(packet(PUBACK, 0, pid.to_bytes(2, "big")))
                await src_writer.drain()
        # fan out (QoS 0 delivery) to every matching subscription.
        # write WITHOUT awaiting drain: one stalled subscriber must not
        # block delivery to the others (or freeze the publisher's read
        # loop); asyncio buffers the bytes, and a closed transport skips
        out = packet(PUBLISH, 0, _utf8(topic) + payload)
        for subs, writer, _lock in list(self._entries.values()):
            if any(topic_matches(f, topic) for f in subs):
                transport = writer.transport
                if transport is None or transport.is_closing():
                    continue
                # bounded buffering replaces drain-backpressure: a slow
                # consumer sheds messages (QoS 0 permits loss) instead of
                # growing broker memory without limit
                if transport.get_write_buffer_size() > self.MAX_BUFFERED:
                    self.messages_shed += 1
                    continue
                try:
                    writer.write(out)
                    self.messages_routed += 1
                except (ConnectionResetError, RuntimeError):
                    continue


# ---------------------------------------------------------------- client
class MqttClient:
    """Minimal MQTT 3.1.1 client: connect/publish/subscribe over TCP."""

    def __init__(
        self,
        host: str,
        port: int,
        client_id: str = "",
        keepalive_s: float = 30.0,
        username: str = "",
        password: str = "",
    ) -> None:
        self.host, self.port = host, port
        self.client_id = client_id or f"swt-{id(self):x}"
        self.keepalive_s = keepalive_s
        self.username, self.password = username, password
        self._reader = None
        self._writer = None
        self._reply_task = None
        self._ping_task = None
        self._handlers: List[Tuple[str, Handler]] = []
        self._pid = 0
        self._acks: Dict[int, asyncio.Future] = {}
        self._connack: Optional[asyncio.Future] = None

    async def connect(self) -> "MqttClient":
        self._reader, self._writer = await asyncio.open_connection(
            self.host, self.port
        )
        loop = asyncio.get_running_loop()
        self._connack = loop.create_future()
        if self.password and not self.username:
            # MQTT 3.1.1 §3.1.2.9: password flag requires username flag —
            # silently dropping a configured credential would surface only
            # as an opaque rc=4 at the broker
            raise ValueError("MQTT password requires a username")
        cflags = 0x02  # clean session
        if self.username:
            cflags |= 0x80
            if self.password:
                cflags |= 0x40
        body = (
            _utf8("MQTT") + bytes([4])           # protocol level 3.1.1
            + bytes([cflags])
            + int(self.keepalive_s).to_bytes(2, "big")
            + _utf8(self.client_id)
        )
        if self.username:
            body += _utf8(self.username)
            if self.password:
                pw = self.password.encode()
                body += len(pw).to_bytes(2, "big") + pw
        self._writer.write(packet(CONNECT, 0, body))
        await self._writer.drain()
        self._reply_task = asyncio.create_task(
            self._read_loop(), name=f"mqtt-client:{self.client_id}"
        )
        rc = await asyncio.wait_for(self._connack, 10.0)
        if rc != 0:
            raise ConnectionError(f"CONNACK refused rc={rc}")
        self._ping_task = asyncio.create_task(self._ping_loop())
        return self

    async def disconnect(self) -> None:
        await cancel_and_wait(self._ping_task)
        self._ping_task = None
        if self._writer is not None:
            try:
                self._writer.write(packet(DISCONNECT, 0, b""))
                await self._writer.drain()
            except (ConnectionResetError, RuntimeError):
                pass
        await cancel_and_wait(self._reply_task)
        self._reply_task = None
        if self._writer is not None:
            self._writer.close()
            self._writer = None

    async def _ping_loop(self) -> None:
        while True:
            await asyncio.sleep(max(self.keepalive_s / 2, 1.0))
            self._writer.write(packet(PINGREQ, 0, b""))
            await self._writer.drain()

    async def _read_loop(self) -> None:
        try:
            while True:
                ptype, flags, body = await read_packet(self._reader)
                if ptype == CONNACK:
                    if self._connack and not self._connack.done():
                        self._connack.set_result(body[1])
                elif ptype in (SUBACK, UNSUBACK, PUBACK):
                    pid = int.from_bytes(body[:2], "big")
                    fut = self._acks.pop(pid, None)
                    if fut and not fut.done():
                        fut.set_result(body[2:])
                elif ptype == PUBLISH:
                    qos = (flags >> 1) & 0x3
                    b = _Body(body)
                    topic = b.utf8()
                    pid = b.u16() if qos else 0
                    payload = b.rest()
                    if qos == 1:
                        self._writer.write(
                            packet(PUBACK, 0, pid.to_bytes(2, "big"))
                        )
                        await self._writer.drain()
                    for filt, handler in list(self._handlers):
                        if topic_matches(filt, topic):
                            try:
                                await handler(topic, payload)
                            except asyncio.CancelledError:
                                raise
                            except Exception:  # noqa: BLE001 - one bad
                                # handler call must not kill the read loop
                                # (the client would stay connected but
                                # deaf forever)
                                continue
        except asyncio.CancelledError:
            raise
        except Exception:  # noqa: BLE001 - connection loss OR a malformed
            # packet (bad varint / invalid UTF-8 topic): either way the
            # session is over — fail every waiter instead of hanging them
            for fut in self._acks.values():
                if not fut.done():
                    fut.set_exception(ConnectionError("mqtt connection lost"))
            self._acks.clear()

    def _next_pid(self) -> int:
        """Nonzero 16-bit packet id (MQTT 3.1.1 §2.3.1), wrapping at 65535
        and skipping ids whose ack is still pending."""
        for _ in range(65535):
            self._pid = self._pid % 65535 + 1
            if self._pid not in self._acks:
                return self._pid
        raise RuntimeError("all 65535 MQTT packet ids await acks")

    def _await_ack(self, pid: int) -> asyncio.Future:
        fut = asyncio.get_running_loop().create_future()
        self._acks[pid] = fut
        return fut

    async def subscribe(self, topic_filter: str, handler: Handler, qos: int = 0) -> None:
        pid = self._next_pid()
        fut = self._await_ack(pid)
        self._handlers.append((topic_filter, handler))
        self._writer.write(packet(
            SUBSCRIBE, 0x02,
            pid.to_bytes(2, "big") + _utf8(topic_filter) + bytes([qos]),
        ))
        await self._writer.drain()
        await asyncio.wait_for(fut, 10.0)

    async def unsubscribe(self, topic_filter: str) -> None:
        pid = self._next_pid()
        fut = self._await_ack(pid)
        self._handlers = [
            (f, h) for f, h in self._handlers if f != topic_filter
        ]
        self._writer.write(packet(
            UNSUBSCRIBE, 0x02, pid.to_bytes(2, "big") + _utf8(topic_filter)
        ))
        await self._writer.drain()
        await asyncio.wait_for(fut, 10.0)

    async def publish(self, topic: str, payload: bytes, qos: int = 0) -> None:
        if qos == 0:
            self._writer.write(packet(PUBLISH, 0, _utf8(topic) + payload))
            await self._writer.drain()
            return
        pid = self._next_pid()
        fut = self._await_ack(pid)
        self._writer.write(packet(
            PUBLISH, 0x02, _utf8(topic) + pid.to_bytes(2, "big") + payload
        ))
        await self._writer.drain()
        await asyncio.wait_for(fut, 10.0)  # PUBACK
