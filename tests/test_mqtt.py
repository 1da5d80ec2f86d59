"""Real MQTT 3.1.1 wire protocol: codec, broker+client over a real
socket, MqttReceiver in the full pipeline, and the HTTP ingest endpoint
(ingest must work from a real network socket)."""

import asyncio
import json

import pytest

from sitewhere_tpu.comm.mqtt import (
    MqttBroker,
    MqttClient,
    encode_varint,
    topic_matches,
)


def test_varint_codec():
    import io

    for n in (0, 1, 127, 128, 16383, 16384, 268435455):
        enc = encode_varint(n)

        class R:
            def __init__(self, data):
                self.buf = io.BytesIO(data)

            async def readexactly(self, k):
                return self.buf.read(k)

        from sitewhere_tpu.comm.mqtt import read_varint

        assert asyncio.run(read_varint(R(enc))) == n


def test_topic_matching():
    assert topic_matches("a/+/c", "a/b/c")
    assert topic_matches("a/#", "a/b/c/d")
    assert topic_matches("#", "anything/at/all")
    assert not topic_matches("a/+/c", "a/b/d")
    assert not topic_matches("a/b", "a/b/c")
    assert not topic_matches("a/b/c", "a/b")


async def test_pub_sub_over_real_socket():
    broker = MqttBroker()
    await broker.initialize()
    await broker.start()
    try:
        sub = await MqttClient("127.0.0.1", broker.bound_port, "sub").connect()
        pub = await MqttClient("127.0.0.1", broker.bound_port, "pub").connect()
        got: list = []

        async def on_msg(topic, payload):
            got.append((topic, payload))

        await sub.subscribe("sensors/+/temp", on_msg)
        await pub.publish(b"sensors/kitchen/temp".decode(), b"21.5")
        await pub.publish("sensors/kitchen/humidity", b"ignored")
        for _ in range(100):
            if got:
                break
            await asyncio.sleep(0.02)
        assert got == [("sensors/kitchen/temp", b"21.5")]
        # qos 1: publish blocks until PUBACK arrives
        await pub.publish("sensors/attic/temp", b"19.0", qos=1)
        for _ in range(100):
            if len(got) >= 2:
                break
            await asyncio.sleep(0.02)
        assert got[1] == ("sensors/attic/temp", b"19.0")
        # unsubscribe stops delivery
        await sub.unsubscribe("sensors/+/temp")
        await pub.publish("sensors/kitchen/temp", b"nope")
        await asyncio.sleep(0.1)
        assert len(got) == 2
        await sub.disconnect()
        await pub.disconnect()
    finally:
        await broker.terminate()


async def test_connack_rejects_bad_protocol():
    broker = MqttBroker()
    await broker.initialize()
    await broker.start()
    try:
        from sitewhere_tpu.comm.mqtt import CONNECT, _utf8, packet, read_packet

        reader, writer = await asyncio.open_connection(
            "127.0.0.1", broker.bound_port
        )
        body = _utf8("HTTP") + bytes([9, 0x02]) + (30).to_bytes(2, "big") + _utf8("x")
        writer.write(packet(CONNECT, 0, body))
        await writer.drain()
        ptype, _, body = await read_packet(reader)
        assert ptype == 2 and body[1] == 0x01  # CONNACK, refused
        writer.close()
    finally:
        await broker.terminate()


async def test_full_pipeline_ingests_from_real_mqtt_socket():
    """Device → MQTT socket → MqttReceiver → decode → inbound → score →
    persist: the platform ingests from an actual network socket."""
    from sitewhere_tpu.instance import SiteWhereInstance
    from sitewhere_tpu.runtime.config import InstanceConfig, MeshConfig

    broker = MqttBroker()
    await broker.initialize()
    await broker.start()
    inst = SiteWhereInstance(InstanceConfig(
        instance_id="mq",
        mesh=MeshConfig(tenant_axis=4, data_axis=2, slots_per_shard=2),
    ))
    await inst.start()
    try:
        await inst.tenant_management.create_tenant(
            "acme", template="iot-temperature",
            mqtt_ingest={"host": "127.0.0.1", "port": broker.bound_port,
                         "topics": ["sitewhere/input/#"]},
        )
        await inst.drain_tenant_updates()
        for _ in range(100):
            if "acme" in inst.tenants:
                break
            await asyncio.sleep(0.02)
        inst.tenants["acme"].device_management.bootstrap_fleet(4)
        device = await MqttClient(
            "127.0.0.1", broker.bound_port, "dev-00000"
        ).connect()
        for i in range(10):
            await device.publish(
                "sitewhere/input/dev-00000",
                json.dumps({
                    "type": "measurement", "device_token": "dev-00000",
                    "name": "temperature", "value": 20.0 + i,
                }).encode(),
            )
        persisted = inst.metrics.counter("event_management.persisted")
        for _ in range(300):
            if persisted.value >= 10:
                break
            await asyncio.sleep(0.02)
        assert persisted.value >= 10, "events did not flow from the socket"
        scored = inst.metrics.counter("tpu_inference.scored_total")
        assert scored.value >= 10
        await device.disconnect()
    finally:
        await inst.terminate()
        await broker.terminate()


async def test_http_ingest_endpoint():
    from aiohttp.test_utils import TestClient, TestServer

    from sitewhere_tpu.api.rest import make_app
    from sitewhere_tpu.instance import SiteWhereInstance
    from sitewhere_tpu.runtime.config import InstanceConfig, MeshConfig

    inst = SiteWhereInstance(InstanceConfig(
        instance_id="hi",
        mesh=MeshConfig(tenant_axis=4, data_axis=2, slots_per_shard=2),
    ))
    await inst.start()
    try:
        await inst.bootstrap(default_tenant="default", dataset_devices=3)
        for _ in range(100):
            if "default" in inst.tenants:
                break
            await asyncio.sleep(0.02)
        auth = inst.tenant_management.get_tenant("default").auth_token
        client = TestClient(TestServer(make_app(inst)))
        await client.start_server()
        try:
            body = json.dumps({
                "type": "measurement", "device_token": "dev-00000",
                "name": "temperature", "value": 23.5,
            }).encode()
            # wrong tenant auth → 401
            r = await client.post(
                "/api/input", data=body,
                headers={"X-SiteWhere-Tenant": "default",
                         "X-SiteWhere-Tenant-Auth": "wrong"},
            )
            assert r.status == 401
            # correct auth → accepted and flows through the pipeline
            r = await client.post(
                "/api/input", data=body,
                headers={"X-SiteWhere-Tenant": "default",
                         "X-SiteWhere-Tenant-Auth": auth},
            )
            assert r.status == 202
            persisted = inst.metrics.counter("event_management.persisted")
            for _ in range(200):
                if persisted.value >= 1:
                    break
                await asyncio.sleep(0.02)
            assert persisted.value >= 1
        finally:
            await client.close()
    finally:
        await inst.terminate()


async def test_broker_connect_auth():
    """With an authenticator installed, CONNECT credentials are honored:
    good creds → CONNACK 0; bad/missing creds → CONNACK rc=4 and the
    client raises (ADVICE r4: broker must not rest on topic secrecy)."""
    broker = MqttBroker(
        authenticator=lambda cid, user, pw: (user, pw) == ("tenant-a", "s3cret")
    )
    await broker.initialize()
    await broker.start()
    try:
        ok = await MqttClient(
            "127.0.0.1", broker.bound_port, "dev1",
            username="tenant-a", password="s3cret",
        ).connect()
        await ok.disconnect()
        with pytest.raises(ConnectionError, match="rc=4"):
            await MqttClient(
                "127.0.0.1", broker.bound_port, "dev2",
                username="tenant-a", password="wrong",
            ).connect()
        with pytest.raises(ConnectionError, match="rc=4"):
            await MqttClient("127.0.0.1", broker.bound_port, "dev3").connect()
    finally:
        await broker.terminate()


def test_packet_ids_wrap_16bit():
    """Packet ids stay in 1..65535 forever and skip pending ids
    (ADVICE r4: itertools.count overflowed to_bytes after 65535)."""
    c = MqttClient("h", 1)
    first = [c._next_pid() for _ in range(3)]
    assert first == [1, 2, 3]
    c._pid = 65534
    assert c._next_pid() == 65535
    assert c._next_pid() == 1  # wraps, not 65536
    # a pending ack blocks reuse of that id
    c._pid = 65534
    c._acks[65535] = object()
    assert c._next_pid() == 1


async def test_embedded_broker_uses_device_auth_gate():
    """InstanceConfig.mqtt_broker_port starts a real-socket broker whose
    CONNECT check IS authenticate_device: tenant token + auth secret."""
    from sitewhere_tpu.instance import SiteWhereInstance
    from sitewhere_tpu.runtime.config import InstanceConfig

    inst = SiteWhereInstance(InstanceConfig(mqtt_broker_port=0))
    await inst.initialize()
    await inst.start()
    try:
        await inst.bootstrap(default_tenant="alpha")
        port = inst.mqtt_broker.bound_port
        secret = inst.tenant_management.get_tenant("alpha").auth_token
        ok = await MqttClient(
            "127.0.0.1", port, "dev", username="alpha", password=secret
        ).connect()
        await ok.disconnect()
        with pytest.raises(ConnectionError, match="rc=4"):
            await MqttClient(
                "127.0.0.1", port, "dev", username="alpha", password="nope"
            ).connect()
        with pytest.raises(ConnectionError, match="rc=4"):
            await MqttClient("127.0.0.1", port, "anon").connect()
    finally:
        await inst.terminate()
