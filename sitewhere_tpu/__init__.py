"""sitewhere_tpu — a TPU-native, multitenant IoT event-processing framework.

Capability-parity rebuild of the reference platform (Tracy6465/sitewhere, an
IoT Application Enablement Platform; see SURVEY.md — the read-only reference
mount was empty at survey time, so parity citations point at the expected
upstream surface, tagged [U] in SURVEY.md).

Architecture (TPU-first, not a Java port):

- ``core``      L1: domain model — devices/assignments/areas/assets/tenants,
                the six event types, and columnar event batches shaped for
                feeding TPUs.
- ``runtime``   L2: lifecycle component trees, tenant engines, the
                topic-named async event bus (Kafka-shaped), layered config,
                metrics.
- ``pipeline``  L4: ingest → decode → inbound → tpu-inference → persist →
                rules (CEP) → outbound, plus command delivery.
- ``models``    Model zoo: LSTM anomaly detector, Transformer/DeepAR
                forecaster, ViT-B/16 frame classifier (pure-JAX pytrees).
- ``ops``       jitted JAX ops (jnp / lax / shard_map) for the hot scoring
                path: window rings, on-device IDCT, ring attention.
- ``parallel``  Mesh management, tenant→mesh-axis router, dp/tp/sp sharding
                helpers built on jax.sharding + shard_map.
- ``services``  L5: device/event/asset/state/schedule/batch/user/tenant
                management services (API-compatible capability surface).
- ``api``       L6: REST (aiohttp) + gRPC surface.
- ``sim``       MQTT-style device simulator used by benchmarks and tests.
"""

__version__ = "0.1.0"
