"""Ring attention vs full attention: exactness over a sequence-sharded
mesh (SURVEY.md §5 long-context; first-class sequence parallelism)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from sitewhere_tpu.ops.ring_attention import (
    full_attention_reference,
    ring_attention,
    ring_attention_local,
)


def _mesh(n):
    devs = jax.devices()[:n]
    return Mesh(np.asarray(devs).reshape(n), ("seq",))


def _qkv(b=2, t=64, h=4, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, t, h, d)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


# each (n_shards, causal) pair is a fresh mesh → a fresh compile; four
# pairs cover both parities of both dimensions without the full product
@pytest.mark.parametrize(
    "n_shards,causal", [(2, True), (4, False), (8, True), (8, False)]
)
def test_ring_matches_full_attention(n_shards, causal):
    q, k, v = _qkv()
    mesh = _mesh(n_shards)
    got = ring_attention(q, k, v, mesh, "seq", causal=causal)
    want = full_attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_ring_single_shard_degenerates_to_full():
    q, k, v = _qkv(t=32)
    got = ring_attention(q, k, v, _mesh(1), "seq")
    want = full_attention_reference(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_local_memory_is_block_sized():
    """Each device's body only ever sees [B, T/n, H, D] blocks — the
    long-context point: per-device memory is O(T/n)."""
    seen = {}

    def probe(q, k, v):
        seen["shape"] = q.shape
        return ring_attention_local(q, k, v, "seq")

    q, k, v = _qkv(t=64)
    mesh = _mesh(8)
    from functools import partial

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(None, "seq", None, None)
    jax.jit(
        shard_map(probe, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec)
    )(q, k, v)
    assert seen["shape"][1] == 64 // 8


def test_long_context_beyond_single_block():
    """A context long enough that every ring step contributes: t=256
    over 8 shards, causal."""
    q, k, v = _qkv(b=1, t=256, h=2, d=8, seed=3)
    got = ring_attention(q, k, v, _mesh(8), "seq", causal=True)
    want = full_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_transformer_backbone_sharded_matches_single_device():
    """The sequence-parallel transformer backbone is numerically the
    single-device backbone (ring attention is exact)."""
    from sitewhere_tpu.models import transformer as tf

    cfg = tf.TransformerForecasterConfig(
        context=64, dim=32, depth=2, heads=4, dtype="float32"
    )
    params = tf.init(jax.random.PRNGKey(0), cfg)
    normed = jax.random.normal(jax.random.PRNGKey(1), (2, 64), jnp.float32)
    want = tf._backbone(params, normed, cfg)
    got = tf.backbone_sharded(
        params, cfg, normed, _mesh(8), axis_name="seq"
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5
    )


def test_forecast_seed_sharded_runs_long_context():
    from sitewhere_tpu.models import transformer as tf

    cfg = tf.TransformerForecasterConfig(
        context=512, dim=32, depth=2, heads=4, dtype="float32"
    )
    params = tf.init(jax.random.PRNGKey(0), cfg)
    t = np.linspace(0, 20, 512, dtype=np.float32)
    windows = jnp.asarray(
        21.0 + 4.0 * np.sin(t)[None] + np.zeros((2, 1), np.float32)
    )
    mu, sigma = tf.forecast_seed_sharded(
        params, cfg, windows, _mesh(8), axis_name="seq"
    )
    assert mu.shape == (2,) and sigma.shape == (2,)
    assert bool(jnp.isfinite(mu).all()) and bool((sigma > 0).all())
    # RAW units: an (untrained) forecast of 21±4 telemetry must land in
    # the data's neighborhood, not normalized space
    assert bool((jnp.abs(mu - 21.0) < 15.0).all()), mu


def test_vit_tensor_parallel_matches_single_device():
    """Megatron-style TP ViT over the model axis is numerically the
    single-device forward (two psums per block)."""
    from sitewhere_tpu.models import vit

    cfg = vit.ViTConfig(image_size=16, patch_size=8, dim=32, depth=2,
                        heads=4, num_classes=7, dtype="float32")
    params = vit.init(jax.random.PRNGKey(0), cfg)
    imgs = jax.random.normal(jax.random.PRNGKey(1), (3, 16, 16, 3), jnp.float32)
    want = vit.apply(params, cfg, imgs)
    for n in (2, 4):
        devs = jax.devices()[:n]
        mesh = Mesh(np.asarray(devs).reshape(n), ("model",))
        blocks, rest = vit.shard_params_tp(params, n)
        got = vit.apply_tp(blocks, rest, cfg, imgs, mesh, "model")
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5
        )


def test_tp_rejects_nondivisible_degree():
    from sitewhere_tpu.models import vit

    cfg = vit.ViTConfig(image_size=16, patch_size=8, dim=32, depth=1,
                        heads=4, num_classes=4, dtype="float32")
    params = vit.init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="must divide"):
        vit.shard_params_tp(params, 3)  # 3 ∤ dim=32


def test_gpipe_pipeline_matches_sequential():
    """GPipe over a stage axis: 4 transformer blocks, one per device,
    microbatched — numerically the sequential stack."""
    from sitewhere_tpu.models.common import (
        transformer_block,
        transformer_block_init,
    )
    from sitewhere_tpu.ops.pipeline_parallel import pipeline_apply

    depth, dim, heads = 4, 32, 4
    keys = jax.random.split(jax.random.PRNGKey(0), depth)
    blocks = [transformer_block_init(k, dim, heads) for k in keys]
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 10, dim), jnp.float32)

    want = x
    for blk in blocks:
        want = transformer_block(blk, want, heads, dtype=jnp.float32)

    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)
    devs = jax.devices()[:depth]
    mesh = Mesh(np.asarray(devs).reshape(depth), ("stage",))

    def stage_fn(blk, act):
        return transformer_block(blk, act, heads, dtype=jnp.float32)

    for m in (2, 8):  # min + deep schedule; each m is a fresh compile
        got = pipeline_apply(stacked, x, stage_fn, mesh, "stage",
                             microbatches=m)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5
        )


def test_gpipe_single_stage_degenerates():
    from sitewhere_tpu.models.common import (
        transformer_block,
        transformer_block_init,
    )
    from sitewhere_tpu.ops.pipeline_parallel import pipeline_apply

    blk = transformer_block_init(jax.random.PRNGKey(0), 16, 2)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 6, 16), jnp.float32)
    want = transformer_block(blk, x, 2, dtype=jnp.float32)
    stacked = jax.tree_util.tree_map(lambda a: a[None], blk)
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("stage",))
    got = pipeline_apply(
        stacked, x,
        lambda p, a: transformer_block(p, a, 2, dtype=jnp.float32),
        mesh, "stage", microbatches=2,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5
    )
