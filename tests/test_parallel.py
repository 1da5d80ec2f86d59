"""Mesh, tenant router, and sharded multi-tenant scoring on the 8-device
virtual CPU mesh (SURVEY.md §4 "TPU-without-TPU")."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sitewhere_tpu.models import get_model, make_config
from sitewhere_tpu.parallel.mesh import MeshManager, default_mesh
from sitewhere_tpu.parallel.sharded import ShardedScorer, stack_params, unstack_slot
from sitewhere_tpu.parallel.tenant_router import PlacementError, TenantRouter


def test_default_mesh_inference():
    m = default_mesh()  # 8 virtual devices → tenant=8
    assert m.shape["tenant"] * m.shape["data"] * m.shape["model"] == 8
    m2 = default_mesh(tenant=4, data=2)
    assert m2.shape["tenant"] == 4 and m2.shape["data"] == 2


def test_mesh_manager_axes():
    mm = MeshManager(tenant=4, data=2)
    assert mm.n_tenant_shards == 4
    assert mm.n_data_shards == 2
    assert mm.n_devices == 8


def test_slice_manager_sub_meshes():
    """Each tenant-axis slice owns exactly its own (data × model)
    devices, cached, with a stable anchor-device label."""
    mm = MeshManager(tenant=4, data=2)
    seen = []
    for sl in range(mm.n_slices):
        sub = mm.slice_manager(sl)
        assert sub is mm.slice_manager(sl)  # cached
        assert sub.n_tenant_shards == 1 and sub.n_data_shards == 2
        devs = list(sub.mesh.devices.flat)
        assert devs == list(mm.mesh.devices[sl].flat)
        seen.extend(devs)
        assert mm.slice_device_label(sl) == (
            f"{devs[0].platform}:{devs[0].id}"
        )
    assert len(set(seen)) == 8  # slices partition the mesh
    with pytest.raises(ValueError):
        mm.slice_manager(4)


def test_partition_rules_and_stacked_specs():
    """match_partition_rules: first regex hit wins, scalars never
    partition; stacked_specs: tenant axis prepended, named axes kept
    only when the mesh has them AND they divide the dim."""
    from jax.sharding import PartitionSpec as P

    from sitewhere_tpu.parallel import partition as pt

    tree = {"wx": {"w": np.zeros((1, 16)), "b": np.zeros((16,))},
            "scale": np.float32(2.0)}
    specs = pt.match_partition_rules(pt.MODEL_PARALLEL_RULES, tree)
    assert specs["wx"]["w"] == P(None, "model")
    assert specs["wx"]["b"] == P()
    assert specs["scale"] == P()  # scalar guard
    with pytest.raises(ValueError):
        pt.match_partition_rules(((r"^only/this$", P()),), tree)

    stacked = {"wx": {"w": np.zeros((8, 1, 16)), "b": np.zeros((8, 16))}}
    # model=1 mesh: the model-axis ask is dropped → replicate in shard
    mm = MeshManager(tenant=4, data=2)
    ss = pt.stacked_specs(pt.MODEL_PARALLEL_RULES, stacked, mm.mesh)
    assert ss["wx"]["w"] == P("tenant", None, None)
    assert ss["wx"]["b"] == P("tenant", None)
    # model=4 mesh: kept where the dim divides (16 % 4 == 0)...
    mm4 = MeshManager(tenant=2, data=1, model=4)
    ss4 = pt.stacked_specs(pt.MODEL_PARALLEL_RULES, stacked, mm4.mesh)
    assert ss4["wx"]["w"] == P("tenant", None, "model")
    # ...and dropped where it does not (15 % 4 != 0)
    ragged = {"wx": {"w": np.zeros((8, 1, 15)), "b": np.zeros((8, 15))}}
    ssr = pt.stacked_specs(pt.MODEL_PARALLEL_RULES, ragged, mm4.mesh)
    assert ssr["wx"]["w"] == P("tenant", None, None)


def test_shard_and_gather_fns_roundtrip():
    from jax.sharding import PartitionSpec as P

    from sitewhere_tpu.parallel import partition as pt

    mm = MeshManager(tenant=4, data=2)
    tree = {"w": np.arange(32, dtype=np.float32).reshape(8, 4)}
    specs = {"w": P("tenant")}
    shard_fns, gather_fns = pt.make_shard_and_gather_fns(mm.mesh, specs)
    placed = pt.shard_tree(tree, shard_fns)
    assert placed["w"].sharding.spec == P("tenant")
    back = gather_fns["w"](placed["w"])
    np.testing.assert_array_equal(back, tree["w"])


class TestTenantRouter:
    def test_balanced_placement_32_tenants(self):
        """The 32-tenant concurrent-scoring config (BASELINE.json:10)."""
        r = TenantRouter(n_shards=4, slots_per_shard=8)
        placements = [r.place(f"t{i:02d}") for i in range(32)]
        loads = r.shard_load("lstm_ad")
        assert loads == [8, 8, 8, 8]
        slots = {(p.shard, p.slot) for p in placements}
        assert len(slots) == 32  # all distinct
        with pytest.raises(PlacementError):
            r.place("t32")

    def test_remove_frees_slot(self):
        r = TenantRouter(2, 1)
        r.place("a")
        r.place("b")
        r.remove("a")
        p = r.place("c")
        assert p.shard in (0, 1)

    def test_failover_moves_shard(self):
        r = TenantRouter(4, 8)
        p0 = r.place("t0")
        p1 = r.failover("t0")
        assert p1.shard != p0.shard
        assert p1.generation == p0.generation + 1
        assert r.placement("t0") == p1

    def test_family_isolation(self):
        r = TenantRouter(2, 1)
        r.place("a", family="lstm_ad")
        r.place("b", family="deepar")  # own stack → own slots
        assert r.shard_load("lstm_ad") in ([1, 0], [0, 1])
        assert r.shard_load("deepar") in ([1, 0], [0, 1])


class TestShardedScorer:
    @pytest.fixture(scope="class")
    def scorer(self):
        mm = MeshManager(tenant=4, data=2)
        spec = get_model("lstm_ad")
        cfg = make_config("lstm_ad", {"window": 8, "hidden": 8})
        return ShardedScorer(
            mm, spec, cfg, slots_per_shard=2, max_streams=16, window=8
        )

    def test_step_shapes_and_masking(self, scorer):
        T, B = scorer.n_slots, 8
        ids = jnp.zeros((T, B), jnp.int32)
        vals = jnp.ones((T, B), jnp.float32)
        valid = jnp.ones((T, B), bool)
        scores = scorer.step(ids, vals, valid)
        assert scores.shape == (T, B)
        # no tenant active yet → all masked to 0
        assert float(jnp.abs(scores).max()) == 0.0

    def test_activate_scores_only_that_slot(self, scorer):
        scorer.activate(3)
        T, B = scorer.n_slots, 8
        ids = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32) % 4, (T, B))
        rng = np.random.default_rng(0)
        # feed several batches so windows warm past the cold-start gate
        for i in range(6):
            vals = jnp.asarray(rng.normal(size=(T, B)), jnp.float32)
            scores = scorer.step(ids, vals, jnp.ones((T, B), bool))
        assert scores.shape == (T, B)
        scores_np = np.asarray(scores)
        inactive = scores_np[[i for i in range(T) if i != 3]]
        assert np.all(inactive == 0.0)
        assert np.any(scores_np[3] != 0.0)
        scorer.deactivate(3)

    def test_sharding_layout(self, scorer):
        """Params sharded over tenant axis; state over (tenant, data)."""
        leaf = jax.tree_util.tree_leaves(scorer.params)[0]
        assert len(leaf.sharding.device_set) >= 4
        for leaf in jax.tree_util.tree_leaves(scorer.state):
            assert len(leaf.sharding.device_set) == 8
        t, s, w = scorer.n_slots, scorer.max_streams, scorer.window
        assert scorer.ring_values().shape == (t, s, w)


def test_data_shards_own_padded_rows_and_match_one_shard():
    """3 streams x W 8 a data shard is 24 floats of a 128-lane row: each
    shard's part of the ring store is padded to a whole row, and the rings
    and scores equal those of the same streams on one shard."""
    spec = get_model("lstm_ad")
    cfg = make_config("lstm_ad", {"window": 8, "hidden": 8})

    def build(data):
        sc = ShardedScorer(
            MeshManager(tenant=1, data=data, devices=jax.devices()[:data]),
            spec, cfg, slots_per_shard=2, max_streams=6, window=8,
        )
        sc.activate(1)
        return sc

    two, one = build(2), build(1)
    assert two.state.values.shape == (2, 2, 128)
    assert one.state.values.shape == (2, 1, 128)
    rng = np.random.default_rng(3)
    b = 5
    for _ in range(4):
        local = rng.integers(0, 3, (2, 2 * b)).astype(np.int32)
        flat = local + np.repeat([0, 3], b)[None, :].astype(np.int32)
        vals = rng.normal(size=(2, 2 * b)).astype(np.float32)
        valid = rng.random((2, 2 * b)) > 0.2
        s2 = np.asarray(two.step(local, vals, valid))
        s1 = np.asarray(one.step(flat, vals, valid))
        np.testing.assert_allclose(s2, s1, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(two.ring_values()), np.asarray(one.ring_values()))
    assert np.asarray(two.ring_values())[1].any()


class TestStepCountsWire:
    """step_counts (wire-thin hot path) must agree with the masked step."""

    def _twin(self, wire_dtype):
        mm = MeshManager(tenant=4, data=2)
        spec = get_model("lstm_ad")
        cfg = make_config("lstm_ad", {"window": 8, "hidden": 8})
        return ShardedScorer(
            mm, spec, cfg, slots_per_shard=2, max_streams=16, window=8,
            wire_dtype=wire_dtype,
        )

    def test_counts_matches_mask_f32(self):
        a, b = self._twin("f32"), self._twin("f32")
        a.activate(1)
        b.activate(1)
        T, D, B = a.n_slots, a.mm.n_data_shards, 4
        rng = np.random.default_rng(1)
        for _ in range(5):
            # front-contiguous lanes: k valid rows per (slot, dshard)
            ids = np.zeros((T, D * B), np.int32)
            vals = np.zeros((T, D * B), np.float32)
            counts = np.zeros((T, D), np.int32)
            mask = np.zeros((T, D * B), bool)
            for t in range(T):
                for d in range(D):
                    k = int(rng.integers(0, B + 1))
                    base = d * B
                    ids[t, base:base + k] = rng.integers(0, 8, k)
                    vals[t, base:base + k] = rng.normal(size=k)
                    counts[t, d] = k
                    mask[t, base:base + k] = True
            sm = np.asarray(a.step(ids, vals, mask))
            sc = np.asarray(b.step_counts(
                ids.astype(b.ids_np_dtype), vals.astype(b.vals_np_dtype),
                counts,
            ))
            # every step must agree (state evolves across iterations)
            np.testing.assert_allclose(sm, sc, rtol=1e-6, atol=1e-6)

    def test_bf16_wire_close_to_f32(self):
        a, b = self._twin("f32"), self._twin("bf16")
        a.activate(0)
        b.activate(0)
        T, D, B = a.n_slots, a.mm.n_data_shards, 8
        assert b.ids_np_dtype == np.uint16
        rng = np.random.default_rng(2)
        ids = np.broadcast_to(
            np.arange(D * B, dtype=np.int32) % 8, (T, D * B)
        ).copy()
        counts = np.full((T, D), B, np.int32)
        mask = np.ones((T, D * B), bool)
        for _ in range(6):
            vals = rng.normal(size=(T, D * B)).astype(np.float32)
            sm = np.asarray(a.step(ids, vals, mask))
            sc = np.asarray(b.step_counts(
                ids.astype(np.uint16), vals.astype(b.vals_np_dtype), counts
            )).astype(np.float32)
            # bf16 wire: ~3 significant digits end to end, every step
            np.testing.assert_allclose(sm, sc, rtol=0.1, atol=0.05)
        assert np.any(sc != 0.0)


def test_stack_unstack_roundtrip():
    spec = get_model("lstm_ad")
    cfg = make_config("lstm_ad", {"hidden": 4})
    ps = [spec.init(jax.random.PRNGKey(i), cfg) for i in range(3)]
    stacked = stack_params(ps)
    back = unstack_slot(stacked, 1)
    for a, b in zip(
        jax.tree_util.tree_leaves(back), jax.tree_util.tree_leaves(ps[1])
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


async def test_train_resident_diverges_active_slots():
    """Sharded training on resident window state: loss drops, active slots
    diverge, inactive slots stay pristine (per-tenant divergence)."""
    import optax
    import jax
    import jax.numpy as jnp
    from sitewhere_tpu.parallel.mesh import MeshManager
    from sitewhere_tpu.parallel.sharded import ShardedScorer, unstack_slot
    from sitewhere_tpu.models import get_model, make_config
    import numpy as np

    mm = MeshManager(tenant=4, data=2)
    spec = get_model("lstm_ad")
    cfg = make_config("lstm_ad", {})
    sc = ShardedScorer(mm, spec, cfg, slots_per_shard=2, max_streams=64, window=16)
    sc.activate(0)
    sc.activate(3)
    rng = np.random.RandomState(0)
    for _ in range(20):
        ids = np.zeros((8, 32), np.int32)
        vals = np.zeros((8, 32), np.float32)
        valid = np.zeros((8, 32), bool)
        for slot, scale in ((0, 1.0), (3, 30.0)):
            ids[slot] = np.tile(np.arange(16, dtype=np.int32), 2)
            vals[slot] = rng.randn(32).astype(np.float32) * scale
            valid[slot] = True
        sc.step(ids, vals, valid)
    sc.init_optimizer(optax.adam(1e-2))
    l0 = np.asarray(sc.train_resident())
    for _ in range(9):
        losses = np.asarray(sc.train_resident())
    assert losses[0] < l0[0] or losses[3] < l0[3]
    leaves = jax.tree_util.tree_leaves
    p0, p1, p3 = (unstack_slot(sc.params, i) for i in (0, 1, 3))
    d03 = sum(float(jnp.abs(a - b).sum()) for a, b in zip(leaves(p0), leaves(p3)))
    drift1 = sum(
        float(jnp.abs(a - b).sum())
        for a, b in zip(leaves(p1), leaves(sc._base_params))
    )
    assert d03 > 1e-3          # active slots trained apart
    assert drift1 == 0.0       # inactive slot untouched
    # scoring still works on the trained stack
    s = np.asarray(sc.step(ids, vals, valid))
    assert np.isfinite(s).all()
