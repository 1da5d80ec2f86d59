"""Fused megabatch kernel suite (docs/PERFORMANCE.md "Fused tenant
kernels"): numerics parity fused vs. legacy vmap on identical stacked
params, K-step per-timestep ordering, per-tenant weight quantization,
honest K/quant FLOPs accounting, the FUSED_STEP_ENABLED rollback, and
the check_fusion jaxpr lint (tier-1 import, like check_hotpath)."""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import sitewhere_tpu.parallel.sharded as sharded
from sitewhere_tpu.models import ModelSpec, get_model, make_config
from sitewhere_tpu.models import lstm_ad
from sitewhere_tpu.models.common import (
    dense_flops,
    lstm_ad_flops_per_row,
    lstm_scan_flops,
    quantize_params,
    transformer_flops_per_row,
)
from sitewhere_tpu.parallel.mesh import MeshManager

_spec = importlib.util.spec_from_file_location(
    "check_fusion",
    Path(__file__).resolve().parent.parent / "tools" / "check_fusion.py",
)
check_fusion = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(check_fusion)

W, HID = 8, 8


def _build(
    fused: bool,
    wire_dtype: str = "f32",
    fuse_k: int = 1,
    param_dtype: str = "f32",
    model_dtype: str = "float32",
    family: str = "lstm_ad",
):
    """A small 4×2-mesh scorer; same seed everywhere ⇒ identical stacked
    params across every twin this suite compares."""
    prev = sharded.FUSED_STEP_ENABLED
    sharded.FUSED_STEP_ENABLED = fused
    try:
        mm = MeshManager(tenant=4, data=2)
        spec = get_model(family)
        over = (
            {"window": W, "hidden": HID, "dtype": model_dtype}
            if family == "lstm_ad"
            else {"hidden": HID, "dtype": model_dtype}
        )
        cfg = make_config(family, over)
        return sharded.ShardedScorer(
            mm, spec, cfg, slots_per_shard=2, max_streams=16, window=W,
            wire_dtype=wire_dtype, fuse_k=fuse_k, param_dtype=param_dtype,
        )
    finally:
        sharded.FUSED_STEP_ENABLED = prev


def _random_flush(rng, scorer, b_lane=4, full=False):
    """One counts-mode wire flush: front-contiguous rows per lane."""
    t, d = scorer.n_slots, scorer.mm.n_data_shards
    ids = np.zeros((t, d * b_lane), np.int32)
    vals = np.zeros((t, d * b_lane), np.float32)
    counts = np.zeros((t, d), np.int32)
    for ti in range(t):
        for di in range(d):
            k = b_lane if full else int(rng.integers(0, b_lane + 1))
            base = di * b_lane
            # few distinct streams so windows warm past the 4-sample
            # cold-start gate within a short drive
            ids[ti, base:base + k] = rng.integers(0, 2, k)
            vals[ti, base:base + k] = rng.normal(size=k)
            counts[ti, di] = k
    return ids, vals, counts


def _drive(scorer, flushes):
    out = []
    for ids, vals, counts in flushes:
        out.append(np.asarray(scorer.step_counts(
            ids.astype(scorer.ids_np_dtype),
            vals.astype(scorer.vals_np_dtype), counts,
        )).astype(np.float32))
    return out


# ------------------------------------------------------- numerics parity
@pytest.mark.parametrize("wire_dtype", ["f32", "bf16", "f16"])
def test_fused_matches_legacy_every_wire_dtype(wire_dtype):
    """Fused vs legacy vmap on identical stacked params, every step of a
    stateful drive (window state evolves) — within the wire's tolerance."""
    legacy = _build(False, wire_dtype=wire_dtype)
    fused = _build(True, wire_dtype=wire_dtype)
    assert not legacy.fused and fused.fused
    for s in (legacy, fused):
        s.activate(1)
        s.activate(5)
    rng = np.random.default_rng(7)
    flushes = [_random_flush(rng, legacy) for _ in range(5)]
    la, fa = _drive(legacy, flushes), _drive(fused, flushes)
    # f32 wire: fp reassociation noise only; bf16/f16 wires can differ by
    # one output-cast ulp on top
    tol = {"f32": 5e-5, "bf16": 2e-2, "f16": 5e-3}[wire_dtype]
    for sl, sf in zip(la, fa):
        np.testing.assert_allclose(sl, sf, rtol=tol, atol=tol)
    assert any(np.any(s != 0.0) for s in fa)  # the drive actually scored


@pytest.mark.parametrize("family", ["lstm_ad", "deepar", "transformer"])
def test_stacked_kernel_matches_legacy_score_per_family(family):
    """Model-level parity for EVERY fused family (the engine-level drive
    above exercises lstm_ad; this closes deepar/transformer): the
    stacked kernel on identical stacked params must reproduce per-slot
    legacy scores, mask cold starts, and keep k>1's newest column equal
    to k=1."""
    spec = get_model(family)
    over = {
        "lstm_ad": {"window": 12, "hidden": 8, "dtype": "float32"},
        "deepar": {"hidden": 8, "dtype": "float32"},
        "transformer": {
            "context": 12, "dim": 16, "depth": 1, "heads": 2,
            "dtype": "float32",
        },
    }[family]
    cfg = make_config(family, over)
    S, B, Wn = 3, 5, 12
    rng = np.random.RandomState(0)
    wins = rng.randn(S, B, Wn).astype(np.float32)
    nv = np.full((S, B), Wn, np.int32)
    nv[0, 0] = 2  # cold start
    ps = [spec.init(jax.random.PRNGKey(i), cfg) for i in range(S)]
    stacked = sharded.stack_params(ps)
    sk = np.asarray(spec.score_stacked(stacked, cfg, wins, nv, k=1))
    legacy = np.stack([
        np.asarray(spec.score(ps[s], cfg, wins[s], nv[s])) for s in range(S)
    ])
    np.testing.assert_allclose(sk[..., 0], legacy, rtol=2e-4, atol=2e-4)
    assert sk[0, 0, 0] == 0.0
    sk3 = np.asarray(spec.score_stacked(stacked, cfg, wins, nv, k=3))
    np.testing.assert_allclose(sk3[..., -1], sk[..., 0], rtol=1e-6, atol=1e-6)
    for pd in ("bf16", "int8"):
        sq = np.asarray(spec.score_stacked(
            quantize_params(stacked, pd), cfg, wins, nv, k=1
        ))
        assert np.isfinite(sq).all()


def test_fused_matches_legacy_engine_deepar():
    """Engine-level fused-vs-legacy parity for the second window-scan
    family (GRU) through the real step_counts wire."""
    legacy = _build(False, family="deepar")
    fused = _build(True, family="deepar")
    assert fused.fused and not legacy.fused
    for s in (legacy, fused):
        s.activate(2)
    rng = np.random.default_rng(17)
    flushes = [_random_flush(rng, legacy) for _ in range(4)]
    for sl, sf in zip(_drive(legacy, flushes), _drive(fused, flushes)):
        np.testing.assert_allclose(sl, sf, rtol=5e-5, atol=5e-5)


def test_fused_gather_rows_matches_legacy_incl_nan_padding():
    """The device-side gather over fused scores: picks equal the legacy
    path's picks and the ladder padding stays NaN."""
    legacy = _build(False)
    fused = _build(True)
    for s in (legacy, fused):
        s.activate(0)
        s.activate(3)
    rng = np.random.default_rng(3)
    ids, vals, counts = _random_flush(rng, legacy, full=True)
    n_rows = int(counts.sum())
    outs = {}
    for name, s in (("legacy", legacy), ("fused", fused)):
        dev = s.step_counts(
            ids.astype(s.ids_np_dtype), vals.astype(s.vals_np_dtype), counts
        )
        g = np.asarray(
            s.gather_rows(dev, jnp.asarray(counts), n_rows)
        ).astype(np.float32)
        outs[name] = g
    size = len(outs["fused"])
    assert size >= n_rows
    np.testing.assert_allclose(
        outs["legacy"][:n_rows], outs["fused"][:n_rows],
        rtol=5e-5, atol=5e-5,
    )
    assert np.isnan(outs["fused"][n_rows:]).all()


def test_cold_start_masking_matches():
    """Rows whose stream has <4 samples score 0 on both paths."""
    legacy = _build(False)
    fused = _build(True)
    for s in (legacy, fused):
        s.activate(2)
    t, d = legacy.n_slots, 2
    ids = np.zeros((t, d * 4), np.int32)
    vals = np.zeros((t, d * 4), np.float32)
    counts = np.zeros((t, d), np.int32)
    vals[2, :2] = [1.0, 2.0]   # 2 samples of stream 0 — cold
    counts[2, 0] = 2
    for s in (legacy, fused):
        out = np.asarray(s.step_counts(
            ids.astype(s.ids_np_dtype), vals.astype(s.vals_np_dtype), counts
        ))
        assert np.all(out == 0.0)


# --------------------------------------------------------- K-step fusion
def test_fuse_k_per_timestep_ordering():
    """A 3-row burst of one stream in one flush: fuse_k=3 resolves each
    row at its OWN window position (distinct scores, arrival-ordered),
    the newest row matches the k=1 score exactly, and k=1 keeps the
    legacy all-rows-take-newest semantics."""
    k3 = _build(True, fuse_k=3)
    k1 = _build(True, fuse_k=1)
    assert k3.k_steps == 3
    for s in (k3, k1):
        s.activate(0)
    rng = np.random.default_rng(11)
    t, d = k3.n_slots, 2
    # warm stream 0 one sample per flush so both twins hold identical state
    for v in rng.normal(size=10).astype(np.float32):
        ids = np.zeros((t, d * 4), np.int32)
        vals = np.zeros((t, d * 4), np.float32)
        counts = np.zeros((t, d), np.int32)
        vals[0, 0] = v
        counts[0, 0] = 1
        for s in (k3, k1):
            s.step_counts(
                ids.astype(s.ids_np_dtype), vals.astype(s.vals_np_dtype),
                counts,
            )
    ids = np.zeros((t, d * 4), np.int32)
    vals = np.zeros((t, d * 4), np.float32)
    counts = np.zeros((t, d), np.int32)
    vals[0, :3] = rng.normal(size=3)
    counts[0, 0] = 3
    s3 = np.asarray(k3.step_counts(
        ids.astype(k3.ids_np_dtype), vals.astype(k3.vals_np_dtype), counts
    ))[0, :3]
    s1 = np.asarray(k1.step_counts(
        ids.astype(k1.ids_np_dtype), vals.astype(k1.vals_np_dtype), counts
    ))[0, :3]
    assert len({round(float(x), 6) for x in s3}) == 3    # per-timestep
    assert abs(float(s3[2] - s1[2])) < 1e-6              # newest == k=1
    assert len({round(float(x), 6) for x in s1}) == 1    # k=1: all newest


def test_fuse_k_clamps_to_window():
    s = _build(True, fuse_k=99)
    assert s.k_steps == W - 1   # only W-1 positions are predictable


# ----------------------------------------------------------- quantization
def test_param_dtype_quantization_close_to_f32():
    """bf16/int8 stacked weights track the f32 fused scores within the
    quantization band; the int8 sidecar genuinely stores int8."""
    f32 = _build(True)
    bf16 = _build(True, param_dtype="bf16")
    int8 = _build(True, param_dtype="int8")
    for s in (f32, bf16, int8):
        s.activate(1)
    rng = np.random.default_rng(5)
    flushes = [_random_flush(rng, f32, full=True) for _ in range(3)]
    base = _drive(f32, flushes)
    for s, tol in ((bf16, 0.05), (int8, 0.1)):
        got = _drive(s, flushes)
        for a, b in zip(base, got):
            np.testing.assert_allclose(a, b, rtol=tol, atol=tol)
    leaf_dtypes = {
        l.dtype for l in jax.tree_util.tree_leaves(int8.kernel_params())
    }
    assert np.dtype(np.int8) in leaf_dtypes
    # the scale tree is per-slot per-channel: [S, 1, out]
    kp = int8.kernel_params()
    assert kp["wh"]["scale"].shape == (int8.n_slots, 1, 4 * HID)


def test_kernel_sidecar_refreshes_after_param_mutation():
    """activate(params=...) must invalidate the quantized sidecar — the
    next flush scores the NEW tenant weights, not a stale dequant."""
    s = _build(True, param_dtype="int8")
    s.activate(0)
    before = s.kernel_params()
    spec = get_model("lstm_ad")
    fresh = spec.init(jax.random.PRNGKey(99), s.cfg)
    s.activate(0, params=fresh)
    after = s.kernel_params()
    assert after is not before
    d = np.abs(
        np.asarray(after["wh"]["qw"][0], np.int32)
        - np.asarray(before["wh"]["qw"][0], np.int32)
    ).max()
    assert d > 0


def test_param_dtype_validation():
    with pytest.raises(ValueError, match="param_dtype"):
        _build(True, param_dtype="fp8")
    with pytest.raises(ValueError, match="fuse_k"):
        _build(True, fuse_k=0)


# --------------------------------------------------------- rollback knob
def test_kill_switch_restores_legacy_bit_for_bit():
    """FUSED_STEP_ENABLED=False ignores fuse_k/param_dtype and scores
    exactly (bitwise) like a plain pre-fusion scorer."""
    plain = _build(False)
    rolled = _build(False, fuse_k=4, param_dtype="int8")
    assert rolled.k_steps == 1 and rolled.param_dtype == "f32"
    assert rolled.kernel_params() is rolled.params
    for s in (plain, rolled):
        s.activate(1)
    rng = np.random.default_rng(13)
    flushes = [_random_flush(rng, plain) for _ in range(3)]
    for a, b in zip(_drive(plain, flushes), _drive(rolled, flushes)):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------------- FLOPs accounting
def test_lstm_fused_flops_hand_computed():
    """K-step + int8 accounting within 5% of an independent hand count
    (the PR 6 acceptance bar), and the legacy default unchanged."""
    cfg = make_config("lstm_ad", {"window": 32, "hidden": 64})
    t = 31
    legacy_hand = (2 * 1 * 256 + 2 * 64 * 256) * t + 2 * 64 * 1 * t
    assert abs(lstm_ad_flops_per_row(cfg, 32) - legacy_hand) / legacy_hand < 0.05
    # fused k=4 int8: scan over 31 steps + head on 4 positions, all MACs
    # at half width (int8 retires 2× faster than bf16 on the MXU)
    fused_hand = 0.5 * ((2 * 1 * 256 + 2 * 64 * 256) * t + 2 * 64 * 1 * 4)
    got = lstm_ad_flops_per_row(cfg, 32, k=4, param_dtype="int8")
    assert abs(got - fused_hand) / fused_hand < 0.05
    # sanity ordering: int8 < bf16 == f32 (same K)
    assert got < lstm_ad_flops_per_row(cfg, 32, k=4, param_dtype="bf16")
    assert (
        lstm_ad_flops_per_row(cfg, 32, k=4, param_dtype="bf16")
        == lstm_ad_flops_per_row(cfg, 32, k=4, param_dtype="f32")
    )


def test_transformer_quant_spares_attention_flops():
    """int8 scales only the weight matmuls: the activation·activation
    attention products stay full width, so int8 must NOT halve the
    transformer total."""
    cfg = make_config("transformer", {"dim": 128, "depth": 4, "heads": 4})
    full = transformer_flops_per_row(cfg, 32, k=1, param_dtype="f32")
    q = transformer_flops_per_row(cfg, 32, k=1, param_dtype="int8")
    t = 31
    attn = cfg.depth * 2 * (2.0 * t * t * cfg.dim)
    assert q == pytest.approx((full - attn) * 0.5 + attn)
    assert q > full * 0.5
    # legacy default (no kwargs) is the pre-fusion number
    assert transformer_flops_per_row(cfg, 32) == pytest.approx(
        dense_flops(1, cfg.dim) * t
        + cfg.depth * (
            4 * dense_flops(cfg.dim, cfg.dim) * t
            + 2 * (2.0 * t * t * cfg.dim)
            + (dense_flops(cfg.dim, 512) + dense_flops(512, cfg.dim)) * t
        )
        + dense_flops(cfg.dim, 2) * t
    )


def test_scorer_flops_reflect_active_variant():
    """ShardedScorer.flops_per_flush must report the variant that RUNS:
    fused int8+K differs from legacy; kill-switch scorer reports legacy."""
    legacy = _build(False, fuse_k=4, param_dtype="int8")
    fused = _build(True, fuse_k=4, param_dtype="int8")
    cfg = fused.cfg
    assert legacy.flops_per_row() == pytest.approx(
        lstm_ad_flops_per_row(cfg, W)
    )
    assert fused.flops_per_row() == pytest.approx(
        lstm_ad_flops_per_row(cfg, W, k=fused.k_steps, param_dtype="int8")
    )
    assert fused.flops_per_row() < legacy.flops_per_row()
    b = 16
    assert fused.flops_per_flush(b) == pytest.approx(
        fused.n_slots * 2 * b * fused.flops_per_row(b)
    )


# ------------------------------------------------------------ fusion lint
def test_check_fusion_lint_is_clean():
    assert check_fusion.lint_fusion() == []


def test_check_fusion_catches_per_slot_loop(monkeypatch):
    """A python loop over slots (S dots at S slots) and a fat scan body
    (3 dots/step) must both be findings; '# fusion: ok' opts out."""
    from sitewhere_tpu.models import MODEL_REGISTRY

    def slot_loop(params, cfg, windows, n_valid, k=1):
        outs = []
        for s in range(windows.shape[0]):
            w = params["wh"]["w"][s]
            outs.append(jnp.tanh(windows[s][:, : w.shape[0]] @ w)[:, :1])
        r = jnp.stack(outs)
        return jnp.repeat(r, k, axis=-1)

    def fat_scan(params, cfg, windows, n_valid, k=1):
        wh = params["wh"]["w"]  # [S, H, 4H]

        def step(c, x_t):
            a = jnp.einsum("sbh,sho->sbo", c, wh)
            b = jnp.einsum("sbh,sho->sbo", c, wh)
            d = jnp.einsum("sbh,sho->sbo", c, wh)
            return c + (a + b + d)[..., : c.shape[-1]] * 0.0, None

        s, b, w = windows.shape
        c0 = jnp.zeros((s, b, wh.shape[-2]), jnp.float32)
        c, _ = jax.lax.scan(step, c0, jnp.moveaxis(windows, -1, 0))
        return jnp.zeros((s, b, k), jnp.float32) + c[..., :1] * 0.0

    def vmap_resurrection(params, cfg, windows, n_valid, k=1):
        # the SUBTLE regression: vmap of the scalar model batches the
        # per-slot dots into single eqns (count checks pass) but drags
        # the degenerate [B,1]x[1,4H] input projection back into the
        # scan body as a batched size-1 contraction
        def scalar(p, w):
            wx = p["wx"]["w"]

            def step(c, x_t):
                g = x_t[:, None] @ wx          # [B,1]x[1,4H]
                return c + g[:, : c.shape[-1]] * 0.0, None

            c0 = jnp.zeros((w.shape[0], p["wh"]["w"].shape[0]), jnp.float32)
            c, _ = jax.lax.scan(step, c0, w.T)
            return c[:, :1]

        r = jax.vmap(lambda p, w: scalar(p, w))(params, windows)
        return jnp.repeat(r, k, axis=-1)

    base = MODEL_REGISTRY["lstm_ad"]
    for name, fn, needle in (
        ("bad_loop", slot_loop, "scales with stacked slots"),
        ("bad_scan", fat_scan, "dot_generals per step"),
        ("bad_vmap", vmap_resurrection, "size-1 contracting dim"),
    ):
        spec = ModelSpec(
            name=name, config_cls=base.config_cls, init=base.init,
            score=base.score, score_stacked=fn,
        )
        monkeypatch.setitem(MODEL_REGISTRY, name, spec)
        findings = check_fusion.lint_fusion(
            {name: {"window": 8, "hidden": 8}}
        )
        assert findings and needle in findings[0], (name, findings)

    def opted(params, cfg, windows, n_valid, k=1):  # fusion: ok
        return slot_loop(params, cfg, windows, n_valid, k)

    spec = ModelSpec(
        name="opted", config_cls=base.config_cls, init=base.init,
        score=base.score, score_stacked=opted,
    )
    monkeypatch.setitem(MODEL_REGISTRY, "opted", spec)
    assert check_fusion.lint_fusion({"opted": {"window": 8, "hidden": 8}}) == []

    # a stale registry entry is itself a finding
    missing = check_fusion.lint_fusion({"no_such_family": {}})
    assert missing and "stale" in missing[0]


# ------------------------------------------------- flightrec attribution
async def test_flightrec_records_kernel_variant():
    """Per-flush blackbox records carry k_steps/param_dtype so incident
    snapshots attribute timings to the kernel variant that ran."""
    import asyncio

    from sitewhere_tpu.core.batch import MeasurementBatch
    from sitewhere_tpu.instance import SiteWhereInstance
    from sitewhere_tpu.runtime.config import InstanceConfig, MeshConfig

    inst = SiteWhereInstance(InstanceConfig(
        instance_id="fusedrec", mesh=MeshConfig(slots_per_shard=2),
    ))
    await inst.start()
    try:
        await inst.tenant_management.create_tenant(
            "fk", template="iot-temperature", decoder="binary",
            fuse_k=2, param_dtype="bf16",
        )
        await inst.drain_tenant_updates()
        for _ in range(200):
            if "fk" in inst.tenants:
                break
            await asyncio.sleep(0.02)
        scorer = inst.inference.scorers["lstm_ad"]
        if scorer.fused:
            assert scorer.k_steps == 2 and scorer.param_dtype == "bf16"
        toks = [
            d.token
            for d in inst.tenants["fk"].device_management.bootstrap_fleet(4)
        ]
        batch = MeasurementBatch.from_columns(
            "fk", [toks[i % 4] for i in range(64)],
            ["temperature"] * 64, [float(i) for i in range(64)], [0.0] * 64,
        )
        await inst.bus.publish(inst.bus.naming.decoded_events("fk"), batch)
        scored = inst.metrics.counter("tpu_inference.scored_total")
        for _ in range(400):
            if scored.value >= 64:
                break
            await asyncio.sleep(0.02)
        assert scored.value >= 64
        rings = inst.flightrec.describe()["rings"]["flush"]
        recs = rings["lstm_ad"]["records"]
        assert recs
        assert recs[-1]["k_steps"] == scorer.k_steps
        assert recs[-1]["param_dtype"] == scorer.param_dtype
    finally:
        await inst.terminate()
