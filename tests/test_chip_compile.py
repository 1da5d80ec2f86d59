"""What the chip's compiler says, without the chip — and the chip smoke's
own control flow on the CPU.

The TPU compiler is installed here and compiles for a DESCRIBED v5e
(`v5e:2x2` topology, nothing attached): the jitted programs of the main
path at real widths either compile or raise what the chip would raise.
Nothing runs, so these say nothing about results or times — a compile
that passes is not a chip run. Skipped where the topology cannot be
described. The persistent compile cache is off around them: such an
entry cannot be read back without a chip.

`chip_smoke.py` itself refuses a CPU, so its phase functions are imported
and driven here at a tiny size; that covers paths, arguments and checks,
not the device.
"""

import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from sitewhere_tpu.models import get_model, make_config
from sitewhere_tpu.parallel.mesh import AXIS_DATA, AXIS_TENANT, MeshManager
from sitewhere_tpu.parallel.sharded import ShardedScorer, init_stacked_state

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
import chip_smoke  # noqa: E402

TD = P(AXIS_TENANT, AXIS_DATA)


# ------------------------------------------------------ described device
@pytest.fixture(scope="module")
def described():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 - no TPU compiler here, or
        # another process holds it (/tmp/libtpu_lockfile)
        pytest.skip(f"cannot describe a v5e:2x2 topology: {exc!r}")


@pytest.fixture
def topo(described):
    """The described topology, with the compile cache off around the
    test (guide §2.3) and back on for the CPU tests that follow."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _sds(tree, mesh, specs):
    """Shapes of ``tree`` placed on ``mesh`` by ``specs`` (a matching
    tree of PartitionSpecs, or one spec for every leaf)."""
    if isinstance(specs, P):
        specs = jax.tree_util.tree_map(lambda _: specs, tree)
    return jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
        tree, specs,
    )


def described_scorer(devices, tenant: int, data: int, n_slots: int = 32):
    """The smoke's scorer (hidden 64, window 32, max_streams 2048, bf16
    wire), built on CPU devices and re-pointed at ``devices`` of the
    described topology — `device_put` to a described device fails, so
    the steering lives here, not in the program."""
    spec = get_model("lstm_ad")
    cfg = make_config("lstm_ad", {"window": 32, "hidden": 64})
    cpu = jax.devices()[: tenant * data]
    scorer = ShardedScorer(
        MeshManager(tenant=tenant, data=data, devices=cpu), spec, cfg,
        slots_per_shard=n_slots // tenant, max_streams=2048, window=32,
        wire_dtype="bf16",
    )
    scorer.init_optimizer()
    scorer.mm = MeshManager(tenant=tenant, data=data, devices=devices)
    return scorer


def lower_step_counts(scorer, b_lane: int, max_streams: int = 0):
    """``step_counts`` at lane size ``b_lane``; ``max_streams`` lowers it
    against a window state of that capacity (shapes only) in place of
    the scorer's own small one."""
    mesh, d = scorer.mm.mesh, scorer.mm.n_data_shards
    t = scorer.n_slots
    state = scorer.state
    if max_streams:
        state = jax.eval_shape(lambda: init_stacked_state(
            t, max_streams, scorer.window, d))
    return scorer._build_step(counts_mode=True).lower(
        _sds(scorer.kernel_params(), mesh, scorer.step_param_specs),
        _sds(state, mesh, TD),
        _sds(scorer.active, mesh, P(AXIS_TENANT)),
        jax.ShapeDtypeStruct((t, d * b_lane), scorer.ids_np_dtype,
                             sharding=NamedSharding(mesh, TD)),
        jax.ShapeDtypeStruct((t, d * b_lane), scorer.vals_np_dtype,
                             sharding=NamedSharding(mesh, TD)),
        jax.ShapeDtypeStruct((t, d), jnp.int32,
                             sharding=NamedSharding(mesh, TD)),
    )


def lower_gather(scorer, b_lane: int, size: int, device):
    one = SingleDeviceSharding(device)
    t, d = scorer.n_slots, scorer.mm.n_data_shards
    scorer._gather = None  # a fresh jit, not the CPU-cached one
    return scorer._gather_fn().lower(
        jax.ShapeDtypeStruct((t, d * b_lane), jnp.bfloat16, sharding=one),
        jax.ShapeDtypeStruct((t, d), jnp.int32, sharding=one),
        size,
    )


def lower_train(scorer, fused: bool):
    """The train-lane step (``fused``) or ``train_resident``'s step."""
    mesh = scorer.mm.mesh
    build = (scorer._build_train_step_fused if fused
             else scorer._build_train_step)
    return build(scorer._optimizer, scorer._lr_sign).lower(
        _sds(scorer.params, mesh, scorer.param_specs),
        _sds(scorer._opt_state, mesh, scorer._opt_specs),
        _sds(scorer.state, mesh, TD),
        _sds(scorer.active, mesh, P(AXIS_TENANT)),
        _sds(scorer.slot_lr, mesh, P(AXIS_TENANT)),
    )


def lower_vit_dct(device, batch: int = 64, k: int = 32):
    from sitewhere_tpu.models import vit
    from sitewhere_tpu.ops.dct import layout_for

    cfg = vit.VIT_B16
    lay = layout_for(cfg.image_size, cfg.image_size, 2, k)
    one = SingleDeviceSharding(device)
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
        jax.eval_shape(lambda key: vit.init(key, cfg), jax.random.PRNGKey(0)),
    )
    y = jax.ShapeDtypeStruct((batch, lay.y_blocks, k), jnp.int16, sharding=one)
    c = jax.ShapeDtypeStruct((batch, lay.c_blocks, k), jnp.int16, sharding=one)
    return jax.jit(
        lambda p, yy, cb, cr: vit.apply_dct(p, cfg, yy, cb, cr, lay)
    ).lower(params, y, c, c)


def compile_report(lowered) -> dict:
    """Compile; (temp bytes, collectives by kind) of the result."""
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    return {
        "temp_mb": round(mem.temp_size_in_bytes / 1e6, 1),
        "args_mb": round(mem.argument_size_in_bytes / 1e6, 1),
        "collectives": chip_smoke.count_collectives(compiled.as_text()),
    }


@pytest.fixture(scope="module")
def one_chip_scorer(described):
    return described_scorer(described.devices[:1], 1, 1)


def test_step_counts_compiles_for_v5e(one_chip_scorer, topo):
    rep = compile_report(lower_step_counts(one_chip_scorer, 2048))
    assert rep["collectives"] == {}, rep
    assert rep["temp_mb"] < 8_000, rep  # fits a 16 GB chip with room


def whole_state_ops(hlo: str, n_elems: int) -> list:
    """(name, opcode, called computation's text) of every instruction of
    the entry computation that produces an array of ``n_elems`` elements
    or more by doing work — parameters, bitcasts and tuple plumbing move
    nothing and are left out."""
    entry = re.search(r"^ENTRY [^\n]*\{\n(.*?)^\}", hlo, re.S | re.M).group(1)
    found = []
    for line in entry.splitlines():
        m = re.match(
            r"\s*(?:ROOT )?(\S+) = (\(.*?\)|\S+) ([\w-]+)\(", line)
        if not m or m.group(3) in (
                "parameter", "bitcast", "tuple", "get-tuple-element"):
            continue
        sizes = [
            int(np.prod([int(d) for d in dims.split(",") if d]))
            for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(2))
        ]
        if max(sizes, default=0) >= n_elems:
            callee = re.search(r"calls=(%[\w.-]+)", line)
            body = ""
            if callee:
                body = re.search(
                    r"^" + re.escape(callee.group(1)) + r" [^\n]*\{\n(.*?)^\}",
                    hlo, re.S | re.M).group(1)
            found.append((m.group(1), m.group(3), body))
    return found


@pytest.mark.parametrize("max_streams", [524_288, 1_048_576])
def test_step_touches_the_window_state_only_where_it_scatters(
        one_chip_scorer, topo, max_streams):
    """At the benchmark cell's size (32 slots, 524,288 streams, W 32,
    bucket 1,024) and at the published fleet's power of two, which the
    [T, S, W] store could not compile ("Used 16.00G of 15.75G"): the
    compiled step holds no temporary of the state's order and one
    operation that yields the whole state — the in-place scatter."""
    t, w = one_chip_scorer.n_slots, one_chip_scorer.window
    compiled = lower_step_counts(
        one_chip_scorer, 1024, max_streams).compile()
    state_bytes = t * max_streams * w * 4
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < state_bytes // 10, mem
    assert mem.alias_size_in_bytes >= state_bytes, mem
    ops = whole_state_ops(compiled.as_text(), t * max_streams * w)
    assert len(ops) == 1, [(name, op) for name, op, _ in ops]
    name, op, body = ops[0]
    assert op == "scatter" or " scatter(" in body, (name, op)


def element_gathers(hlo: str, n_elems: int) -> list:
    """Every instruction of the module, fused or not, that gathers SINGLE
    elements (``slice_sizes`` all 1) into ``n_elems`` or more, or that
    the program's ``take_along_axis`` became — the row fetch, whose
    slices are 128 lanes long, is not one."""
    found = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?(\S+) = \w+\[([\d,]*)\]\S* gather\(", line)
        if not m:
            continue
        size = int(np.prod([int(d) for d in m.group(2).split(",") if d]))
        by_element = re.search(r"slice_sizes=\{1(,1)*\}", line)
        if size >= n_elems and (by_element or "take_along_axis" in line):
            found.append(m.group(1))
    return found


@pytest.mark.parametrize("b_lane", [1024, 16_384])
def test_step_picks_window_lanes_without_an_element_gather(
        one_chip_scorer, topo, b_lane):
    """At the benchmark cell's size (32 slots, 1,171,875 streams, W 32)
    and its smallest and largest buckets: the windows come out of the
    fetched rows by selects and rolls, so no gather of the compiled step
    yields the T x B x W window plane one element at a time (10.7 of the
    step's 27 ms on the v5e, PERF.md section 6, PR 37), the state is
    still updated in place, and step and state fit the chip."""
    t, w, s = one_chip_scorer.n_slots, one_chip_scorer.window, 1_171_875
    compiled = lower_step_counts(one_chip_scorer, b_lane, s).compile()
    hlo = compiled.as_text()
    assert element_gathers(hlo, t * b_lane * w) == []
    # the reader still reads this compiler's text: ``pos`` and ``count``
    # come by element gathers of T x B
    assert element_gathers(hlo, t * b_lane)
    state_bytes = t * s * w * 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= state_bytes, mem
    if b_lane == 1024:
        assert mem.temp_size_in_bytes < state_bytes // 10, mem
    # the largest bucket's temporaries are the model's, 2.8 GB with or
    # without the element gather
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 12e9, mem


def test_gather_compiles_for_v5e(one_chip_scorer, topo):
    rep = compile_report(
        lower_gather(one_chip_scorer, 2048, 8192, topo.devices[0]))
    assert rep["collectives"] == {}, rep


def test_train_lane_step_compiles_for_v5e(one_chip_scorer, topo):
    assert one_chip_scorer.train_lane
    rep = compile_report(lower_train(one_chip_scorer, fused=True))
    assert rep["collectives"] == {}, rep
    assert rep["temp_mb"] < 8_000, rep


def test_vit_b16_apply_dct_compiles_for_v5e(topo):
    rep = compile_report(lower_vit_dct(topo.devices[0]))
    assert rep["collectives"] == {}, rep
    assert rep["temp_mb"] < 8_000, rep


def test_four_chip_mesh_serving_has_no_collectives(topo):
    """tenant=2 × data=2 over the four described chips: the serving step
    stays collective-free; ``train_resident``'s step holds all-reduces
    (the psum over ``data``) and no other kind."""
    scorer = described_scorer(topo.devices, 2, 2)
    assert compile_report(
        lower_step_counts(scorer, 2048))["collectives"] == {}
    train = compile_report(lower_train(scorer, fused=False))["collectives"]
    assert set(train) == {"all-reduce"}, train


# --------------------------------------------- the smoke's phases, on CPU
TINY = chip_smoke.EventsSize(
    tenants=4, devices=2, burst=20, rounds=6, max_streams=64,
    buckets=(64, 256), hidden=16, window=16,
)


async def test_smoke_events_phase_tiny_on_cpu():
    line = await chip_smoke.phase_events(TINY, 0, "cpu", 4000.0)
    assert line["ok"] and line["published"] == line["scored"] == line["stored"]
    assert line["ref_rows"] == TINY.devices * TINY.burst
    assert line["ref_max_abs_err"] <= chip_smoke.SCORE_ATOL


async def test_smoke_fails_on_a_broken_scorer(monkeypatch):
    """The product passes events through unscored, with zero loss, when
    its scorer dies — the smoke must call that a failure."""
    prewarm = ShardedScorer.prewarm

    def prewarm_then_break(self, lane_sizes):
        prewarm(self, lane_sizes)
        self.fault_steps = 10**9

    monkeypatch.setattr(ShardedScorer, "prewarm", prewarm_then_break)
    with pytest.raises(chip_smoke.SmokeFailure):
        await chip_smoke.phase_events(TINY, 0, "cpu", 4000.0)


async def test_smoke_media_phase_tiny_on_cpu():
    line = await chip_smoke.phase_media(True, 0, batch=8, n_batches=2)
    assert line["ok"] and line["pil_fallbacks"] == 0
    assert all(c.startswith("dct") for c in line["codecs"])


async def test_smoke_chips4_phase_tiny_on_virtual_devices():
    line = await chip_smoke.phase_chips4(TINY, 0, "cpu")
    assert line["ok"] and len(line["per_device"]) == 4
    assert line["serving_collectives"] == 0 and line["train_all_reduces"] >= 1


@pytest.mark.parametrize("one_step, rows", [(True, 32), (False, 8)])
def test_stream_state_programs_compile_for_v5e_at_published_widths(
        topo, one_step, rows, monkeypatch):
    """The stateful family's two programs at the published widths and the
    provisioned store (shapes only): they fit beside 6.4 GB of weights
    and 5.5 GB of state, the store is updated in place, the grouped
    expert product is a kernel, and nothing as large as a layer's expert
    weights is copied (an ``up`` matrix whose minor dimension is not
    whole lane tiles was, every step, until it was stored padded)."""
    from sitewhere_tpu.models.common import sketch_edges
    from sitewhere_tpu.ops import moe
    from sitewhere_tpu.parallel.streamstate import StreamPrograms

    # the backend here is the CPU; the chip's branch is what compiles
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    one = SingleDeviceSharding(topo.devices[0])
    spec = get_model("nemotron_h")
    cfg = make_config("nemotron_h", {})

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                (1,) + x.shape, x.dtype, sharding=one), tree)

    params = placed(jax.eval_shape(
        lambda: spec.init(jax.random.PRNGKey(0), cfg)))
    state = placed(jax.eval_shape(lambda: spec.init_state(cfg, 512)))
    progs = StreamPrograms(spec, cfg, 1, 512, jnp.float32,
                           sketch_edges(1.0, 64.0, 64))
    length = 1 if one_step else cfg.chunk_size

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    compiled = progs.program(one_step, 0).lower(
        params, state, arg(rows, 2 + 2 * length)).compile()
    mem = compiled.memory_analysis()
    state_bytes = sum(
        int(np.prod(x.shape)) * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(state))
    assert mem.alias_size_in_bytes == state_bytes       # donated, in place
    assert mem.temp_size_in_bytes < 1.2e9
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 0.85 * 16 * 2**30)
    text = compiled.as_text()
    assert text.count('kernel_name = "gmm"') >= 8 or text.count(
        "gmm") >= 8                                      # 4 layers x up, down
    assert "ragged-dot" not in text
    assert not re.search(r"bf16\[1,64,\d+,\d+\]\S* copy\(", text)


def test_smoke_script_refuses_a_cpu():
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout == ""  # no result line of any kind
    assert "platform 'cpu'" in proc.stderr, proc.stderr


def test_reference_lstm_matches_the_model_in_f32():
    """The numpy reference in chip_smoke.py against models/lstm_ad.py run
    in f32: two independent writings of the same equations."""
    spec = get_model("lstm_ad")
    cfg = make_config("lstm_ad", {"window": 32, "hidden": 64,
                                  "dtype": "float32"})
    params = spec.init(jax.random.PRNGKey(3), cfg)
    windows = np.random.RandomState(0).randn(64, 32).astype(np.float32) + 20
    want = np.asarray(spec.score(
        params, cfg, jnp.asarray(windows), jnp.full((64,), 32)))
    got = chip_smoke.lstm_ad_reference(
        jax.tree_util.tree_map(np.asarray, params), windows)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
