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


@pytest.mark.parametrize("b_lane", [2048, ShardedScorer.NARROW_LANES])
def test_step_counts_compiles_for_v5e(one_chip_scorer, topo, b_lane):
    rep = compile_report(lower_step_counts(one_chip_scorer, b_lane))
    assert rep["collectives"] == {}, rep
    assert rep["temp_mb"] < 8_000, rep  # fits a 16 GB chip with room


def computation(hlo: str, name: str = "ENTRY") -> str:
    """The body of the computation ``name`` (``%region_3.12``), or of
    the entry computation."""
    head = r"^ENTRY [^\n]*" if name == "ENTRY" else r"^" + re.escape(name) + " [^\n]*"
    return re.search(head + r"\{\n(.*?)^\}", hlo, re.S | re.M).group(1)


def whole_state_ops(hlo: str, n_elems: int, name: str = "ENTRY",
                    exact: bool = False) -> list:
    """(name, opcode, called computation's text, result type) of every
    instruction of the computation ``name`` (the entry one by default)
    that produces an array of ``n_elems`` elements or more (exactly
    that many with ``exact``) by doing work — parameters, bitcasts and
    tuple plumbing move nothing and are left out."""
    found = []
    for line in computation(hlo, name).splitlines():
        m = re.match(
            r"\s*(?:ROOT )?(\S+) = (\(.*?\)|\S+) ([\w-]+)\(", line)
        if not m or m.group(3) in (
                "parameter", "bitcast", "tuple", "get-tuple-element"):
            continue
        sizes = [
            int(np.prod([int(d) for d in dims.split(",") if d]))
            for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(2))
        ]
        if (n_elems in sizes) if exact else max(sizes, default=0) >= n_elems:
            callee = re.search(r"calls=(%[\w.-]+)", line)
            body = computation(hlo, callee.group(1)) if callee else ""
            found.append((m.group(1), m.group(3), body, line))
    return found


@pytest.mark.parametrize("b_lane", [1024, ShardedScorer.NARROW_LANES])
@pytest.mark.parametrize("max_streams", [524_288, 1_048_576])
def test_step_touches_the_window_state_only_where_it_scatters(
        one_chip_scorer, topo, max_streams, b_lane):
    """At the benchmark cell's size (32 slots, 524,288 streams, W 32,
    bucket 1,024, and the narrow plane of 128 lanes below it) and at the
    published fleet's power of two, which the [T, S, W] store could not
    compile ("Used 16.00G of 15.75G"): the compiled step holds no
    temporary of the state's order and one operation that yields the
    whole state — the in-place scatter."""
    t, w = one_chip_scorer.n_slots, one_chip_scorer.window
    compiled = lower_step_counts(
        one_chip_scorer, b_lane, max_streams).compile()
    state_bytes = t * max_streams * w * 4
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < state_bytes // 10, mem
    assert mem.alias_size_in_bytes >= state_bytes, mem
    ops = whole_state_ops(compiled.as_text(), t * max_streams * w)
    assert len(ops) == 1, [(name, op) for name, op, _, _ in ops]
    name, op, body, _ = ops[0]
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


def operand_types(hlo: str, opcode: str) -> list:
    """(name, result type, first operand's type) of every ``opcode``
    instruction of the module, fused or not; an operand's type is read
    from its definition in the same computation."""
    found = []
    for body in re.findall(r"^\S[^\n]*\{\n(.*?)^\}", hlo, re.S | re.M):
        types = dict(re.findall(
            r"^\s*(?:ROOT )?(%\S+) = (\S+) ", body, re.M))
        for m in re.finditer(
                r"^\s*(?:ROOT )?(%\S+) = (\S+) " + opcode + r"\((%[^,)]+)",
                body, re.M):
            found.append((m.group(1), m.group(2),
                          types.get(m.group(3), "")))
    return found


@pytest.mark.parametrize("b_lane", [1024, 16_384])
def test_step_bins_the_sketch_without_a_gather_a_scatter_or_a_loop(
        one_chip_scorer, topo, b_lane):
    """At the benchmark cell's size (32 slots, 1,171,875 streams, W 32)
    and its smallest and largest buckets: the score sketch compares each
    score with the 63 edges and counts by a reduction, so no gather of
    the compiled step reads the f32[63] edges (a binary search, 1.5 ms of
    the 8.8 ms step on the v5e, PERF.md section 5), no scatter writes
    anything smaller than the state (the sketch's segment sum into
    32 x 65 bins), no loop carries a T x B plane, ``pos`` and ``count``
    still come by their element gathers, and the T x B x 63 comparison
    lives inside its reduction, never an array of its own."""
    t, s = one_chip_scorer.n_slots, 1_171_875
    edges = len(one_chip_scorer.sketch_edges)
    compiled = lower_step_counts(one_chip_scorer, b_lane, s).compile()
    hlo = compiled.as_text()
    assert [g for g in operand_types(hlo, "gather")
            if g[2].startswith(f"f32[{edges}]")] == []
    scatters = operand_types(hlo, "scatter")
    assert scatters and [
        (name, out) for name, out, _ in scatters
        if int(np.prod([int(d) for d in re.match(
            r"\w+\[([\d,]*)\]", out).group(1).split(",") if d])) < t * s
    ] == []
    plane = f"s32[{t},{b_lane}]"
    assert [line for line in hlo.splitlines()
            if " while(" in line and plane in line] == []
    assert element_gathers(hlo, t * b_lane)
    assert whole_state_ops(hlo, t * b_lane * edges, exact=True) == []
    # the parent's binary search held 302.0 / 2,767.5 MB of temporaries
    # at these buckets (described-v5e compile; PERF.md section 6)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < (302.1e6 if b_lane == 1024
                                      else 2_767.5e6), mem


@pytest.mark.parametrize("b_lane", [1024, ShardedScorer.NARROW_LANES])
def test_step_writes_the_counters_in_place_at_the_batch_streams(
        one_chip_scorer, topo, b_lane):
    """At the benchmark cell's size (32 slots, 1,171,875 streams, W 32,
    bucket 1,024 and the narrow plane of 128 lanes below it): ``pos``
    and ``count`` are read at the batch's ids and written back there.
    The TPU's scatter writes only a linear array, and the [32, 1171875]
    counters are tiled (8, 128): every scatter into
    them went through a copy to a flat s32[37500000] and back, in a
    ``while`` (5.1 of the step's 16.4 ms on the v5e, PERF.md section 5).
    Outside one conditional nothing of the step yields an array of the
    counters' size; the branch a batch of up to ``T * S //
    COUNTERS_PER_WRITE`` streams (961 here) takes holds no flat copy and
    writes the counters where they lie, one ``dynamic-update-slice`` a
    stream; the store stays aliased."""
    from sitewhere_tpu.ops.windows import COUNTERS_PER_WRITE

    t, w, s = one_chip_scorer.n_slots, one_chip_scorer.window, 1_171_875
    assert 0 < t * s // COUNTERS_PER_WRITE < t * b_lane  # a conditional
    compiled = lower_step_counts(one_chip_scorer, b_lane, s).compile()
    hlo = compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= t * s * (w + 2) * 4, mem
    entry = whole_state_ops(hlo, t * s, exact=True)
    assert [op for _, op, _, _ in entry] == ["conditional"], [
        (name, op) for name, op, _, _ in entry]
    flat = f"s32[{t * s}]"
    assert not [line for line in computation(hlo).splitlines()
                if " while(" in line and flat in line]
    branches = re.search(
        r"branch_computations=\{([^}]*)\}", entry[0][3]).group(1).split(", ")
    in_place = []
    for branch in branches:
        ops = whole_state_ops(hlo, t * s, branch, exact=True)
        if any(flat in line for _, _, _, line in ops):
            continue  # the whole-array branch
        for name, op, _, line in ops:
            assert op == "while" and f"s32[{t},{s}]" in line, (name, op)
            body = re.search(r"body=(%[\w.-]+)", line).group(1)
            writes = whole_state_ops(hlo, t * s, body, exact=True)
            assert {op for _, op, _, _ in writes} == {
                "dynamic-update-slice"}, [(n, o) for n, o, _, _ in writes]
            in_place.append(name)
    assert len(in_place) == 1, (branches, in_place)


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


def _stream_program(topo, one_step, rows, monkeypatch):
    """The stateful family's program at the published widths and the
    provisioned store of 512 streams, compiled for the described chip
    (shapes only): (compiled, the store's bytes)."""
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
    packed = jax.ShapeDtypeStruct((rows, 2 + 2 * length), jnp.int32,
                                  sharding=one)
    compiled = progs.program(one_step, 0).lower(
        params, state, packed).compile()
    state_bytes = sum(
        int(np.prod(x.shape)) * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(state))
    return compiled, state_bytes


@pytest.mark.parametrize("one_step, rows", [(True, 32), (False, 8)])
def test_stream_state_programs_compile_for_v5e_at_published_widths(
        topo, one_step, rows, monkeypatch):
    """The stateful family's two programs at the published widths and the
    provisioned store (shapes only): they fit beside 6.4 GB of weights
    and 5.5 GB of state, the store is updated in place, the grouped
    expert product is a kernel, and nothing as large as a layer's expert
    weights is copied (an ``up`` matrix whose minor dimension is not
    whole lane tiles was, every step, until it was stored padded)."""
    compiled, state_bytes = _stream_program(topo, one_step, rows, monkeypatch)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == state_bytes       # donated, in place
    assert mem.temp_size_in_bytes < 1.2e9
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 0.85 * 16 * 2**30)
    text = compiled.as_text()
    assert text.count('kernel_name = "gmm"') >= 8 or text.count(
        "gmm") >= 8                                      # 4 layers x up, down
    assert "ragged-dot" not in text
    assert not re.search(r"bf16\[1,64,\d+,\d+\]\S* copy\(", text)


@pytest.mark.parametrize("rows", [32, 128])
def test_one_step_program_moves_the_live_rows_mixer_state_only(
        topo, rows, monkeypatch):
    """The one-step program reads and writes a mixer layer's state (2 MB
    a stream) for its live rows only, in place in the store: no
    instruction makes the rows' states ``f32[rows,64,64,128]`` (a gather
    of every row, padding too, then its copy and scatter back), the
    store is still donated whole, and a loop carries each layer's
    store."""
    compiled, state_bytes = _stream_program(topo, True, rows, monkeypatch)
    assert compiled.memory_analysis().alias_size_in_bytes == state_bytes
    text = compiled.as_text()
    assert f"f32[{rows},64,64,128]" not in text
    loops = [line for line in text.splitlines()
             if re.search(r"\) while\(", line)
             and "f32[512,64,64,128]" in line]
    assert len(loops) == 4                               # one a mixer layer


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


@pytest.mark.parametrize("one_step, rows", [(True, 32), (True, 128),
                                            (False, 8)])
def test_paged_stream_programs_compile_for_v5e_at_published_widths(
        topo, one_step, rows, monkeypatch):
    """The paged family's two programs at the published widths, 128
    streams' window rings and the full layers' pool (shapes only): they
    fit beside 6.04 GB of weights and 6.84 GB of store, the store is
    written in place, attention is the paged kernel over pages where
    they live (no temporary of rows x a stream's pages), and the expert
    products are kernels."""
    from sitewhere_tpu.models.common import sketch_edges
    from sitewhere_tpu.ops import moe, paged_attention
    from sitewhere_tpu.parallel.streamstate import StreamPrograms

    # the backend here is the CPU; the chip's branches are what compile
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    monkeypatch.setattr(paged_attention, "_on_tpu", lambda: True)
    one = SingleDeviceSharding(topo.devices[0])
    spec = get_model("afmoe")
    cfg = make_config("afmoe", {})

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                (1,) + x.shape, x.dtype, sharding=one), tree)

    params = placed(jax.eval_shape(
        lambda: spec.init(jax.random.PRNGKey(0), cfg)))
    state = placed(jax.eval_shape(lambda: spec.init_state(cfg, 128)))
    progs = StreamPrograms(spec, cfg, 1, 128, jnp.float32,
                           sketch_edges(1.0, 64.0, 64))
    length = 1 if one_step else cfg.chunk_size
    packed = jax.ShapeDtypeStruct(
        (rows, 2 + 2 * length + progs.extra), jnp.int32, sharding=one)
    compiled = progs.program(one_step, 0).lower(
        params, state, packed).compile()
    mem = compiled.memory_analysis()
    state_bytes = sum(
        int(np.prod(x.shape)) * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(state))
    assert state_bytes == 128 * 28_311_552 + 3_221_225_472 + 128 * 8196
    assert mem.alias_size_in_bytes == state_bytes       # written in place
    # a row's pages are read where they live: no temporary holds a
    # stream's 128 x 512 KB of full-layer pages for every row
    assert mem.temp_size_in_bytes < 0.6e9
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 0.88 * 16 * 2**30)
    text = compiled.as_text()
    assert text.count("ragged_paged_attention") >= 8     # 8 layers
    assert text.count("gmm") >= 12                       # 6 layers x 2
    assert not re.search(r"bf16\[\d+,256,8,128\]\S* copy\(", text)


_LFM2_COMPILED: dict = {}


def _lfm2_program(topo, one_step, rows, monkeypatch):
    """The LFM2-MoE family's program at the published widths, 512
    streams' tails and both attention layers' pools, compiled for the
    described chip (shapes only, once a shape): (compiled, the store)."""
    from sitewhere_tpu.models.common import sketch_edges
    from sitewhere_tpu.ops import moe, paged_attention
    from sitewhere_tpu.parallel.streamstate import StreamPrograms

    # the backend here is the CPU; the chip's branches are what compile
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    monkeypatch.setattr(paged_attention, "_on_tpu", lambda: True)
    one = SingleDeviceSharding(topo.devices[0])
    spec = get_model("lfm2_moe")
    cfg = make_config("lfm2_moe", {})

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                (1,) + x.shape, x.dtype, sharding=one), tree)

    state = placed(jax.eval_shape(lambda: spec.init_state(cfg, 512)))
    if (one_step, rows) not in _LFM2_COMPILED:
        params = placed(jax.eval_shape(
            lambda: spec.init(jax.random.PRNGKey(0), cfg)))
        progs = StreamPrograms(spec, cfg, 1, 512, jnp.float32,
                               sketch_edges(1.0, 64.0, 64))
        length = 1 if one_step else cfg.chunk_size
        packed = jax.ShapeDtypeStruct(
            (rows, 2 + 2 * length + progs.extra), jnp.int32, sharding=one)
        _LFM2_COMPILED[(one_step, rows)] = progs.program(
            one_step, 0).lower(params, state, packed).compile()
    return _LFM2_COMPILED[(one_step, rows)], state


@pytest.mark.parametrize("one_step, rows", [(True, 32), (True, 128),
                                            (False, 8)])
def test_lfm2_stream_programs_compile_for_v5e_at_published_widths(
        topo, one_step, rows, monkeypatch):
    """The LFM2-MoE family's two programs at the published widths: they
    fit beside 10.54 GB of weights and 3.56 GB of store (84% of the
    chip), the store is written in place, attention is the paged kernel
    (heads paired into 128 lanes), the expert products are kernels, and
    neither the pool nor the tied head's embedding is copied."""
    compiled, state = _lfm2_program(topo, one_step, rows, monkeypatch)
    mem = compiled.memory_analysis()
    state_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(state))
    assert state_bytes == (2 * 3328 * 256 * 2048 + 8 * 512 * 2 * 2048 * 4
                           + 512 * (2048 + 1) * 4)
    assert mem.alias_size_in_bytes == state_bytes       # written in place
    assert mem.temp_size_in_bytes < 0.4e9
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 0.86 * 16 * 2**30)
    text = compiled.as_text()
    assert text.count("ragged_paged_attention") >= 2     # 2 layers
    assert text.count("gmm") >= 16                       # 8 layers x 2
    assert not re.search(r"bf16\[\d+,256,8,128\]\S* copy\(", text)
    assert not re.search(r"bf16\[\S*65536,2048\]\S* (copy|transpose)\(",
                         text)


def test_lfm2_pool_takes_2kb_a_position_a_layer_with_no_lane_padding(
        topo, monkeypatch):
    """Keys and values at head_dim 64 with kv heads paired into the lanes:
    the compiled program takes each attention layer's pool as
    ``bf16[1,3328,256,8,128]`` in the row-major (8, 128) tiling — 128
    values of a position's pair on the lanes, 8 rows on the sublanes, no
    padding — so its buffer is 3328 x 256 x 2,048 bytes, not twice that
    (``[..., 16, 64]`` row-major would pad every row to 128 lanes)."""
    compiled, state = _lfm2_program(topo, True, 32, monkeypatch)
    text = compiled.as_text()
    layouts = set(re.findall(r"bf16\[1,3328,256,8,128\]\{[^}]*\}", text))
    assert layouts == {"bf16[1,3328,256,8,128]{4,3,2,1,0:T(8,128)(2,1)}"}
    pool = [x for x in jax.tree_util.tree_leaves(state)
            if x.shape == (1, 3328, 256, 8, 128)]
    assert len(pool) == 2
    assert all(x.size * x.dtype.itemsize == 3328 * 256 * 2048 for x in pool)
    # the arguments are the weights, the store and the call: nothing padded
    weights = 10_536_463_360
    assert compiled.memory_analysis().argument_size_in_bytes < (
        weights + sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(state)) + 2**20)


# sha256 of the jaxprs of the other paged / stream-state families' two
# programs at their published widths (the TPU branches), as the commit
# before the LFM2-MoE family made them: the shared ``ops/moe.py``,
# ``ops/paged_attention.py`` and ``parallel/streamstate.py`` lower them
# as they did
OTHER_FAMILIES_JAXPRS = {
    ("afmoe", True): "2784a1ac0cb1f30da174206db7568c3ffaae5ffd25e74701129e433654599ac9",
    ("afmoe", False): "971ad3d34300d66969cb4b0f7f580e3ca48ba5730360d873abd13c083a495611",
    ("nemotron_h", True): "5ef621bb736e8800bc25dcc59e56f171264d22bd90ae5eea370381feeb36cbf5",
    ("nemotron_h", False): "0880d78320916768b1e01e2281f3b391d2e6888276cb46ce63c4fe383b47c662",
}


@pytest.mark.parametrize("family, one_step", sorted(OTHER_FAMILIES_JAXPRS))
def test_afmoe_and_nemotron_programs_lower_as_they_did(
        family, one_step, monkeypatch):
    import hashlib

    from sitewhere_tpu.models.common import sketch_edges
    from sitewhere_tpu.ops import moe, paged_attention
    from sitewhere_tpu.parallel.streamstate import StreamPrograms

    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    monkeypatch.setattr(paged_attention, "_on_tpu", lambda: True)
    streams = {"afmoe": 128, "nemotron_h": 512}[family]
    spec, cfg = get_model(family), make_config(family, {})

    def stacked(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((1,) + x.shape, x.dtype), tree)

    params = stacked(jax.eval_shape(
        lambda: spec.init(jax.random.PRNGKey(0), cfg)))
    state = stacked(jax.eval_shape(lambda: spec.init_state(cfg, streams)))
    progs = StreamPrograms(spec, cfg, 1, streams, jnp.float32,
                           sketch_edges(1.0, 64.0, 64))
    rows, length = (32, 1) if one_step else (8, cfg.chunk_size)
    packed = jax.ShapeDtypeStruct((rows, 2 + 2 * length + progs.extra),
                                  jnp.int32)
    text = str(jax.make_jaxpr(progs.program(one_step, 0))(
        params, state, packed))
    assert hashlib.sha256(text.encode()).hexdigest() == (
        OTHER_FAMILIES_JAXPRS[(family, one_step)])
