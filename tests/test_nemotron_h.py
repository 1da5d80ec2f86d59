"""The stream-state family (``models/nemotron_h.py``) behind
``ShardedScorer``, at a tiny size on the CPU: same pattern letters as the
published period, few heads and experts. The plain reference is
``benchmark/reference/nemotron_h.py`` (imports nothing of the program);
the program runs in f32 here, so the two differ by summation order."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders.nemotron_h_stream import install_weights, model_config
from benchmark.reference import nemotron_h as ref
from sitewhere_tpu.models import get_model, make_config
from sitewhere_tpu.ops import moe
from sitewhere_tpu.parallel.mesh import MeshManager
from sitewhere_tpu.parallel.sharded import ShardedScorer
from sitewhere_tpu.parallel.streamstate import (
    CHUNK_RUNS,
    ONE_STEP_ROWS,
    SHORT_RUN,
    plan,
)

TINY = {
    "family": "nemotron_h", "pattern": "EMEMEMEM*", "hidden_size": 64,
    "vocab_size": 256, "mamba_num_heads": 4, "mamba_head_dim": 8,
    "n_groups": 2, "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 128,
    "n_routed_experts_published": 8, "experts_held": [0, 4],
    "num_experts_per_tok": 3, "routed_scaling_factor": 2.5,
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "context_positions": 256, "norm_eps": 1e-5, "compute_dtype": "float32",
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
}
TOL = 2e-4  # f32 against f32: the order of sums through 9 layers


def scorer_for(model=TINY, seed=7, max_streams=8, slots=1):
    d = ref.dims(model)
    weights = ref.make_weights(seed, d)
    cfg = make_config("nemotron_h", model_config(model))
    mm = MeshManager(tenant=1, data=1, devices=jax.devices()[:1])
    sc = ShardedScorer(mm, get_model("nemotron_h"), cfg,
                       slots_per_shard=slots, max_streams=max_streams,
                       window=1, wire_dtype="f32")
    for slot in range(slots):
        install_weights(sc, weights, slot)
        sc.activate(slot)
    return sc, weights, d


def flush(sc, rows, bucket=32, slot=0):
    """One flush through the service's own calls: ``rows`` is a list of
    (stream, value); returns their scores in order."""
    ids = np.zeros((sc.n_slots, bucket), sc.ids_np_dtype)
    vals = np.zeros((sc.n_slots, bucket), np.float32)
    counts = np.zeros((sc.n_slots, 1), np.int32)
    for i, (s, v) in enumerate(rows):
        ids[slot, i], vals[slot, i] = s, v
    counts[slot, 0] = len(rows)
    staged = sc.stage_inputs(ids, vals, counts)
    plane = sc.step_counts(*staged)
    got = np.asarray(sc.gather_rows(plane, staged[2], len(rows)))
    return got[:len(rows)]


@pytest.fixture(scope="module")
def tiny():
    return scorer_for()


@pytest.fixture
def fresh(tiny):
    sc, weights, d = tiny
    sc.reset_slot(0)
    install_weights(sc, weights)
    sc.activate(0)
    return tiny


def series_for(n_streams, length, seed=0):
    return np.random.default_rng(seed).integers(0, 256, (n_streams, length))


# ------------------------------------------------- against the reference
def test_one_step_decode_through_the_store_matches_the_full_forward(fresh):
    sc, weights, d = fresh
    series = series_for(3, 24)
    want = np.stack([ref.surprisal(weights, d, s) for s in series])
    got = np.stack([flush(sc, [(5, series[0, t]), (2, series[1, t]),
                               (7, series[2, t])]) for t in range(24)], 1)
    assert np.abs(got - want).max() < TOL
    # a stream's first reading is scored from no history: ln vocab
    assert np.allclose(got[:, 0], np.log(256), atol=1e-5)
    dev, host = sc.last_stats
    assert host["rows_one_step"] == 3 and host["rows_chunked"] == 0


@pytest.mark.parametrize("run", [1, 127, 128, 129, 300])
def test_chunked_program_agrees_with_the_one_step_program(fresh, run):
    """A run of one stream in one flush (the chunked program, 128 tokens
    a pass, the ring wrapping past 256) against the same readings one a
    flush, and against the reference's sequential recurrence."""
    sc, weights, d = fresh
    series = series_for(1, run + 3, seed=run)[0]
    warm, rest = series[:3], series[3:]
    for v in warm:      # both paths start from a state that is not empty
        flush(sc, [(4, v)])
    chunked = flush(sc, [(4, v) for v in rest], bucket=512)
    _dev, host = sc.last_stats
    if run == 1:
        assert host["rows_one_step"] == 1 and host["calls_chunked"] == 0
    else:
        assert host["rows_chunked"] == run
        assert host["calls_chunked"] == -(-run // 128)
        assert host["rows_same_stream"] == run
    want = ref.surprisal(weights, d, series)[3:]
    assert np.abs(chunked - want).max() < TOL
    for v in warm:
        flush(sc, [(6, v)])
    stepped = np.concatenate([flush(sc, [(6, v)]) for v in rest])
    assert np.abs(chunked - stepped).max() < TOL


def test_two_events_of_one_stream_in_one_flush_apply_in_order(fresh):
    sc, weights, d = fresh
    series = series_for(3, 6, seed=3)
    # stream 1 rides the flush three times, between two other streams
    rows = [(0, series[0, 0]), (1, series[1, 0]), (2, series[2, 0]),
            (1, series[1, 1]), (1, series[1, 2])]
    got = flush(sc, rows)
    _dev, host = sc.last_stats
    # three passes of the one-step program: 3 streams, then stream 1 twice
    assert host == {"rows_one_step": 5, "rows_chunked": 0,
                    "rows_same_stream": 3, "calls_one_step": 3,
                    "calls_chunked": 0, "streams_advanced": 5}
    want = [ref.surprisal(weights, d, s) for s in series]
    assert abs(got[0] - want[0][0]) < TOL and abs(got[2] - want[2][0]) < TOL
    assert np.abs(got[[1, 3, 4]] - want[1][:3]).max() < TOL
    # and the state it left is the state three single steps leave
    nxt = flush(sc, [(1, series[1, 3])])
    assert abs(nxt[0] - want[1][3]) < TOL


def test_state_survives_across_flushes_and_buckets(fresh):
    sc, weights, d = fresh
    series = series_for(1, 9, seed=5)[0]
    got = [flush(sc, [(3, v)], bucket=b)[0]
           for v, b in zip(series, [32, 128, 32, 512, 128, 32, 2048, 32, 128])]
    assert np.abs(np.asarray(got) - ref.surprisal(weights, d, series)).max() < TOL


def test_reset_slot_clears_a_streams_state(fresh):
    sc, weights, d = fresh
    series = series_for(1, 5, seed=9)[0]
    first = [flush(sc, [(2, v)])[0] for v in series]
    assert int(np.asarray(sc.state["pos"])[0, 2]) == 5
    sc.reset_slot(0)
    assert all(not np.asarray(x).any()
               for x in jax.tree_util.tree_leaves(sc.state))
    install_weights(sc, weights)
    sc.activate(0)
    again = [flush(sc, [(2, v)])[0] for v in series]
    assert np.allclose(first, again, atol=1e-6)


def test_a_second_slot_keeps_its_own_streams():
    sc, weights, d = scorer_for(slots=2, max_streams=4)
    series = series_for(2, 4, seed=11)
    got0 = [flush(sc, [(1, v)], slot=0)[0] for v in series[0]]
    got1 = [flush(sc, [(1, v)], slot=1)[0] for v in series[1]]
    assert np.abs(got0 - ref.surprisal(weights, d, series[0])).max() < TOL
    assert np.abs(got1 - ref.surprisal(weights, d, series[1])).max() < TOL


# ------------------------------------------------------- the expert layer
def test_two_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Experts 0-3 on one chip, 4-7 on another, the shared expert counted
    once: the two partial sums add up to the reference's uncut layer."""
    whole = ref.dims({**TINY, "experts_held": [0, 8]})
    lw = ref.make_weights(3, whole)["layers"][0]
    u = np.random.default_rng(1).standard_normal((20, 64)).astype(np.float32)
    want = np.asarray(ref.moe_layer(jnp.asarray(u), lw, whole))
    idx, gates = moe.route(jnp.asarray(u), lw["router"][0] * lw["router"][1],
                           lw["e_bias"], 3, 2.5)
    up = lw["up"][0].astype(np.float32) * lw["up"][1]
    down = lw["down"][0].astype(np.float32) * lw["down"][1]
    total = moe.dense_relu2(
        jnp.asarray(u), lw["s_up"][0] * lw["s_up"][1],
        lw["s_down"][0] * lw["s_down"][1])
    pairs = 0
    for lo, hi in ((0, 4), (4, 8)):
        share, stats = moe.held_experts(
            jnp.asarray(u), idx, gates, jnp.ones((20,), bool),
            jnp.asarray(up[lo:hi]), jnp.asarray(down[lo:hi]), lo)
        # a share alone is not the layer
        assert np.abs(np.asarray(share)).max() > 1e-4
        assert int(stats[0]) == 60
        pairs += int(stats[1])
        total = total + share
    assert pairs == 60  # every routed pair fell on exactly one share
    assert np.abs(np.asarray(total) - want).max() < 1e-6 < np.abs(want).max()
    # the reference's own share agrees with the program's
    half = ref.dims(TINY)
    lw_half = ref.make_weights(3, half)["layers"][0]
    assert np.array_equal(lw_half["up"][0], lw["up"][0][:4])


def test_router_top_k_normalisation_and_scale_by_hand():
    """Two experts' scores tie apart from the correction bias: the bias
    decides the choice and never the weight."""
    u = jnp.asarray([[1.0, 0.0]])
    w = jnp.asarray([[2.0, 1.0, 0.0, -1.0], [0.0, 0.0, 0.0, 0.0]])
    bias = jnp.asarray([0.0, 0.0, 0.5, 0.0])
    idx, gates = moe.route(u, w, bias, top_k=2, scale=2.5)
    s = 1 / (1 + np.exp(-np.asarray([2.0, 1.0, 0.0, -1.0])))
    # s + bias = .881, .731, 1.0, .269: experts 2 and 0
    assert sorted(np.asarray(idx)[0].tolist()) == [0, 2]
    by = dict(zip(np.asarray(idx)[0].tolist(), np.asarray(gates)[0].tolist()))
    assert by[0] == pytest.approx(2.5 * s[0] / (s[0] + s[2]), rel=1e-6)
    assert by[2] == pytest.approx(2.5 * s[2] / (s[0] + s[2]), rel=1e-6)
    assert sum(by.values()) == pytest.approx(2.5, rel=1e-6)


def test_padding_rows_and_absent_experts_add_nothing():
    d = ref.dims(TINY)
    lw = ref.make_weights(5, d)["layers"][0]
    u = jnp.asarray(np.random.default_rng(2).standard_normal(
        (6, 64)).astype(np.float32))
    idx, gates = moe.route(u, lw["router"][0] * lw["router"][1],
                           lw["e_bias"], 3, 2.5)
    up = jnp.asarray(lw["up"][0].astype(np.float32) * lw["up"][1])
    down = jnp.asarray(lw["down"][0].astype(np.float32) * lw["down"][1])
    valid = jnp.asarray([True, True, True, False, False, False])
    share, stats = moe.held_experts(u, idx, gates, valid, up, down, 0)
    assert not np.asarray(share)[3:].any()
    assert int(stats[0]) == 9
    assert int(stats[1]) == int((np.asarray(idx)[:3] < 4).sum())
    assert int(stats[2]) == len(set(
        np.asarray(idx)[:3][np.asarray(idx)[:3] < 4].tolist()))


# ---------------------------------------------------------------- the plan
def _plan(ids, chunk=128, cap=512):
    ids = np.asarray([ids], np.int32)
    return plan(ids, ids * 0 + 7, np.asarray([ids.shape[1]]), chunk, cap)


LONG = SHORT_RUN + 1


@pytest.mark.parametrize("ids, one_step, chunked, calls", [
    ([3, 1, 2], 3, 0, (1, 0)),
    (list(range(40)), 40, 0, (1, 0)),                     # 40 rows: the 128
    (list(range(200)), 200, 0, (2, 0)),                   # 128 + 72
    ([5] * 300, 0, 300, (0, 3)),                          # 128 + 128 + 44
    ([5] * 130 + [6] * 2 + [7], 3, 130, (2, 2)),          # 6, 7 then 6 again
    ([1, 2] * 3 + list(range(10, 20)), 16, 0, (3, 0)),    # a backlog: passes
    (list(range(400)) * 3, 1200, 0, (12, 0)),             # 3 s of a fleet
    ([9] * SHORT_RUN + [4], SHORT_RUN + 1, 0, (SHORT_RUN, 0)),
    (sum(([s] * LONG for s in range(CHUNK_RUNS + 1)), []), 0,
     LONG * (CHUNK_RUNS + 1), (0, 2)),                     # runs > a call
])
def test_plan_sends_each_row_through_one_program_in_order(
        ids, one_step, chunked, calls):
    made, stats = _plan(ids)
    assert stats["rows_one_step"] == one_step
    assert stats["rows_chunked"] == chunked
    assert (stats["calls_one_step"], stats["calls_chunked"]) == calls
    # every row is scored exactly once, into its own plane column
    cols = np.concatenate([c.cols[c.cols < len(ids)].reshape(-1)
                           for c in made])
    assert sorted(cols.tolist()) == list(range(len(ids)))
    for c in made:
        assert c.ids.shape[0] in (ONE_STEP_ROWS if c.one_step
                                  else (CHUNK_RUNS,))
        live = c.ids[c.lens > 0]
        assert len(set(live.tolist())) == len(live)   # distinct streams
    # a stream's rows keep their order across its passes
    for s in set(ids):
        mine = [c.cols[i, :c.lens[i]]
                for c in made for i in np.flatnonzero(
                    (c.ids == s) & (c.lens > 0))]
        seen = np.concatenate(mine)
        assert seen.tolist() == [i for i, x in enumerate(ids) if x == s]


# ------------------------------------------------------------- bf16, fp8
def test_bf16_program_is_near_the_reference_and_the_fp8_control_is_not():
    model = {**TINY, "compute_dtype": "bfloat16"}
    sc, weights, d = scorer_for(model)
    series = series_for(2, 40, seed=13)
    got = np.stack([flush(sc, [(0, series[0, t]), (1, series[1, t])])
                    for t in range(40)], 1)
    want = np.stack([ref.surprisal(weights, d, s) for s in series])
    control = np.stack([ref.surprisal(weights, d, s, control=True)
                        for s in series])
    err, err_control = np.abs(got - want), np.abs(control - want)
    assert err.mean() < 0.05
    assert err_control.mean() > 2 * err.mean()


# ------------------------------------------------------------- the store
def test_the_store_is_provisioned_once_and_counted(tiny):
    sc, _w, _d = tiny
    spec = sc.spec
    per_stream = sum(
        int(np.prod(x.shape[2:])) * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(sc.state))
    assert sc.state_nbytes == 8 * per_stream
    read, written = spec.state_traffic(sc.cfg, 3, 3)
    assert read == 3 * per_stream
    rings = 2 * 256 * 2 * 16 * 4          # k, v: ctx x kv x d, f32 here
    assert written == 3 * (per_stream - rings) + 3 * 2 * 2 * 16 * 4


def test_published_widths_weigh_what_the_configuration_says():
    """Bytes of the real configuration from shapes alone (nothing is
    allocated): weights 6.33 GB as published + 0.18 GB of zero padding
    (each expert matrix's 1,856 padded to 1,920), 10.8 MB of state a
    stream."""
    cfg = make_config("nemotron_h", {})
    spec = get_model("nemotron_h")
    shapes = jax.eval_shape(lambda: spec.init(jax.random.PRNGKey(0), cfg))
    weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(shapes))
    pad = 4 * 64 * 2 * 2688 * 64 * 2
    assert weights == 6_335_576_064 + pad == 6_511_736_832
    from sitewhere_tpu.models.nemotron_h import state_bytes_per_stream

    assert state_bytes_per_stream(cfg) == (
        4 * (64 * 64 * 128 * 4 + 3 * 6144 * 4)     # state-space + conv
        + 2 * 2048 * 2 * 128 * 2                    # key/value ring
        + 2688 * 4 + 4)                             # last hidden, position


def test_a_tenant_alone_on_the_loop_is_not_rationed():
    from sitewhere_tpu.runtime.overload import DeficitRoundRobin

    drr = DeficitRoundRobin(quantum=100)
    drr.configure("only", 1.0)
    drr.charge("only", 10_000)          # a bulk message far past the burst
    assert drr.budget("only") == float("inf")
    drr.configure("other", 1.0)         # company: rationed like any other
    assert drr.budget("only") < 0
    drr.remove("other")
    assert drr.budget("only") == float("inf")
