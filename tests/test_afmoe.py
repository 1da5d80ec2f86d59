"""The paged stream-state family (``models/afmoe.py``) behind
``ShardedScorer``, at a tiny size on the CPU: the published layer kinds
(window and full attention, dense and expert MLPs) at tiny widths —
window 16, pages of 4, 8 experts of which 4 are held, top-2, 256 ids. The
plain reference is ``benchmark/reference/afmoe.py`` (imports nothing of
the program); the program runs in f32 here, so the two differ by
summation order."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders.afmoe_stream import model_config
from benchmark.builders.nemotron_h_stream import install_weights
from benchmark.reference import afmoe as ref
from sitewhere_tpu.models import get_model, make_config
from sitewhere_tpu.ops import moe, paged_attention
from sitewhere_tpu.parallel.mesh import MeshManager
from sitewhere_tpu.parallel.sharded import ShardedScorer
from sitewhere_tpu.parallel.streamstate import (
    Call,
    PageLayout,
    PagePool,
    PoolExhausted,
)

TINY = {
    "family": "afmoe", "attention": "WWWFWWWF", "pattern": "DDEEEEEE",
    "hidden_size": 64, "vocab_size": 256, "intermediate_size": 96,
    "num_experts_published": 8, "experts_held": [0, 4],
    "num_experts_per_tok": 2, "route_scale": 2.826,
    "moe_intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 16,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "chunk_size": 128,
    "page_size": 4, "pool_pages": 400, "full_context_positions": 512,
    "compute_dtype": "float32",
}
TOL = 2e-4  # f32 against f32: the order of sums through 8 layers


def scorer_for(model=TINY, seed=7, max_streams=8, slots=1):
    d = ref.dims(model)
    weights = ref.make_weights(seed, d)
    cfg = make_config("afmoe", model_config(model))
    mm = MeshManager(tenant=1, data=1, devices=jax.devices()[:1])
    sc = ShardedScorer(mm, get_model("afmoe"), cfg, slots_per_shard=slots,
                       max_streams=max_streams, window=1, wire_dtype="f32")
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
@pytest.mark.parametrize("length", [
    10,     # shorter than the window
    16,     # the window exactly
    23,     # across it, and across page boundaries
    160,    # ten windows
])
def test_one_step_decode_through_the_pages_matches_the_full_forward(
        fresh, length):
    sc, weights, d = fresh
    series = series_for(2, length, seed=length)
    want = np.stack([ref.surprisal(weights, d, s) for s in series])
    got = np.stack([flush(sc, [(5, series[0, t]), (2, series[1, t])])
                    for t in range(length)], 1)
    assert np.abs(got - want).max() < TOL
    # a stream's first reading is scored from no history: ln vocab
    assert np.allclose(got[:, 0], np.log(256), atol=1e-5)
    _dev, host = sc.last_stats
    assert host["rows_one_step"] == 2 and host["rows_chunked"] == 0
    # the pool holds each stream's history, a page every 4 positions
    assert host["kv_pages_in_use"] == 2 * -(-length // 4)


@pytest.mark.parametrize("run", [1, 127, 128, 129, 300])
def test_chunked_program_agrees_with_the_one_step_program(fresh, run):
    """A run of one stream in one flush (the chunked program, 128 tokens
    a pass, the window's ring wrapping many times) against the same
    readings one a flush, and against the reference."""
    sc, weights, d = fresh
    series = series_for(1, run + 3, seed=run)[0]
    warm, rest = series[:3], series[3:]
    for v in warm:      # both paths start from a store that is not empty
        flush(sc, [(4, v)])
    chunked = flush(sc, [(4, v) for v in rest], bucket=512)
    _dev, host = sc.last_stats
    if run == 1:
        assert host["rows_one_step"] == 1 and host["calls_chunked"] == 0
    else:
        assert host["rows_chunked"] == run
        assert host["calls_chunked"] == -(-run // 128)
    want = ref.surprisal(weights, d, series)[3:]
    assert np.abs(chunked - want).max() < TOL
    for v in warm:
        flush(sc, [(6, v)])
    stepped = np.concatenate([flush(sc, [(6, v)]) for v in rest])
    assert np.abs(chunked - stepped).max() < TOL


def test_two_events_of_one_stream_in_one_flush_apply_in_order(fresh):
    sc, weights, d = fresh
    series = series_for(3, 6, seed=3)
    rows = [(0, series[0, 0]), (1, series[1, 0]), (2, series[2, 0]),
            (1, series[1, 1]), (1, series[1, 2])]
    got = flush(sc, rows)
    _dev, host = sc.last_stats
    assert (host["rows_one_step"], host["calls_one_step"]) == (5, 3)
    want = [ref.surprisal(weights, d, s) for s in series]
    assert abs(got[0] - want[0][0]) < TOL and abs(got[2] - want[2][0]) < TOL
    assert np.abs(got[[1, 3, 4]] - want[1][:3]).max() < TOL
    # stream 1 took its first page once, and holds it
    assert host["kv_pages_granted"] == 3
    nxt = flush(sc, [(1, series[1, 3])])
    assert abs(nxt[0] - want[1][3]) < TOL


def test_reset_slot_returns_the_pages_and_they_are_used_again(fresh):
    sc, weights, d = fresh
    series = series_for(1, 9, seed=9)[0]
    first = [flush(sc, [(2, v)])[0] for v in series]
    pager = sc.pager
    held = list(pager.held[(0, 2)])
    assert len(held) == 3 and pager.in_use == 3
    sc.reset_slot(0)
    assert pager.in_use == 0 and pager.totals["freed"] >= 3
    assert all(not np.asarray(x).any()
               for x in jax.tree_util.tree_leaves(sc.state))
    install_weights(sc, weights)
    sc.activate(0)
    again = [flush(sc, [(7, v)])[0] for v in series]
    assert np.allclose(first, again, atol=1e-6)
    # another stream, the same pages: the pool hands out what came back
    assert sorted(pager.held[(0, 7)]) == sorted(held)
    _dev, host = sc.last_stats
    assert host["kv_pages_in_use"] == 3


def test_a_second_slot_keeps_its_own_streams_and_pool():
    sc, weights, d = scorer_for(slots=2, max_streams=4)
    series = series_for(2, 21, seed=11)
    got0 = [flush(sc, [(1, v)], slot=0)[0] for v in series[0]]
    got1 = [flush(sc, [(1, v)], slot=1)[0] for v in series[1]]
    assert np.abs(got0 - ref.surprisal(weights, d, series[0])).max() < TOL
    assert np.abs(got1 - ref.surprisal(weights, d, series[1])).max() < TOL


# ------------------------------------------------------------ the pool
LAYOUT = PageLayout(pool=6, page=4, table=4, window=16, n_window=6,
                    n_full=2, position_bytes=64, row_bytes=260)


def _call(stream_lens, extra=1 + LAYOUT.table, rows=8):
    call = Call.empty(True, 0, rows, 1, 16, 1 << 20, extra)
    for i, (s, n) in enumerate(stream_lens):
        call.ids[i], call.lens[i] = s, n
    return call


def test_the_pool_grants_pages_as_histories_grow():
    pool = PagePool(1, 16, LAYOUT)
    c = _call([(3, 1), (5, 1)])
    stats = pool.grant([c])
    assert stats["kv_pages_granted"] == 2 and stats["kv_pages_in_use"] == 2
    assert c.grants[0, 0] == 0 and c.grants[1, 0] == 0  # first logical page
    assert c.grants[0, 1] != c.grants[1, 1]
    # three more tokens of stream 3 stay on its page; a fourth crosses
    for _ in range(3):
        assert pool.grant([_call([(3, 1)])])["kv_pages_granted"] == 0
    c = _call([(3, 1)])
    assert pool.grant([c])["kv_pages_granted"] == 1
    assert list(c.grants[0, 1:3]) == list(pool.held[(0, 3)])
    # padding rows take nothing and their tables stay empty
    assert not c.grants[1:].any()


def test_a_stream_recycles_its_own_oldest_page_when_the_pool_is_empty():
    pool = PagePool(1, 16, LAYOUT)
    pool.grant([_call([(1, 12)])])          # 3 pages
    pool.grant([_call([(2, 12)])])          # 3 more: the pool is empty
    oldest = pool.held[(0, 1)][0]
    c = _call([(1, 1)])
    stats = pool.grant([c])
    assert stats["kv_pages_recycled"] == 1 and stats["kv_pages_granted"] == 0
    # the stream now attends from its second page on; its oldest page
    # holds its newest positions; stream 2 kept all of its own
    assert c.grants[0, 0] == 1
    assert pool.held[(0, 1)][-1] == oldest and len(pool.held[(0, 2)]) == 3
    # a full table recycles too (4 entries), and a stream that holds
    # nothing cannot be given a page from an empty pool
    with pytest.raises(PoolExhausted):
        pool.grant([_call([(9, 1)])])


def _pool_state(pool):
    return (pool.tokens.tolist(), pool.first.tolist(),
            {k: list(v) for k, v in pool.held.items() if v},
            [list(f) for f in pool.free], dict(pool.totals))


def test_a_grant_that_finds_no_page_leaves_the_pool_as_it_was():
    def filled():
        pool = PagePool(1, 16, LAYOUT)
        pool.grant([_call([(1, 12)])])      # 3 pages
        pool.grant([_call([(2, 8)])])       # 2 more: one left
        return pool

    pool, twin = filled(), filled()
    before = _pool_state(pool)
    # stream 1 takes the last free page, stream 2 recycles its own
    # oldest, then stream 9, which holds nothing, finds no page
    with pytest.raises(PoolExhausted):
        pool.grant([_call([(1, 1), (2, 1)]), _call([(9, 1)])])
    assert _pool_state(pool) == before
    # what follows is what it would have been without the failed grant,
    # its counters included
    c, c_twin = _call([(1, 1), (2, 1)]), _call([(1, 1), (2, 1)])
    assert pool.grant([c]) == twin.grant([c_twin])
    assert (c.grants == c_twin.grants).all()
    assert _pool_state(pool) == _pool_state(twin)


@pytest.mark.parametrize("fault", ["pool", "program"])
def test_a_flush_after_a_failed_one_scores_as_the_reference(fault):
    """A flush that fails — a new stream finds the pool empty, or the
    program raises — leaves the pool's token counts where the device's
    positions are: the next flushes of the streams it held match the
    reference (a count left one ahead would recycle a page early)."""
    model = {**TINY, "pool_pages": 4, "full_context_positions": 16}
    sc, weights, d = scorer_for(model, max_streams=4)
    series = series_for(2, 12, seed=5)
    want = ref.surprisal(weights, d, series[0])
    for v in series[0, :10]:
        flush(sc, [(0, v)])                 # 3 pages
    for v in series[1, :4]:
        flush(sc, [(1, v)])                 # 1 more: the pool is empty
    before = _pool_state(sc.pager)
    if fault == "pool":
        rows = [(0, series[0, 10]), (2, 7)]
        with pytest.raises(PoolExhausted):
            flush(sc, rows)
    else:
        run = sc.programs.run

        def broken(*a, **k):
            raise RuntimeError("the program failed")

        sc.programs.run = broken
        try:
            with pytest.raises(RuntimeError):
                flush(sc, [(0, series[0, 10])])
        finally:
            sc.programs.run = run
    assert _pool_state(sc.pager) == before
    got = [flush(sc, [(0, v)])[0] for v in series[0, 10:]]
    assert np.abs(np.asarray(got) - want[10:]).max() < TOL
    assert sc.pager.totals["recycled"] == 0


def test_the_program_attends_the_positions_a_recycled_stream_still_holds():
    """A pool of 3 pages of 4 (a table of 3): past 12 positions the full
    layers see the stream's last two or three pages — the reference with
    the full layers limited to those positions is not built, so this
    asserts the run is finite, counted, and the window layers unchanged."""
    model = {**TINY, "pool_pages": 3, "full_context_positions": 12}
    sc, weights, d = scorer_for(model, max_streams=2)
    series = series_for(1, 20, seed=4)[0]
    got = [flush(sc, [(0, v)])[0] for v in series]
    assert np.isfinite(got).all()
    want = ref.surprisal(weights, d, series)
    assert np.abs(np.asarray(got[:12]) - want[:12]).max() < TOL
    assert sc.pager.totals["recycled"] == 2


# ---------------------------------------------------- the attention op
def _store(rng, pages, page, g, d):
    return jnp.asarray(rng.standard_normal((pages, page, 2 * g, d)),
                       jnp.float32)


@pytest.mark.parametrize("window", [None, 5])
def test_a_window_sees_the_last_w_positions_and_full_sees_all(window):
    """By hand: one row, 11 positions over 3 pages in a scrambled order,
    its last 2 positions the queries."""
    rng = np.random.default_rng(0)
    page, g, hq, d = 4, 1, 2, 8
    store = _store(rng, 5, page, g, d)
    table = jnp.asarray([[3, 0, 4]])
    q = jnp.asarray(rng.standard_normal((1, 2, hq, d)), jnp.float32)
    got = paged_attention.attend(q, store, table, jnp.asarray([11]),
                                 jnp.asarray([2]), 0.5, window)
    keys = np.concatenate([np.asarray(store)[p] for p in (3, 0, 4)])[:11]
    k, v = keys[:, 0::2], keys[:, 1::2]
    for j, pos in enumerate((9, 10)):
        seen = np.arange(11) <= pos
        if window:
            seen &= pos - np.arange(11) < window
        s = np.einsum("hd,pd->hp", np.asarray(q)[0, j], k[:, 0]) * 0.5
        s = np.where(seen[None], s, -np.inf)
        w = np.exp(s - s.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        want = w @ v[:, 0]
        assert np.abs(np.asarray(got)[0, j] - want).max() < 1e-5
        assert seen.sum() == (window if window else pos + 1)


def test_padding_rows_read_nothing_and_give_zeros():
    rng = np.random.default_rng(1)
    store = _store(rng, 4, 4, 1, 8)
    q = jnp.asarray(rng.standard_normal((3, 1, 2, 8)), jnp.float32)
    out = paged_attention.attend(
        q, store, jnp.asarray([[1, 2], [0, 0], [0, 0]]),
        jnp.asarray([6, 0, 0]), jnp.asarray([1, 0, 0]), 1.0)
    assert np.isfinite(np.asarray(out)).all()
    assert not np.asarray(out)[1:].any() and np.asarray(out)[0].any()


# ------------------------------------------------------- the expert layer
def test_two_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Experts 0-3 on one chip, 4-7 on another, the shared expert counted
    once: the two partial sums add up to the reference's uncut layer."""
    whole = ref.dims({**TINY, "experts_held": [0, 8]})
    lw = ref.make_weights(3, whole)["layers"][2]
    u = np.random.default_rng(1).standard_normal((20, 64)).astype(np.float32)
    want = np.asarray(ref.moe_layer(jnp.asarray(u), lw, whole))

    def wide(w):
        return jnp.asarray(w[0].astype(np.float32) * w[1])

    idx, gates = moe.route(jnp.asarray(u), wide(lw["router"]),
                           lw["e_bias"], 2, 2.826)
    total = moe.dense_swiglu(jnp.asarray(u), wide(lw["s_gu"]),
                             wide(lw["s_down"]))
    gu, down = wide(lw["gu"]), wide(lw["down"])
    pairs = 0
    for lo, hi in ((0, 4), (4, 8)):
        share, stats = moe.held_experts(
            jnp.asarray(u), idx, gates, jnp.ones((20,), bool), gu[lo:hi],
            down[lo:hi], lo, gated=True)
        assert np.abs(np.asarray(share)).max() > 1e-4
        assert int(stats[0]) == 40
        pairs += int(stats[1])
        total = total + share
    assert pairs == 40  # every routed pair fell on exactly one share
    assert np.abs(np.asarray(total) - want).max() < 1e-5 < np.abs(want).max()
    half = ref.make_weights(3, ref.dims(TINY))["layers"][2]
    assert np.array_equal(half["gu"][0], lw["gu"][0][:4])


def test_router_top_8_bias_normalisation_and_scale_by_hand():
    """Ten experts, top-8 of score + bias: the bias lifts expert 9 into
    the eight and drops expert 7; the gates are the SCORES normalised
    over the eight and scaled by 2.826."""
    logits = np.asarray([3.0, 2.5, 2.0, 1.5, 1.0, 0.5, 0.0, -0.5, -1.0, -3.0])
    u = jnp.asarray([[1.0, 0.0]])
    w = jnp.asarray(np.stack([logits, np.zeros(10)]))
    bias = np.zeros(10)
    bias[9] = 1.0
    idx, gates = moe.route(u, w, jnp.asarray(bias), top_k=8, scale=2.826)
    s = 1 / (1 + np.exp(-logits))
    top = [0, 1, 2, 3, 4, 5, 6, 9]
    assert sorted(np.asarray(idx)[0].tolist()) == top
    by = dict(zip(np.asarray(idx)[0].tolist(), np.asarray(gates)[0].tolist()))
    for e in top:
        assert by[e] == pytest.approx(2.826 * s[e] / s[top].sum(), rel=1e-6)
    assert sum(by.values()) == pytest.approx(2.826, rel=1e-6)


# --------------------------------------------------------- bf16, fp8
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
def test_published_widths_weigh_what_the_configuration_says():
    """Bytes of the real configuration from shapes alone (nothing is
    allocated): 6.04 GB of weights; 28.3 MB of window rings a stream;
    the pool 3.22 GB whatever the number of streams."""
    cfg = make_config("afmoe", {})
    spec = get_model("afmoe")
    shapes = jax.eval_shape(lambda: spec.init(jax.random.PRNGKey(0), cfg))
    weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(shapes))
    parts = {
        "attention x 8, norms": 436_477_952,
        "dense MLP x 2": 150_994_944,
        "routed experts: 6 x 64": 4_831_838_208,
        "shared experts x 6": 75_497_472,
        "routers and biases (f32)": 6_294_528,
        "embedding, head, final norm": 536_879_104,
    }
    assert weights == sum(parts.values()) == 6_037_982_208
    assert cfg.ring_pages == 9 and cfg.table_pages == 128
    for streams in (1, 128):
        st = jax.eval_shape(lambda: spec.init_state(cfg, streams))
        rings = sum(x.size * x.dtype.itemsize for x in st["window"])
        pool = sum(x.size * x.dtype.itemsize for x in st["full"])
        assert rings == streams * 6 * 9 * 256 * 2048
        assert pool == 2 * 3072 * 256 * 2048


# ------------------------------------------ the other family, unchanged
# sha256 of the jaxprs of Nemotron-H's two programs at the tiny size of
# tests/test_nemotron_h.py, as the commit before the page pool made them
# (the one-step program's since its mixers read and write the live rows'
# state in the store, ``ssd.step_in_store``)
NEMOTRON_JAXPRS = {
    True: "681d5a5d2db61fed5e0f39364d8afeff99c6485991304bce39d1abe2cbac897c",
    False: "370d30f209f3430131c466452773aabb4169392ea22f653ad1982b2224c2fd98",
}


@pytest.mark.parametrize("one_step", [True, False])
def test_nemotron_programs_lower_as_they_did_before_the_pool(one_step):
    from tests.test_nemotron_h import scorer_for as nemotron_scorer

    sc, _w, _d = nemotron_scorer()
    assert sc.pager is None and sc.programs.extra == 0
    call = sc.programs.warm_calls(0)[0 if one_step else -1]
    text = str(jax.make_jaxpr(sc.programs.program(one_step, 0))(
        sc.params, sc.state, call.packed))
    assert hashlib.sha256(text.encode()).hexdigest() == NEMOTRON_JAXPRS[
        one_step]
