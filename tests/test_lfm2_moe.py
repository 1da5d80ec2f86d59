"""The stream-state family with convolution tails beside paged attention
(``models/lfm2_moe.py``) behind ``ShardedScorer``, at a tiny size on the
CPU: the published layer kinds (gated short convolutions and attention,
dense and expert MLPs, published layers 0-9's letters) at tiny widths —
pages of 4, 16 experts all held, top-4, 256 ids. The plain reference is
``benchmark/reference/lfm2_moe.py`` (imports nothing of the program); the
program runs in f32 here, so the two differ by summation order."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders.lfm2_stream import install_weights, model_config
from benchmark.reference import afmoe as ref_afmoe
from benchmark.reference import lfm2_moe as ref
from sitewhere_tpu.models import get_model, lfm2_moe, make_config
from sitewhere_tpu.ops import moe, paged_attention, shortconv
from sitewhere_tpu.parallel.mesh import MeshManager
from sitewhere_tpu.parallel.sharded import ShardedScorer

TINY = {
    "family": "lfm2_moe", "mixers": "CCACCCACCC", "pattern": "DDEEEEEEEE",
    "hidden_size": 64, "vocab_size": 256, "intermediate_size": 96,
    "num_experts": 16, "experts_held": [0, 16], "num_experts_per_tok": 4,
    "routed_scaling_factor": 1.0, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "conv_L_cache": 3, "rope_theta": 1000000.0, "norm_eps": 1e-5,
    "chunk_size": 128, "page_size": 4, "pool_pages": 400,
    "full_context_positions": 512, "compute_dtype": "float32",
}
TOL = 2e-4  # f32 against f32: the order of sums through 10 layers


def scorer_for(model=TINY, seed=7, max_streams=8):
    d = ref.dims(model)
    weights = ref.make_weights(seed, d)
    cfg = make_config("lfm2_moe", model_config(model))
    mm = MeshManager(tenant=1, data=1, devices=jax.devices()[:1])
    sc = ShardedScorer(mm, get_model("lfm2_moe"), cfg, slots_per_shard=1,
                       max_streams=max_streams, window=1, wire_dtype="f32")
    install_weights(sc, weights)
    sc.activate(0)
    return sc, weights, d


def flush(sc, rows, bucket=32):
    """One flush through the service's own calls: ``rows`` is a list of
    (stream, value); returns their scores in order."""
    ids = np.zeros((1, bucket), sc.ids_np_dtype)
    vals = np.zeros((1, bucket), np.float32)
    counts = np.zeros((1, 1), np.int32)
    for i, (s, v) in enumerate(rows):
        ids[0, i], vals[0, i] = s, v
    counts[0, 0] = len(rows)
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
    2,      # inside the convolution's reach
    9,      # across page boundaries
    70,     # many pages, the tails carried through every step
])
def test_one_step_decode_through_the_pages_and_tails_matches_the_full_forward(
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
    # the pool holds each stream's history, a page every 4 positions;
    # each row read and wrote its 8 layers' tails of 2 x 64 f32
    assert host["kv_pages_in_use"] == 2 * -(-length // 4)
    assert host["conv_tail_bytes"] == 2 * 2 * 8 * 2 * 64 * 4


@pytest.mark.parametrize("run", [1, 2, 127, 129, 300])
def test_chunked_program_agrees_with_the_one_step_program(fresh, run):
    """A run of one stream in one flush (the chunked program, 128 tokens
    a pass, the tails carried from pass to pass) against the same
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
    elif run > 8:
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
    nxt = flush(sc, [(1, series[1, 3])])
    assert abs(nxt[0] - want[1][3]) < TOL


def test_reset_slot_clears_the_tails_and_returns_the_pages(fresh):
    sc, weights, d = fresh
    series = series_for(1, 9, seed=9)[0]
    first = [flush(sc, [(2, v)])[0] for v in series]
    assert sc.pager.in_use == 3
    sc.reset_slot(0)
    assert sc.pager.in_use == 0
    assert all(not np.asarray(x).any()
               for x in jax.tree_util.tree_leaves(sc.state))
    install_weights(sc, weights)
    sc.activate(0)
    again = [flush(sc, [(7, v)])[0] for v in series]
    assert np.allclose(first, again, atol=1e-6)


# ---------------------------------------------------- the short conv
def test_the_short_conv_by_hand_with_its_tail_carried_equals_the_whole():
    """Three taps by hand over a whole series (zeros before it) against
    ``shortconv.conv`` taken in three calls of 5, 1 and 6 tokens, each
    from the tail the last one left; padding tokens past ``lens`` leave
    the tail alone."""
    rng = np.random.default_rng(4)
    c, t = 8, 12
    z = rng.standard_normal((t, c)).astype(np.float32)
    w = rng.standard_normal((3, c)).astype(np.float32)
    padded = np.concatenate([np.zeros((2, c), np.float32), z])
    want = sum(w[j] * padded[j:j + t] for j in range(3))
    tail = jnp.zeros((1, 2, c), jnp.float32)
    got = []
    for a, b in ((0, 5), (5, 6), (6, 12)):
        run = np.zeros((1, 8, c), np.float32)   # 8 slots, the rest padding
        run[0, :b - a] = z[a:b]
        y, tail = shortconv.conv(jnp.asarray(run), tail, jnp.asarray(w),
                                 jnp.asarray([b - a]))
        got.append(np.asarray(y)[0, :b - a])
        assert np.array_equal(np.asarray(tail)[0], z[b - 2:b])
    assert np.abs(np.concatenate(got) - want).max() < 1e-5
    # a padding row (lens 0) keeps its tail
    _y, kept = shortconv.conv(jnp.ones((1, 4, c)), tail, jnp.asarray(w),
                              jnp.asarray([0]))
    assert np.array_equal(np.asarray(kept), np.asarray(tail))


def test_the_mixer_gates_in_and_out_of_the_convolution():
    """``[B | C | x~] = W_in u``, ``z = B x~``, ``W_out (C y)`` by hand."""
    rng = np.random.default_rng(5)
    h = 8
    p = {"w_in": jnp.asarray(rng.standard_normal((h, 3 * h)), jnp.float32),
         "conv_w": jnp.asarray(rng.standard_normal((3, h)), jnp.float32),
         "w_out": jnp.asarray(rng.standard_normal((h, h)), jnp.float32)}
    u = rng.standard_normal((1, 3, h)).astype(np.float32)
    tail = rng.standard_normal((1, 2, h)).astype(np.float32)
    out, new = shortconv.mixer(jnp.asarray(u), p, jnp.asarray(tail),
                               jnp.asarray([3]))
    bcx = u[0] @ np.asarray(p["w_in"])
    z = np.concatenate([tail[0], bcx[:, :h] * bcx[:, 2 * h:]])
    w = np.asarray(p["conv_w"])
    y = w[0] * z[0:3] + w[1] * z[1:4] + w[2] * z[2:5]
    want = (bcx[:, h:2 * h] * y) @ np.asarray(p["w_out"])
    assert np.abs(np.asarray(out)[0] - want).max() < 1e-4
    assert np.allclose(np.asarray(new)[0], z[3:5])


# ------------------------------------------------------- attention
def test_pooled_keys_are_normed_then_rotated_and_paired_into_the_lanes():
    """One attention layer writes each token's keys into its page after
    the per-head RMSNorm and then RoPE at the token's absolute position,
    kv heads 2 i and 2 i + 1 side by side in one row of the pool, values
    beside them; and its output is plain attention over those keys."""
    cfg = make_config("lfm2_moe", model_config(TINY))
    d, g = cfg.head_dim, cfg.kv_heads
    weights = ref.make_weights(3, ref.dims(TINY))
    lw = weights["layers"][2]
    lp = {k: (jnp.asarray(v[0].astype(np.float32) * v[1])
              if isinstance(v, tuple) else jnp.asarray(v))
          for k, v in lw.items()}
    rng = np.random.default_rng(6)
    u = jnp.asarray(rng.standard_normal((1, 3, 64)), jnp.float32)
    t_abs = jnp.asarray([[5, 6, 7]])            # positions 5-7: page 1
    store = jnp.zeros((4, 4, g, 2 * d), jnp.float32)
    table = jnp.asarray([[2, 0]])               # logical pages 0, 1
    # positions 0-4 already pooled: some keys on page 2, one on page 0
    old = jnp.asarray(rng.standard_normal((5, g, 2 * d)), jnp.float32)
    store = store.at[2].set(old[:4]).at[0, 0].set(old[4])
    where = (jnp.asarray([[0, 0, 0]]), jnp.asarray([[1, 2, 3]]))
    out, store = lfm2_moe._attention(u, lp, store, where, table,
                                     jnp.asarray([8]), jnp.asarray([3]),
                                     t_abs, cfg)
    k = np.asarray(u[0]) @ np.asarray(lp["wk"])
    v = np.asarray(u[0]) @ np.asarray(lp["wv"])
    k = np.asarray(ref_afmoe._rms(jnp, jnp.asarray(k.reshape(3, g, d)),
                                  lp["k_norm"], cfg.eps))
    k = np.asarray(ref_afmoe.rope(jnp.asarray(k), jnp.arange(5, 8),
                                  cfg.rope_theta))
    rows = np.asarray(store)[0, 1:4]            # [3, g, 2 d]
    assert np.abs(rows[:, 0, :d] - k[:, 0]).max() < 1e-5
    assert np.abs(rows[:, 0, d:] - k[:, 1]).max() < 1e-5
    assert np.abs(rows[:, 1] - v.reshape(3, 2 * d)).max() < 1e-5
    # the output against plain attention over the 8 positions
    keys = np.concatenate([np.asarray(store)[2], np.asarray(store)[0]])[:8]
    q = np.asarray(u[0]) @ np.asarray(lp["wq"])
    q = np.asarray(ref_afmoe.rope(ref_afmoe._rms(
        jnp, jnp.asarray(q.reshape(3, 4, d)), lp["q_norm"], cfg.eps),
        jnp.arange(5, 8), cfg.rope_theta))
    kk = np.stack([keys[:, 0, :d], keys[:, 0, d:]], 1)
    vv = np.stack([keys[:, 1, :d], keys[:, 1, d:]], 1)
    o = np.zeros((3, 4, d))
    for i, pos in enumerate((5, 6, 7)):
        for head in range(4):
            s = kk[:pos + 1, head // 2] @ q[i, head] / np.sqrt(d)
            e = np.exp(s - s.max())
            o[i, head] = (e / e.sum()) @ vv[:pos + 1, head // 2]
    want = o.reshape(3, 4 * d) @ np.asarray(lp["wo"])
    assert np.abs(np.asarray(out)[0] - want).max() < 1e-4


@pytest.mark.parametrize("window", [None, 3])
def test_paired_heads_attend_as_their_own_heads(window):
    """``attend_paired`` over a paired store equals ``attend`` over the
    same keys and values laid out a head a row, to f32 rounding."""
    rng = np.random.default_rng(7)
    page, g, hq, d = 4, 4, 8, 8
    k = rng.standard_normal((3, page, g, d)).astype(np.float32)
    v = rng.standard_normal((3, page, g, d)).astype(np.float32)
    plain = np.stack([k, v], 3).reshape(3, page, 2 * g, d)
    pair = np.asarray(paged_attention.paired(jnp.asarray(k), jnp.asarray(v)))
    assert pair.shape == (3, page, g, 2 * d)
    assert np.array_equal(pair[:, :, 2, d:], k[:, :, 3])
    q = jnp.asarray(rng.standard_normal((2, 2, hq, d)), jnp.float32)
    args = (jnp.asarray([[1, 0, 2], [2, 1, 0]]), jnp.asarray([10, 7]),
            jnp.asarray([2, 1]), 0.3, window)
    want = paged_attention.attend(q, jnp.asarray(plain), args[0], *args[1:])
    got = paged_attention.attend_paired(q, jnp.asarray(pair), args[0],
                                        *args[1:])
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


# ------------------------------------------------------- the expert layer
def test_router_top_4_of_sigmoid_and_bias_normalised_at_scale_1():
    """Eight experts, top-4 of score + bias: the bias lifts expert 7 into
    the four and drops expert 3; the gates are the SCORES normalised over
    the four, scale 1, so they sum to 1."""
    logits = np.asarray([3.0, 2.5, 2.0, 1.5, 1.0, 0.5, 0.0, -0.5])
    u = jnp.asarray([[1.0, 0.0]])
    w = jnp.asarray(np.stack([logits, np.zeros(8)]))
    bias = np.zeros(8)
    bias[7] = 2.5
    idx, gates = moe.route(u, w, jnp.asarray(bias), top_k=4, scale=1.0)
    s = 1 / (1 + np.exp(-logits))
    top = [0, 1, 2, 7]
    assert sorted(np.asarray(idx)[0].tolist()) == top
    by = dict(zip(np.asarray(idx)[0].tolist(), np.asarray(gates)[0].tolist()))
    for e in top:
        assert by[e] == pytest.approx(s[e] / s[top].sum(), rel=1e-6)
    assert sum(by.values()) == pytest.approx(1.0, rel=1e-6)


def test_all_64_held_is_the_uncut_layer_and_two_halves_add_up_to_it():
    """64 experts at tiny widths, top-4, no shared expert: the program's
    expert layer holding all 64 is the reference's uncut layer, and two
    chips' halves (0-31, 32-63) add up to it, every routed pair on
    exactly one of them."""
    whole = ref.dims({**TINY, "num_experts": 64, "experts_held": [0, 64]})
    lw = ref.make_weights(3, whole)["layers"][2]
    u = np.random.default_rng(1).standard_normal((20, 64)).astype(np.float32)
    want = np.asarray(ref_afmoe.moe_layer(jnp.asarray(u), lw, whole,
                                          shared=False))

    def wide(w):
        return jnp.asarray(w[0].astype(np.float32) * w[1])

    idx, gates = moe.route(jnp.asarray(u), wide(lw["router"]),
                           lw["e_bias"], 4, 1.0)
    gu, down = wide(lw["gu"]), wide(lw["down"])
    valid = jnp.ones((20,), bool)
    all64, stats = moe.held_experts(jnp.asarray(u), idx, gates, valid, gu,
                                    down, 0, gated=True)
    assert np.abs(np.asarray(all64) - want).max() < 1e-5 < np.abs(want).max()
    assert int(stats[0]) == int(stats[1]) == 80      # every pair held
    total, pairs = 0.0, 0
    for lo, hi in ((0, 32), (32, 64)):
        share, st = moe.held_experts(jnp.asarray(u), idx, gates, valid,
                                     gu[lo:hi], down[lo:hi], lo, gated=True)
        total = total + np.asarray(share)
        pairs += int(st[1])
    assert pairs == 80
    assert np.abs(total - want).max() < 1e-5


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
    assert err.mean() < 0.02
    assert err_control.mean() > 3 * err.mean()


# ------------------------------------------------------------- the store
def test_published_widths_weigh_5267m_parameters():
    """Bytes of the real configuration from shapes alone (nothing is
    allocated): 5,267M parameters = 10.54 GB of weights; the pool 2 KB a
    position a layer, 3.49 GB for both; 128 KB of tails a stream."""
    cfg = make_config("lfm2_moe", {})
    spec = get_model("lfm2_moe")
    shapes = jax.eval_shape(lambda: spec.init(jax.random.PRNGKey(0), cfg))
    leaves = jax.tree_util.tree_leaves(shapes)
    params = sum(int(np.prod(x.shape)) for x in leaves)
    parts = {
        "conv mixers x 8 (projections, taps)": 8 * (4 * 2048 ** 2 + 3 * 2048),
        "attention x 2 (q, k, v, o; q and k norms)":
            2 * (2048 * (2 * 2048 + 2 * 512) + 2 * 64),
        "dense MLP x 2": 2 * 3 * 2048 * 11776,
        "routed experts: 8 x 64": 8 * 64 * 3 * 2048 * 1536,
        "routers and biases x 8": 8 * (2048 * 64 + 64),
        "embedding (the head too), norms": 65536 * 2048 + 21 * 2048,
    }
    assert params == sum(parts.values()) == 5_267_090_176
    weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in leaves)
    assert weights == 10_536_463_360
    st = jax.eval_shape(lambda: spec.init_state(cfg, 512))
    for pool in st["full"]:
        assert pool.shape == (3328, 256, 8, 128)
        assert pool.size * pool.dtype.itemsize == 3328 * 256 * 2048
    assert sum(x.size * 4 for x in st["conv"]) == 512 * 8 * 2 * 2048 * 4
    layout = lfm2_moe.page_pool(cfg)
    assert layout.position_bytes == 2048 and layout.tail_bytes == 131072
