"""Window-state ops: scatter/gather correctness incl. duplicates & padding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sitewhere_tpu.ops import windows as windows_mod
from sitewhere_tpu.ops.windows import (
    LANES,
    gather_windows,
    init_window_state,
    ring_values,
    update_and_gather,
    update_gather_ranked,
    update_windows,
)


def _np_windows(samples_by_stream, window, stream):
    """Reference: last `window` samples, left-padded with the first one."""
    vals = samples_by_stream[stream][-window:]
    if not vals:
        return [0.0] * window
    pad = [vals[0]] * (window - len(vals))
    return pad + vals


def test_single_stream_ordering():
    st = init_window_state(max_streams=4, window=4)
    ids = jnp.array([1, 1, 1], jnp.int32)
    vals = jnp.array([10.0, 20.0, 30.0], jnp.float32)
    st = update_windows(st, ids, vals, jnp.ones(3, bool))
    w, n = gather_windows(st, jnp.array([1], jnp.int32))
    assert int(n[0]) == 3
    np.testing.assert_allclose(np.asarray(w[0]), [10.0, 10.0, 20.0, 30.0])


def test_ring_wraparound():
    st = init_window_state(max_streams=2, window=3)
    for v in [1.0, 2.0, 3.0, 4.0, 5.0]:
        st = update_windows(
            st, jnp.array([0], jnp.int32), jnp.array([v], jnp.float32), jnp.ones(1, bool)
        )
    w, n = gather_windows(st, jnp.array([0], jnp.int32))
    assert int(n[0]) == 3
    np.testing.assert_allclose(np.asarray(w[0]), [3.0, 4.0, 5.0])


def test_duplicates_and_padding_vs_reference():
    rng = np.random.default_rng(0)
    S, W, B, steps = 8, 5, 16, 7
    st = init_window_state(S, W)
    ref = {s: [] for s in range(S)}
    for _ in range(steps):
        ids = rng.integers(0, S, B).astype(np.int32)
        vals = rng.normal(size=B).astype(np.float32)
        valid = rng.random(B) > 0.25
        for i in range(B):
            if valid[i]:
                ref[int(ids[i])].append(float(vals[i]))
        st = update_windows(st, jnp.asarray(ids), jnp.asarray(vals), jnp.asarray(valid))
    for s in range(S):
        w, n = gather_windows(st, jnp.array([s], jnp.int32))
        assert int(n[0]) == min(len(ref[s]), W)
        np.testing.assert_allclose(
            np.asarray(w[0]), _np_windows(ref, W, s), rtol=1e-6
        )


def test_update_and_gather_includes_new_sample():
    st = init_window_state(4, 3)
    st, w, n = update_and_gather(
        st,
        jnp.array([2, 2], jnp.int32),
        jnp.array([7.0, 8.0], jnp.float32),
        jnp.ones(2, bool),
    )
    # both rows see the post-update window for stream 2
    np.testing.assert_allclose(np.asarray(w[1]), [7.0, 7.0, 8.0])
    assert int(n[1]) == 2


def test_jit_static_shapes_no_recompile():
    st = init_window_state(16, 4)
    fn = jax.jit(update_and_gather)
    ids = jnp.zeros((8,), jnp.int32)
    vals = jnp.ones((8,), jnp.float32)
    valid = jnp.ones((8,), bool)
    st, w, n = fn(st, ids, vals, valid)
    st, w, n = fn(st, ids, vals, valid)  # same shapes → cached
    assert w.shape == (8, 4)


def test_burst_larger_than_window_keeps_newest():
    """>W same-stream rows in one batch: newest W win deterministically."""
    st = init_window_state(2, 3)
    ids = jnp.zeros((7,), jnp.int32)
    vals = jnp.arange(7, dtype=jnp.float32)
    st = update_windows(st, ids, vals, jnp.ones(7, bool))
    w, n = gather_windows(st, jnp.array([0], jnp.int32))
    assert int(n[0]) == 3
    np.testing.assert_allclose(np.asarray(w[0]), [4.0, 5.0, 6.0])


class _NumpyRings:
    """The [S, W] store written one row at a time, in batch order."""

    def __init__(self, s, w, dtype=np.float32):
        self.ring = np.zeros((s, w), dtype)
        self.pos = np.zeros(s, np.int32)
        self.count = np.zeros(s, np.int32)
        self.samples = {i: [] for i in range(s)}

    def write(self, ids, vals, valid):
        w = self.ring.shape[1]
        for i, v, ok in zip(ids, vals, valid):
            if ok:
                self.ring[i, self.pos[i]] = v
                self.pos[i] = (self.pos[i] + 1) % w
                self.count[i] += 1
                self.samples[int(i)].append(v)


def _batch(rng, s, w, burst):
    """One padded batch: every stream up to W//2 + 1 rows (duplicates),
    ``burst`` rows more than W for stream 0, three padded rows whose ids
    mean nothing; shuffled, so same-stream rows are apart."""
    cap = w // 2 + 1
    b = s * cap + w + 6
    counts = rng.integers(0, cap + 1, s)
    if burst:
        counts[0] = w + 3
    ids = np.repeat(np.arange(s), counts)
    valid = np.zeros(b, bool)
    valid[: len(ids)] = True
    ids = np.concatenate([ids, rng.integers(0, s, b - len(ids))])
    order = rng.permutation(b)
    return ids[order].astype(np.int32), valid[order]


@pytest.mark.parametrize("slots", [0, 3], ids=["jit-donated", "vmap-3-slots"])
@pytest.mark.parametrize(
    "s,w", [(4, 3), (6, 4), (8, 32), (10, 48), (4, 128), (3, 160)]
)
def test_lane_dense_store_vs_reference(s, w, slots):
    """Rings that share a 128-lane row (W 4, 32), straddle rows (3, 48),
    fill one (128) or span several (160): duplicates, a burst > W, padded
    rows and wrap-around, every batch's windows and the final rings
    against the row-at-a-time [S, W] reference."""
    rng = np.random.default_rng(1000 * s + w)
    t = max(slots, 1)
    refs = [_NumpyRings(s, w) for _ in range(t)]
    st = init_window_state(s, w)
    fn = update_and_gather
    if slots:
        st = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (t,) + x.shape).copy(), st
        )
        fn = jax.vmap(fn)
    fn = jax.jit(fn, donate_argnums=0)
    for step in range(8):
        ids, valid = map(
            np.stack, zip(*[_batch(rng, s, w, step == 2) for _ in refs]))
        vals = rng.normal(size=ids.shape).astype(np.float32)
        args = (ids, vals, valid) if slots else (ids[0], vals[0], valid[0])
        st, win, n = fn(st, *map(jnp.asarray, args))
        win = np.asarray(win).reshape(ids.shape + (w,))
        n = np.asarray(n).reshape(ids.shape)
        for k, ref in enumerate(refs):
            ref.write(ids[k], vals[k], valid[k])
            for row in np.flatnonzero(valid[k]):
                i = int(ids[k, row])
                assert n[k, row] == min(len(ref.samples[i]), w)
                np.testing.assert_array_equal(
                    win[k, row],
                    np.asarray(_np_windows(ref.samples, w, i), np.float32),
                )
    rings = np.asarray(ring_values(st)).reshape(t, s, w)
    for k, ref in enumerate(refs):
        assert ref.count.min() > w  # every ring wrapped
        np.testing.assert_array_equal(rings[k], ref.ring)
        np.testing.assert_array_equal(
            np.asarray(st.pos).reshape(t, s)[k], ref.pos)
        np.testing.assert_array_equal(
            np.asarray(st.count).reshape(t, s)[k], ref.count)


def _mixed_batch(rng, streams, cap, w):
    """Rows for the ids ``streams`` of a store of ``cap``: duplicates up
    to W//2 + 1 rows a stream, bursts of W + 1 to W + 9 rows on two
    streams picked at random (any id, so a burst may sort after a shorter
    run), padding rows with ids in range and past it; shuffled."""
    counts = rng.integers(0, w // 2 + 2, len(streams))
    counts[rng.choice(len(streams), 2, replace=False)] = rng.integers(
        w + 1, w + 10, 2)
    ids = np.repeat(streams, counts)
    pad = np.concatenate(
        [rng.integers(0, cap, 5), cap + rng.integers(0, 9, 2)])
    valid = np.concatenate([np.ones(len(ids), bool), np.zeros(len(pad), bool)])
    order = rng.permutation(len(valid))
    return np.concatenate([ids, pad])[order].astype(np.int32), valid[order]


def _later(ids, valid):
    """Valid rows of the same stream after each row (0 for padding)."""
    later = np.zeros(len(ids), np.int32)
    for row in np.flatnonzero(valid):
        after = valid[row + 1:] & (ids[row + 1:] == ids[row])
        later[row] = after.sum()
    return later


# a store of 7 streams, every one written: its counters are written whole
# (7 x 3 counters cost less than one stream written in place); a store of
# 3 x COUNTERS_PER_WRITE streams a slot with 3 of them written: in place,
# one stream at a time (W 160 is left out of it: 75 MB of rings a slot)
_SMALL, _LARGE = "7-streams-whole", "3-in-117k-one-by-one"


@pytest.mark.parametrize("slots", ["one", "stacked-3", "vmap-3"])
@pytest.mark.parametrize("w,store", [
    (32, _SMALL), (48, _SMALL), (160, _SMALL), (32, _LARGE), (48, _LARGE)])
def test_ranked_update_vs_sequential_rings(w, store, slots):
    """Every output of ``update_gather_ranked`` — the store, ``pos``,
    ``count``, each row's window, ``n`` and ``later`` — against the
    row-at-a-time reference, over batches with duplicates, padding and
    bursts longer than W on any stream: one slot, three slots stacked as
    the step passes them, three under ``vmap``; the counters written
    whole in a small store and one stream at a time in a large one."""
    rng = np.random.default_rng(40_000 + w)
    t = 1 if slots == "one" else 3
    if store == _SMALL:
        s, streams = 7, [np.arange(7)] * t
    else:
        s = 3 * windows_mod.COUNTERS_PER_WRITE
        streams = [rng.choice(s, 3, replace=False) for _ in range(t)]
    refs = [_NumpyRings(s, w) for _ in range(t)]
    st = init_window_state(s, w)
    fn = update_gather_ranked
    if t > 1:
        st = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (t,) + x.shape).copy(), st)
        if slots == "vmap-3":
            fn = jax.vmap(fn)
    fn = jax.jit(fn, donate_argnums=0)
    for _ in range(4):
        batches = [_mixed_batch(rng, ids, s, w) for ids in streams]
        b = max(len(ids) for ids, _ in batches)
        ids = np.stack([np.pad(i, (0, b - len(i))) for i, _ in batches])
        valid = np.stack([np.pad(v, (0, b - len(v))) for _, v in batches])
        vals = rng.normal(size=ids.shape).astype(np.float32)
        args = (ids, vals, valid) if t > 1 else (ids[0], vals[0], valid[0])
        st, win, n, later = fn(st, *map(jnp.asarray, args))
        win = np.asarray(win).reshape(ids.shape + (w,))
        n = np.asarray(n).reshape(ids.shape)
        later = np.asarray(later).reshape(ids.shape)
        for k, ref in enumerate(refs):
            ref.write(ids[k], vals[k], valid[k])
            np.testing.assert_array_equal(
                later[k], _later(ids[k], valid[k]))
            for row in np.flatnonzero(valid[k]):
                i = int(ids[k, row])
                assert n[k, row] == min(len(ref.samples[i]), w)
                np.testing.assert_array_equal(
                    win[k, row],
                    np.asarray(_np_windows(ref.samples, w, i), np.float32))
        rings = np.asarray(ring_values(st)).reshape(t, s, w)
        for k, ref in enumerate(refs):
            np.testing.assert_array_equal(rings[k], ref.ring)
            np.testing.assert_array_equal(
                np.asarray(st.pos).reshape(t, s)[k], ref.pos)
            np.testing.assert_array_equal(
                np.asarray(st.count).reshape(t, s)[k], ref.count)


@pytest.mark.parametrize("cap", [4, 2 * windows_mod.COUNTERS_PER_WRITE],
                         ids=["whole", "one-by-one"])
def test_a_longer_run_on_a_higher_id_leaves_a_shorter_one_whole(cap):
    """Streams 0 and 1 with 8 and 40 rows in one batch, W 32 (PERF.md
    section 7, 1: a reversed cummax gave stream 0 the total 40, so none
    of its rows was the newest W and none was written): stream 0 keeps
    all 8, stream 1 its newest 32, and both counters are exact — in a
    store of 4 (counters written whole) and of 78,000 (in place)."""
    w = 32
    ids = np.array([0] * 8 + [1] * 40, np.int32)
    order = np.random.default_rng(8).permutation(len(ids))
    ids = ids[order]
    vals = np.arange(len(ids), dtype=np.float32)
    st, win, n, later = jax.jit(update_gather_ranked)(
        init_window_state(cap, w), jnp.asarray(ids), jnp.asarray(vals),
        jnp.ones(len(ids), bool))
    want0 = vals[ids == 0]
    want1 = vals[ids == 1][-w:]
    np.testing.assert_array_equal(np.asarray(st.count)[:4], [8, 40, 0, 0])
    np.testing.assert_array_equal(np.asarray(st.pos)[:4], [8, 40 % w, 0, 0])
    assert not np.asarray(st.count)[2:].any()
    rows = np.asarray(win)
    np.testing.assert_array_equal(
        rows[np.flatnonzero(ids == 0)[-1]],
        np.concatenate([np.full(w - 8, want0[0]), want0]))
    np.testing.assert_array_equal(rows[np.flatnonzero(ids == 1)[-1]], want1)
    np.testing.assert_array_equal(np.asarray(n), np.where(ids == 0, 8, w))
    np.testing.assert_array_equal(np.asarray(later), _later(ids, ids >= 0))


@pytest.mark.parametrize("slots", [1, 3])
@pytest.mark.parametrize("room", ["none", "one", "all-but-one", "all"])
def test_the_counters_shape_picks_how_they_are_written(room, slots):
    """``update_windows`` over batches of 8 rows a slot, the last of them
    padding, so 2 to 7 streams a slot are written: the counters' size
    alone decides how. A store whose size over ``COUNTERS_PER_WRITE``
    leaves room to write ``most`` streams in place builds only the
    whole-array add where that is 0 (``none``), only the in-place loop
    where every row of the batch fits (``all``), and a conditional
    between the two otherwise: with room for ``one`` every batch takes
    the whole add, with room for ``all-but-one`` every batch the loop.
    ``pos``, ``count`` and the rings are exact either way."""
    b, w = 8, 4
    most = {"none": 0, "one": 1, "all-but-one": slots * b - 1,
            "all": slots * b}[room]
    built = {"none": "whole", "all": "one-by-one"}.get(room, "cond")
    s = -(-most * windows_mod.COUNTERS_PER_WRITE // slots) if most else 7
    rng = np.random.default_rng(most * 10 + slots)
    refs = [_NumpyRings(s, w) for _ in range(slots)]
    st = init_window_state(s, w)
    if slots > 1:
        st = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (slots,) + x.shape).copy(),
            st)
    streams = rng.choice(s, 5, replace=False)
    for step in range(3):
        ids = rng.choice(streams, (slots, b)).astype(np.int32)
        ids[0, :2] = streams[:2]  # two streams or more a batch
        valid = np.arange(b) < b - 1
        valid = np.broadcast_to(valid, ids.shape)
        vals = rng.normal(size=ids.shape).astype(np.float32)
        args = [jnp.asarray(a if slots > 1 else a[0])
                for a in (ids, vals, valid)]
        if step == 0:
            text = str(jax.make_jaxpr(update_windows)(st, *args))
            assert ("cond[" in text) == (built == "cond"), text
            assert ("while[" in text) == (built != "whole"), text
        st = jax.jit(update_windows, donate_argnums=0)(st, *args)
        for k, ref in enumerate(refs):
            ref.write(ids[k], vals[k], valid[k])
    for k, ref in enumerate(refs):
        np.testing.assert_array_equal(
            np.asarray(st.pos).reshape(slots, s)[k], ref.pos)
        np.testing.assert_array_equal(
            np.asarray(st.count).reshape(slots, s)[k], ref.count)
        np.testing.assert_array_equal(
            np.asarray(ring_values(st)).reshape(slots, s, w)[k], ref.ring)


# bit patterns a store can hold and arithmetic would not pass through:
# -0.0, quiet and signalling NaNs with payloads, both infinities, a denormal
_SPECIAL_BITS = np.array(
    [0x80000000, 0x7FC00000, 0xFFC12345, 0x7F800001, 0x7F800000, 0xFF800000,
     0x00000001], np.uint32)


def _bit_values(rng, shape):
    """uint32 patterns, a third of them special; read as f32 they are the
    values fed to the program, kept as integers they are the reference."""
    bits = rng.integers(0, 2**32, shape, dtype=np.uint32)
    special = rng.choice(_SPECIAL_BITS, shape)
    return np.where(rng.random(shape) < 1 / 3, special, bits)


def _state_of(refs):
    """The device state holding ``refs``' rings: ring slot k of stream s
    at flat position s*W + k of the 128-lane rows."""
    st = init_window_state(*refs[0].ring.shape)
    flat = np.zeros((len(refs), st.values.size), np.uint32)
    for k, ref in enumerate(refs):
        flat[k, : ref.ring.size] = ref.ring.reshape(-1)
    values = flat.view(np.float32).reshape((len(refs),) + st.values.shape)
    pos = np.stack([ref.pos for ref in refs])
    count = np.stack([ref.count for ref in refs])
    return st.__class__(
        jnp.asarray(values), jnp.asarray(pos), jnp.asarray(count), st.window)


@pytest.mark.parametrize("slots", [0, 3], ids=["jit", "vmap-3-slots"])
@pytest.mark.parametrize("w", [1, 2, 4, 8, 16, 32, 64, 128, 48, 160])
def test_windows_are_the_reference_rings_bit_for_bit(w, slots):
    """The select path (W divides 128) over every part of a row, every
    ``pos``, histories of 0, 1, W-1, W and more samples (the left-pad),
    duplicates and invalid rows, and values no arithmetic passes through
    — and the element-gather path (48, 160) beside it: each window equal
    as uint32 to the last W samples of the row-at-a-time reference."""
    rng = np.random.default_rng(7000 + w)
    parts = LANES // w if LANES % w == 0 else 1
    s = max(2 * parts, w) + 3
    t = max(slots, 1)
    refs = [_NumpyRings(s, w, np.uint32) for _ in range(t)]
    for k, ref in enumerate(refs):  # stream i starts with (i + k) % (W + 3)
        for i in range(s):          # samples: every pos, n from 0 to past W
            h = (i + k) % (w + 3)
            ref.write([i] * h, _bit_values(rng, h), [True] * h)
    st = _state_of(refs)
    if not slots:
        st = jax.tree_util.tree_map(lambda x: x[0], st)

    def check(win, n, ids, valid):
        win = np.asarray(win).view(np.uint32).reshape(ids.shape + (w,))
        n = np.asarray(n).reshape(ids.shape)
        for k, ref in enumerate(refs):
            for row in np.flatnonzero(valid[k]):
                i = int(ids[k, row])
                assert n[k, row] == min(len(ref.samples[i]), w)
                np.testing.assert_array_equal(
                    win[k, row],
                    np.asarray(_np_windows(ref.samples, w, i), np.uint32),
                )

    # a read of every stream, half of them twice, as the state stands
    ids = np.stack([
        rng.permutation(np.concatenate([np.arange(s), np.arange(0, s, 2)]))
        for _ in refs]).astype(np.int32)
    gather = jax.vmap(gather_windows) if slots else gather_windows
    win, n = jax.jit(gather)(st, jnp.asarray(ids if slots else ids[0]))
    check(win, n, ids, np.ones(ids.shape, bool))
    seen = np.asarray(n).reshape(-1)
    assert {0, 1, w - 1, w} <= set(seen.tolist())
    assert {int(p) for ref in refs for p in ref.pos} == set(range(w))
    assert {i * w % LANES // w for i in range(s)} >= set(range(parts))

    # then batches that write: duplicates, padded rows, wrap-around
    step = jax.vmap(update_and_gather) if slots else update_and_gather
    step = jax.jit(step, donate_argnums=0)
    for _ in range(3):
        ids, valid = map(
            np.stack, zip(*[_batch(rng, s, w, False) for _ in refs]))
        bits = _bit_values(rng, ids.shape)
        for k, ref in enumerate(refs):
            ref.write(ids[k], bits[k], valid[k])
        args = (ids, bits.view(np.float32), valid)
        st, win, n = step(
            st, *(jnp.asarray(a if slots else a[0]) for a in args))
        check(win, n, ids, valid)
    rings = np.asarray(ring_values(st)).view(np.uint32).reshape(t, s, w)
    for k, ref in enumerate(refs):
        np.testing.assert_array_equal(rings[k], ref.ring)


def test_ring_values_of_a_store_split_over_data_shards():
    """Two shards of 3 streams, W 4: each owns one padded 128-lane row."""
    st = init_window_state(6, 4, shards=2)
    assert st.values.shape == (2, 128)
    lanes = jnp.arange(256, dtype=jnp.float32).reshape(2, 128)
    got = np.asarray(ring_values(st.__class__(lanes, st.pos, st.count, 4), 2))
    want = np.concatenate([np.arange(12), 128 + np.arange(12)]).reshape(6, 4)
    np.testing.assert_array_equal(got, want)
