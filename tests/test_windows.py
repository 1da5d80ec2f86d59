"""Window-state ops: scatter/gather correctness incl. duplicates & padding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sitewhere_tpu.ops.windows import (
    LANES,
    gather_windows,
    init_window_state,
    ring_values,
    update_and_gather,
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
    mean nothing; shuffled, so same-stream rows are apart. (The burst
    sits on the lowest id, and no other stream passes W in a batch:
    ``_segment_ranks`` gives a run the largest total of the runs sorted
    after it — PERF.md section 7, 1 — and would drop their rows.)"""
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
