"""Window-state ops: scatter/gather correctness incl. duplicates & padding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sitewhere_tpu.ops.windows import (
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

    def __init__(self, s, w):
        self.ring = np.zeros((s, w), np.float32)
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
                self.samples[int(i)].append(float(v))


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


def test_ring_values_of_a_store_split_over_data_shards():
    """Two shards of 3 streams, W 4: each owns one padded 128-lane row."""
    st = init_window_state(6, 4, shards=2)
    assert st.values.shape == (2, 128)
    lanes = jnp.arange(256, dtype=jnp.float32).reshape(2, 128)
    got = np.asarray(ring_values(st.__class__(lanes, st.pos, st.count, 4), 2))
    want = np.concatenate([np.arange(12), 128 + np.arange(12)]).reshape(6, 4)
    np.testing.assert_array_equal(got, want)
