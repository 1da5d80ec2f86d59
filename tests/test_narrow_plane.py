"""The window family's narrow plane (``ShardedScorer.planes``): a flush
whose fullest lane holds at most 128 rows runs the step over 128 lanes a
(slot, data shard) instead of the smallest bucket's. The step's work is
per row and padding rows drop in the scatter, so the same rows through
either plane give the same scores and leave the same store."""

import jax
import numpy as np
import pytest

from sitewhere_tpu.models import get_model, make_config
from sitewhere_tpu.parallel.mesh import MeshManager
from sitewhere_tpu.parallel.sharded import ShardedScorer

NARROW, BUCKET = ShardedScorer.NARROW_LANES, 1024


def _scorer(slots: int) -> ShardedScorer:
    sc = ShardedScorer(
        MeshManager(tenant=1, data=1, devices=jax.devices()[:1]),
        get_model("lstm_ad"),
        make_config("lstm_ad", {"window": 32, "hidden": 8}),
        slots_per_shard=slots, max_streams=48, window=32, wire_dtype="bf16",
    )
    for slot in range(slots):
        sc.activate(slot)
    return sc


def _flush(sc: ShardedScorer, ids, vals, counts, b_lane: int) -> np.ndarray:
    """One flush through the service's calls at lane width ``b_lane``:
    the rows of ``ids`` / ``vals`` ([T, NARROW]) front-packed into the
    plane; returns the gathered scores of the valid rows."""
    t = sc.n_slots
    ids_p = np.zeros((t, b_lane), sc.ids_np_dtype)
    vals_p = np.zeros((t, b_lane), sc.vals_np_dtype)
    ids_p[:, :NARROW], vals_p[:, :NARROW] = ids, vals
    staged = sc.stage_inputs(ids_p, vals_p, counts)
    moved = int(counts.sum())
    scores = sc.gather_rows(sc.step_counts(*staged), staged[2], moved)
    return np.asarray(scores).astype(np.float32)[:moved]


@pytest.mark.parametrize("slots", [1, 3])
def test_the_narrow_and_the_bucket_plane_score_and_store_alike(slots):
    """Flushes of up to 128 rows a lane — repeated streams, bursts past
    the window, an empty lane — through a 128-lane and a 1,024-lane
    plane: equal scores, and equal rings, ``pos`` and ``count``."""
    narrow, wide = _scorer(slots), _scorer(slots)
    assert narrow.planes((BUCKET, 4096)) == (NARROW, BUCKET, 4096)
    rng = np.random.default_rng(47)
    t = narrow.n_slots
    for k in range(6):
        ids = rng.integers(0, 48 if k % 2 else 6, (t, NARROW))
        vals = rng.normal(size=(t, NARROW)).astype(np.float32)
        counts = rng.integers(0, NARROW + 1, (t, 1)).astype(np.int32)
        counts[k % t, 0] = NARROW if k < 3 else 0
        got = _flush(narrow, ids, vals, counts, NARROW)
        want = _flush(wide, ids, vals, counts, BUCKET)
        assert len(got) == int(counts.sum())
        np.testing.assert_array_equal(got, want)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(
        np.asarray(narrow.ring_values()), np.asarray(wide.ring_values()))
    for leaf in ("pos", "count"):
        np.testing.assert_array_equal(
            np.asarray(getattr(narrow.state, leaf)),
            np.asarray(getattr(wide.state, leaf)))
    assert int(np.asarray(narrow.state.count).sum()) > 0


def test_a_window_scorer_adds_the_narrow_plane_below_a_wider_bucket_only():
    sc = _scorer(1)
    assert sc.planes([1024, 4096, 16384]) == (NARROW, 1024, 4096, 16384)
    assert sc.planes((256, 1024)) == (NARROW, 256, 1024)
    # a smallest bucket at or under the lane tile is already narrow
    assert sc.planes((NARROW, 512)) == (NARROW, 512)
    assert sc.planes((32, 64)) == (32, 64)
