"""``stream_surprisal`` for a fleet whose streams each have their own
pre-fill length (``encoders/sensor_counts_aged.py``): the accounting is
``scored_events``' and the recorder, collection, diagnosis and latencies
are ``stream_surprisal``'s (their functions, not copies — every limit of
them stays at 0); the scores are compared position by position from each
sampled stream's OWN pre-fill length: the stored score of the k-th timed
reading of a stream against the reference's surprisal at that position of
the stream's WHOLE series.

Which streams: ``check.streams`` of those that send in the window, drawn
from the seed — always the one with the shortest history and the one
with the longest, so that a full-attention layer over the fleet's longest
history is compared in every run.
"""

from __future__ import annotations

import gc

import numpy as np

from benchmark.checks import scored_events as base
from benchmark.checks import stream_surprisal as plain

recorder = plain.recorder
collect = plain.collect
diagnose = plain.diagnose
latencies_ms = plain.latencies_ms

BLOCK = 256  # the reference's series capacity is a whole number of these


def picks(run, seed: int, t: int) -> np.ndarray:
    """Tenant t's compared devices: its shortest and longest history,
    then the rest drawn from the seed."""
    devices, have = run.traffic.devices, run.traffic.lengths
    due = run.traffic.due
    mine = due[(due >= t * devices) & (due < (t + 1) * devices)] % devices
    age = have[t * devices + mine]
    ends = np.unique([mine[np.argmin(age)], mine[np.argmax(age)]])
    k = min(run.traffic.params["check"]["streams"], len(mine))
    rng = np.random.default_rng([seed, 0xC4EC, t])
    rest = rng.choice(np.setdiff1d(mine, ends), max(k - len(ends), 0),
                      replace=False)
    return np.concatenate([ends, rest]).astype(np.int64)


def _score_errors(run, facts, ref, seed, published, stand_in=None) -> tuple:
    """|stored score - reference surprisal| for every timed row of the
    sampled streams, and a line about the worst row."""
    devices, have = run.traffic.devices, run.traffic.lengths
    errs, worst = [], (0.0, "")
    for t in range(len(run.traffic.tenants)):
        chosen = picks(run, seed, t)
        dev, val, _msg = published[t]
        store = facts["store"][t]
        if len(store["device"]) != len(dev):
            continue  # the accounting checks have already failed this run
        order_p = np.argsort(dev, kind="stable")
        order_s = np.argsort(store["device"], kind="stable")
        lo = np.searchsorted(dev[order_p], chosen, "left")
        hi = np.searchsorted(dev[order_p], chosen, "right")
        # one capacity for every series: the reference compiles once
        capacity = -(-int((hi - lo).max()) // BLOCK) * BLOCK
        for a, b, device in zip(lo, hi, chosen):
            n_pre = int(have[t * devices + device])
            if b - a <= n_pre:
                continue
            ids = ref.tokens(val[order_p[a:b]])
            want = ref.score(ids, capacity)[n_pre:].astype(np.float64)
            if stand_in is not None:
                got = stand_in.score(ids, capacity)[n_pre:].astype(np.float64)
            else:
                got = store["score"][order_s[a + n_pre:b]].astype(np.float64)
            err = np.abs(got - want)
            errs.append(err)
            i = int(np.argmax(err)) if np.isfinite(err).all() else int(
                np.argmax(~np.isfinite(err)))
            if not err[i] <= worst[0]:
                worst = (float(err[i]), (
                    f"worst row: tenant {t} device {device} position "
                    f"{n_pre + i} of {b - a} (pre-fill {n_pre}): score "
                    f"{got[i]:.5f}, reference {want[i]:.5f}; the stream's "
                    f"mean |d| {err.mean():.5f}"))
    errs = np.concatenate(errs) if errs else np.zeros((0,))
    return errs, worst[1]


def judge(facts: dict, run, ref, seed: int, stand_in=None) -> dict:
    """``scored_events.judge`` for the accounting (its score comparison
    compares no row here), then the scores position by position."""
    # the program is torn down and nothing below reads it: what it kept
    # on the device goes back before the reference is placed there
    run.system = run.broker = None
    gc.collect()
    verdict = base.judge(facts, run, ref, seed)
    checks, notes = verdict["checks"], verdict["notes"]
    errs, worst_row = _score_errors(
        run, facts, ref, seed, base.published_rows(run), stand_in)
    if worst_row:
        notes.append(worst_row)
    if len(errs):
        limits = ref.config["limits"]
        checks["score_err_max"] = [float(errs.max()), limits["score_err_max"]]
        checks["score_err_mean"] = [float(errs.mean()),
                                    limits["score_err_mean"]]
        checks["score_err_p99"] = [float(np.quantile(errs, 0.99)),
                                   limits["score_err_p99"]]
    checks["score_rows_compared"] = [len(errs), ">0"]
    correct = len(errs) > 0 and all(
        v <= lim for v, lim in checks.values() if not isinstance(lim, str))
    return {"correct": bool(correct), "failed": verdict["failed"],
            "checks": checks, "notes": notes}
