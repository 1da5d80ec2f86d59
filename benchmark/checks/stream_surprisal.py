"""What decides ``correct`` for a configuration whose tenant scores each
reading from a state that lives from event to event: the accounting of
every event is ``scored_events``' (its functions, not copies of them —
every limit of it stays at 0), and the scores are compared position by
position: the stored score of the k-th reading of a stream against the
reference's surprisal at position k of that stream's WHOLE series,
pre-fill included. There is no window to choose and no flush semantics
to allow for: a stream's readings are scored once each, in order, on its
own state — a reading scored twice, out of order or on another stream's
state moves every later score of the stream.

The traffic file's ``check.streams`` is how many streams (drawn from the
seed among those that send in the window) are compared over all their
timed rows; ``check.streams_per_tenant`` is 0, which makes
``scored_events``' own window comparison compare nothing.
"""

from __future__ import annotations

import gc

import numpy as np

from benchmark.checks import scored_events as base

recorder = base.recorder
collect = base.collect
diagnose = base.diagnose
latencies_ms = base.latencies_ms


def _score_errors(run, facts, ref, seed, published, stand_in=None) -> tuple:
    """|stored score - reference surprisal| for every timed row of the
    sampled streams, and a line about the worst row."""
    n_pre = run.traffic.prefill_samples
    devices = run.traffic.devices
    due = run.traffic.due
    errs, worst = [], (0.0, "")
    for t in range(len(run.traffic.tenants)):
        rng = np.random.default_rng([seed, 0xC4EC, t])
        mine = due[(due >= t * devices) & (due < (t + 1) * devices)] % devices
        picks = rng.choice(
            mine, min(run.traffic.params["check"]["streams"], len(mine)),
            replace=False)
        dev, val, _msg = published[t]
        store = facts["store"][t]
        if len(store["device"]) != len(dev):
            continue  # the accounting checks have already failed this run
        order_p = np.argsort(dev, kind="stable")
        order_s = np.argsort(store["device"], kind="stable")
        lo = np.searchsorted(dev[order_p], picks, "left")
        hi = np.searchsorted(dev[order_p], picks, "right")
        longest = int((hi - lo).max())
        for a, b, device in zip(lo, hi, picks):
            if b - a <= n_pre:
                continue
            # every series padded to one length: the reference is causal,
            # and compiles its recurrence once
            ids = np.zeros(longest, np.int32)
            ids[:b - a] = ref.tokens(val[order_p[a:b]])
            want = ref.score(ids)[n_pre:b - a].astype(np.float64)
            if stand_in is not None:
                got = stand_in.score(ids)[n_pre:b - a].astype(np.float64)
            else:
                got = store["score"][order_s[a + n_pre:b]].astype(np.float64)
            err = np.abs(got - want)
            errs.append(err)
            i = int(np.argmax(err)) if np.isfinite(err).all() else int(
                np.argmax(~np.isfinite(err)))
            if not err[i] <= worst[0]:
                worst = (float(err[i]), (
                    f"worst row: tenant {t} device {device} position "
                    f"{n_pre + i} of {b - a}: score {got[i]:.5f}, reference "
                    f"{want[i]:.5f}; the stream's mean |d| {err.mean():.5f}"))
    errs = np.concatenate(errs) if errs else np.zeros((0,))
    return errs, worst[1]


def judge(facts: dict, run, ref, seed: int, stand_in=None) -> dict:
    """``scored_events.judge`` for the accounting (its score comparison
    compares no row here), then the scores position by position."""
    # the program is torn down and nothing below reads it: what it kept
    # on the device goes back before the reference is placed there (the
    # runner's ``run`` still pointed at the stopped system)
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
