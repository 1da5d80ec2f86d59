"""What decides ``correct`` for a configuration whose tenants score
measurement events: the accounting of every event the window offered, and
the comparison of what the timed path produced with the plain reference.
A configuration names this module under ``"check"``; its limits, its
rule threshold and the counters that must stay at zero are the
configuration file's (``limits``, ``rule.min_score``, ``zero_counters``,
``lost_topic_endings``), not this module's.

``Recorder`` copies what is seen on the scored topics into arrays as it
arrives; ``collect`` reads the program while it is still up (stores,
counters, topics); ``judge`` runs after it has been torn down and
compares. Every number compared is printed beside its limit.

Flush semantics the comparison allows for (the program's ``fuse_k`` = 1):
a flush scores every row of a stream with the window that ends at the
stream's NEWEST row in that flush. So row i of a message may carry the
score of the window ending at any later row of the same message (a flush
boundary fell inside it), or at a row of a later message of the same
stream that was sent before row i came out scored (they rode one flush).
The comparison takes the nearest of those reference scores; which rows
may share a flush is decided from the benchmark's own send and
seen-scored times, never from the program.
"""

from __future__ import annotations

import asyncio
import sys
import time

import numpy as np

from benchmark.encoders.bulk_binary import EPOCH_MS, device_token


class Recorder:
    """Every row seen on a scored topic — when, whose, value, score,
    event_ts — copied into preallocated arrays the moment it is seen, so
    that the harness holds no batch of the program's (and grows no
    collector-tracked object of its own) while the window runs."""

    def __init__(self, capacity: int) -> None:
        self.cap, self.n, self.opened_at = max(1024, capacity), 0, 0
        self.t = np.empty(self.cap, np.float64)
        self.tenant = np.empty(self.cap, np.int32)
        self.value = self.score = self.ts = None

    def _room(self, k: int, item) -> None:
        if self.value is None:
            self.value = np.empty(self.cap, np.asarray(item.values).dtype)
            self.score = np.empty(self.cap, np.asarray(item.scores).dtype)
            self.ts = np.empty(self.cap, np.asarray(item.event_ts).dtype)
        if self.n + k > self.cap:  # more rows than were offered: keep them
            self.cap = 2 * (self.n + k)
            for name in ("t", "tenant", "value", "score", "ts"):
                old = getattr(self, name)
                new = np.empty(self.cap, old.dtype)
                new[:self.n] = old[:self.n]
                setattr(self, name, new)

    def add(self, t_seen: float, tenant_index: int, item) -> int:
        """Record one item of a scored topic; the rows it held."""
        if getattr(item, "stream_ids", None) is None:
            return 0
        k = int(item.n)
        self._room(k, item)
        a, b = self.n, self.n + k
        self.t[a:b] = t_seen
        self.tenant[a:b] = tenant_index
        self.value[a:b] = item.values
        self.score[a:b] = item.scores
        self.ts[a:b] = item.event_ts
        self.n = b
        return k

    def open_window(self) -> None:
        self.opened_at = self.n


def _family_sum(metrics, name: str) -> float:
    """An unlabeled counter, or the sum over a labeled family's children."""
    return float(sum(v for v in metrics.snapshot_families((name,)).values()
                     if isinstance(v, (int, float))))


def recorder(traffic) -> Recorder:
    rows = sum(r.count * r.samples for r in traffic.prefill)
    return Recorder(rows + traffic.timed.count * traffic.timed.samples)


def _lost_topic(topic: str, config: dict) -> bool:
    return ".dead-letter." in topic or topic.endswith(
        tuple(config["lost_topic_endings"]))


def diagnose(system, config: dict) -> None:
    """Say on standard error what the program did with rows that did not
    come out: the non-zero counters of the zero list, the lost topics."""
    for name in config["zero_counters"]:
        total = _family_sum(system.metrics, name)
        if total:
            print(f"note {name} = {total}", file=sys.stderr)
    for t in system.bus.topics():
        if _lost_topic(t, config):
            n = system.bus.peek(t, 1)["latest"]
            if n:
                print(f"note topic {t} holds {n}", file=sys.stderr)
    for e in system.errors()[:3]:
        print(f"note error {e!r}"[:400], file=sys.stderr)


def published_rows(run) -> dict:
    """Per tenant index: (device, value, message) of every row published,
    in publish order — pre-fill rounds, then the timed messages that were
    sent (message = index into the timed messages, negative in pre-fill)."""
    parts = []
    for r, msgs in enumerate(run.traffic.prefill):
        parts.append((msgs, np.ones(msgs.count, bool), -1 - r))
    parts.append((run.timed, ~np.isnan(run.sent_at), 0))
    out = {}
    for t in range(len(run.traffic.tenants)):
        dev, val, msg = [], [], []
        for msgs, sent, tag in parts:
            sel = np.flatnonzero(sent & (msgs.tenant == t))
            n = msgs.samples
            dev.append(np.repeat(msgs.device[sel].astype(np.int64), n))
            val.append(msgs.values[sel].reshape(-1))
            msg.append(np.repeat(sel if tag == 0 else np.full(len(sel), tag), n))
        out[t] = tuple(np.concatenate(x) for x in (dev, val, msg))
    return out


async def collect(system, run, window, attempted: int, drained: bool,
                  config: dict) -> dict:
    """Facts read from the live program, after the window has closed."""
    m = system.metrics
    prefill_rows = sum(r.count * r.samples for r in run.traffic.prefill)
    published = prefill_rows + attempted

    # persistence, rules and outbound trail the scored topic: give them
    # the same patience a late answer gets
    t_end = time.monotonic() + (30.0 if drained else 1.0)
    while time.monotonic() < t_end:
        if (_family_sum(m, "rules.evaluated") >= published
                and sum(system.outbound_rows(t) for t in system.tenants)
                >= published):
            break
        await asyncio.sleep(0.05)
    bus = system.bus
    lost = {t: bus.peek(t, 1)["latest"] for t in bus.topics()
            if _lost_topic(t, config)}
    per_slice: dict = {}
    for i, tok in enumerate(system.tenants):
        per_slice.setdefault(system.slice_of(tok), []).append(i)
    return {
        "published": published, "prefill_rows": prefill_rows,
        "drained": drained,
        "scored_total": int(m.counter("tpu_inference.scored_total").value),
        "store": {i: system.store_columns(tok)
                  for i, tok in enumerate(system.tenants)},
        "outbound": {i: system.outbound_rows(tok)
                     for i, tok in enumerate(system.tenants)},
        "rules_evaluated": _family_sum(m, "rules.evaluated"),
        "zero": {name: _family_sum(m, name)
                 for name in config["zero_counters"]},
        "lost": {t: n for t, n in lost.items() if n},
        "errors": [repr(e)[:300] for e in system.errors()[:3]],
        "compiles": window.count("tpu_inference.compiles"),
        "labels": system.scorer_labels(),
        "per_slice": per_slice,
        "slice_labels": {sl: system.slice_label(sl) for sl in per_slice},
        "device_rows": {
            k: v for k, v in m.snapshot_families(
                ("tpu_inference_device_rows_total",)).items()},
        "info": dict(system.info),
    }


def _emitted(run) -> dict:
    """Per tenant index: (value, score, event_ts, t_seen) of every row
    seen on its scored topic, in the order seen."""
    rec = run.seen
    n = rec.n
    if rec.value is None:
        z = np.zeros((0,))
        return {t: (z, z, z, z) for t in range(len(run.traffic.tenants))}
    order = np.argsort(rec.tenant[:n], kind="stable")
    cuts = np.searchsorted(rec.tenant[:n][order],
                           np.arange(len(run.traffic.tenants) + 1))
    return {
        t: tuple(col[:n][order[cuts[t]:cuts[t + 1]]]
                 for col in (rec.value, rec.score, rec.ts, rec.t))
        for t in range(len(run.traffic.tenants))}


def _score_errors(run, facts, ref, seed, published, emitted,
                  stand_in=None) -> tuple:
    """|stored score - nearest allowed reference score| for every timed
    row of the sampled streams, and a line about the worst row. With
    ``stand_in`` (the control: a reference in the program's place) its
    score for the same window is compared instead of the stored one."""
    window = ref.window
    n_pre = run.traffic.prefill_samples
    per_tenant = run.traffic.params["check"]["streams_per_tenant"]
    devices = run.traffic.devices
    due = run.traffic.due
    n = run.timed.samples
    errs = []
    worst = (0.0, "")
    for t in range(len(run.traffic.tenants)):
        # a sample, drawn from the seed, of this tenant's devices that
        # send in the window (all of them where the sample is as large)
        rng = np.random.default_rng([seed, 0xC4EC, t])
        mine = due[(due >= t * devices) & (due < (t + 1) * devices)] % devices
        picks = rng.choice(mine, min(per_tenant, len(mine)), replace=False)
        dev, val, msg = published[t]
        store = facts["store"][t]
        seen_t = emitted[t][3]
        if len(store["device"]) != len(dev) or len(seen_t) != len(dev):
            continue  # the accounting checks have already failed this run
        order_p = np.argsort(dev, kind="stable")
        order_s = np.argsort(store["device"], kind="stable")
        lo = np.searchsorted(dev[order_p], picks, "left")
        hi = np.searchsorted(dev[order_p], picks, "right")
        windows, spans = [], []
        for a, b in zip(lo, hi):
            series = ref.wire(val[order_p[a:b]])
            if b - a <= n_pre:
                spans.append((a, b, 0))
                continue
            ends = np.arange(n_pre, b - a)
            idx = ends[:, None] + np.arange(1 - window, 1)[None, :]
            windows.append(series[idx])
            spans.append((a, b, len(ends)))
        if not windows:
            continue
        windows = np.concatenate(windows)
        scores = ref.score(t, windows)
        posed = stand_in.score(t, windows) if stand_in is not None else None
        at = 0
        for (a, b, k), dev_of_span in zip(spans, picks):
            if not k:
                continue
            want = scores[at:at + k]
            pose = posed[at:at + k] if posed is not None else None
            at += k
            rows_s = order_s[a + n_pre:b]
            got = store["score"][rows_s].astype(np.float64)
            seen_at = seen_t[rows_s] - run.t0
            msgs = msg[order_p[a + n_pre:b]]
            # last row of each row's own message, then of every later
            # message of the stream sent before the row came out scored
            first_of_msg = np.arange(0, k, n)
            sent = run.sent_at[msgs[first_of_msg]]
            for i in range(k):
                j = i // n
                last = j
                while last + 1 < len(sent) and sent[last + 1] < seen_at[i]:
                    last += 1
                cand = want[i:(last + 1) * n]
                nearest = int(np.abs(cand - got[i]).argmin())
                shown = got[i] if pose is None else pose[i + nearest]
                errs.append(abs(cand[nearest] - shown))
                if not errs[-1] <= worst[0]:
                    # where in its stream the worst row's score does fit
                    fit = int(np.abs(want - shown).argmin())
                    worst = (errs[-1], (
                        f"worst row: tenant {t} device {dev_of_span}"
                        f" row {i} of {k} (row {i % n} of its message), score "
                        f"{shown:.5f}, nearest allowed reference "
                        f"{cand[nearest]:.5f} (window ending {nearest} rows "
                        f"later; {len(cand)} allowed); over the whole stream "
                        f"it fits the window ending {fit - i:+d} rows away "
                        f"(|d| {abs(want[fit] - shown):.5f})"))
    return np.asarray(errs, np.float64), worst[1]


def judge(facts: dict, run, ref, seed: int, stand_in=None) -> dict:
    """``ref`` is the family's plain reference (``builders/<family>.py``
    ``reference``); it carries the configuration, whose ``limits`` and
    ``rule`` are held here."""
    config = ref.config
    checks: dict = {}
    notes: list = []
    tenants = run.traffic.tenants
    published = published_rows(run)
    emitted = _emitted(run)
    per_tenant_rows = {t: len(published[t][0]) for t in published}
    total = facts["published"]

    # -- accounting: nothing shed, lost, unscored or compiled ------------
    faults = [f"{k} = {v}" for k, v in facts["zero"].items() if v]
    faults += [f"topic {k} holds {v}" for k, v in facts["lost"].items()]
    faults += [f"error {e}" for e in facts["errors"]]
    if not facts["drained"]:
        faults.append("the window's events did not all come out scored")
    if facts["scored_total"] != total:
        faults.append(f"scored_total {facts['scored_total']} != published {total}")
    for t, tok in enumerate(tenants):
        stored = len(facts["store"][t]["device"])
        if stored != per_tenant_rows[t]:
            faults.append(f"{tok}: stored {stored} != published {per_tenant_rows[t]}")
        if facts["outbound"][t] != per_tenant_rows[t]:
            faults.append(f"{tok}: outbound {facts['outbound'][t]} != "
                          f"published {per_tenant_rows[t]}")
    if facts["rules_evaluated"] < total:
        faults.append(f"rules.evaluated {facts['rules_evaluated']} < {total}")
    platform = facts["labels"][0].split(":")[0] if facts["labels"] else "?"
    if len(facts["per_slice"]) > 1:
        # every chip scored its own tenants' rows, and only those
        for sl, members in facts["per_slice"].items():
            want = sum(per_tenant_rows[t] for t in members)
            label = facts["slice_labels"][sl]
            got = sum(v for k, v in facts["device_rows"].items()
                      if f'device="{label}"' in k)
            if got != want:
                faults.append(f"chip {label} scored {got} rows, its "
                              f"tenants published {want}")
    checks["accounting_faults"] = [len(faults), 0]
    notes += faults[:12]
    checks["compiles_in_window"] = [int(facts["compiles"]), 0]

    # -- every event scored, with a finite score, and persisted ----------
    unscored = decode_bad = emit_bad = 0
    fired_want = set()
    for t in range(len(tenants)):
        store = facts["store"][t]
        dev, val, _msg = published[t]
        timed = store["event_ts"] >= EPOCH_MS
        unscored += int((~np.isfinite(store["score"][timed])).sum())
        # decode: per device, stored values == published values, in order
        if len(store["device"]) == len(dev):
            o_s = np.argsort(store["device"], kind="stable")
            o_p = np.argsort(dev, kind="stable")
            decode_bad += int((
                (store["device"][o_s] != dev[o_p])
                | (store["value"][o_s] != val[o_p])).sum())
        else:
            decode_bad += abs(len(store["device"]) - len(dev))
        # emission: what was seen on the scored topic is what was stored
        e_val, e_score, e_ts, _seen = emitted[t]
        if len(e_val) == len(store["value"]):
            emit_bad += int((
                (e_val != store["value"]) | (e_ts != store["event_ts"])
                | ~((e_score == store["score"])
                    | (np.isnan(e_score) & np.isnan(store["score"])))).sum())
        else:
            emit_bad += abs(len(e_val) - len(store["value"]))
        hot = np.unique(
            store["device"][store["score"] >= config["rule"]["min_score"]])
        fired_want |= {(tenants[t], device_token(int(d))) for d in hot}
    sent_rows = int((~np.isnan(run.sent_at)).sum()) * run.timed.samples
    stored_timed = sum(int((facts["store"][t]["event_ts"] >= EPOCH_MS).sum())
                       for t in range(len(tenants)))
    failed = max(0, sent_rows - stored_timed) + unscored
    checks["unscored_or_missing_events"] = [failed, 0]
    checks["decode_mismatch_rows"] = [decode_bad, 0]
    checks["emitted_vs_stored_mismatch_rows"] = [emit_bad, 0]
    # the rule leg: an alert left on the outbound MQTT topic for exactly
    # the devices that had a row at or over the configuration's rule
    checks["rule_mismatch_devices"] = [len(fired_want ^ run.alerts), 0]

    # -- the scores themselves, against the plain reference --------------
    errs, worst_row = _score_errors(run, facts, ref, seed, published,
                                    emitted, stand_in)
    n_rows = len(errs)
    if worst_row:
        notes.append(worst_row)
    if n_rows:
        limits = config["limits"]
        checks["score_err_max"] = [float(errs.max()), limits["score_err_max"]]
        checks["score_err_mean"] = [float(errs.mean()),
                                    limits["score_err_mean"]]
    checks["score_rows_compared"] = [n_rows, ">0"]
    if platform != "tpu":
        notes.append(f"scorers ran on {facts['labels']}, not a TPU")
    correct = n_rows > 0 and all(
        v <= lim for v, lim in checks.values() if not isinstance(lim, str))
    return {"correct": bool(correct), "failed": int(failed),
            "checks": checks, "notes": notes}


def latencies_ms(run, t_giveup: float) -> np.ndarray:
    """Per timed event: ms from its due time to the moment its batch was
    seen on its tenant's scored topic; an event that never came out (or
    came out unscored) counts as still waiting when the run gave up."""
    rec = run.seen
    sent = ~np.isnan(run.sent_at)
    offered = int(sent.sum()) * run.timed.samples
    if rec.value is None:
        return np.full(offered, (t_giveup - run.t0) * 1000.0)
    rows = slice(rec.opened_at, rec.n)
    due = rec.ts[rows] - EPOCH_MS
    ms = (rec.t[rows] - run.t0) * 1000.0 - due
    ok = np.isfinite(rec.score[rows]) & (due >= 0)
    missing = max(0, offered - int(ok.sum()))
    return np.concatenate(
        [ms[ok], np.full(missing, (t_giveup - run.t0) * 1000.0)])
