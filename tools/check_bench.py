#!/usr/bin/env python
"""Bench regression gate: fresh headline JSON vs the newest BENCH_r*.json.

The bench headline (one compact JSON line — see bench.py) is the driver's
contract, but nothing so far FAILED when a PR quietly cost 15% of
throughput or doubled p99. This gate compares a fresh headline against
the newest recorded ``BENCH_r*.json`` with per-kind tolerances:

- **throughput keys** (``value``, ``*_ev_s``, ``*_fps``, ``*_fc_s``,
  ``*_mbps*``): regression when fresh < baseline × (1 − 10%);
- **p99 keys** (``*_p99_ms``): regression when fresh > baseline ×
  (1 + 25%) — latency keys tolerate more than throughput keys;
- everything else (MFU figures, counts, notes) is reported
  informationally and never gates — accounting definitions may change
  (e.g. the analytic-FLOPs MFU fix) without being a perf regression.

Report is a table on stderr; exit 1 iff any gated key regressed. The
gate runs POST-bench (driver / operator), not in tier-1 — tier-1
unit-tests the comparator (tests/test_flightrec.py).

Usage:
    python bench.py && python tools/check_bench.py <(echo "$HEADLINE")
    python tools/check_bench.py fresh.json [--baseline some_headline.json]

No baseline is recorded for today's machine (the earlier rig's records
were removed with the rig), so the gate says "no baseline" and passes
until the ledger exists.
"""

from __future__ import annotations

import glob
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

_TOOLS_DIR = os.path.dirname(os.path.abspath(__file__))
if _TOOLS_DIR not in sys.path:
    sys.path.insert(0, _TOOLS_DIR)

import astlib  # noqa: E402

REPO_ROOT = str(astlib.REPO_ROOT)

THROUGHPUT_TOL = 0.10   # fresh may sit up to 10% below baseline
P99_TOL = 0.25          # fresh may sit up to 25% above baseline

_THROUGHPUT_SUFFIXES = ("_ev_s", "_fps", "_fc_s", "_mbps", "_mbps_staged")

# higher-is-better keys gated by NAME (suffix rules don't cover them):
# the 32-tenant engine MFU and the fused-vs-legacy step speedup — losing
# either quietly is exactly the compute-structure regression ISSUE 8
# exists to prevent. New keys report n/a against pre-fusion baselines.
# Noise note: both are chip-gated figures — baselines are recorded on
# the accelerator, where the twins run back-to-back in one process
# (common-mode drift cancels in the ratio). A CPU rig's ±10% step noise
# would make this gate flake — but CPU headlines are never recorded as
# baselines.
# ev_s_8dev (ISSUE 11): total events/s over the 8-device mesh serving
# row — the direct horizontal-scale figure; chip-recorded baselines
# gate it like any throughput key (new key reports n/a against
# single-chip baselines). mesh_balance stays info-class: a balance dip
# is a routing-quality signal, not a throughput regression per se.
# vit_pipeline_ratio (ISSUE 12): media pipeline f/s ÷ model-only f/s —
# the compressed-wire acceptance figure (real-chip goal ≥ 0.5, i.e.
# pipeline within 2× of model-only). Higher is better and a drop is
# exactly the h2d-ceiling regression the compressed wire exists to
# prevent; vit_fps and vit_wire_mbps already gate via the suffix rules
# (n/a against pre-compression baselines that lack the keys).
_THROUGHPUT_EXACT = {
    "mfu_32t_pct", "fused_speedup_32t", "ev_s_8dev", "vit_pipeline_ratio",
}

# info-class by NAME even though a suffix rule would gate them:
# vit_wire_mbps = wire bytes/frame × submit rate, so a DELIBERATE wire
# diet (smaller jpegs after an encoder change) would read as a
# throughput regression — fps/ratio regressions are already gated by
# vit_fps / vit_pipeline_ratio.
_INFO_EXACT = {"vit_wire_mbps"}

# lower-is-better keys gated by NAME (ISSUE 13): serve_p99_train_delta =
# serve p99 with the train lane active ÷ the training-off twin's, same
# offered load — the train lane's whole contract is that this ratio
# stays ~1.0 (acceptance: within 10%). Gated with the p99 tolerance
# (the twins run back-to-back in one process, so common-mode rig drift
# cancels in the ratio; chip baselines make it stable). train_ev_s (the
# lane's replay-fed rows/s) gates via the _ev_s suffix rule.
# zipf512_p99_ratio (ISSUE 19): Zipf-mix p99 over 512 virtualized
# tenants ÷ the all-resident 32-tenant row's p99, same rig/process —
# the weight-paging acceptance figure (goal ≤ 1.2). Lower is better;
# zipf512_ev_s / p99_zipf512_ms / cold_activation_p99_ms gate via the
# suffix/prefix rules above (n/a against pre-paging baselines).
_P99_EXACT = {"serve_p99_train_delta", "zipf512_p99_ratio"}


def _is_latency_key(key: str) -> bool:
    """The paced-bench latency column family (ISSUE 17): ``p99_e2e_ms``
    and the per-stage ``p99_<stage>_ms`` columns. Prefix style (p99_
    first) so the family reads as one block in the headline; the legacy
    ``*_p99_ms`` suffix rule can't cover it. Lower is better, gated at
    the p99 tolerance; new keys report n/a against pre-paced baselines."""
    return key.startswith("p99_") and key.endswith("_ms")


def classify(key: str) -> str:
    """'throughput' (higher is better, gated), 'p99' (lower is better,
    gated), or 'info' (reported, never gates)."""
    if key in _INFO_EXACT:
        return "info"
    if key.endswith("_p99_ms") or key in _P99_EXACT or _is_latency_key(key):
        return "p99"
    if (
        key == "value"
        or key in _THROUGHPUT_EXACT
        or key.endswith(_THROUGHPUT_SUFFIXES)
    ):
        return "throughput"
    return "info"


def compare(
    fresh: Dict,
    baseline: Dict,
    throughput_tol: float = THROUGHPUT_TOL,
    p99_tol: float = P99_TOL,
) -> Tuple[List[Dict], List[Dict]]:
    """Per-key comparison rows + the subset that regressed.

    Keys missing on either side, non-numeric values, and zero/absent
    baselines report as ``n/a`` and never gate (a new bench key must not
    fail the gate the first time it appears)."""
    rows: List[Dict] = []
    regressions: List[Dict] = []
    for key in sorted(set(fresh) | set(baseline)):
        kind = classify(key)
        f, b = fresh.get(key), baseline.get(key)
        row = {"key": key, "kind": kind, "baseline": b, "fresh": f,
               "delta_pct": None, "status": "n/a"}
        if (
            isinstance(f, (int, float)) and isinstance(b, (int, float))
            and not isinstance(f, bool) and not isinstance(b, bool)
            and b
        ):
            delta = (f - b) / abs(b)
            row["delta_pct"] = round(100.0 * delta, 2)
            if kind == "throughput":
                row["status"] = "REGRESSION" if delta < -throughput_tol else "ok"
            elif kind == "p99":
                row["status"] = "REGRESSION" if delta > p99_tol else "ok"
            else:
                row["status"] = "info"
            if row["status"] == "REGRESSION":
                regressions.append(row)
        rows.append(row)
    return rows, regressions


def format_table(rows: List[Dict]) -> str:
    def cell(v) -> str:
        if v is None:
            return "-"
        if isinstance(v, float):
            return f"{v:.1f}"
        return str(v)

    header = f"{'key':36} {'kind':10} {'baseline':>14} {'fresh':>14} {'Δ%':>8}  status"
    out = [header, "-" * len(header)]
    for r in rows:
        out.append(
            f"{r['key'][:36]:36} {r['kind']:10} {cell(r['baseline']):>14} "
            f"{cell(r['fresh']):>14} {cell(r['delta_pct']):>8}  {r['status']}"
        )
    return "\n".join(out)


def newest_baseline_path(root: str = REPO_ROOT) -> Optional[str]:
    """The newest recorded bench headline: BENCH_r*.json sorted by the
    zero-padded round number in the name."""
    paths = sorted(glob.glob(os.path.join(root, "BENCH_r*.json")))
    return paths[-1] if paths else None


def load_headline(path: str) -> Dict:
    """A headline dict from a bench output file: either the bare JSON
    object, or a driver-format wrapper whose ``parsed`` (or the last
    JSON line of ``tail``) holds it."""
    with open(path) as fh:
        doc = json.load(fh)
    if "metric" in doc:
        return doc
    parsed = doc.get("parsed")
    if isinstance(parsed, dict) and "metric" in parsed:
        return parsed
    tail = doc.get("tail", "")
    for line in reversed(tail.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                cand = json.loads(line)
            except ValueError:
                continue
            if "metric" in cand:
                return cand
    raise ValueError(f"no bench headline found in {path}")


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("fresh", help="fresh bench headline JSON ('-' = stdin)")
    ap.add_argument("--baseline", default="",
                    help="baseline headline (default: newest BENCH_r*.json)")
    ap.add_argument("--throughput-tol", type=float, default=THROUGHPUT_TOL)
    ap.add_argument("--p99-tol", type=float, default=P99_TOL)
    args = ap.parse_args(argv)

    if args.fresh == "-":
        fresh = json.loads(sys.stdin.read())
    else:
        fresh = load_headline(args.fresh)
    base_path = args.baseline or newest_baseline_path()
    if base_path is None:
        print("check_bench: no BENCH_r*.json baseline found — nothing to "
              "gate against", file=sys.stderr)
        return 0
    baseline = load_headline(base_path)

    rows, regressions = compare(
        fresh, baseline, args.throughput_tol, args.p99_tol
    )
    print(f"check_bench: baseline {os.path.basename(base_path)}",
          file=sys.stderr)
    print(format_table(rows), file=sys.stderr)
    if regressions:
        print(f"check_bench: {len(regressions)} regression(s):",
              file=sys.stderr)
        for r in regressions:
            print(f"  {r['key']}: {r['baseline']} -> {r['fresh']} "
                  f"({r['delta_pct']:+.1f}%)", file=sys.stderr)
        return 1
    print("check_bench: no regressions", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
