"""The benchmark's files agree with one another: what a later PR's
additions must satisfy before they cost a chip call -- and, since both
are plain dictionaries too, ``benchmark.sweep``'s one rule reads every
window of ``sweep_windows.json`` as recorded there. Plain JSON and
``os.path``; imports neither JAX, numpy, ``benchmark.run`` nor pytest.

    python3 -m benchmark.file_cases

runs every case and exits 1 where one fails (``benchmark.selftest`` runs
the same cases). ``CASES`` is a list of (check, subject) pairs, one for
every cell, configuration and metric, so that a tier-1 file under
``tests/`` need only parametrise over it for each to count.
"""

from __future__ import annotations

import json
import os
import sys

from benchmark import sweep

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


BENCH = _load(ROOT, "BENCHMARK.json")
CONFIGS = {c["name"]: c for c in BENCH["configs"]}
CELLS = {w["name"]: w for w in BENCH["workloads"]}
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
WINDOWS = {w["name"]: w for w in _load(HERE, "sweep_windows.json")["windows"]}


def _config_file(name: str) -> dict:
    return _load(ROOT, CONFIGS[name]["file"])


def _traffic_and_config(cell: str) -> tuple:
    w = CELLS[cell]
    return (_load(HERE, "traffic", w["traffic"] + ".json"),
            _config_file(w["config"]))


def _has(*parts: str) -> bool:
    return os.path.isfile(os.path.join(HERE, *parts))


def cell_names_its_files(cell: str) -> None:
    w = CELLS[cell]
    assert w["config"] in CONFIGS, w["config"]
    assert _has("traffic", w["traffic"] + ".json"), w["traffic"]
    traffic = _load(HERE, "traffic", w["traffic"] + ".json")
    assert _has("generators", traffic["kind"] + ".py"), traffic["kind"]
    assert _has("encoders", traffic["encoder"] + ".py"), traffic["encoder"]


def cell_offers_each_device_at_most_once(cell: str) -> None:
    """Rate x ``run_seconds`` does not exceed the registered devices: a
    device reports once an interval, and no window is longer than one."""
    traffic, config = _traffic_and_config(cell)
    if "rate_ev_s" not in traffic:
        return
    messages = (traffic["rate_ev_s"] * BENCH["run_seconds"]
                / traffic.get("samples_per_message", 1))
    assert messages <= config["tenants"] * config["devices_per_tenant"]


def cell_registers_the_fleet_its_rate_implies(cell: str) -> None:
    """A re-seat cannot move the rate and leave the fleet: where every
    device reports once a published interval, the devices registered are
    rate x interval / tenants, the rule ``sweep.with_value`` applies."""
    traffic, config = _traffic_and_config(cell)
    interval = config.get("published", {}).get("report_interval_s")
    if "rate_ev_s" not in traffic or not interval:
        return
    implied = sweep.fleet_per_tenant(
        traffic["rate_ev_s"], interval, config["tenants"])
    assert config["devices_per_tenant"] == implied, implied


def config_names_its_files(name: str) -> None:
    entry = CONFIGS[name]
    assert os.path.isfile(os.path.join(ROOT, entry["file"])), entry["file"]
    config = _config_file(name)
    family = config["model"]["family"]
    for kind, module in (("builders", config["builder"]),
                         ("checks", config["check"]),
                         ("costs", family), ("reference", family)):
        assert _has(kind, module + ".py"), (kind, module)


def config_says_why_each_key_is_reduced(name: str) -> None:
    config = _config_file(name)
    assert sorted(config["reduced"]) == sorted(CONFIGS[name]["reduced"])
    for key in config["reduced"]:
        assert config.get("reduced_why", {}).get(key), key


def metric_has_a_reader(name: str) -> None:
    assert _has("metrics", name + ".py"), name


def metric_lists_cells_that_exist(name: str) -> None:
    metric = next(m for m in METRICS if m["name"] == name)
    assert set(metric.get("workloads", [])) <= set(CELLS)


def bound_is_one_to_ten_percent(name: str) -> None:
    """Under 1% no host clock keeps it; over 10% the contract refuses."""
    bound = next(m for m in BENCH["end_to_end"] if m["name"] == name)["bound"]
    assert 0.01 <= bound <= 0.10, bound


def sweep_rule_reads_the_window_as_recorded(name: str) -> None:
    """``sweep.sustained`` on a recorded (or a made-up) window gives the
    verdict the file records and, where that is no, names the reason."""
    w = WINDOWS[name]
    verdict, reasons = sweep.sustained(w["correct"], w["failed"], w["info"])
    assert verdict == w["sustained"], reasons
    assert all(any(part in r for r in reasons) for part in w.get("says", []))
    assert verdict or w.get("says"), "a refusal is recorded with its reason"


def at_most_a_quarter_of_the_cells_take_four_chips(_: str) -> None:
    four = sum(w["chips"] == 4 for w in CELLS.values())
    assert four <= max(1, len(CELLS) // 4), four


CASES = (
    [(cell_names_its_files, c) for c in CELLS]
    + [(cell_offers_each_device_at_most_once, c) for c in CELLS]
    + [(cell_registers_the_fleet_its_rate_implies, c) for c in CELLS]
    + [(config_names_its_files, c) for c in CONFIGS]
    + [(config_says_why_each_key_is_reduced, c) for c in CONFIGS]
    + [(metric_has_a_reader, m["name"]) for m in METRICS]
    + [(metric_lists_cells_that_exist, m["name"]) for m in METRICS]
    + [(bound_is_one_to_ten_percent, m["name"]) for m in BENCH["end_to_end"]]
    + [(at_most_a_quarter_of_the_cells_take_four_chips, "workloads")]
    + [(sweep_rule_reads_the_window_as_recorded, w) for w in WINDOWS]
)


def main() -> None:
    failed = 0
    for check, subject in CASES:
        try:
            check(subject)
        except (AssertionError, KeyError, OSError, ValueError) as exc:
            failed += 1
            print(f"FAIL {check.__name__}[{subject}]: {exc!r}")
    print(f"{len(CASES) - failed} of {len(CASES)} file cases hold")
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
