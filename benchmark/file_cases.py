"""The benchmark's files agree with one another: what a later PR's
additions must satisfy before they cost a chip call. Plain JSON and
``os.path``; imports neither JAX, ``benchmark.run`` nor pytest.

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

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


BENCH = _load(ROOT, "BENCHMARK.json")
CONFIGS = {c["name"]: c for c in BENCH["configs"]}
CELLS = {w["name"]: w for w in BENCH["workloads"]}
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def _config_file(name: str) -> dict:
    return _load(ROOT, CONFIGS[name]["file"])


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
    w = CELLS[cell]
    traffic = _load(HERE, "traffic", w["traffic"] + ".json")
    config = _config_file(w["config"])
    if "rate_ev_s" not in traffic:
        return
    messages = (traffic["rate_ev_s"] * BENCH["run_seconds"]
                / traffic.get("samples_per_message", 1))
    assert messages <= config["tenants"] * config["devices_per_tenant"]


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


def at_most_a_quarter_of_the_cells_take_four_chips(_: str) -> None:
    four = sum(w["chips"] == 4 for w in CELLS.values())
    assert four <= max(1, len(CELLS) // 4), four


CASES = (
    [(cell_names_its_files, c) for c in CELLS]
    + [(cell_offers_each_device_at_most_once, c) for c in CELLS]
    + [(config_names_its_files, c) for c in CONFIGS]
    + [(config_says_why_each_key_is_reduced, c) for c in CONFIGS]
    + [(metric_has_a_reader, m["name"]) for m in METRICS]
    + [(metric_lists_cells_that_exist, m["name"]) for m in METRICS]
    + [(at_most_a_quarter_of_the_cells_take_four_chips, "workloads")]
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
