"""The cell ``nemotron3-nano-30b-1t.sensors-1hz`` rehearsed on the CPU at
a tiny size through the benchmark's own runner (``run.run_cell``: build,
prewarm, pre-fill through the chunked program, a window of one-token
steps, the check against the plain reference), and what its files say."""

import asyncio
import copy
import json

import jax
import numpy as np
import pytest

from benchmark import run
from benchmark.costs import nemotron_h as costs
from benchmark.encoders import sensor_counts
from benchmark.generators import open_loop_periodic

CELL = "nemotron3-nano-30b-1t.sensors-1hz"
BENCH = json.loads((run.ROOT / "BENCHMARK.json").read_text())


def tiny_cell() -> dict:
    """Fleets and buckets shrunk as ``selftest.shrink`` shrinks them, and
    — what a CPU cannot hold — the widths: same pattern letters, few
    heads and experts."""
    cell = copy.deepcopy(run.load_cell(CELL))
    cfg, tr = cell["config"], cell["traffic"]
    cfg["model"].update({
        "hidden_size": 64, "vocab_size": 65536, "mamba_num_heads": 4,
        "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
        "n_routed_experts_published": 8, "experts_held": [0, 4],
        "num_experts_per_tok": 3, "moe_intermediate_size": 32,
        "moe_shared_expert_intermediate_size": 64,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "context_positions": 256, "chunk_size": 16,
        "compute_dtype": "float32",
    })
    cfg["max_streams"] = 16
    cfg["devices_per_tenant"] = 6
    cfg["buckets"] = [32, 128]
    cfg["rule"]["min_score"] = 11.2
    cfg["limits"] = {"score_err_max": 1e-3, "score_err_mean": 1e-4,
                     "score_err_p99": 1e-3}
    tr["prefill"]["samples"] = 40
    tr["prefill"]["rate_ev_s"] = 2000
    tr["check"]["streams"] = 3
    return cell


def go(cell: dict, seed: int, seconds: float = 3.0, **kw) -> dict:
    jax.config.update("jax_cpu_enable_async_dispatch", False)  # PERF.md 7, 2
    try:
        result = asyncio.run(run.run_cell(
            cell, seed, seconds, False, jax.devices()[:1],
            drain_timeout_s=10.0, **kw))
    finally:
        jax.config.update("jax_cpu_enable_async_dispatch", True)
    return json.loads(json.dumps(result))


@pytest.fixture(scope="module")
def rehearsal():
    return go(tiny_cell(), seed=2**31 + 39, control=True)


def test_the_cells_path_is_correct_on_the_cpu(rehearsal):
    res = rehearsal
    assert res["program_correct"], res["program_checks"]
    checks = res["program_checks"]
    for name in ("accounting_faults", "compiles_in_window",
                 "unscored_or_missing_events", "decode_mismatch_rows",
                 "emitted_vs_stored_mismatch_rows", "rule_mismatch_devices"):
        assert checks[name] == [0, 0], (name, checks[name])
    # 6 devices x 3 reports, every one a one-token step at position 40+
    assert res["attempted"] == 18 and res["failed"] == 0
    assert checks["score_rows_compared"][0] == 9
    assert res["metrics"]["scored_p50_ms"]["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0


def test_the_fp8_control_is_not_correct(rehearsal):
    assert not rehearsal["correct"]
    assert rehearsal["checks"]["score_err_mean"][0] > 100 * rehearsal[
        "program_checks"]["score_err_mean"][0]


def test_the_window_offers_devices_times_seconds_over_the_interval():
    params = json.loads(
        (run.ROOT / "benchmark/traffic/sensors-1hz.json").read_text())
    assert "rate_ev_s" not in params
    for devices, seconds in ((400, 40.0), (7, 3.0), (512, 60.0)):
        stream, due = open_loop_periodic.plan(params, devices, 11, seconds)
        assert len(stream) == devices * int(seconds / params["report_interval_s"])
        assert due.min() >= 0 and due.max() < seconds * 1000
        assert (np.diff(due) >= 0).all()
        # every device reports once an interval, in its own order
        counts = np.bincount(stream, minlength=devices)
        assert (counts == int(seconds)).all()
        first = np.asarray([due[stream == s] for s in range(min(devices, 5))])
        gaps = np.diff(first, axis=1)
        assert gaps.min() >= 1000 - 2 * params["jitter_ms"] - 50
    cfg = json.loads((run.ROOT / BENCH["configs"][-1]["file"]).read_text())
    assert cfg["devices_per_tenant"] <= cfg["max_streams"]


def test_the_series_spread_over_the_slice_and_follow_the_seed():
    a = sensor_counts.series(5, 64, 552, 1.0)
    b = sensor_counts.series(5, 64, 552, 1.0)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, sensor_counts.series(6, 64, 552, 1.0))
    assert a.min() >= 0 and a.max() <= 65535
    assert np.array_equal(a, np.rint(a))          # whole counts, exact in f32
    assert a.max() - a.min() > 40000              # over the 16-bit range
    assert np.abs(np.diff(a, axis=1)).mean() > 100  # a walk, not a constant


def test_the_configuration_holds_the_published_widths():
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "nemotron3-nano-30b-1t")
    cfg = json.loads((run.ROOT / entry["file"]).read_text())
    catalog = None
    try:
        with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
            catalog = next(json.loads(l) for l in f
                           if "Nemotron-3-Nano-30B" in l)["config"]
    except OSError:
        pytest.skip("no catalog beside the guide here")
    changed = {k for k, v in catalog.items() if cfg.get(k) != v}
    assert changed == set(entry["reduced"]) == set(cfg["reduced"])
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (9, 64, 65536)
    # the model block repeats published keys for the builder: they agree
    model = cfg["model"]
    for key, value in model.items():
        if key in catalog and key not in entry["reduced"]:
            assert catalog[key] == value, key
    assert model["pattern"] == catalog["hybrid_override_pattern"][34:43]
    assert len(model["pattern"]) == cfg["num_hidden_layers"]
    assert model["experts_held"] == [0, cfg["n_routed_experts"]]
    assert model["n_routed_experts_published"] == catalog["n_routed_experts"]
    assert model["vocab_size"] == cfg["vocab_size"]


def test_costs_count_distinct_experts_not_pairs():
    cfg = json.loads((run.ROOT / "benchmark/configs/"
                      "nemotron3-nano-30b-1t.json").read_text())
    model, wire = cfg["model"], cfg["wire"]
    assert costs.expert_params(model) == 9_977_856
    assert costs.mixer_params(model) == 27_697_152 + 11_010_048
    ssm, ring, y = costs.state_bytes_per_stream(model)
    assert ssm == 4 * (2_097_152 + 73_728) and ring == 2_097_152
    # 16 rows a step: 48 held pairs a layer, but at most 64 experts, and
    # a second row on an expert reads no more weights
    f1, b1 = costs.moe_cost(model, 4 * 48, 4 * 30)
    f2, b2 = costs.moe_cost(model, 4 * 96, 4 * 30)
    assert f2 == 2 * f1 and b2 - b1 < 0.005 * b1   # activations only
    assert costs.expected_experts_hit(model, 1e9) == pytest.approx(64)
    assert 30 < costs.expected_experts_hit(model, 16) < 36
    flops, nbytes = costs.step_cost(model, wire, 16, 1, 1)
    # a step of 16 rows: 0.5-1 GFLOP a row, 3-5 GB moved
    assert 16 * 0.5e9 < flops < 16 * 1.2e9
    assert 3e9 < nbytes < 5e9


def test_new_readers_read_the_counters_and_nothing_where_there_is_none():
    """The counter readers on made-up counters; every new reader returns
    None (and does not raise) on a program that has no such counter, span
    or profile — what the parent commit gives them."""
    cfg = json.loads((run.ROOT / "benchmark/configs/"
                      "nemotron3-nano-30b-1t.json").read_text())
    new = [m["name"] for m in BENCH["per_layer"]
           if m["workloads"][0] == CELL]
    assert len(new) == 8
    bare = {"window": run.Span({}, {}, 40.0), "traced": None, "trace": None,
            "config": cfg, "device": {"kind": "TPU v5 lite"}}
    for name in new:
        assert run.module("metrics", name).read(bare) is None, name
    k = "tpu_inference.stream_"
    after = {k + "calls_one_step": 100.0, k + "experts_hit": 100 * 4 * 30.0,
             k + "pairs_routed": 9600.0, k + "pairs_held": 4800.0,
             k + "state_read_bytes": 2e9, k + "state_written_bytes": 1e9,
             "tpu_inference.flushes": 100.0}
    ctx = dict(bare, window=run.Span({}, after, 40.0))
    read = {n: run.module("metrics", n).read(ctx) for n in new}
    assert read["experts_hit_per_step"] == 30.0
    assert read["held_pair_share_pct"] == 50.0
    assert read["state_mb_per_step"] == 30.0
    assert all(read[n] is None for n in new if n.endswith(("_ms", "_roofline")))
