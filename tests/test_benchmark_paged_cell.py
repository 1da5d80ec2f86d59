"""The cell ``trinity-mini-1t.sensors-aged-1hz`` rehearsed on the CPU at a
tiny size through the benchmark's own runner (``run.run_cell``: build,
prewarm, each stream's own pre-fill through the chunked program, a window
of one-token steps, the check against the plain reference), and what its
files say."""

import asyncio
import copy
import json

import jax
import numpy as np
import pytest

from benchmark import run
from benchmark.costs import afmoe as costs
from benchmark.encoders import sensor_counts_aged

CELL = "trinity-mini-1t.sensors-aged-1hz"
BENCH = json.loads((run.ROOT / "BENCHMARK.json").read_text())


def tiny_cell() -> dict:
    """Fleets, buckets and histories shrunk, and — what a CPU cannot hold
    — the widths: the same layer kinds, few heads and experts, pages of 4
    positions and a window of 16."""
    cell = copy.deepcopy(run.load_cell(CELL))
    cfg, tr = cell["config"], cell["traffic"]
    cfg["model"].update({
        "hidden_size": 64, "intermediate_size": 96,
        "num_experts_published": 8, "experts_held": [0, 4],
        "num_experts_per_tok": 2, "moe_intermediate_size": 32,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "sliding_window": 16, "chunk_size": 16, "page_size": 4,
        "pool_pages": 200, "full_context_positions": 256,
        "compute_dtype": "float32",
    })
    cfg["max_streams"] = 16
    cfg["devices_per_tenant"] = 6
    cfg["buckets"] = [32, 128]
    cfg["rule"]["min_score"] = 11.2
    cfg["limits"] = {"score_err_max": 1e-3, "score_err_mean": 1e-4,
                     "score_err_p99": 1e-3}
    tr["prefill"].update(samples=16, min_samples=16, max_samples=96,
                         rate_ev_s=2000)
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
    return go(tiny_cell(), seed=2**31 + 41, control=True)


def test_the_cells_path_is_correct_on_the_cpu(rehearsal):
    res = rehearsal
    assert res["program_correct"], res["program_checks"]
    checks = res["program_checks"]
    for name in ("accounting_faults", "compiles_in_window",
                 "unscored_or_missing_events", "decode_mismatch_rows",
                 "emitted_vs_stored_mismatch_rows", "rule_mismatch_devices"):
        assert checks[name] == [0, 0], (name, checks[name])
    # 6 devices x 3 reports, one-token steps after each stream's history
    assert res["attempted"] == 18 and res["failed"] == 0
    assert checks["score_rows_compared"][0] == 9
    assert res["metrics"]["scored_p50_ms"]["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0


def test_the_fp8_control_is_not_correct(rehearsal):
    assert not rehearsal["correct"]
    assert rehearsal["checks"]["score_err_mean"][0] > 100 * rehearsal[
        "program_checks"]["score_err_mean"][0]


def test_histories_are_stratified_and_follow_the_seed():
    a = sensor_counts_aged.lengths(5, 128, 512, 16384, 512)
    b = sensor_counts_aged.lengths(6, 128, 512, 16384, 512)
    # the same fleet of ages, another device for each
    assert sorted(a) == sorted(b) and not np.array_equal(a, b)
    assert a.sum() == 585_216 and (a.min(), a.max()) == (512, 16384)
    assert (a % 512 == 0).all()
    # log-uniform: as many streams under 2,048 as over 4,096 (two of the
    # five octaves each), within the rounding to whole messages
    assert abs((a < 2048).sum() / (a > 4096).sum() - 1) < 0.1


def test_each_stream_continues_its_own_series_from_its_own_length():
    cell = tiny_cell()
    from benchmark.generators import open_loop_periodic

    tr = sensor_counts_aged.build(cell["traffic"], cell["config"], 9, 3.0,
                                  open_loop_periodic.plan)
    have = tr.lengths
    assert len(tr.prefill) == have.max() // 16
    for s in range(6):
        rounds = [r for r in tr.prefill if s in r.device]
        assert len(rounds) == have[s] // 16
    # the encoder draws every stream's walk to the longest's length
    whole = sensor_counts_aged.series(9, 6, int(have.max()) + 3, 1.0)
    first = np.flatnonzero(tr.timed.device == 2)[0]
    assert tr.timed.values[first, 0] == whole[2, have[2]]


# the published config.json (huggingface.co/arcee-ai/Trinity-Mini)
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 8,
}


def test_the_configuration_holds_the_published_widths():
    entry = next(c for c in BENCH["configs"] if c["name"] == "trinity-mini-1t")
    cfg = json.loads((run.ROOT / entry["file"]).read_text())
    catalog = PUBLISHED
    changed = {k for k, v in catalog.items() if cfg.get(k) != v}
    assert changed == set(entry["reduced"]) == set(cfg["reduced"])
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (8, 64, 65536)
    model = cfg["model"]
    for key, value in model.items():
        if key in catalog and key not in entry["reduced"]:
            assert catalog[key] == value, key
    assert model["attention"] == "".join(
        "F" if t == "full_attention" else "W"
        for t in catalog["layer_types"][:8])
    assert model["pattern"] == "D" * catalog["num_dense_layers"] + "E" * 6
    assert model["num_experts_published"] == catalog["num_experts"]
    assert model["experts_held"] == [0, cfg["num_experts"]]
    assert "tpu_inference.stream_kv_pages_recycled" in cfg["zero_counters"]


def test_costs_count_distinct_experts_and_attended_positions():
    cfg = json.loads((run.ROOT / "benchmark/configs/"
                      "trinity-mini-1t.json").read_text())
    model, wire = cfg["model"], cfg["wire"]
    assert costs.expert_params(model) == 6_291_456
    assert costs.attention_params(model) == 27_262_976
    f1, b1 = costs.moe_cost(model, 6 * 8, 6 * 8)
    f2, b2 = costs.moe_cost(model, 6 * 16, 6 * 8)
    assert f2 == 2 * f1 and b2 - b1 < 0.01 * b1   # activations only
    # a position is K and V of 4 heads x 128 in bf16: 2,048 bytes
    f, b = costs.attn_cost(model, 1000, 0)
    assert f == 4 * 4096 * 1000 and b == 2048 * 1000
    flops, nbytes = costs.step_cost(model, wire, 2, 1, 1)
    # a step of 2 rows reads about 1.6 GB: attention weights, dense MLPs,
    # the hit experts, the head and the rows' windows; 1.5 GFLOP a row
    assert 1.3e9 < nbytes < 1.9e9
    assert 2 * 1.2e9 < flops < 2 * 1.8e9


def test_new_readers_read_the_counters_and_nothing_where_there_is_none():
    """Every new reader returns None (and does not raise) on a program
    that has no such counter, gauge or profile — what the parent commit
    gives them — and the pool's reader reads the gauge."""
    cfg = json.loads((run.ROOT / "benchmark/configs/"
                      "trinity-mini-1t.json").read_text())
    # the six this cell brought list it first (a later cell may follow)
    new = [m["name"] for m in BENCH["per_layer"] if m["workloads"][0] == CELL]
    assert len(new) == 6
    bare = {"window": run.Span({}, {}, 40.0), "traced": None, "trace": None,
            "config": cfg, "device": {"kind": "TPU v5 lite"}}
    for name in new:
        assert run.module("metrics", name).read(bare) is None, name
    after = {"tpu_inference.stream_kv_pages_in_use": 2414.0}
    ctx = dict(bare, window=run.Span({}, after, 40.0))
    assert run.module("metrics", "kv_pool_used_pct").read(ctx) == (
        pytest.approx(100 * 2414 / 3072))
    # the parent's readers of the Nemotron cell read nothing here either
    for name in ("moe_ms", "ssm_ms", "attn_ms"):
        assert run.module("metrics", name).read(bare) is None
