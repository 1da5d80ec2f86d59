"""The cell ``lfm2-24b-a2b-1t.sensors-20min-1hz`` rehearsed on the CPU at a
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
from benchmark.costs import lfm2_moe as costs
from benchmark.encoders import sensor_counts_aged

CELL = "lfm2-24b-a2b-1t.sensors-20min-1hz"
BENCH = json.loads((run.ROOT / "BENCHMARK.json").read_text())
NEW = ("shortconv_ms", "shortconv_roofline", "attn_hd64_ms",
       "attn_hd64_roofline")


def tiny_cell() -> dict:
    """Fleets, buckets and histories shrunk, and — what a CPU cannot hold
    — the widths: the same layer kinds, few heads and experts, pages of 4
    positions."""
    cell = copy.deepcopy(run.load_cell(CELL))
    cfg, tr = cell["config"], cell["traffic"]
    cfg["model"].update({
        "hidden_size": 64, "intermediate_size": 96, "num_experts": 16,
        "experts_held": [0, 16], "moe_intermediate_size": 32,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "chunk_size": 16, "page_size": 4, "pool_pages": 200,
        "full_context_positions": 256, "compute_dtype": "float32",
    })
    cfg["max_streams"] = 16
    cfg["devices_per_tenant"] = 6
    cfg["buckets"] = [32, 128]
    cfg["rule"]["min_score"] = 11.2
    cfg["limits"] = {"score_err_max": 1e-3, "score_err_mean": 1e-4,
                     "score_err_p99": 1e-3}
    tr["prefill"].update(samples=16, min_samples=24, max_samples=96,
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
    return go(tiny_cell(), seed=2**31 + 45, control=True)


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
    a = sensor_counts_aged.lengths(5, 512, 768, 1536, 256)
    b = sensor_counts_aged.lengths(6, 512, 768, 1536, 256)
    # the same fleet of ages, another device for each
    assert sorted(a) == sorted(b) and not np.array_equal(a, b)
    assert a.sum() == 565_760 and (a.min(), a.max()) == (768, 1536)
    assert (a % 256 == 0).all()
    # every stream's history and its 40 timed readings: 2,722 pages of
    # 256, 82% of the pool of 3,328 a layer
    assert int(np.ceil((a + 40) / 256).sum()) == 2722


# the published config.json (huggingface.co/LiquidAI/LFM2-24B-A2B), as the
# catalog row holds it
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536,
    "layer_types": ["conv", "conv"] + (
        ["full_attention", "conv", "conv", "conv"] * 10)[:38],
}


def test_the_configuration_holds_the_published_widths():
    entry = next(c for c in BENCH["configs"] if c["name"] == "lfm2-24b-a2b-1t")
    cfg = json.loads((run.ROOT / entry["file"]).read_text())
    changed = {k for k, v in PUBLISHED.items() if cfg.get(k) != v}
    assert changed == set(entry["reduced"]) == set(cfg["reduced"]) == {
        "num_hidden_layers"}
    assert cfg["num_hidden_layers"] == 10
    model = cfg["model"]
    for key, value in model.items():
        if key in PUBLISHED and key not in entry["reduced"]:
            assert PUBLISHED[key] == value, key
    assert model["mixers"] == "".join(
        "A" if t == "full_attention" else "C"
        for t in PUBLISHED["layer_types"][:10])
    assert model["pattern"] == "D" * PUBLISHED["num_dense_layers"] + "E" * 8
    assert model["experts_held"] == [0, PUBLISHED["num_experts"]]
    assert model["rope_theta"] == PUBLISHED["rope_parameters"]["rope_theta"]
    assert model["head_dim"] * model["num_attention_heads"] == 2048
    assert "tied_head" in cfg["assumed"] and "pool_pages" in cfg["assumed"]
    assert "tpu_inference.stream_kv_pages_recycled" in cfg["zero_counters"]


def test_costs_count_distinct_experts_tails_and_attended_positions():
    cfg = json.loads((run.ROOT / "benchmark/configs/"
                      "lfm2-24b-a2b-1t.json").read_text())
    model, wire = cfg["model"], cfg["wire"]
    assert costs.expert_params(model) == 9_437_184
    assert costs.conv_params(model) == 16_777_216
    assert costs.attention_params(model) == 10_485_760
    f1, b1 = costs.moe_cost(model, 8 * 4, 8 * 4)
    f2, b2 = costs.moe_cost(model, 8 * 8, 8 * 4)
    assert f2 == 2 * f1 and b2 - b1 < 0.01 * b1   # activations only
    # a position is K and V of 8 heads x 64 in bf16: 2,048 bytes
    f, b = costs.attn_cost(model, 1000, 0)
    assert f == 4 * 2048 * 1000 and b == 2048 * 1000
    # the conv layers' weights once a step: 8 x 33.5 MB
    _f, b = costs.shortconv_cost(model, 0, 1, 0)
    assert b == 8 * (16_777_216 * 2 + 3 * 2048 * 4)
    # ~7 rows a step hit ~23 of the 64 experts a layer
    assert 22 < costs.expected_experts_hit(model, 7) < 24
    flops, nbytes = costs.step_cost(model, wire, 7, 1, 1)
    # the hit experts (3.5 GB) and the rest (0.9 GB); 1.6 GFLOP a row
    assert 4.0e9 < nbytes < 4.8e9
    assert 7 * 1.3e9 < flops < 7 * 1.9e9


def test_new_readers_read_the_counters_and_nothing_where_there_is_none():
    """The new readers return None (and do not raise) on a program that
    has no such counter or profile — what the parent commit gives them —
    and the cell's metrics are those its readers can read."""
    cfg = json.loads((run.ROOT / "benchmark/configs/"
                      "lfm2-24b-a2b-1t.json").read_text())
    mine = {m["name"] for m in BENCH["per_layer"] if CELL in m["workloads"]}
    assert set(NEW) <= mine
    assert not mine & {"moe_ms", "ssm_ms", "attn_ms", "swa_ms",
                       "full_attn_ms", "gated_moe_ms", "attn_roofline"}
    bare = {"window": run.Span({}, {}, 40.0), "traced": None, "trace": None,
            "config": cfg, "device": {"kind": "TPU v5 lite"}}
    for name in NEW:
        assert run.module("metrics", name).read(bare) is None, name
    after = {"tpu_inference.stream_pairs_routed": 800.0,
             "tpu_inference.stream_pairs_held": 800.0,
             "tpu_inference.stream_kv_pages_in_use": 2722.0}
    ctx = dict(bare, window=run.Span({}, after, 40.0))
    assert run.module("metrics", "held_pair_share_pct").read(ctx) == 100.0
    assert run.module("metrics", "kv_pool_used_pct").read(ctx) == (
        pytest.approx(100 * 2722 / 3328))
