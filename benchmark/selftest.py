"""Rehearsal on the CPU at tiny sizes, before any chip time is spent, and
the kept tests of what ``correct`` can see. Not a tier-1 test.

    JAX_PLATFORMS=cpu python -m benchmark.selftest            # everything
    JAX_PLATFORMS=cpu python -m pytest benchmark/selftest.py  # the same

It skips the harness's look for a chip and drives the rest of a run
(``run.run_cell``) on CPU devices, with every cell shrunk by ``shrink``.
It checks that

- every cell's result line parses and is ``correct``, traced or not, and
  that no device metric is printed without a TPU;
- a cell spread over four one-device slices (four forced host devices;
  what a four-chip cell will be) is ``correct`` too, every device having
  scored its own tenants' rows and no others;
- the control — the reference computed in fp8, one precision step below
  the configuration's bf16, put in the program's place — comes out NOT
  correct;
- the timed path broken underneath comes out NOT correct, once for each
  fault these cells can have: half of the offered messages left out (a
  shed event), a step that returns its state unchanged, an answer altered
  where it is produced;
- the FLOP and byte counts agree with a hand count for hidden 64, window
  32, and do not see padding;
- ``benchmark.sweep``'s one rule reads a window that keeps up as
  ``sustained`` and one whose last stage is withheld as not, and says which
  group fell behind;
- the benchmark's files agree with one another
  (``benchmark/file_cases.py``'s cases).
"""

from __future__ import annotations

import asyncio
import copy
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import numpy as np  # noqa: E402

from benchmark import metrics, run, sweep  # noqa: E402
from benchmark import file_cases as files  # noqa: E402
from benchmark.costs import lstm_ad as lstm_ad_costs  # noqa: E402

BENCH = json.loads((run.ROOT / "BENCHMARK.json").read_text())
# what only a device trace can give: never printed without a TPU
DEVICE_METRICS = {m["name"] for m in BENCH["per_layer"]
                  if m["source"] == "device_trace"}


def cells() -> list:
    return [w["name"] for w in BENCH["workloads"]]


def shrink(cell: dict) -> dict:
    """The same cell at a size a CPU test can hold: 2 tenants a slice, 64
    devices a tenant, small buckets, a low rate."""
    cell = copy.deepcopy(cell)
    cfg, tr = cell["config"], cell["traffic"]
    per_slice = 2
    cfg["mesh"]["slots_per_shard"] = per_slice
    cfg["tenants"] = per_slice * cfg["mesh"]["tenant_axis"]
    cfg["max_streams"] = 256
    cfg["devices_per_tenant"] = 64
    cfg["buckets"] = [64, 256]
    if "rate_ev_s" in tr:
        # a device reports at most once in a window: a few dozen reports
        tr["rate_ev_s"] = 12 * cfg["tenants"] * tr["samples_per_message"]
    return cell


def go(name: str, seed: int = 7, seconds: float = 2.0, trace: bool = False,
       sabotage=None, edit=None, control: bool = False) -> dict:
    import jax

    # A program finding (PERF.md section 7, 2), not the harness's: on the
    # CPU backend ``jax.device_put`` ALIASES an aligned numpy buffer (no
    # copy), so the put of a staging set is "ready" at once and
    # ``_StagingSet.ensure_reusable``, which waits on the put, guards
    # nothing: with dispatch asynchronous a step now and then reads rows the
    # next flush has already packed over them (one tiny run in three:
    # ``score_err_max`` 1.5-5.8). On the TPU the put is a copy into HBM and
    # that wait is the guard. The rehearsal is of the harness, so it
    # dispatches synchronously; the race stays the program's to mend.
    jax.config.update("jax_cpu_enable_async_dispatch", False)
    cell = shrink(run.load_cell(name))
    if edit is not None:
        edit(cell)
    devices = jax.devices()[:cell["config"]["mesh"]["tenant_axis"]]
    result = asyncio.run(run.run_cell(
        cell, seed, seconds, trace, devices, sabotage=sabotage,
        control=control, drain_timeout_s=3.0))
    return json.loads(json.dumps(result))  # the line parses


# ------------------------------------------------------------ every cell
def test_every_cell_is_correct_and_prints_no_device_metric():
    for name in cells():
        for trace in (False, True):
            res = go(name, seed=2**31 + 11, trace=trace)
            assert res["correct"], (name, trace, res["checks"])
            assert res["failed"] == 0 and res["attempted"] > 0
            assert set(res) >= {"correct", "attempted", "failed", "metrics",
                                "device", "checks"}
            assert list(res)[-1] == "checks"
            assert res["device"]["platform"] == "cpu"
            assert not set(res["metrics"]) & DEVICE_METRICS, res["metrics"]
            assert "busy_s" not in res["device"]
            if trace:
                assert "rows_per_flush" in res["metrics"], res["metrics"]
            else:
                assert "setup_s" in res["metrics"]
                assert res["metrics"]["scored_p50_ms"]["value"] > 0


def test_four_slices_on_four_devices():
    def four_slices(cell: dict) -> None:
        cfg = cell["config"]
        cfg["mesh"]["tenant_axis"] = 4
        cfg["tenants"] = 4 * cfg["mesh"]["slots_per_shard"]
        cell["traffic"]["rate_ev_s"] = 12 * cfg["tenants"]

    res = go(cells()[0], edit=four_slices)
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4


def test_same_seed_same_inputs():
    cell = shrink(run.load_cell(cells()[0]))
    encoder = run.module("encoders", cell["traffic"]["encoder"])
    kind = run.module("generators", cell["traffic"]["kind"])
    a, b = (encoder.build(cell["traffic"], cell["config"], 5, 2.0, kind.plan)
            for _ in range(2))
    assert a.timed.payloads == b.timed.payloads
    assert [r.payloads for r in a.prefill] == [r.payloads for r in b.prefill]


# ----------------------------------------------------------- the control
def test_control_fp8_is_not_correct():
    res = go(cells()[0], control=True)
    assert not res["correct"], res["checks"]
    # everything but the scores still holds: the scores failed it
    assert all(v <= lim for k, (v, lim) in res["checks"].items()
               if not k.startswith("score_") and not isinstance(lim, str))


# ------------------------------------------------- the timed path, broken
def _state_unchanged(system, _run):
    """The step scores but hands back the window state it was given."""
    for scorer in system.inst.inference.scorers.values():
        inner = scorer._step_counts

        def frozen(params, state, *rest, _inner=inner):
            import jax

            keep = jax.tree_util.tree_map(lambda x: x + 0, state)
            out = _inner(params, state, *rest)
            return (keep,) + tuple(out[1:])

        scorer._step_counts = frozen


def _answer_altered(system, _run):
    """One score in a thousand is changed where the flush resolves it."""
    svc = system.inst.inference
    inner = svc._resolve_rows

    async def altered(seqs, rows, scores, *a, **kw):
        if scores is not None and len(scores):
            scores = np.array(scores, copy=True)
            scores[:: 1000] += 0.5
        return await inner(seqs, rows, scores, *a, **kw)

    svc._resolve_rows = altered


def test_broken_timed_path_is_not_correct():
    name = cells()[0]
    for sabotage in (_state_unchanged, _answer_altered):
        res = go(name, sabotage=sabotage)
        assert not res["correct"], (sabotage.__name__, res["checks"])
    # a shed message breaks the streams' windows for the reference too, so
    # the sabotage starts after the pre-fill: shed from the window only
    res = go(name, sabotage=_shed_in_window)
    assert not res["correct"], res["checks"]
    assert res["failed"] > 0


def _shed_in_window(system, _run):
    """The program's receivers shed every other payload once the window
    is open (``receiver_shed_total`` counts them, as overload would)."""
    start = _run.start_window

    def start_and_shed():
        for rt in system.inst.tenants.values():
            rx = rt.source.receiver
            submit, state = rx.submit, [0]

            async def lossy(payload, _submit=submit, _rx=rx, _st=state, **ctx):
                _st[0] += 1
                if _st[0] % 2:
                    return await _submit(payload, **ctx)
                _rx._on_shed(2, 1)

            rx.submit = lossy
        start()

    _run.start_window = start_and_shed


# ------------------------------------------------------ the sweep's rule
def _egress_withheld(system, _run):
    """The outbound connectors take no turn while the window is open:
    the last stage's group falls behind, and catches up once it has
    closed, so every event still comes out scored and delivered."""
    gate = asyncio.Event()
    gate.set()  # the pre-fill is delivered as ever
    for rt in system.inst.tenants.values():
        for connector in rt.outbound.connectors:
            inner = connector.process_batch

            async def held(batch, _inner=inner):
                await gate.wait()
                return await _inner(batch)

            connector.process_batch = held
    start, close = _run.start_window, _run.close_window

    def start_and_withhold():
        gate.clear()
        start()

    def close_and_release():
        t_close = close()
        gate.set()
        return t_close

    _run.start_window, _run.close_window = start_and_withhold, close_and_release


def test_sweep_rule_tells_a_window_that_falls_behind():
    # 5 s at the tiny rate: 60 one-event batches a tenant, so a stage that
    # is withheld passes the slack; a device is still due at most once.
    # The half-medians of 60 events on a shared CPU swing by more than the
    # rule allows, so of a real window only the lag clause is held here.
    def behind(res: dict) -> list:
        _ok, reasons = sweep.sustained(
            res["correct"], res["failed"], res["info"])
        return [r for r in reasons if " closes " in r]

    name = cells()[0]
    res = go(name, seconds=5.0)
    assert res["correct"] and not behind(res), res["info"]
    res = go(name, seconds=5.0, sabotage=_egress_withheld)
    assert res["correct"] and res["failed"] == 0, res["checks"]
    late = behind(res)
    assert late and all("outbound-connectors closes" in r for r in late), (
        late, res["info"])
    # each clause of the rule alone: sweep_windows.json, with the files' cases


# ---------------------------------------------------- the files, together
def test_benchmark_files_agree():
    for check, subject in files.CASES:
        check(subject)


# ------------------------------------------------------------ hand counts
def test_flops_and_bytes_by_hand_and_blind_to_padding():
    # hidden 64, window 32: 31 steps x 2 x (1 + 64) x 256 + head 2 x 64
    assert lstm_ad_costs.flops_per_row(64, 32) == 31 * 2 * 65 * 256 + 128
    assert lstm_ad_costs.flops_per_row(64, 32) == 1_031_808
    # params: wx 256+256, wh 64*256+256, head 64+1
    assert lstm_ad_costs.param_bytes(64) == 4 * (512 + 16640 + 65)
    # window read 128 B + scatter 4 + pos/count 8 + i32 id 4 + bf16 in/out 2+2
    assert lstm_ad_costs.bytes_per_row(32, 4, 2, 2) == 148
    ctx = {"config": {
        "model": {"family": "lstm_ad", "hidden": 64, "window": 32},
        "wire": {"id_bytes": 4, "value_bytes": 2, "score_bytes": 2}}}
    # one valid row costs the same in a 1024-lane and a 16384-lane bucket:
    # the functions never see the bucket
    one = metrics.step_cost(ctx, 1, 1, 1)
    assert one == (1_031_808, 148 + 4 * 17217)
    assert metrics.step_cost(ctx, 32 * 1024, 1, 32)[0] == 32 * 1024 * 1_031_808


def main() -> None:
    tests = [v for k, v in sorted(globals().items()) if k.startswith("test_")]
    for t in tests:
        print(f"selftest: {t.__name__} ...", file=sys.stderr, flush=True)
        t()
    print(f"selftest: {len(tests)} passed")


if __name__ == "__main__":
    main()
