"""What the LFM2-MoE readers share: device time of the one-step
program's operations by layer, told by shapes only those layers carry —
``_paged_trace``'s own functions (``benchmark/rebind.py``) with this
family's signatures:

- the grouped products by their kernels' names (``gmm``), first, so that
  no expert product is counted below;
- attention at head_dim 64 by its pool in the paired layout, ``[pool,
  page, kv_heads, 2 x head_dim]`` — ``[3328,256,8,128]`` in the cell: the
  paged-attention kernel over each row's history and the pool's writes;
- the gated short convolutions by the width of their input projection,
  ``,3 x hidden]`` (``,6144]``: ``W_in`` [2048, 6144] and the rows'
  [B | C | x~]), or by their tails, ``,(taps - 1) x hidden]``
  (``,4096]``: the store [512, 4096] a layer and the rows' tails).
  Nemotron-H's convolution is 6,144 wide too, so these marks are this
  family's only: a configuration names its reader. Not counted: the
  prefetch of each layer's ``W_out`` (a ``copy-done`` of ``bf16[1,2048,
  2048]``, the shape of attention's ``W_q`` and ``W_o`` too) — about
  0.09 ms of a step in the cell (TPU v5e trace).

The counters, the step count and the cost functions are
``_stream_trace``'s. Every function returns None where there is nothing
to read."""

from __future__ import annotations

from benchmark.metrics import _paged_trace
from benchmark.metrics._stream_trace import (  # noqa: F401 - shared
    costs,
    counter,
    steps,
)
from benchmark.rebind import rebound


def _signatures(config: dict) -> list:
    """(layer, substrings) in order of precedence; a KeyError where the
    configuration has no such layers."""
    model = config["model"]
    h, taps = model["hidden_size"], model["conv_L_cache"]
    pool = (f"[{model['pool_pages']},{model['page_size']},"
            f"{model['num_key_value_heads']},{2 * model['head_dim']}]")
    return [
        ("gated_moe", ["%gmm", "ragged-dot"]),
        ("attn_hd64", [pool]),
        ("shortconv", [f",{3 * h}]", f",{(taps - 1) * h}]"]),
    ]


_layer_seconds, layer_seconds, layer_ms, roofline_pct = rebound(
    {"_signatures": _signatures, "_CACHE": {}}, _paged_trace._layer_seconds,
    _paged_trace.layer_seconds, _paged_trace.layer_ms,
    _paged_trace.roofline_pct)
