"""LSTM-AD (hidden H, window W), per scored row:
  scan     (W-1) steps x 2 x (1 + H) x 4H   input + recurrent gate matmuls
  head     2 x H                            one prediction, at the newest step
Bytes per scored row: the row's gathered window (W x 4 read), its scatter
(4 written + 8 for pos/count read-modify-write), wire in (id + value) and
out (score). Per flush, once: the weights of every tenant slot that holds
a valid row.
"""

from __future__ import annotations


def param_bytes(hidden: int, bytes_per_param: int = 4) -> int:
    h = hidden
    n = (1 * 4 * h + 4 * h) + (h * 4 * h + 4 * h) + (h + 1)
    return n * bytes_per_param


def flops_per_row(hidden: int, window: int) -> float:
    h = hidden
    return (window - 1) * 2.0 * (1 + h) * 4 * h + 2.0 * h


def bytes_per_row(window: int, id_bytes: int, value_bytes: int,
                  score_bytes: int) -> float:
    return window * 4.0 + 4.0 + 8.0 + id_bytes + value_bytes + score_bytes


def step_cost(model: dict, wire: dict, valid_rows: float, flushes: float,
              slots_used_per_flush: float) -> tuple[float, float]:
    flops = valid_rows * flops_per_row(model["hidden"], model["window"])
    nbytes = valid_rows * bytes_per_row(
        model["window"], wire["id_bytes"], wire["value_bytes"],
        wire["score_bytes"])
    nbytes += flushes * slots_used_per_flush * param_bytes(model["hidden"])
    return flops, nbytes
