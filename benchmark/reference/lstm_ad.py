"""Plain reference for the LSTM-AD configurations: seeded weights, the
bf16 value wire, and the f32 anomaly score of a window — numpy only.

It imports nothing of ``sitewhere_tpu`` and takes nothing the program has
made. Equations as published in the program's model card
(``models/lstm_ad.py`` docstring): normalize the window by its own mean
and (std + 1e-6); run an LSTM (forget-gate bias +1, gates ordered i, f,
g, o) over samples 0..W-2; predict sample W-1 from the last hidden state
with a linear head; score = |normalized newest sample - prediction|.

The configuration states bf16 compute (its ``model.compute_dtype``), so
the program's scores differ from these f32 ones by bf16 rounding through
the 31-step recurrence; the configuration file holds the
measured limits (``limits``).
"""

from __future__ import annotations

import numpy as np

EPS = 1e-6


def make_weights(seed: int, tenant_index: int, hidden: int) -> dict:
    """One tenant's weights from (seed, tenant index): every tenant's
    differ, so a row scored with a neighbour's weights shows. Biases are
    non-zero so that a dropped bias shows too."""
    rng = np.random.default_rng([int(seed), 0xA11CE, int(tenant_index)])
    h = hidden

    def normal(shape, scale):
        return (rng.standard_normal(shape) * scale).astype(np.float32)

    return {
        "wx": {"w": normal((1, 4 * h), 1.0), "b": normal((4 * h,), 0.1)},
        "wh": {"w": normal((h, 4 * h), 1.0 / np.sqrt(h)),
               "b": normal((4 * h,), 0.1)},
        "head": {"w": normal((h, 1), 1.0 / np.sqrt(h)),
                 "b": normal((1,), 0.1)},
    }


def bf16_wire(values: np.ndarray) -> np.ndarray:
    """What a bf16 host->device wire makes of f32 values."""
    import ml_dtypes

    return values.astype(ml_dtypes.bfloat16).astype(np.float32)


def fp8_rounder():
    """Round-trip through float8 e4m3 — the control's precision, one step
    below the configuration's bf16 (3 bits of significand for 8)."""
    import ml_dtypes

    return lambda a: a.astype(ml_dtypes.float8_e4m3fn).astype(np.float32)


def _sigmoid(a: np.ndarray) -> np.ndarray:
    return 1.0 / (1.0 + np.exp(-a))


def score_windows(params: dict, windows: np.ndarray, block: int = 65536,
                  rounder=None) -> np.ndarray:
    """windows f32[B, W] (oldest -> newest, all W samples real) -> the
    anomaly score f32[B], in blocks of rows so that any B fits. Plain f32;
    ``rounder`` (the control) rounds the weights and every intermediate
    through a lower precision."""
    r = rounder or (lambda a: a)
    out = np.empty((windows.shape[0],), np.float32)
    wx, wh = r(params["wx"]["w"]), r(params["wh"]["w"])
    bias = r(params["wx"]["b"] + params["wh"]["b"])
    w_head, b_head = r(params["head"]["w"]), r(params["head"]["b"])
    for a in range(0, windows.shape[0], block):
        w = windows[a:a + block].astype(np.float32)
        mu = w.mean(-1, keepdims=True)
        x = r((w - mu) / (w.std(-1, keepdims=True) + EPS))
        h = c = np.zeros((w.shape[0], wh.shape[0]), np.float32)
        for t in range(w.shape[1] - 1):
            gates = r(x[:, t:t + 1] @ wx + h @ wh + bias)
            i, f, g, o = np.split(gates, 4, -1)
            c = r(_sigmoid(f + 1.0) * c + _sigmoid(i) * np.tanh(g))
            h = r(_sigmoid(o) * np.tanh(c))
        pred = r((h @ w_head)[:, 0] + b_head[0])
        out[a:a + block] = np.abs(x[:, -1] - pred)
    return out
