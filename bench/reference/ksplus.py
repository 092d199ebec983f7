"""Plain reference of the KS+ predictor (arXiv:2408.12290, section II).

Fit, per task family, from the training executions:

1. Algorithm 1 segments each trace into at most ``k`` segments: a new
   segment at every strict running maximum, then greedy merges of the
   adjacent pair whose merge wastes least (``(P[i+1] - P[i]) * S[i]``,
   first minimum on ties) until ``k`` remain.  An execution with fewer
   than ``k`` segments fills the missing slots with a start at the end of
   the run and its overall peak.
2. Least squares of each segment's start (s), each segment's peak (GB)
   and the runtime (s) on the input size.

Predict: starts scaled by ``1 - start_offset``, peaks by
``1 + peak_offset``, the first start pinned to 0, both made
non-decreasing; the runtime estimate is the runtime regression times
``1 + runtime_margin``.

``dtype`` sets the precision of the arithmetic; traces enter the fit at
the precision the configuration states for fitting (``fit_dtype``).
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

import numpy as np


def segments(M: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
    S = [1]
    P = [M[0]]
    for m in M[1:]:
        if m > P[-1]:
            P.append(m)
            S.append(1)
        else:
            S[-1] += 1
    while len(P) > k:
        e = [(P[i + 1] - P[i]) * S[i] for i in range(len(P) - 1)]
        i = int(np.argmin(e))
        S[i + 1] += S[i]
        del S[i], P[i]
    return np.asarray(S, np.int64), np.asarray(P, M.dtype)


def _lstsq(x: np.ndarray, y: np.ndarray):
    xm = x.mean()
    ym = y.mean()
    var = ((x - xm) ** 2).mean()
    cov = ((x - xm) * (y - ym)).mean()
    slope = cov / var if var > 1e-18 else 0.0 * var
    return slope, ym - slope * xm


def fit(mems: Sequence[np.ndarray], dts: Sequence[float],
        inputs: Sequence[float], pred: dict, *, dtype=np.float64,
        fit_dtype=np.float32) -> Dict[str, np.ndarray]:
    k = int(pred["k"])
    n = len(mems)
    starts = np.zeros((n, k), dtype)
    peaks = np.zeros((n, k), dtype)
    runtimes = np.zeros(n, dtype)
    for e, (m, dt) in enumerate(zip(mems, dts)):
        S, P = segments(np.asarray(m).astype(fit_dtype).astype(dtype), k)
        st = np.cumsum(S) - S
        starts[e, :] = len(m)
        peaks[e, :] = P.max()
        starts[e, :len(S)] = st
        peaks[e, :len(P)] = P
        starts[e] *= dtype(dt)
        runtimes[e] = dtype(len(m) * dt)
    x = np.asarray(inputs, dtype)
    cols = np.concatenate([starts, peaks, runtimes[:, None]], axis=1)
    sol = np.asarray([_lstsq(x, cols[:, j]) for j in range(cols.shape[1])],
                     dtype)
    return {"k": k, "slope": sol[:, 0], "intercept": sol[:, 1],
            "dtype": dtype, **{key: float(pred[key]) for key in
                               ("peak_offset", "start_offset",
                                "runtime_margin")}}


def predict(model: dict, inputs: Sequence[float]):
    """``(starts, peaks, est_runtime)`` of shapes (B, k), (B, k), (B,)."""
    dtype = model["dtype"]
    k = model["k"]
    x = np.asarray(inputs, dtype)[:, None]
    y = model["slope"][None, :] * x + model["intercept"][None, :]
    starts = y[:, :k] * dtype(1 - model["start_offset"])
    peaks = y[:, k:2 * k] * dtype(1 + model["peak_offset"])
    starts = np.maximum.accumulate(np.maximum(starts, dtype(0)), axis=1)
    starts[:, 0] = 0
    peaks = np.maximum.accumulate(np.maximum(peaks, dtype(1e-6)), axis=1)
    est = np.maximum(y[:, 2 * k], dtype(0)) * dtype(1 + model["runtime_margin"])
    return starts, peaks, est


def plan_gap(got, want) -> float:
    """Worst relative gap of plans ``got`` against the reference's
    ``want``: ``(starts, peaks[, est_runtime])`` of shapes (B, k), (B, k),
    (B,).  Starts and runtime estimates are measured against the plan's
    time scale (the larger of the reference's estimate and its last
    start), peaks against the reference's peaks."""
    ws, wp, we = want[:3]
    scale = np.maximum(np.maximum(we, ws[:, -1]), 1e-9)
    gap = max(float(np.max(np.abs(got[0] - ws) / scale[:, None])),
              float(np.max(np.abs(got[1] - wp) / np.maximum(wp, 1e-9))))
    if len(got) > 2:
        gap = max(gap, float(np.max(np.abs(got[2] - we) / scale)))
    return gap
