"""What the metric readers share: per-request times of a run's window.

Every reader in ``bench/metrics/`` gets the run (``run.py`` builds it):
``requests`` (per-request timestamps on ``time.perf_counter``), the
window ``t0``..``t1`` and its ``seconds``, ``n_due`` (requests due
inside the window come first), ``deltas`` of the program's counters
across the window, ``trace`` (``trace_reduce.reduce_xplane`` of the
traced part, or ``None``), the configuration's ``model``, ``sizes``
(nodes and edges of each pool graph), ``chips``, the chip's ``peaks``
and ``counts`` (``bench/counts.py``).
"""
from __future__ import annotations

import math

import numpy as np


def latencies_ms(run) -> np.ndarray:
    """Due-to-settled time of every request due in the window; a failed
    or unsettled request counts as missing every limit (infinite)."""
    rec = run.requests
    out = np.full(run.n_due, math.inf)
    for i in range(run.n_due):
        if i not in rec.errors and not math.isnan(rec.done[i]):
            out[i] = (rec.done[i] - rec.due[i]) * 1e3
    return out


def percentile(values: np.ndarray, q: float):
    """``q``-th percentile, or ``None`` where it is not a number."""
    if not len(values):
        return None
    v = float(np.percentile(values, q))
    return v if math.isfinite(v) else None


def submit_ms(run):
    """Mean host time inside ``submit_json`` per request sent in the
    window."""
    rec = run.requests
    d = [rec.submitted[i] - rec.sent[i] for i in range(len(rec))
         if run.t0 <= rec.sent[i] < run.t1]
    return 1e3 * float(np.mean(d)) if d else None


def generator_lag_ms(run) -> np.ndarray:
    """How late each request due in the window left the generator."""
    rec = run.requests
    return np.array([(rec.sent[i] - rec.due[i]) * 1e3
                     for i in range(run.n_due)])


def completed_in_window(run):
    """Indices of the requests that settled with an answer inside the
    window."""
    rec = run.requests
    return [i for i in range(len(rec)) if i not in rec.errors
            and run.t0 <= rec.done[i] <= run.t1]


def graphs_per_bin(run):
    bins = run.deltas["bins"]
    return run.deltas["completed"] / bins if bins else None


def device_idle_pct(run):
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])

