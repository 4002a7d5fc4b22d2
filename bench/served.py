"""What the metric readers of the serving record share.

A prediction the program's engine ran carries its serving record,
``Prediction.serving``: the nodes and edges it was served with, its
bin's device shape and its queue wait in ms. A program without that
record gives these readers nothing to read, and they return ``None``.
"""
from __future__ import annotations

from window import completed_in_window


def records(run):
    """``(request index, serving record)`` of each request that settled
    with an answer inside the window, or ``None`` where there is none or
    a prediction carries no record."""
    rec = run.requests
    out = []
    for i in completed_in_window(run):
        served = getattr(rec.futures[i].result(0), "serving", None)
        if served is None:
            return None
        out.append((i, served))
    return out or None
