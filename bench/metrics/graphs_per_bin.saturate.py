"""Predictions completed per device bin across the window (``ServeStats``
completed over bins)."""
from window import graphs_per_bin


def read(run):
    return graphs_per_bin(run)
