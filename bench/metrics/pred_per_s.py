"""Predictions that settled with an answer inside the window, per second
of the window."""
from window import completed_in_window


def read(run):
    return len(completed_in_window(run)) / run.seconds
