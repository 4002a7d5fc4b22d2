"""Set-up: process start to the window's start (load, weights, compile or
load every program shape, untimed traffic)."""


def read(run):
    return run.setup_s
