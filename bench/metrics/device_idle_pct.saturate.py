"""Share of the traced window in which no operation ran on the device,
averaged over the cell's devices."""
from window import device_idle_pct


def read(run):
    return device_idle_pct(run)
