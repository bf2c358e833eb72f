"""Share of the traced window in which no operation ran on the device
(averaged over the chips used)."""
from devtrace import device_busy


def read(run):
    busy_s, window_s = device_busy(run.trace)
    if window_s <= 0:
        return None
    return (1.0 - busy_s / window_s) * 100.0
