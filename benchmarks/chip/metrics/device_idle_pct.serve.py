"""1 - (union of the device's operation intervals / traced window), in
%, the mean over the chips of the cell."""
from devtrace import mean_busy_s


def read(cell, trace, measured):
    return 100.0 * (1.0 - mean_busy_s(trace) / trace.window_s)
