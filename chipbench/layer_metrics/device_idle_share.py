"""device: 1 - (union of the intervals in which any op runs on the chip) /
traced window, in percent; for four chips the mean over chips."""

from chipbench import trace as t


def read(reading):
    trace = t.traced(reading)
    if trace is None:
        return None
    return 100.0 * (1.0 - t.busy_seconds(trace) / t.window_seconds(trace))
