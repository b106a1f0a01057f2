"""job loop: steps of the untraced window whose completion-to-completion
interval exceeds 1.1 x the window's median."""

import statistics


def read(reading):
    intervals = [s["interval_s"] for s in reading["steps"]]
    return sum(i > 1.1 * statistics.median(intervals) for i in intervals)
