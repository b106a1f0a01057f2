"""ring layer: the part of the collective-permute time per step during
which no op that is not a collective runs on that chip (mean over chips)."""

from chipbench import trace as t


def read(reading):
    trace = t.traced(reading)
    if trace is None:
        return None
    return 1e3 * t.permute_seconds(trace)[1] / trace["steps"]
