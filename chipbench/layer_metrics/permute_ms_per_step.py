"""ring layer: time per step in which a collective-permute runs or is in
flight on one chip (mean over chips)."""

from chipbench import trace as t


def read(reading):
    trace = t.traced(reading)
    if trace is None:
        return None
    return 1e3 * t.permute_seconds(trace)[0] / trace["steps"]
