"""kernels layer: summed device time per step of the events named
`burst_flash_*` (mean over chips)."""

from chipbench import trace as t


def read(reading):
    trace = t.traced(reading)
    if trace is None:
        return None
    return 1e3 * t.flash_seconds(trace) / trace["steps"]
