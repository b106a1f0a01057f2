"""model layer: device time per step of every op that is neither a
`burst_flash_*` kernel nor a collective (self time, mean over chips)."""

from chipbench import trace as t


def read(reading):
    trace = t.traced(reading)
    if trace is None:
        return None
    other = lambda name: not (t.is_flash(name) or t.is_collective(name))
    return 1e3 * t.mean_over_devices(
        trace, lambda segs: t.seconds_where(segs, other)) / trace["steps"]
