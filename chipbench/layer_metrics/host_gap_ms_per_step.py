"""job loop: mean completion-to-completion interval minus mean blocked step,
over the untraced window: what the host adds between two steps."""


def read(reading):
    steps = reading["steps"]
    return 1e3 * (sum(s["interval_s"] for s in steps)
                  - sum(s["step_s"] for s in steps)) / len(steps)
