"""data layer: mean time a step waited in `next(batches)` (the benchmark's
`bench.next_batch` span), over the untraced window."""


def read(reading):
    steps = reading["steps"]
    return 1e3 * sum(s["wait_s"] for s in steps) / len(steps)
