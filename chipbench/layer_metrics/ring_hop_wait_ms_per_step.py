"""ring layer: self time per step of the collective-permute `-start` and
`-done` ops on the core's op line under an `obs.ring.*` scope: the core
stalled on a hop, mean over chips and traced steps (the transfers in flight
beside the core are not counted).  None where the program has no
`obs.ring.bwd.*` scopes or the cell is not an op cell."""

from chipbench import ring_readings as r


def read(reading):
    return r.ms_per_step(reading, "hop")
