"""ring layer: device self time per step of the ops traced under an
`obs.ring.*` scope (parallel/burst.py: the forward's and backward's rounds,
delta and the payload at entry, the return home, the casts of the
gradients) that are neither a `burst_flash_*` kernel nor a collective: the
ring's glue, mean over chips and traced steps.  None where the program has
no `obs.ring.bwd.*` scopes or the cell is not an op cell."""

from chipbench import ring_readings as r


def read(reading):
    return r.ms_per_step(reading, "glue")
