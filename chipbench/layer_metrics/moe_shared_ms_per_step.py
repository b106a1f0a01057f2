"""expert layer: device self time per step of the ops traced under
`obs.model.moe.shared` (parallel/moe.moe_held: the shared experts' SwiGLU),
every phase, mean over chips and traced steps.  None where the program has
no such scope."""

from chipbench import moe_readings as m


def read(reading):
    return m.scope_ms_per_step(reading, "obs.model.moe.shared")
