"""model layer: device self time per step of the ops traced under
`obs.model.gdla.diff` (models/transformer._gdla_out: lambda's product and
sigmoid, each signal head minus lambda times its group's noise head, the
elementwise gate's product, sigmoid and multiply), every phase, mean over
chips and traced steps.  None where the program has no such scope."""

from chipbench import moe_readings as m


def read(reading):
    return m.scope_ms_per_step(reading, "obs.model.gdla.diff")
