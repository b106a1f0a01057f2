"""model layer: device self time per step of the ops traced under
`obs.model.mhc` (models/transformer.py: mHC's maps, their Sinkhorn-Knopp
iterations, the pre-mix that makes a sublayer's input and the res / post mix
that makes the streams after it, the streams' copy from the embedding and
their sum before the final norm), every phase, mean over chips and traced
steps.  None where the program has no such scope."""

from chipbench import moe_readings as m


def read(reading):
    return m.scope_ms_per_step(reading, "obs.model.mhc")
