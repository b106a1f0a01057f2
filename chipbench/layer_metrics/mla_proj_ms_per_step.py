"""model layer: device self time per step of the ops traced under
`obs.model.mla.q`, `.kv_down` and `.kv_up` (models/transformer._latent_qkv:
the latent attention's projections, the latent's norm, the rotary embedding
and the concatenation that builds k), every phase, mean over chips and traced
steps.  None where the program has no such scope."""

from chipbench import moe_readings as m


def read(reading):
    return m.scope_ms_per_step(reading, "obs.model.mla.")
