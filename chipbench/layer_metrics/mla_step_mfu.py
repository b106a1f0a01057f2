"""train step: the step's model FLOPs (flops_mla_moe.step_model_flops at the
window's mean `moe.slots_here`; no recomputation counted) over the untraced
window's median `step_ms`, over the chip's bf16 peak: the share of the whole
step.  None where the program leaves no `moe.slots_here`, or in a cell whose
configuration is not a latent-attention one."""

import statistics

from chipbench import flops_mla_moe, moe_readings as m, peaks


def read(reading):
    cell = reading["cell"]
    if "qk_head_dim" not in cell["config"]:
        return None
    slots = m.mean_slots_here(reading)
    if slots is None:
        return None
    seconds = statistics.median(s["step_s"] for s in reading["steps"])
    return flops_mla_moe.share_of_peak(
        flops_mla_moe.step_model_flops(cell["config"], cell["traffic"], slots),
        seconds, peaks.peak(reading["device_kind"]))
