"""model layer: the least time of mHC's unavoidable bytes in one step
(flops_motif.mhc_least_seconds: the streams read and written once a
sublayer pass, and the sublayer's input and output rows, forward,
recomputed forward and a backward of twice the forward, over 819 GB/s) over
the device time under `obs.model.mhc` (mhc_ms_per_step).  None where the
program has no such scope or the configuration no mHC."""

from chipbench import flops_motif, moe_readings as m, peaks


def read(reading):
    cell = reading["cell"]
    ms = m.scope_ms_per_step(reading, "obs.model.mhc")
    if ms is None or "mhc_expansion_rate" not in cell["config"]:
        return None
    least = flops_motif.mhc_least_seconds(
        cell["config"], cell["traffic"], peaks.peak(reading["device_kind"]))
    return 100.0 * least / (ms * 1e-3)
