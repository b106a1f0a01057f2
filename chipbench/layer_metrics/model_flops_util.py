"""model layer: (6 x matmul parameters + 3 x causal attention forward) per
token x tokens/s/chip of the untraced window, over the chip's bf16 peak.
No recomputation counted."""

from chipbench import flops, peaks


def read(reading):
    cell = reading["cell"]
    return flops.model_flops_util(
        cell["config"], cell["traffic"]["seq"], reading["tokens_per_s_chip"],
        peaks.peak(reading["device_kind"]))
