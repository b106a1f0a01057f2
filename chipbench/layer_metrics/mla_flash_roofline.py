"""kernels layer: the USEFUL FLOPs of one step's latent-attention calls
(layers x 3.5 x (2 qk_head_dim + 2 v_head_dim) x batch x seq^2 / 2 x heads,
the reference's forward + backward convention at the two widths the heads
have) over the chip's bf16 peak, over the flash kernels' measured time per
step: a kernel that pads a width pays for it here.  None in a cell whose
configuration has no two widths."""

from chipbench import flops_mla_moe, peaks, trace as t


def read(reading):
    cell, trace = reading["cell"], t.traced(reading)
    if trace is None or "qk_head_dim" not in cell["config"]:
        return None
    least = (flops_mla_moe.flash_kernel_flops(cell["config"], cell["traffic"])
             / peaks.peak(reading["device_kind"])["bf16_flops_s"])
    return 100.0 * least * trace["steps"] / t.flash_seconds(trace)
