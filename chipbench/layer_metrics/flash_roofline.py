"""kernels layer: the least time the chip could take for the attention calls
of one step over the flash kernels' measured time per step.  A call's least
time is the larger of its FLOPs over peak FLOP/s and its bytes over peak
bytes/s (FLOPs in the reference's convention, forward + backward = 3.5 x
forward, which counts the backward's recomputation), summed over the calls;
each layer's mask, head widths and heads from the configuration
(flops.attention_calls).  Per chip: on a ring each chip does 1/sp of it."""

from chipbench import flops, peaks, trace as t


def least_seconds(cell, device_kind):
    peak, least = peaks.peak(device_kind), 0.0
    for layers, flop, nbytes in flops.attention_calls(cell["config"],
                                                      cell["traffic"]):
        share, _ = flops.roofline_share(
            layers * flops.KERNEL_PASSES * flop, layers * nbytes, 1.0, peak)
        least += share / 100.0
    return least / cell["traffic"]["sp"]


def read(reading):
    trace = t.traced(reading)
    if trace is None:
        return None
    return (100.0 * least_seconds(reading["cell"], reading["device_kind"])
            * trace["steps"] / t.flash_seconds(trace))
