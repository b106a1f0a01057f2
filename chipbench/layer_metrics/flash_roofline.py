"""kernels layer: the least time the chip could take for the attention calls
of one step (the larger of FLOPs over peak FLOP/s and bytes over peak
bytes/s; FLOPs in the reference's convention, forward + backward = 3.5 x
forward, which counts the backward's recomputation) over the flash kernels'
measured time per step.  Per chip: on a ring each chip does 1/sp of it."""

from chipbench import flops, peaks, trace as t


def attention_calls(cell):
    """(calls per step, batch, seq, heads, kv_heads, d_head) of the cell."""
    model, mix = cell["config"], cell["traffic"]
    return (model.get("num_hidden_layers", 1), mix["batch"], mix["seq"],
            model["num_attention_heads"], model["num_key_value_heads"],
            model["head_dim"])


def least_seconds(cell, device_kind):
    calls, b, s, n, n_kv, d = attention_calls(cell)
    share, _ = flops.roofline_share(
        calls * flops.attention_kernel_flops(b, s, n, d),
        calls * flops.attention_kernel_bytes(b, s, n, n_kv, d),
        1.0, peaks.peak(device_kind))
    return share / 100.0 / cell["traffic"]["sp"]


def read(reading):
    trace = t.traced(reading)
    if trace is None:
        return None
    return (100.0 * least_seconds(reading["cell"], reading["device_kind"])
            * trace["steps"] / t.flash_seconds(trace))
