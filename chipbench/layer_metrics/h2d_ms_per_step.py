"""data layer: mean over the untraced window's `train.step` records of the
seconds of their children `train.h2d`: `batch_from_host`, host layout and
`device_put`. None where the program leaves no such records."""

from chipbench import program_readings as p


def read(reading):
    return p.mean_attr_ms(reading, "h2d_s")
