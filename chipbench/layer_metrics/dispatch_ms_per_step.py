"""train step: mean over the untraced window's `train.step` records of the
seconds of their children `train.dispatch`: the call of the jitted step until
it returns. None where the program leaves no such records."""

from chipbench import program_readings as p


def read(reading):
    return p.mean_attr_ms(reading, "dispatch_s")
