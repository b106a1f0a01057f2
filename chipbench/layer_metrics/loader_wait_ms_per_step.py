"""data layer: mean over the untraced window's `train.step` records of the
seconds of their children `train.loader_wait`: the loop's thread in
`next(dl)`, waiting for the loader's workers. None where the program leaves
no such records."""

from chipbench import program_readings as p


def read(reading):
    return p.mean_attr_ms(reading, "loader_wait_s")
