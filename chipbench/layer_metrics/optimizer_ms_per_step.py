"""train step: device self time per step of gradient clipping, AdamW and the
update: ops under `obs.train.optimizer`. Flash kernels included, mean over
chips and traced steps. The op_name comes from the step's executable
(`program_readings.device_ms`); None where the program has no scopes."""

from chipbench import program_readings as p


def read(reading):
    return p.scope_ms(reading, phase="optimizer")
