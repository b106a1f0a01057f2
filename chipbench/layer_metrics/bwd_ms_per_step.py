"""model layer: device self time per step of the backward: ops whose op_name has
`transpose(...)` and not `rematted_computation`. Flash kernels included, mean
over chips and traced steps. The op_name comes from the step's executable
(`program_readings.device_ms`); None where the program has no scopes."""

from chipbench import program_readings as p


def read(reading):
    return p.scope_ms(reading, phase="bwd")
