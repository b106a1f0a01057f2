"""model layer: device self time per step of the loss head, every phase: final
norm and logits matmul (`obs.model.loss_head`) and the log-softmax / nll over
them (`obs.train.loss`). Flash kernels included, mean over chips and traced
steps. The op_name comes from the step's executable
(`program_readings.device_ms`); None where the program has no scopes."""

from chipbench import program_readings as p


def read(reading):
    return p.scope_ms(reading, module="loss_head")
