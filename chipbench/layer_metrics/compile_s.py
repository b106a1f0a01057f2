"""train step layer: seconds JAX spent in backend compiles during set-up,
cache reads included (`jax.monitoring`)."""


def read(reading):
    return reading["setup"]["compile_s"]
