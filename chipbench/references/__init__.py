"""Plain references: float32 `jax.numpy`, no kernels, no cache, no batching
tricks.  A configuration's file names its reference by module name."""
