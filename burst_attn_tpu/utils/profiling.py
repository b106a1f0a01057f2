"""XLA profiler capture.

The reference has no profiling subsystem beyond its benchmark harness
(SURVEY.md §5); here the device side lives in this module and the host
side in the obs subsystem (`obs.span`, `obs.StepTimer`, `obs.annotate`):

  * `trace(log_dir)` — XLA profiler capture (XProf/TensorBoard, incl. the
    collective-permute/compute overlap of the ring scan).  Device
    timelines are profiler state, not obs registry state, so it lives
    here.

    with trace("/tmp/profile"):
        step(state, batch)          # -> /tmp/profile/plugins/profile/...
"""

import contextlib

import jax

__all__ = ["trace"]


@contextlib.contextmanager
def trace(log_dir: str, *, host_tracer_level: int = 2):
    """Capture an XLA profiler trace of the enclosed block.

    On TPU this records device timelines (kernel + collective activity) —
    the tool for confirming the ring's permute/compute overlap that the
    reference eyeballed with CUDA stream timing.  obs spans entered inside
    the block appear on the same timeline (spans wrap
    jax.profiler.TraceAnnotation).
    """
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = host_tracer_level
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
