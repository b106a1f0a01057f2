"""XLA profiler capture + deprecation shims for the moved timing helpers.

The reference has no profiling subsystem beyond its benchmark harness
(SURVEY.md §5); here the device side lives in this module and the host
side in the obs subsystem:

  * `trace(log_dir)` — XLA profiler capture (XProf/TensorBoard, incl. the
    collective-permute/compute overlap of the ring scan).  Device
    timelines are profiler state, not obs registry state, so it stays
    here.
  * `StepTimer` / `annotate` — MOVED to `burst_attn_tpu.obs.spans` (they
    are host-side timing, which is obs's job; StepTimer now also feeds the
    registry histogram `span.step_timer`).  Re-exported here so existing
    imports keep working; new code should import from `burst_attn_tpu.obs`.

    with trace("/tmp/profile"):
        step(state, batch)          # -> /tmp/profile/plugins/profile/...
"""

import contextlib

import jax

# deprecation shims — canonical home is obs.spans (see module docstring)
from ..obs.spans import StepTimer, annotate  # noqa: F401

__all__ = ["trace", "StepTimer", "annotate"]


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
