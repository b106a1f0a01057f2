"""What every step-driven runner shares: the benchmark's own spans and clock,
the warm-up rule, the measured window, the traced window, compile counting
and the memory readings.

A runner hands `measure_steps` a session: an object with `step(span)` (one
step of the program, its phases inside the benchmark's spans, blocked on its
outputs), `tokens_per_step`, `program_bytes` (the compiler's count for the
timed program on one chip), `checks` (filled during set-up) and
`finish(first, last)` (the checks made after the window, whose steps were
the session's `first`-th up to the `last`-th; returns how many failed), and
`detail` (what the run's record keeps of the set-up).
"""

import contextlib
import gc
import statistics
import tempfile
import time
from dataclasses import dataclass, field

import jax

from . import trace as tracelib

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

WARMUP_MIN_STEPS = 3
WARMUP_MAX_STEPS = 12
WARMUP_AGREE = 0.02  # the last three steps within 2 % of each other
TRACE_STEPS = 5


class CompileClock:
    """Seconds JAX spent in backend compiles (cache reads included), their
    number, and persistent-cache hits and misses, since the last `take()`
    (chip_smoke.py's, with the count)."""

    def __init__(self):
        self._reset()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _reset(self):
        self._s, self._n, self._hits, self._misses = 0.0, 0, 0, 0

    def _duration(self, event, seconds, **_):
        if event == _COMPILE_EVENT:
            self._s += seconds
            self._n += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self._hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self._misses += 1

    def take(self):
        out = {"compile_s": self._s, "compiles": self._n,
               "cache_hits": self._hits, "cache_misses": self._misses}
        self._reset()
        return out


class Spans:
    """The benchmark's spans: (name, start, end) on the host's clock, and a
    TraceAnnotation of the same name so that a profiler trace carries them
    on the device's time line."""

    def __init__(self):
        self.records = []

    @contextlib.contextmanager
    def __call__(self, name):
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))


class GcLog:
    """Every collection of the benchmark's own process while it is attached:
    (generation, seconds after it was attached, seconds it took)."""

    def __init__(self):
        self.records = []
        self._t0 = None

    def _callback(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.records.append((info["generation"],
                                 self._t0 - self._origin,
                                 time.perf_counter() - self._t0))

    def __enter__(self):
        self._origin = time.perf_counter()
        gc.callbacks.append(self._callback)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._callback)


def _one_step(session):
    """Run one step; its record on the host clock (seconds)."""
    spans = Spans()
    session.step(spans)
    by_name = {name: (t0, t1) for name, t0, t1 in spans.records}
    t_dispatch = by_name["bench.dispatch"][0]
    t_done = by_name["bench.block"][1]
    wait = by_name.get("bench.next_batch")
    return {"wait_s": wait[1] - wait[0] if wait else 0.0,
            "dispatch_s": by_name["bench.dispatch"][1] - t_dispatch,
            "step_s": t_done - t_dispatch, "done": t_done}


def warm_up(session):
    """Steps until three in a row agree (the first compiles or reads the
    cache, and the second has run 2-4 x a later one on the chip); their
    seconds, and whether they settled inside WARMUP_MAX_STEPS."""
    times = []
    while len(times) < WARMUP_MAX_STEPS:
        times.append(_one_step(session)["step_s"])
        last = times[-3:]
        if (len(times) >= WARMUP_MIN_STEPS
                and max(last) <= (1 + WARMUP_AGREE) * min(last)):
            return times, True
    return times, False


def window(session, seconds, t_start):
    """Steps from `t_start` (the end of set-up's last act) until the first
    completes `seconds` later; each with its interval to the step before
    (the first's to `t_start`)."""
    steps, prev = [], t_start
    while prev - t_start < seconds:
        rec = _one_step(session)
        done = rec.pop("done")
        rec["interval_s"], prev = done - prev, done
        steps.append(rec)
    return steps


def traced_window(session, trace_dir):
    """TRACE_STEPS steps under the profiler, inside one `bench.window` span;
    the reduced trace."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(tracelib.WINDOW_SPAN):
            for _ in range(TRACE_STEPS):
                session.step(Spans())
    finally:
        jax.profiler.stop_trace()
    raw = tracelib.read_xplane(trace_dir)
    return tracelib.reduce_trace(raw, TRACE_STEPS), raw


def program_bytes(compiled):
    """Device memory one chip needs to run `compiled`, by the compiler's
    count: arguments + outputs + temporaries - what the outputs alias."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def peak_bytes_in_use(devices):
    """The run-time counter on the fullest chip; None where the backend has
    none (the CPU)."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return None
    return max(s["peak_bytes_in_use"] for s in stats)


@dataclass
class Context:
    """What a run is given: the cell, the clock's origin and the knobs of
    the command line."""
    cell: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    out_dir: str
    t_process_start: float
    clock: CompileClock = field(default_factory=CompileClock)
    phases: list = field(default_factory=list)

    def mark(self, phase):
        """Note that set-up's `phase` ended now: (phase, seconds since the
        process started).  PERF.md's account of set-up is read from these."""
        self.phases.append(
            (phase, time.perf_counter() - self.t_process_start))


@dataclass
class Measurement:
    """What a runner returns: the end-to-end values it can take, what the
    per-layer readers read, and the verdict."""
    end_to_end: dict
    reading: dict
    checks: dict
    attempted: int
    failed: int
    memory_peak_bytes: int
    detail: dict


def measure_steps(session, ctx):
    """Warm up, measure for ctx.seconds, trace a few steps if asked, make
    the session's last checks; the session's set-up is done already."""
    warm_times, settled = warm_up(session)
    ctx.mark("warm_up")
    setup = ctx.clock.take()
    # The benchmark's own garbage: the heap that imports and tracing built
    # is moved out of the collector's sight, so that a full collection
    # inside the window would have nothing old to walk.  None came in the
    # window without this either (PERF.md, PR 24); the log stays as proof.
    gc.collect()
    gc.freeze()
    with GcLog() as gc_log:
        t_start = time.perf_counter()
        steps = window(session, ctx.seconds, t_start)
    in_window = ctx.clock.take()
    setup_s = t_start - ctx.t_process_start

    trace = raw = None
    if ctx.trace:
        with tempfile.TemporaryDirectory(dir=ctx.out_dir) as trace_dir:
            trace, raw = traced_window(session, trace_dir)
    first = len(warm_times)
    failed = session.finish(first, first + len(steps))
    checks = dict(session.checks)
    checks["no_compile_in_window"] = in_window["compiles"] == 0
    checks["warmup_settled"] = settled

    chips = len(ctx.devices)
    elapsed = sum(s["interval_s"] for s in steps)
    tokens_per_s_chip = session.tokens_per_step * len(steps) / elapsed / chips
    counter = peak_bytes_in_use(ctx.devices)
    end_to_end = {
        "step_ms": 1e3 * statistics.median(s["step_s"] for s in steps),
        "tokens_per_s_chip": tokens_per_s_chip,
        "hbm_gib": session.program_bytes / 2**30,
        "setup_s": setup_s,
    }
    reading = {
        "cell": ctx.cell, "steps": steps, "trace": trace,
        "setup": setup, "tokens_per_s_chip": tokens_per_s_chip,
        "device_kind": ctx.devices[0].device_kind,
    }
    detail = {
        "warmup_step_s": warm_times, "setup": setup, "in_window": in_window,
        "setup_phases": ctx.phases,
        "gc_in_window": gc_log.records, "window_s": elapsed,
        "hbm": {"compiler_program_bytes": session.program_bytes,
                "peak_bytes_in_use": counter},
        "steps": steps, "session": session.detail, "trace_raw": raw,
    }
    # the peak on the fullest chip: the run-time counter leaves a program's
    # temporaries out (PERF.md, PR 22 and 24), and the timed program's are
    # on the chip whenever it runs, so the larger of the two counts
    peak = max(counter or 0, session.program_bytes)
    return Measurement(end_to_end, reading, checks, len(steps), failed,
                       peak, detail)
