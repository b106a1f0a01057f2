"""burst_attn_tpu.obs — unified observability: metrics, spans, logging.

The north-star workloads (heavy serving traffic, long training runs, ring
kernels whose whole value is comm/compute overlap) can only be steered by
evidence; this package is where that evidence accumulates:

  * `registry` — per-process counters / gauges / fixed-bucket histograms
    (thread-safe, host-only), with JSONL and Prometheus-text exporters.
  * `spans` — structured span tracer (context manager + decorator,
    monotonic clocks, parent/child nesting, thread-safe) that doubles as a
    `jax.profiler` annotation so the same names appear in xprof; no-op
    under a jax trace.
  * `logs` — the obs logger (log records counted in the registry) and
    `safe_warn` for teardown paths.
  * CLI — `python -m burst_attn_tpu.obs [--json|--prom]` renders a report
    from a run's JSONL export (bench.py and the runner write
    `results/obs.jsonl`).

Metric catalog and naming conventions: docs/observability.md.

JIT safety contract (enforced by burstlint's `obs-jit-safe` rule): no
registry or span call may be reachable from inside a jit-traced function —
instrumentation lives at host boundaries (dispatch wrappers, engine loops,
bench harnesses).  Counters incremented at TRACE time (e.g. the burst
dispatch counters) advance once per compiled program and are documented as
such.
"""

from . import registry as _registry_mod
from .registry import (
    Counter, Gauge, Histogram, Registry, LATENCY_BUCKETS_S,
    default_registry,
)
from .spans import (
    Span, StepTimer, annotate, begin, completed_spans, current_span, end,
    instruction_texts, phase_of, reset_spans, scope_map, span, span_records,
    traced,
)
from .logs import dropped_messages, get_logger, safe_warn
# request tracing: per-request causal timelines (TraceContext propagation,
# tail-sampled trees, TTFT critical-path analyzer).  OFF by default; the
# submodule import keeps span-vs-trace naming explicit at call sites
# (`trace.record_span`), so only the submodule and its context type are
# re-exported here.
from . import trace
from .trace import TraceContext
# devstats is the deliberately IN-JIT half of obs: a purely functional
# telemetry pytree the ring accumulates in-graph (collect_stats=True) and
# publishes host-side afterwards.  burstlint's obs-jit-safe AST rule
# exempts it by name; the jaxpr rule `devstats-pure` proves its purity.
from . import devstats
from .devstats import DevStats


def counter(name: str, help: str = "") -> Counter:
    """Get-or-create a counter in the default registry."""
    return default_registry().counter(name, help)


def gauge(name: str, help: str = "") -> Gauge:
    return default_registry().gauge(name, help)


def histogram(name: str, help: str = "", buckets=None) -> Histogram:
    return default_registry().histogram(name, help, buckets=buckets)


def snapshot():
    """Every metric child in the default registry as JSON-able dicts."""
    return default_registry().snapshot()


def to_prometheus() -> str:
    return default_registry().to_prometheus()


def _process_index() -> int:
    """This process's multi-host index (0 single-process / pre-jax-init);
    lazy so registry-only users never pay a backend initialization."""
    try:
        import jax

        return int(jax.process_index())
    except Exception:  # noqa: BLE001 — uninitialized backend == process 0
        return 0


def export_jsonl(path: str) -> str:
    """Append a full snapshot (metrics + completed spans) to `path`,
    fsynced, tagged with this process's `process_index` so per-process
    files merge cleanly (`python -m burst_attn_tpu.obs --merge`).  This is
    the artifact `python -m burst_attn_tpu.obs` reads."""
    extra = (span_records() + trace.trace_records()
             + trace.exemplar_records())
    return default_registry().export_jsonl(path,
                                           extra_records=extra,
                                           process_index=_process_index())


def reset() -> None:
    """Clear the default registry, span and trace buffers (tests only)."""
    default_registry().reset()
    reset_spans()
    trace.reset_traces()


__all__ = [
    "Counter", "DevStats", "Gauge", "Histogram", "Registry", "Span",
    "StepTimer", "LATENCY_BUCKETS_S", "TraceContext", "annotate", "begin",
    "completed_spans", "counter", "current_span", "default_registry",
    "devstats", "dropped_messages", "end", "export_jsonl", "gauge",
    "get_logger", "histogram", "instruction_texts", "phase_of", "reset",
    "reset_spans", "safe_warn", "scope_map", "snapshot", "span",
    "span_records", "to_prometheus", "trace", "traced",
]
