"""Structured span tracing for host-side phases.

`span("serve.run")` / `@traced("eval")` wrap a block with:

  * a monotonic clock (`time.perf_counter_ns`) whose duration feeds the
    registry histogram `span.<name>` — so the CLI report shows aggregate
    count/total/mean per span name with zero extra bookkeeping;
  * parent/child nesting via a per-thread stack (thread-safe by
    construction: each thread nests independently, completed spans land in
    one shared ring buffer under a lock);
  * a `jax.profiler.TraceAnnotation`, so the same names appear on the
    xprof/TensorBoard timeline when a capture (`utils.profiling.trace`) is
    active — one naming convention across obs output and device profiles.

On-device safety: if the calling thread is inside a jax trace (the span
would otherwise record TRACE time and, worse, tempt callers into host
callbacks), `span()` degrades to a pure `jax.named_scope` — the name still
reaches the compiled program's metadata/xprof, but no clock is read and no
registry state is touched.  This is the no-op path the burstlint
`obs-jit-safe` rule assumes; instrumentation is still expected to live at
host boundaries, the degrade just makes an accidental traced call harmless.

`begin(name)` / `end(live)` are the two halves of `span()` for a span that
stays open across calls (the trainer's `train.step`, dispatch to dispatch):
spans entered meanwhile on that thread nest under it through the same stack.

`phase_of` / `scope_map` read the DEVICE side of the same convention: the
`obs.<layer>.<what>` named scopes and JAX's own transform components in an
HLO instruction's `op_name`, so that a profiler event can be charged to
forward / recomputed forward / backward / optimizer and to a module.

`StepTimer` and `annotate` live here; `trace()` — the XLA profiler capture —
stays in utils/profiling.py since it is about device timelines, not obs state.
"""

import collections
import functools
import itertools
import re
import threading
import time
import weakref
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax

from .registry import default_registry

# completed spans, newest last; bounded so a long-serving process cannot
# grow without limit (aggregates live in the registry histograms forever)
MAX_SPANS = 4096
_completed = collections.deque(maxlen=MAX_SPANS)
_completed_lock = threading.Lock()
_ids = itertools.count(1)
_tls = threading.local()


def _stack() -> list:
    """This thread's open spans, outermost first, as weak references: a
    span is open as long as someone holds its handle (a `with` block, the
    caller of `begin`).  One whose holder went away without `end()` is
    skipped from then on (`_top`), by the thread that owns the stack."""
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def _top(stack):
    """The innermost span of `stack` that is still held, else None."""
    while stack:
        live = stack[-1]()
        if live is not None:
            return live
        stack.pop()
    return None


def _tracing() -> bool:
    """True when the calling thread is inside a jax trace (jit/scan/vmap
    tracing, abstract eval) — spans must not read clocks or mutate the
    registry there."""
    return not jax.core.trace_ctx.is_top_level()


@dataclass
class Span:
    """One completed span (what the exporter/CLI sees)."""

    name: str
    span_id: int
    parent_id: Optional[int]
    depth: int
    thread: str
    start_s: float          # perf_counter-based, comparable within-process
    duration_s: float
    attrs: Dict[str, object] = field(default_factory=dict)

    def record(self) -> dict:
        return {"kind": "span", "name": self.name, "span_id": self.span_id,
                "parent_id": self.parent_id, "depth": self.depth,
                "thread": self.thread, "start_s": round(self.start_s, 6),
                "duration_s": round(self.duration_s, 9),
                "attrs": self.attrs}


class _LiveSpan:
    """Handle of an open span (`begin()` returns it, `span()` yields it);
    `set(k, v)` attaches attrs.  `child_s` holds the summed seconds of the
    spans that ended directly under it, by name: a span's self time, and a
    parent's account of its children, without a walk of the ring."""

    __slots__ = ("name", "span_id", "parent_id", "depth", "attrs", "t0",
                 "child_s", "stack", "parent", "__weakref__")

    def __init__(self, name, span_id, parent, stack):
        self.name = name
        self.span_id = span_id
        self.stack = stack  # the opening thread's; end() takes it off there
        # the parent OBJECT is held only while this span is open (end() adds
        # to its child_s and lets go): what outlives the span is the id
        self.parent = parent
        self.parent_id = None if parent is None else parent.span_id
        self.depth = 0 if parent is None else parent.depth + 1
        self.attrs: Dict[str, object] = {}
        self.t0 = 0.0
        self.child_s: Dict[str, float] = {}

    def set(self, key: str, value) -> None:
        self.attrs[key] = value


class _NoopSpan:
    __slots__ = ()
    name = None
    span_id = None
    parent_id = None
    depth = 0
    attrs: Dict[str, object] = {}

    def set(self, key: str, value) -> None:
        return None


_NOOP = _NoopSpan()


def _open(name, attrs, parent, stack):
    live = _LiveSpan(name, next(_ids), parent, stack)
    live.attrs.update(attrs)
    stack.append(weakref.ref(live))
    live.t0 = time.perf_counter()
    return live


def begin(name: str, **attrs):
    """Open a span and return its handle; `end(handle)`, on the same thread,
    completes it.  The two halves of `span()`, for a span that stays open
    across calls: spans entered meanwhile on this thread nest under it.

    Such a span outlives the block that opened it, so it belongs to none:
    it is a root (no parent, depth 0) whatever is open when it begins.  A
    loop that opens each one inside a child of the last (the trainer's
    `train.step`, begun inside the caller's span around the step) would
    otherwise chain every span of the run under the first.  It is open
    while its handle is held: one dropped without `end()` is gone from the
    stack too, and goes to no ring.  No profiler annotation (that is a
    context manager's to hold).  Under a jax trace it returns the no-op
    handle and touches nothing."""
    if _tracing():
        return _NOOP
    return _open(name, attrs, None, _stack())


def end(live, observe: bool = True) -> Optional["Span"]:
    """Complete an open span: the Span that went into the ring, None for
    the no-op handle.  `observe=False` leaves the registry histogram
    `span.<name>` alone, for a caller that keeps the duration in a
    histogram of its own."""
    if live is _NOOP:
        return None
    dur = time.perf_counter() - live.t0
    stack = live.stack
    # the last entry, but for a span opened across calls and ended under
    # later ones (or over entries whose holders went away)
    for i in range(len(stack) - 1, -1, -1):
        if stack[i]() is live:
            del stack[i]
            break
    parent, live.parent = live.parent, None
    if parent is not None:
        sums = parent.child_s
        sums[live.name] = sums.get(live.name, 0.0) + dur
    done = Span(name=live.name, span_id=live.span_id,
                parent_id=live.parent_id, depth=live.depth,
                thread=threading.current_thread().name,
                start_s=live.t0, duration_s=dur, attrs=live.attrs)
    with _completed_lock:
        _completed.append(done)
    if observe:
        default_registry().histogram("span." + live.name).observe(dur)
    return done


class span:
    """Context manager: time a host-side block as a named span.

        with span("serve.step", live=3) as sp:
            ...
            sp.set("admitted", 2)

    Under a jax trace this is a no-op that only applies `jax.named_scope`
    (see module docstring).  A class and not a generator: a span costs a
    few microseconds, and the trainer opens three a step."""

    __slots__ = ("_name", "_attrs", "_live", "_mark")

    def __init__(self, name: str, **attrs):
        self._name = name
        self._attrs = attrs

    def __enter__(self):
        if _tracing():
            self._live = _NOOP
            self._mark = jax.named_scope(self._name)
        else:
            stack = _stack()
            self._live = _open(self._name, self._attrs, _top(stack), stack)
            self._mark = jax.profiler.TraceAnnotation(self._name)
        self._mark.__enter__()
        return self._live

    def __exit__(self, *exc):
        try:
            self._mark.__exit__(*exc)
        finally:
            end(self._live)
        return False


def traced(name: Optional[str] = None):
    """Decorator form of `span`: `@traced("eval")` or bare `@traced()`
    (uses the function's qualname)."""

    def deco(fn):
        label = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with span(label):
                return fn(*args, **kwargs)

        return wrapper

    return deco


def current_span():
    """The innermost live span on this thread (None at top level)."""
    return _top(_stack())


def completed_spans(limit: Optional[int] = None) -> List[Span]:
    """Most recent completed spans, oldest first (bounded by MAX_SPANS)."""
    with _completed_lock:
        out = list(_completed)
    return out[-limit:] if limit else out


def span_records(limit: Optional[int] = None) -> List[dict]:
    return [s.record() for s in completed_spans(limit)]


def reset_spans() -> None:
    """Drop the completed-span buffer (tests)."""
    with _completed_lock:
        _completed.clear()


# -- the device side of the naming convention --------------------------------
#
# An HLO instruction's `op_name` is the name stack JAX traced it under:
#   jit(step)/jvp(obs.model.attn)/bsd,dnh->bnsh/dot_general
#   jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/obs.model.mlp/mul
#   jit(step)/transpose(jvp(jvp()))/checkpoint/obs.model.attn/bsd,dnh->bnsh/dot_general
#   jit(step)/obs.train.optimizer/add
# The transforms write the phase (`transpose(...)`: backward;
# `rematted_computation`: the forward run again under jax.checkpoint), the
# `obs.model.*` / `obs.train.*` scopes the module.

PHASES = ("fwd", "remat", "bwd", "optimizer", "other")
MODULES = ("embed", "attn", "mlp", "loss_head", "other")
_MODULE_SCOPE = re.compile(r"obs\.(?:model\.(embed|attn|mlp|loss_head)"
                           r"|train\.(loss))\b")


def phase_of(op_name: str) -> Tuple[str, str]:
    """(phase, module) of an HLO instruction from its `op_name`: phase in
    PHASES, module in MODULES.  `obs.train.loss` (log-softmax and nll over
    the logits) counts to module `loss_head`.  A name that carries neither
    a transform nor a scope (a parameter's name, a bare `reduce_sum`, no
    name at all) is ("other", "other")."""
    scope = _MODULE_SCOPE.search(op_name)
    module = "other" if scope is None else scope.group(1) or "loss_head"
    if "obs.train.optimizer" in op_name:
        return "optimizer", module
    if "rematted_computation" in op_name:
        return "remat", module
    if "transpose(" in op_name:
        return "bwd", module
    if "jvp(" in op_name or scope is not None:
        return "fwd", module
    return "other", module


_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?(%[^\s(]+)\s*\(.*\{\s*$")
_HLO_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?(%[^\s=]+)\s*=\s*(.*)$")
_HLO_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')
_HLO_CALLS = re.compile(r"\bcalls=(%[^\s,)}]+)")
_HLO_REF = re.compile(r"%[\w.\-]+")
_HLO_TRAILER = re.compile(r",\s*(?:metadata|backend_config|"
                          r"frontend_attributes)=")


def scope_map(hlo_text: str) -> Dict[str, str]:
    """{instruction name (as a profiler event's name begins: `%fusion.388`):
    op_name} over an executable's `as_text()`, every computation's
    instructions alike.

    The compiler's own instructions carry no metadata: an async copy or
    slice it put in to prefetch an operand, a fusion it built around them.
    Such an instruction takes, in this order, the op_name of the ROOT of
    the computation it calls, of the nearest instruction that uses its
    result (data movement is for its consumer), of the nearest that makes
    its operands; "" where none of these has one."""
    names: Dict[str, str] = {}
    roots: Dict[str, str] = {}        # computation -> its ROOT instruction
    calls: Dict[str, str] = {}        # instruction -> computation it calls
    operands: Dict[str, List[str]] = {}
    users: Dict[str, List[str]] = {}
    computation = None
    for line in hlo_text.splitlines():
        opened = _HLO_COMPUTATION.match(line)
        if opened:
            computation = opened.group(1)
            continue
        head = _HLO_INSTRUCTION.match(line)
        if head is None:
            continue
        is_root, name, rest = head.groups()
        if name in names:  # a name two computations share: keep the first
            continue
        found = _HLO_OP_NAME.search(rest)
        names[name] = found.group(1) if found else ""
        if is_root and computation is not None:
            roots[computation] = name
        called = _HLO_CALLS.search(rest)
        if called:
            calls[name] = called.group(1)
        trailer = _HLO_TRAILER.search(rest)
        body = rest[:trailer.start()] if trailer else rest
        operands[name] = [r for r in _HLO_REF.findall(body)
                          if r != name and r != calls.get(name)]
        for ref in operands[name]:
            users.setdefault(ref, []).append(name)

    def nearest(start, edges):
        seen, frontier = {start}, [start]
        while frontier:
            nxt = []
            for node in frontier:
                for other in edges.get(node, ()):
                    if other in seen:
                        continue
                    if names.get(other):
                        return names[other]
                    seen.add(other)
                    nxt.append(other)
            frontier = nxt
        return ""

    out = dict(names)
    for name, op_name in names.items():
        if op_name:
            continue
        root = roots.get(calls.get(name))
        out[name] = (names.get(root, "") or nearest(name, users)
                     or nearest(name, operands))
    return out


def instruction_texts(hlo_text: str) -> Dict[str, str]:
    """{instruction name: the instruction as `as_text()` prints it after the
    `=`, less its trailing metadata / backend_config}: what a profiler
    event's name, the same instruction printed with operand types, can be
    held against before the two are joined by name."""
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        head = _HLO_INSTRUCTION.match(line)
        if head is None or head.group(2) in out:
            continue
        rest = head.group(3)
        trailer = _HLO_TRAILER.search(rest)
        out[head.group(2)] = rest[:trailer.start()] if trailer else rest
    return out


def annotate(name: str):
    """Named region on the xprof timeline only (no clocks, no registry) —
    the raw `jax.profiler.TraceAnnotation`, kept for callers that want the
    profiler mark without obs state."""
    return jax.profiler.TraceAnnotation(name)


class StepTimer:
    """Wall-clock step timer that blocks on the step's OUTPUTS at exit so
    device work is included without serializing unrelated async work (a
    global live-array sweep would block on e.g. the next batch's
    host-to-device prefetch and destroy the IO/compute overlap):

        with timer as t:
            state, metrics = step(state, batch)
            t.watch(state)

    Each completed step also feeds the registry histogram `span.step_timer` so step times
    show up in obs exports alongside explicit spans.
    """

    def __init__(self, metric: str = "step_timer"):
        self.times: List[float] = []
        self._metric = "span." + metric
        self._t0: Optional[float] = None
        self._watched = None

    def watch(self, *outputs):
        """Register the step's outputs; exit blocks until they are ready."""
        self._watched = outputs
        return outputs[0] if len(outputs) == 1 else outputs

    def __enter__(self):
        self._watched = None
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            if self._watched is None:
                raise RuntimeError("StepTimer: call t.watch(outputs) inside the block")
            jax.block_until_ready(self._watched)
            dt = time.perf_counter() - self._t0
            self.times.append(dt)
            default_registry().histogram(self._metric).observe(dt)
        self._watched = None
        return False

    def summary(self, skip_first: int = 1) -> dict:
        """Stats over recorded steps.  The first `skip_first` steps are
        dropped as compile/warmup — unless that would drop EVERYTHING
        (e.g. a single-step run with the default skip_first=1), in which
        case all recorded steps are kept: every field is always finite,
        never NaN, and `steps` reports how many samples the stats cover."""
        ts = self.times[skip_first:] or self.times
        if not ts:
            return {"steps": 0, "mean_s": 0.0, "min_s": 0.0, "max_s": 0.0,
                    "p50_s": 0.0, "std_s": 0.0}
        mean = sum(ts) / len(ts)
        var = sum((t - mean) ** 2 for t in ts) / len(ts)  # 0.0 for 1 step
        return {
            "steps": len(ts),
            "mean_s": mean,
            "min_s": min(ts),
            "max_s": max(ts),
            "p50_s": sorted(ts)[len(ts) // 2],
            "std_s": var ** 0.5,
        }
