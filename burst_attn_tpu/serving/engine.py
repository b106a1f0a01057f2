"""RaggedServeEngine: continuous batching over the one-launch ragged
kernel.

models/serve.py's engine prefills a whole prompt at admission (one
program per prompt page count) and then decodes one token per tick —
a long prompt stalls every in-flight stream for its full prefill.  This
engine schedules PREFILL AS CHUNKS through the same launch that decodes:

  * submit() queues; admission reserves a request's FULL page lifetime
    up front (prompt + budget + speculative slack — mid-generation OOM
    stays impossible by construction) but moves NO tokens.
  * Every tick builds one ragged batch: each mid-prefill slot consumes
    its next `chunk` prompt tokens, each decoding slot its single next
    token, idle slots ride along predicated off.  One
    `ragged_model_step` launch serves them all; a slot whose chunk
    completes its prompt samples its first token THAT tick (TTFT).
  * Speculative decoding is a SCHEDULER POLICY, not a separate engine:
    when a draft model is attached and no slot is mid-prefill, the tick
    becomes a speculative round (k draft proposals per slot, one ragged
    all-logits verify, per-slot prefix acceptance, vectorized rollback).
    Mixed ticks fall back to plain chunking, with the draft cache kept
    in sync through its own ragged catch-up step.
  * Load shedding (`max_queue`): POOL pressure sheds before QUEUE
    pressure — a request that would wait behind others for pages that
    are not free is rejected `pool-exhausted` even when the queue still
    has room; `queue-full` only fires when pages were never the
    bottleneck.  An optional `admission` policy
    (burst_attn_tpu.admission.AdmissionPolicy) sheds EARLY with
    hysteresis from the live queue-depth / pool-occupancy values (typed
    reasons `admission-pool` / `admission-queue`), and every rejection
    is a typed InvalidRequest / LoadShed (`.reason`); `try_submit()` is
    the non-raising router surface.

Kernel routing: `ragged_supported` probes each launch width once; a
declined shape runs the dense-gather fallback and counts a labeled
`burst.fused_fallback{pass="serve"}` — never a raise (ISSUE 8 satellite).

Metrics: every serve.* instrument models/serve.py exports is preserved
(same registry names), plus the `serve.ragged_batch_*` family describing
what each one-launch batch carried (docs/observability.md).
"""

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..obs import trace as tracing
from ..admission import (
    AdmissionPolicy, InvalidRequest, LoadShed, RejectReason, SubmitRejected,
    SubmitResult,
)

logger = obs.get_logger(__name__)

# same instrument names as models/serve.py — the registry get-or-creates,
# so both engines share one catalog and dashboards see one serve.* family
_M_SUBMITTED = obs.counter("serve.requests_submitted")
_M_REJECTED = obs.counter("serve.requests_rejected",
                          "submissions refused up front, by reason")
_M_ADMITTED = obs.counter("serve.requests_admitted")
_M_RETIRED = obs.counter("serve.requests_retired",
                         "finished requests, by cause (eos | budget)")
_M_STEPS = obs.counter("serve.engine_steps")
_M_TOKENS = obs.counter("serve.tokens_generated")
_M_QUEUE = obs.gauge("serve.queue_depth")
_M_LIVE = obs.gauge("serve.live_slots")
_M_POOL = obs.gauge("serve.page_pool_occupancy",
                    "fraction of usable pool pages currently held; also "
                    "published per pool storage dtype under a {dtype} label")
_M_SPEC_RATE = obs.gauge("serve.spec_acceptance_rate")
_M_TTFT = obs.histogram("serve.ttft_s")
_M_TOK_LAT = obs.histogram("serve.token_latency_s")
# host time the tick spent OUTSIDE the device launch+sample window, as a
# fraction of launch-tick wall time (cumulative) — the gap ROADMAP item 3's
# async pipelining is gated against (bench_loadgen emits it as the
# headline_loadgen_hostgap headline).  Always on: host clock reads never
# touch the jaxpr, so the tick's trace stays bit-identical.  On a
# pipelined engine the device window is instead estimated from launch
# dispatch to deferred-readback completion (host work overlapped with a
# busy device is NOT a gap), so the same gauge compares both engines.
_M_HOST_GAP = obs.gauge("serve.host_gap_fraction",
                        "host gap seconds / launch-tick wall seconds")
# pipelined-engine family: speculative schedule divergences and fusion
_M_RECONCILE = obs.counter(
    "serve.pipeline_reconciles",
    "speculatively scheduled pipelined work discarded, by divergence cause")
_M_MULTI = obs.counter(
    "serve.multi_step_launches",
    "fused multi-step decode launches, by static scan depth {k}")
# ragged-batch family: what each one-launch batch carried
_M_RB_LAUNCH = obs.counter("serve.ragged_batch_launches",
                           "one-kernel ragged launches, by batch kind")
_M_RB_PREFILL = obs.counter("serve.ragged_batch_prefill_tokens",
                            "prompt tokens absorbed through ragged launches")
_M_RB_DECODE = obs.counter("serve.ragged_batch_decode_tokens",
                           "decode tokens advanced through ragged launches")
_M_RB_FILL = obs.gauge("serve.ragged_batch_fill",
                       "real-token fraction of the last launch's [slots, "
                       "chunk] token grid")
_M_FALLBACK = obs.counter(
    "burst.fused_fallback",
    "ragged-kernel launches declined to the dense-gather path, by reason")
# prefix-cache family: admission-time sharing and the write barrier
_M_PREFIX_HITS = obs.counter("serve.prefix_hits",
                             "admissions that pinned >= 1 cached prefix page")
_M_PREFIX_MISSES = obs.counter(
    "serve.prefix_misses", "cache-enabled admissions finding no cached prefix")
_M_PAGES_SHARED = obs.counter(
    "serve.pages_shared", "prefix pages pinned (refcount bumped) at admission")
_M_COW = obs.counter("serve.cow_copies",
                     "shared pages privatized by the copy-on-write barrier")
_M_SKIPPED = obs.counter(
    "serve.prefill_tokens_skipped",
    "prompt tokens whose prefill was skipped via cached pages")
_M_POOL_PHYS = obs.gauge(
    "serve.page_pool_occupancy_physical",
    "fraction of usable pool pages physically held (shared pages count "
    "ONCE — identical to serve.page_pool_occupancy)")
_M_POOL_LOG = obs.gauge(
    "serve.page_pool_occupancy_logical",
    "sum of page refcounts over usable pages — may exceed 1.0; the gap to "
    "the physical gauge is the pages saved by prefix sharing")
_M_POOL_BYTES = obs.gauge(
    "serve.page_pool_bytes",
    "HBM bytes physically held by in-use KV pages (k + v + scale banks "
    "across all layers), by pool storage {dtype} — a quantized pool holds "
    "~4x the sequences in the same byte budget")

from ..models.decode import sample_logits
from ..models.paged_decode import (
    PagePool, PagedState, PrefixCache, init_paged_state, paged_decode_step,
    paged_prefill, provision_capacity, retire_slot,
)
from ..models.transformer import ModelConfig
from ..ops.ragged_paged import ragged_supported
from .model import (
    assign_pages, cow_pages, free_slot, free_slots, multi_step_decode,
    pipelined_tick,
    ragged_model_step,
)

# reason-string prefix -> bounded counter label (probe reasons embed
# shapes, which would explode label cardinality verbatim)
_FALLBACK_LABELS = (
    ("empty q chunk", "empty-chunk"),
    ("GQA group mismatch", "gqa-group"),
    ("page size", "page-size"),
    ("q-block rows", "block-rows"),
    ("VMEM plan", "vmem-budget"),
    ("head dim", "head-dim"),
)


def _fallback_label(reason: str) -> str:
    for prefix, label in _FALLBACK_LABELS:
        if reason.startswith(prefix):
            return label
    return "other"


@dataclass
class _Request:
    rid: int
    prompt: np.ndarray          # [T] int32
    max_new_tokens: int
    tokens: List[int] = field(default_factory=list)
    t_submit: float = 0.0
    n_prefilled: int = 0        # prompt tokens absorbed so far


def _readback_choices(choices) -> np.ndarray:
    """THE pipeline sync point: block on an in-flight launch's sampled
    choices.  Module-level so the recovery fuzzer can kill the process
    exactly here — after the launch was dispatched, before any of its
    tokens were read back, journaled, or delivered."""
    return np.asarray(choices)


@dataclass
class _Pending:
    """An in-flight pipelined launch whose sampled choices are still on
    device: everything the deferred readback needs to replay the
    synchronous engine's post-sample host accounting one step late."""
    choices: object              # [k, slots] int32 device array
    k: int                       # fused decode depth (1 = plain tick)
    q_lens: np.ndarray           # [slots] per-step token counts
    advance: np.ndarray          # [slots] device length advance (q_lens * k)
    prefill_advance: np.ndarray  # [slots] prompt tokens consumed (k == 1)
    tok_delta: np.ndarray        # [slots] tokens appended at readback
                                 # assuming no EOS fires inside the launch
    rng_before: object           # engine rng before this launch's split(s)
    table_rows: Dict[int, np.ndarray]  # slot -> pre-captured table row for
                                 # prefix registration at readback
    n_prefill_toks: int
    kind: str
    t_dispatch: float
    feed_next: object = None     # [slots] last choice row, sliced at
                                 # dispatch time (enqueued behind the
                                 # launch) so a speculative follow-up
                                 # pays no jnp dispatch in its critical
                                 # pre-dispatch window


class RaggedServeEngine:
    """Host-side continuous-batching loop over ragged_model_step.  Not
    thread-safe; drive it from one thread."""

    def __init__(self, params, cfg: ModelConfig, *, slots: int, n_pages: int,
                 page: int = 128, max_pages_per_seq: int = 64,
                 quantize: bool = False, eos_id: Optional[int] = None,
                 temperature: float = 0.0, top_k=None, top_p=None, rng=None,
                 chunk: Optional[int] = None, max_queue: Optional[int] = None,
                 admission: Optional[AdmissionPolicy] = None,
                 draft_params=None, draft_cfg: Optional[ModelConfig] = None,
                 spec_k: int = 4, use_ragged: Optional[bool] = None,
                 prefix_cache: bool = False, group_attn: bool = True,
                 journal=None, pipeline: bool = False, multi_step: int = 1):
        self.params = params
        self.cfg = cfg
        self.eos_id = eos_id
        self.page = page
        self.chunk = page if chunk is None else chunk
        if self.chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {self.chunk}")
        self.max_queue = max_queue
        self.admission = admission
        self.temperature = temperature
        self.top_k, self.top_p = top_k, top_p
        # optional write-ahead TokenJournal (serving/checkpoint.py): token
        # appends / done / reset records per tick, fsynced once per step()
        # BEFORE results are returned — crash recovery resumes from here
        self.journal = journal
        # pipeline: defer each tick's sampling readback one step so host
        # scheduling for tick N+1 overlaps device execution of tick N;
        # multi_step additionally fuses up to K pure-decode ticks into one
        # jitted lax.scan launch when no admission/retire event can land
        # inside the window.  Token-exact vs the synchronous engine by
        # construction (docs/serving.md "Pipelined engine"); with a draft
        # model attached the speculative-decoding scheduler policy stays
        # on the synchronous path (its rounds are already fused).
        self.pipeline = bool(pipeline)
        self.multi_step = int(multi_step)
        if self.multi_step < 1:
            raise ValueError(f"multi_step must be >= 1, got {multi_step}")
        if self.multi_step > 1 and not self.pipeline:
            raise ValueError("multi_step > 1 requires pipeline=True")
        self._pending: Optional[_Pending] = None
        self._flushed_done: List[Tuple[int, List[int]]] = []
        self._rng = rng if rng is not None else jax.random.PRNGKey(0)
        # quantize: False keeps the pool at cfg.dtype; True/"int8" or "fp8"
        # makes that 1 B/elem dtype the pool's NATIVE storage (per-page
        # scale banks ride beside the pages; resolve_pool_dtype validates)
        self.state, self.pool = init_paged_state(
            cfg, slots=slots, n_pages=n_pages, page=page,
            max_pages_per_seq=max_pages_per_seq, quantize=quantize)
        self.quantize = quantize
        # obs label + per-page HBM cost for serve.page_pool_bytes: the
        # pool's storage dtype tag ("int8"/"fp8", else the full-precision
        # jnp dtype name) and bytes per held page across k/v/scale banks
        self._pool_dtype = (self.pool.dtype or
                            jnp.dtype(self.state.k_pages[0].dtype).name)
        banks = list(self.state.k_pages) + list(self.state.v_pages)
        if self.state.k_scales is not None:
            banks += list(self.state.k_scales) + list(self.state.v_scales)
        self._page_nbytes = sum(a.nbytes // a.shape[0] for a in banks)
        # None: probe per launch width; True/False force a path
        self.use_ragged = use_ragged
        self._attn_cache: Dict[int, str] = {}
        # content-hashed prefix cache (models/paged_decode.PrefixCache):
        # admission pins cached pages by refcount and skips their prefill;
        # every write to a shared page goes through the CoW barrier
        self.cache = PrefixCache(self.pool) if prefix_cache else None
        # group_attn: score each prefix group's shared pages once per tick
        # (attn="grouped") when >= 2 live members share pinned pages;
        # False keeps the plain per-slot launch (still prefill-skipping)
        self.group_attn = group_attn
        # slot -> the tuple of shared page ids pinned at admission; the
        # grouping key for attn="grouped".  Trimmed when the CoW barrier
        # privatizes a boundary page, dropped at retire/drain.
        self._shared: Dict[int, Tuple[int, ...]] = {}
        self.draft = None
        self.spec_k = 0
        if draft_params is not None:
            if draft_cfg is None:
                raise ValueError("draft_params needs draft_cfg")
            if temperature != 0.0:
                raise ValueError("speculative serving requires "
                                 "temperature == 0")
            if draft_cfg.vocab != cfg.vocab:
                raise ValueError("draft and target must share a vocabulary")
            if spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
            self.draft = (draft_params, draft_cfg)
            self.spec_k = spec_k
            self.dstate, self.dpool = init_paged_state(
                draft_cfg, slots=slots, n_pages=n_pages, page=page,
                max_pages_per_seq=max_pages_per_seq, quantize=quantize)
        self.slots: List[Optional[_Request]] = [None] * slots
        self._next_tok = np.zeros((slots,), np.int32)
        self._queue: List[_Request] = []
        self._next_id = 0
        self._finished: Dict[int, List[int]] = {}
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_rounds = 0

    # -- client surface ----------------------------------------------------

    def _reject(self, exc_cls, reason: RejectReason, message: str):
        _M_REJECTED.inc(reason=reason.value)
        raise exc_cls(reason, message)

    def _occupancy(self) -> float:
        """Live PHYSICAL pool occupancy, the same value
        `serve.page_pool_occupancy` exports (fraction of usable pages
        held; a shared page counts once; page 0 is the sink)."""
        usable = self.pool.n_pages - 1
        return (usable - self.pool.available) / usable if usable else 0.0

    def _set_pool_gauges(self) -> None:
        """Physical occupancy (each shared page ONCE — what actually
        bounds admission) on both the legacy gauge and its explicit
        `_physical` alias, plus the logical view (sum of refcounts; the
        gap is pages saved by sharing)."""
        occ = self._occupancy()
        _M_POOL.set(occ)
        _M_POOL.set(occ, dtype=self._pool_dtype)
        _M_POOL_PHYS.set(occ)
        usable = self.pool.n_pages - 1
        _M_POOL_LOG.set(self.pool.logical_refs / usable if usable else 0.0)
        held = usable - self.pool.available if usable else 0
        _M_POOL_BYTES.set(held * self._page_nbytes, dtype=self._pool_dtype)

    def submit(self, tokens, max_new_tokens: int) -> int:
        """Queue a prompt; returns a request id.  Raises InvalidRequest
        (a ValueError) on malformed / permanently unservable requests,
        LoadShed (a RuntimeError) when shed — both carry a typed
        `.reason` matching the `rejected{reason=…}` counter label.  Pool
        pressure sheds BEFORE queue pressure, hard exhaustion before the
        soft `admission` policy."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        if tokens.size == 0:
            self._reject(InvalidRequest, RejectReason.EMPTY_PROMPT,
                         "empty prompt")
        if max_new_tokens < 1:
            self._reject(InvalidRequest, RejectReason.BAD_BUDGET,
                         f"max_new_tokens must be >= 1, got "
                         f"{max_new_tokens}")
        need = self._pages_for(tokens.size, max_new_tokens)
        if need > self.state.page_table.shape[1]:
            self._reject(InvalidRequest, RejectReason.TABLE_WIDTH,
                         f"request needs {need} pages > max_pages_per_seq "
                         f"{self.state.page_table.shape[1]}")
        if need > self.pool.n_pages - 1:  # page 0 is the reserved sink
            self._reject(InvalidRequest, RejectReason.POOL_SIZE,
                         f"request needs {need} pages but the pool only has "
                         f"{self.pool.n_pages - 1} usable pages total")
        if self.max_queue is not None:
            # pool pressure first: a request that would queue behind others
            # for pages that are not free only deepens the backlog; pages
            # the prefix cache could evict on demand count as free here
            avail = self.pool.available
            if self.cache is not None:
                avail += self.cache.evictable()
            if self._queue and need > avail:
                self._reject(LoadShed, RejectReason.POOL_EXHAUSTED,
                             f"load shed (pool-exhausted): request needs "
                             f"{need} pages, {avail} free or evictable, "
                             f"{len(self._queue)} already waiting")
            if len(self._queue) >= self.max_queue:
                self._reject(LoadShed, RejectReason.QUEUE_FULL,
                             f"load shed (queue-full): {len(self._queue)} "
                             f"waiting >= max_queue {self.max_queue}")
        if self.admission is not None:
            occ = self._occupancy()
            reason = self.admission.decide(queue_depth=len(self._queue),
                                           pool_occupancy=occ)
            if reason is not None:
                self._reject(LoadShed, reason,
                             f"load shed ({reason}): admission policy — "
                             f"queue_depth={len(self._queue)}, "
                             f"pool_occupancy={occ:.3f}")
        rid = self._next_id
        self._next_id += 1
        req = _Request(rid, tokens, max_new_tokens,
                       t_submit=time.perf_counter())
        # attribute, not a dataclass field — checkpoint serialization must
        # not see the trace context (same contract as _prefix_hashes)
        req._tc = tracing.start_request(rid)
        self._queue.append(req)
        _M_SUBMITTED.inc()
        _M_QUEUE.set(len(self._queue))
        return rid

    def try_submit(self, tokens, max_new_tokens: int) -> SubmitResult:
        """Non-raising submit for routers: rid on success, typed reason
        (with its `retryable` bit) on rejection."""
        try:
            return SubmitResult(rid=self.submit(tokens, max_new_tokens))
        except SubmitRejected as e:
            return SubmitResult(reason=e.reason, message=str(e))

    @property
    def pending(self) -> int:
        return len(self._queue)

    @property
    def live(self) -> int:
        return sum(r is not None for r in self.slots)

    def results(self) -> Dict[int, List[int]]:
        return dict(self._finished)

    @property
    def acceptance_rate(self) -> Optional[float]:
        if self.spec_proposed == 0:
            return None
        return self.spec_accepted / self.spec_proposed

    def run(self, max_steps: int = 100_000) -> Dict[int, List[int]]:
        with obs.span("serve.run"):
            for _ in range(max_steps):
                if not self._queue and self.live == 0:
                    return self.results()
                self.step()
        raise RuntimeError(f"run() exceeded {max_steps} steps")

    def drain(self) -> List[int]:
        """Graceful shutdown: release every in-flight slot's pages and put
        its request BACK at the queue head (reset to un-prefilled; greedy
        decode regenerates the identical tokens on re-admission), then
        refresh the gauges so a drained engine reads live=0 /
        occupancy=0.  Returns the requeued rids in their new queue order.
        The engine stays usable — run() after drain() serves everything,
        requeued work first, to completion."""
        # quiesce the pipeline first: an in-flight launch's tokens are
        # accounted (and its finishers retired through the journal) before
        # the survivors are reset and requeued
        self.flush_pipeline()
        inflight = [req for req in self.slots if req is not None]
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            self.state = free_slot(self.state, self.pool, slot)
            if self.draft is not None:
                self.dstate = retire_slot(self.dstate, self.dpool, slot)
            self.slots[slot] = None
        self._shared.clear()
        inflight.sort(key=lambda r: r.rid)
        for req in reversed(inflight):
            req.tokens = []
            req.n_prefilled = 0
            self._queue.insert(0, req)
            if self.journal is not None:
                self.journal.reset(req.rid)
        if self.journal is not None:
            self.journal.sync()
        _M_QUEUE.set(len(self._queue))
        _M_LIVE.set(0)
        self._set_pool_gauges()
        return [r.rid for r in inflight]

    # -- engine ------------------------------------------------------------

    def _pages_for(self, prompt_len: int, max_new: int) -> int:
        slack = self.spec_k + 1 if self.draft is not None else 0
        return -(-(prompt_len + max_new + slack) // self.page)

    def _attn_for(self, qt: int) -> str:
        """Kernel route for a launch width, probed once per width; a
        declined probe counts one labeled fallback per width."""
        if self.use_ragged is True:
            return "ragged"
        if self.use_ragged is False:
            return "dense"
        if qt not in self._attn_cache:
            reason = ragged_supported(
                n_kv_heads=self.cfg.n_kv_heads, n_q_heads=self.cfg.n_heads,
                q_tokens=qt, d_head=self.cfg.d_head, page=self.page,
                quantized=self.quantize)
            if reason is not None:
                _M_FALLBACK.inc(reason=_fallback_label(reason),
                                **{"pass": "serve"})
                logger.info("ragged kernel declined (qt=%d): %s — dense "
                            "fallback", qt, reason)
            self._attn_cache[qt] = "dense" if reason is not None else "ragged"
        return self._attn_cache[qt]

    def _hashes(self, req: _Request) -> List[bytes]:
        """Full-page rolling hash chain of `req.prompt`, memoized on the
        request (an attribute, not a dataclass field — checkpoint
        serialization must not see it)."""
        h = getattr(req, "_prefix_hashes", None)
        if h is None:
            h = PrefixCache.chain(req.prompt, self.page,
                                  dtype=self.pool.dtype)
            req._prefix_hashes = h
        return h

    def _register_prefix(self, slot: int, req: _Request,
                         row: Optional[np.ndarray] = None) -> None:
        """Register a just-prefilled prompt's full pages in the prefix
        cache.  Runs AFTER the prompt-completing chunk, so any CoW the
        re-absorbed last token forced has already rewritten the table —
        the registered page ids are the post-CoW (content-correct) ones;
        insert() is touch-only for hashes already cached.  The pipelined
        engine registers at deferred-readback time and passes the table
        `row` it captured at launch, so a later speculative launch's CoW
        can never shift the registered ids (and reading the row never
        forces a device sync on an in-flight state)."""
        if self.cache is None:
            return
        hashes = self._hashes(req)
        if not hashes:
            return
        if row is None:
            row = np.asarray(self.state.page_table[slot])
        row = row[:len(hashes)]
        self.cache.insert(hashes, [int(p) for p in row])

    def _admit(self) -> None:
        """Reserve queued requests' full page lifetime into free slots
        (FIFO; the head is never starved by admitting behind it).  No
        tokens move here — prefill is chunked through subsequent ticks.

        With a prefix cache, the head's prompt is first looked up in the
        hash chain: hit pages are pinned (refcount bumped) and wired into
        the slot's table directly, chunked prefill resumes at the
        divergence point, and only the remainder is acquired fresh.  A
        FULL-prompt hit resumes at T-1 so the last prompt token is
        re-absorbed through one ragged chunk — that re-scatter into the
        last shared page is what the CoW barrier privatizes."""
        for slot, occupant in enumerate(self.slots):
            if occupant is not None or not self._queue:
                continue
            req = self._queue[0]
            need = self._pages_for(len(req.prompt), req.max_new_tokens)
            hits: List[int] = []
            if self.cache is not None:
                hits = self.cache.lookup(self._hashes(req))
                short = (need - len(hits)) - self.pool.available
                if short > 0:
                    self.cache.evict(short)
                need -= len(hits)
            if need > self.pool.available:
                if hits:
                    self.pool.release(hits)
                break
            if self.draft is not None and \
                    need + len(hits) > self.dpool.available:
                if hits:
                    self.pool.release(hits)
                break
            ids = self.pool.acquire(need)
            try:
                self.state = assign_pages(self.state, slot, hits + ids)
                if hits:
                    t_pre = len(hits) * self.page
                    # full-prompt hit: resume at T-1, not T — the engine
                    # needs the last token's logits to sample token 0, so
                    # one token is re-absorbed through a 1-token chunk
                    t_resume = (t_pre if t_pre < len(req.prompt)
                                else len(req.prompt) - 1)
                    self.state = self.state._replace(
                        lengths=self.state.lengths.at[slot].set(t_resume))
                    req.n_prefilled = t_resume
                    self._shared[slot] = tuple(hits)
                    _M_PREFIX_HITS.inc()
                    _M_PAGES_SHARED.inc(len(hits))
                    _M_SKIPPED.inc(t_resume)
                elif self.cache is not None:
                    _M_PREFIX_MISSES.inc()
                if self.draft is not None:
                    # draft prefills its WHOLE prompt now (one program, the
                    # draft is cheap); its cache then tracks the target's
                    # accepted stream via per-tick catch-up steps
                    dp, dc = self.draft
                    _, self.dstate = paged_prefill(
                        dp, jnp.asarray(req.prompt), self.dstate,
                        self.dpool, slot, dc)
                    self.dstate = provision_capacity(
                        self.dstate, self.dpool, slot,
                        req.max_new_tokens + self.spec_k + 1)
            except Exception:
                # free_slot releases hits and ids together (one ref each —
                # the lookup's pin and the acquire both belong to the row)
                req.n_prefilled = 0
                self._shared.pop(slot, None)
                self.state = free_slot(self.state, self.pool, slot)
                if self.draft is not None:
                    try:
                        self.dstate = retire_slot(self.dstate, self.dpool,
                                                  slot)
                    except Exception as rollback_err:  # noqa: BLE001
                        logger.warning(
                            "admission rollback: draft retire_slot(%d) "
                            "failed (%s: %s); continuing", slot,
                            type(rollback_err).__name__, rollback_err)
                raise
            self._queue.pop(0)
            self.slots[slot] = req
            _M_ADMITTED.inc()
            _M_QUEUE.set(len(self._queue))
            tc = getattr(req, "_tc", None)
            if tc is not None:
                req._t_admit = time.perf_counter()
                tracing.record_span(tc, "serve.queued", req.t_submit,
                                    req._t_admit)

    def _cow_barrier(self, q_lens) -> None:
        """Privatize every page the imminent launch will scatter into
        while the allocator holds it at refcount > 1 (serving/model.
        cow_pages), and trim the slot's pinned-prefix key past the first
        privatized column.  Gated on pool.has_shared so cache-off and
        zero-overlap runs never pay the scan."""
        if not self.pool.has_shared:
            return
        for slot, req in enumerate(self.slots):
            if req is None or not q_lens[slot]:
                continue
            self.state, copies = cow_pages(
                self.state, self.pool, slot, int(q_lens[slot]),
                cache=self.cache)
            if not copies:
                continue
            _M_COW.inc(len(copies))
            shared = self._shared.get(slot)
            if shared:
                first = min(col for col, _, _ in copies)
                if first < len(shared):
                    if first:
                        self._shared[slot] = shared[:first]
                    else:
                        del self._shared[slot]

    def _build_groups(self):
        """Group live slots whose pinned shared-prefix tuples are EXACTLY
        equal; returns (group_id[slots], shared_table[n_groups+1, n_sh],
        shared_lens[n_groups+1]) device arrays, or None unless some group
        has >= 2 live members (a 1-member "group" saves nothing and would
        only move its math off the bit-identical plain path).  Group 0 is
        the null group (shared_lens 0) every ungrouped slot rides in;
        n_sh is padded to a power of two to bound retraces."""
        groups: Dict[Tuple[int, ...], List[int]] = {}
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            key = self._shared.get(slot)
            if key:
                groups.setdefault(key, []).append(slot)
        real = sorted((k, v) for k, v in groups.items() if len(v) >= 2)
        if not real:
            return None
        n_sh = max(len(k) for k, _ in real)
        n_sh = 1 << (n_sh - 1).bit_length()
        gid = np.zeros((len(self.slots),), np.int32)
        # group axis padded to slots+1 rows (compile-stable: the traced
        # shape never varies with how many groups this tick happens to
        # have; at most slots//2 rows are real, the rest stay null)
        n_rows = len(self.slots) + 1
        table = np.zeros((n_rows, n_sh), np.int32)
        lens = np.zeros((n_rows,), np.int32)
        for g, (key, members) in enumerate(real, start=1):
            table[g, :len(key)] = key
            lens[g] = len(key) * self.page
            for s in members:
                gid[s] = g
        return jnp.asarray(gid), jnp.asarray(table), jnp.asarray(lens)

    def _sample(self, logits):
        self._rng, key = jax.random.split(self._rng)
        return np.asarray(sample_logits(
            logits, key, temperature=self.temperature, top_k=self.top_k,
            top_p=self.top_p, nan_sentinel=True))

    def _retire_finished(self) -> List[Tuple[int, List[int]]]:
        done = []
        retiring: List[int] = []
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            hit_eos = self.eos_id is not None and req.tokens \
                and req.tokens[-1] == self.eos_id
            if hit_eos or len(req.tokens) >= req.max_new_tokens:
                retiring.append(slot)
                if self.draft is not None:
                    self.dstate = retire_slot(self.dstate, self.dpool, slot)
                self.slots[slot] = None
                self._shared.pop(slot, None)
                self._finished[req.rid] = req.tokens
                done.append((req.rid, req.tokens))
                if self.journal is not None:
                    self.journal.done(req.rid)
                _M_RETIRED.inc(cause="eos" if hit_eos else "budget")
                tc = getattr(req, "_tc", None)
                if tc is not None:
                    now = time.perf_counter()
                    tracing.record_span(
                        tc, "serve.decode",
                        getattr(req, "_t_first", req.t_submit), now,
                        tokens=len(req.tokens))
                    tracing.record_span(tc, "serve.request", req.t_submit,
                                        now, root=True, rid=req.rid)
        if retiring:
            # one batched table edit for the whole wave (pages release in
            # slot order, so the pool free list matches per-slot frees)
            self.state = free_slots(self.state, self.pool, retiring)
        if done:
            # retirement frees pages AFTER the tick's _note_tick ran; keep
            # the gauges honest so a drained engine reads occupancy 0
            _M_LIVE.set(self.live)
            self._set_pool_gauges()
        return done

    def _note_tick(self, dt: float, added: int,
                   dev_s: Optional[float] = None) -> None:
        # dev_s = the tick's device launch+sample window; the remainder is
        # host gap, folded into the cumulative serve.host_gap_fraction gauge
        if dev_s is not None:
            self._host_gap_s = getattr(self, "_host_gap_s", 0.0) \
                + max(0.0, dt - dev_s)
            self._launch_wall_s = getattr(self, "_launch_wall_s", 0.0) + dt
            _M_HOST_GAP.set(self._host_gap_s / self._launch_wall_s)
        _M_STEPS.inc()
        _M_QUEUE.set(len(self._queue))
        live = self.live
        _M_LIVE.set(live)
        self._set_pool_gauges()
        if added:
            _M_TOKENS.inc(added)
            _M_TOK_LAT.observe(dt * live / added)
        rate = self.acceptance_rate
        if rate is not None:
            _M_SPEC_RATE.set(rate)

    def _journal_barrier(self, done: List[Tuple[int, List[int]]]) -> None:
        """Durability-then-delivery barrier: fsync the tick's journal
        appends, then run the journal machine's deliver transition for
        every stream leaving the engine — protocols.journal raises if any
        returned token is not yet durable (the delivered ⟹ durable
        contract burstcheck model-checks as proto-journal-durable)."""
        if self.journal is None:
            return
        self.journal.sync()
        for rid, toks in done:
            self.journal.delivered(rid, len(toks))

    def step(self) -> List[Tuple[int, List[int]]]:
        """One engine tick (see _step; _pipelined_step when pipeline=True
        and no draft model is attached).  When a journal is attached this
        is also the durability barrier: the tick's journal appends are
        fsynced BEFORE its results are returned, so any token a caller
        has seen survives a crash (write-ahead).  On the pipelined path
        the fsync stays before delivery — which means delivery lags one
        step behind generation (the launch whose tokens are returned here
        was dispatched a step ago; this tick's launch is still in
        flight)."""
        if self.pipeline and self.draft is None:
            return self._pipelined_step()
        done = self._step()
        self._journal_barrier(done)
        return done

    def _step(self) -> List[Tuple[int, List[int]]]:
        """One engine tick: retire -> admit -> ONE ragged launch moving
        every active slot (prefill chunks + decode singles together, or a
        whole speculative round when a draft is attached and nothing is
        mid-prefill).  Returns requests that finished THIS tick."""
        t0 = time.perf_counter()
        done = self._retire_finished()
        self._admit()
        if self.live == 0:
            self._note_tick(time.perf_counter() - t0, 0)
            return done

        prefilling = [s for s, r in enumerate(self.slots)
                      if r is not None and r.n_prefilled < len(r.prompt)]
        if self.draft is not None and not prefilling:
            td0 = time.perf_counter()
            added = self._spec_round()
            # the whole round counts as device window (its launches are
            # back-to-back; the python glue between them is noise here)
            self._note_tick(time.perf_counter() - t0, added,
                            time.perf_counter() - td0)
            done += self._retire_finished()
            return done

        qt = self.chunk if prefilling else 1
        slots = len(self.slots)
        toks = np.zeros((slots, qt), np.int32)
        q_lens = np.zeros((slots,), np.int32)
        n_prefill_toks = 0
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            if req.n_prefilled < len(req.prompt):
                seg = req.prompt[req.n_prefilled:req.n_prefilled + qt]
                toks[slot, :len(seg)] = seg
                q_lens[slot] = len(seg)
                n_prefill_toks += len(seg)
            else:
                toks[slot, 0] = self._next_tok[slot]
                q_lens[slot] = 1
        self._cow_barrier(q_lens)
        td0 = time.perf_counter()  # device window: launch through sample sync
        attn = self._attn_for(qt)
        groups = (self._build_groups()
                  if self.group_attn and self._shared and attn == "ragged"
                  else None)
        if groups is not None:
            gid, gtable, glens = groups
            logits, self.state = ragged_model_step(
                self.params, jnp.asarray(toks), jnp.asarray(q_lens),
                self.state, self.cfg, attn="grouped", group_id=gid,
                shared_table=gtable, shared_lens=glens)
        else:
            logits, self.state = ragged_model_step(
                self.params, jnp.asarray(toks), jnp.asarray(q_lens),
                self.state, self.cfg, attn=attn)
        choice = self._sample(logits)
        dev_s = time.perf_counter() - td0

        kind = ("mixed" if prefilling and len(prefilling) < self.live
                else "prefill" if prefilling else "decode")
        _M_RB_LAUNCH.inc(kind=kind)
        if n_prefill_toks:
            _M_RB_PREFILL.inc(n_prefill_toks)
        _M_RB_FILL.set(float(q_lens.sum()) / (slots * qt))

        added = 0
        dtoks = np.zeros((slots,), np.int32)   # draft catch-up feed
        dlens = np.zeros((slots,), np.int32)
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            if choice[slot] < 0:  # sample_logits NaN-poison sentinel
                raise RuntimeError(
                    f"slot {slot} (rid {req.rid}) logits are NaN-poisoned: "
                    "a live slot was stepped without assigned pages")
            if req.n_prefilled < len(req.prompt):
                was = req.n_prefilled
                req.n_prefilled = was + int(q_lens[slot])
                if req.n_prefilled == len(req.prompt):
                    self._register_prefix(slot, req)
                    # chunk completed the prompt: its last-token logits ARE
                    # the first-token distribution (TTFT lands here)
                    tok = int(choice[slot])
                    req.tokens.append(tok)
                    if self.journal is not None:
                        self.journal.tokens(req.rid, [tok])
                    self._next_tok[slot] = tok
                    added += 1
                    now = time.perf_counter()
                    _M_TTFT.observe(now - req.t_submit)
                    tc = getattr(req, "_tc", None)
                    if tc is not None:
                        # contiguous phases on one clock: queued ends where
                        # prefill starts, prefill ends at the first-token
                        # instant — the breakdown sums to TTFT exactly
                        t_adm = getattr(req, "_t_admit", req.t_submit)
                        req._t_first = now
                        tracing.record_span(tc, "serve.prefill", t_adm, now,
                                            prompt_len=len(req.prompt))
                        tracing.marker(tc, "serve.first_token", now)
                        tracing.note_ttft(tc, now - req.t_submit)
                        tracing.publish_breakdown(
                            {"queued": t_adm - req.t_submit,
                             "prefill": now - t_adm})
            else:
                tok = int(choice[slot])
                req.tokens.append(tok)
                if self.journal is not None:
                    self.journal.tokens(req.rid, [tok])
                # draft cache catch-up: it must absorb the token the target
                # just consumed (the PREVIOUS next_tok) to stay aligned
                dtoks[slot] = toks[slot, 0]
                dlens[slot] = 1
                self._next_tok[slot] = tok
                added += 1
                _M_RB_DECODE.inc()
        if self.draft is not None and dlens.any():
            dp, dc = self.draft
            _, self.dstate = ragged_model_step(
                dp, jnp.asarray(dtoks[:, None]), jnp.asarray(dlens),
                self.dstate, dc, attn="dense")
        self._note_tick(time.perf_counter() - t0, added, dev_s)
        done += self._retire_finished()
        return done

    # -- pipelined engine --------------------------------------------------
    #
    # step() under pipeline=True keeps exactly one launch in flight: each
    # tick dispatches the NEXT launch (speculatively, when no admission or
    # retire event can land at the unread launch's readback) BEFORE
    # blocking on the previous one, so host scheduling for tick N+1
    # overlaps device execution of tick N.  The readback replays the
    # synchronous engine's post-sample accounting one step late; the
    # journal fsync stays before delivery, so delivery lags one step.
    # Token-exactness rests on two facts: (1) every launch is the SAME
    # compiled program as the synchronous tick (burstlint asserts the K=1
    # jaxprs are string-identical), and (2) jax.random.categorical's
    # per-row noise depends only on (key, shape, row) — a slot's sampled
    # token never depends on other slots' logits — so feeding a still-on-
    # device choice into the next launch cannot change any slot's stream.

    def _spec_plan(self) -> Optional[int]:
        """Fused decode depth k for a speculative launch on top of the
        unread pending launch, or None when the synchronous engine could
        admit or retire at the pending readback (speculating would build
        on a wrong schedule; EOS is the one event this cannot predict —
        the reconcile path in _pipelined_step handles it)."""
        p = self._pending
        if self._queue and any(r is None for r in self.slots):
            return None                  # admission would land next tick
        any_live = False
        k = self.multi_step
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            any_live = True
            if req.n_prefilled + int(p.prefill_advance[slot]) \
                    < len(req.prompt):
                return None              # still mid-prefill after pending
            remaining = req.max_new_tokens \
                - (len(req.tokens) + int(p.tok_delta[slot]))
            if remaining < 1:
                return None              # budget retire at pending readback
            k = min(k, remaining)
        if not any_live:
            return None
        if self._shared and self.group_attn:
            # shared-prefix ticks follow the synchronous engine's per-tick
            # grouped-launch decision; never fuse across them
            k = 1
        return k

    def _dispatch_deferred(self, *, feed, q_lens, qt, k, prefill_advance,
                           tok_delta, n_prefill_toks, kind) -> _Pending:
        """Shared dispatch for both pipelined launch flavors: CoW-protect
        the window, route the kernel, launch WITHOUT reading the sampled
        choice back.  `feed` is the [slots, qt] token grid for k == 1 or
        the [slots] next-token feed for a fused k-step scan (either host
        numpy or a still-in-flight device array)."""
        self._cow_barrier(q_lens * k)
        # capture the post-CoW table row of any slot completing its prompt
        # this launch: prefix registration at readback must see the table
        # exactly as the synchronous engine would, before a later launch's
        # CoW rewrites it
        table_rows: Dict[int, np.ndarray] = {}
        if self.cache is not None:
            for slot, req in enumerate(self.slots):
                if req is not None and prefill_advance[slot] and \
                        req.n_prefilled + int(prefill_advance[slot]) \
                        == len(req.prompt):
                    table_rows[slot] = np.asarray(self.state.page_table[slot])
        attn = self._attn_for(qt)
        rng_before = self._rng
        q_lens_dev = jnp.asarray(q_lens)
        t_d = time.perf_counter()
        if k > 1:
            choices, self.state, self._rng = multi_step_decode(
                self.params, jnp.asarray(feed), q_lens_dev, self.state,
                self._rng, self.cfg, k=k, attn=attn,
                temperature=self.temperature, top_k=self.top_k,
                top_p=self.top_p)
            _M_MULTI.inc(k=str(k))
        else:
            groups = (self._build_groups()
                      if self.group_attn and self._shared
                      and attn == "ragged" else None)
            self._rng, key = jax.random.split(self._rng)
            if groups is not None:
                gid, gtable, glens = groups
                choice, self.state = pipelined_tick(
                    self.params, jnp.asarray(feed), q_lens_dev, self.state,
                    key, self.cfg, attn="grouped",
                    temperature=self.temperature, top_k=self.top_k,
                    top_p=self.top_p, group_id=gid, shared_table=gtable,
                    shared_lens=glens)
            else:
                choice, self.state = pipelined_tick(
                    self.params, jnp.asarray(feed), q_lens_dev, self.state,
                    key, self.cfg, attn=attn,
                    temperature=self.temperature, top_k=self.top_k,
                    top_p=self.top_p)
            choices = choice[None]
        _M_RB_LAUNCH.inc(kind=kind)
        if n_prefill_toks:
            _M_RB_PREFILL.inc(n_prefill_toks)
        _M_RB_FILL.set(float(q_lens.sum()) / (len(self.slots) * qt))
        return _Pending(
            choices=choices, k=k, q_lens=q_lens,
            advance=(q_lens * k).astype(np.int32),
            prefill_advance=prefill_advance, tok_delta=tok_delta,
            rng_before=rng_before, table_rows=table_rows,
            n_prefill_toks=n_prefill_toks, kind=kind, t_dispatch=t_d,
            feed_next=choices[-1])

    def _launch_deferred(self) -> _Pending:
        """Pipeline (re)fill: the synchronous tick's batch build — prefill
        chunks + decode singles from the fully-accounted host state — as
        one deferred launch, fused to multi_step depth when every live
        slot is pure-decode and no admission/retire can land inside the
        window."""
        prefilling = [s for s, r in enumerate(self.slots)
                      if r is not None and r.n_prefilled < len(r.prompt)]
        qt = self.chunk if prefilling else 1
        slots = len(self.slots)
        toks = np.zeros((slots, qt), np.int32)
        q_lens = np.zeros((slots,), np.int32)
        prefill_advance = np.zeros((slots,), np.int32)
        tok_delta = np.zeros((slots,), np.int32)
        n_prefill_toks = 0
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            if req.n_prefilled < len(req.prompt):
                seg = req.prompt[req.n_prefilled:req.n_prefilled + qt]
                toks[slot, :len(seg)] = seg
                q_lens[slot] = len(seg)
                prefill_advance[slot] = len(seg)
                if req.n_prefilled + len(seg) == len(req.prompt):
                    tok_delta[slot] = 1
                n_prefill_toks += len(seg)
            else:
                toks[slot, 0] = self._next_tok[slot]
                q_lens[slot] = 1
                tok_delta[slot] = 1
        k = 1
        if not prefilling and self.multi_step > 1 \
                and not (self._shared and self.group_attn) \
                and not (self._queue
                         and any(r is None for r in self.slots)):
            k = self.multi_step
            for req in self.slots:
                if req is not None:
                    k = min(k, req.max_new_tokens - len(req.tokens))
            k = max(1, k)
        if k > 1:
            tok_delta = q_lens * k
        kind = ("mixed" if prefilling and len(prefilling) < self.live
                else "prefill" if prefilling else "decode")
        return self._dispatch_deferred(
            feed=(toks if k == 1 else toks[:, 0]), q_lens=q_lens, qt=qt,
            k=k, prefill_advance=prefill_advance, tok_delta=tok_delta,
            n_prefill_toks=n_prefill_toks, kind=kind)

    def _launch_speculative(self, k: int) -> _Pending:
        """Launch the next k decode steps on top of the UNREAD pending
        launch, feeding its last on-device choice row straight in as the
        next tokens — zero host readbacks between the two launches."""
        p = self._pending
        slots = len(self.slots)
        q_lens = np.asarray([1 if r is not None else 0
                             for r in self.slots], np.int32)
        feed = p.feed_next if p.feed_next is not None else p.choices[-1]
        return self._dispatch_deferred(
            feed=(feed[:, None] if k == 1 else feed), q_lens=q_lens, qt=1,
            k=k, prefill_advance=np.zeros((slots,), np.int32),
            tok_delta=q_lens * k, n_prefill_toks=0, kind="decode")

    def _readback(self, p: _Pending) -> Tuple[int, bool, bool]:
        """Deferred host half of launch `p`: block on its sampled choices
        (THE pipeline sync point) and replay the synchronous engine's
        post-sample accounting.  A fused launch is truncated at its FIRST
        EOS step — tokens past it are schedule the synchronous engine
        would never have produced — by rolling the device lengths back
        and re-deriving the rng from the pre-launch snapshot, so the
        per-slot streams stay bit-identical.  Returns (tokens added,
        diverged, truncated); `diverged` means the readback produced an
        event (EOS / budget retire / truncation) that invalidates any
        schedule speculated on top of this launch."""
        choices = _readback_choices(p.choices)
        slots = len(self.slots)
        keep = p.k
        if p.k > 1 and self.eos_id is not None:
            for j in range(p.k):
                if any(self.slots[s] is not None and p.q_lens[s]
                       and choices[j, s] == self.eos_id
                       for s in range(slots)):
                    keep = j + 1
                    break
        added = 0
        nan_at = None
        for j in range(keep):
            row = choices[j]
            for slot, req in enumerate(self.slots):
                if req is None or not p.q_lens[slot]:
                    continue
                if row[slot] < 0:  # sample_logits NaN-poison sentinel
                    nan_at = (slot, req.rid)
                    break
                if j == 0 and p.prefill_advance[slot]:
                    was = req.n_prefilled
                    req.n_prefilled = was + int(p.prefill_advance[slot])
                    if req.n_prefilled == len(req.prompt):
                        self._register_prefix(slot, req,
                                              row=p.table_rows.get(slot))
                        tok = int(row[slot])
                        req.tokens.append(tok)
                        if self.journal is not None:
                            self.journal.tokens(req.rid, [tok])
                        self._next_tok[slot] = tok
                        added += 1
                        now = time.perf_counter()
                        _M_TTFT.observe(now - req.t_submit)
                        tc = getattr(req, "_tc", None)
                        if tc is not None:
                            t_adm = getattr(req, "_t_admit", req.t_submit)
                            req._t_first = now
                            tracing.record_span(tc, "serve.prefill", t_adm,
                                                now,
                                                prompt_len=len(req.prompt))
                            tracing.marker(tc, "serve.first_token", now)
                            tracing.note_ttft(tc, now - req.t_submit)
                            tracing.publish_breakdown(
                                {"queued": t_adm - req.t_submit,
                                 "prefill": now - t_adm})
                else:
                    tok = int(row[slot])
                    req.tokens.append(tok)
                    if self.journal is not None:
                        self.journal.tokens(req.rid, [tok])
                    self._next_tok[slot] = tok
                    added += 1
                    _M_RB_DECODE.inc()
            if nan_at is not None:
                break
        truncated = keep < p.k
        if truncated:
            # scattered K/V beyond the rolled-back logical length is
            # harmless garbage — always overwritten before it can be read
            undo = np.where(p.q_lens > 0, p.k - keep, 0).astype(np.int32)
            self.state = self.state._replace(
                lengths=self.state.lengths - jnp.asarray(undo))
            rng = p.rng_before
            for _ in range(keep):
                rng, _ = jax.random.split(rng)
            self._rng = rng
            _M_RECONCILE.inc(cause="scan-eos")
        if nan_at is not None:
            slot, rid = nan_at
            raise RuntimeError(
                f"slot {slot} (rid {rid}) logits are NaN-poisoned: a live "
                "slot was stepped without assigned pages")
        eos = self.eos_id is not None and any(
            req is not None and req.tokens
            and req.tokens[-1] == self.eos_id for req in self.slots)
        budget = any(
            req is not None and len(req.tokens) >= req.max_new_tokens
            for req in self.slots)
        return added, (eos or budget or truncated), truncated

    def _pipelined_step(self) -> List[Tuple[int, List[int]]]:
        """One pipelined tick: dispatch the next launch (speculatively if
        safe), THEN block on the previous one — its results are what this
        call returns, so delivery lags one step.  On divergence (the
        readback retired a stream the speculation assumed live) the
        speculative launch is rolled back — lengths and rng restored —
        and the tick falls back to the synchronous retire/admit/launch
        sequence, so the schedule is always the synchronous engine's."""
        t0 = time.perf_counter()
        done = self._flushed_done
        self._flushed_done = []
        p = self._pending
        if p is None:
            # pipeline (re)fill: the synchronous tick head, one deferred
            # launch, nothing to read back or deliver yet
            done += self._retire_finished()
            self._admit()
            if self.live == 0:
                self._note_tick(time.perf_counter() - t0, 0)
                self._journal_barrier(done)
                return done
            self._pending = self._launch_deferred()
            dt = time.perf_counter() - t0
            self._note_tick(
                dt, 0, min(dt, time.perf_counter()
                           - self._pending.t_dispatch))
            self._journal_barrier(done)
            return done
        ir = getattr(p.choices, "is_ready", None)
        ready0 = bool(ir()) if ir is not None else False
        k_spec = self._spec_plan()
        spec = self._launch_speculative(k_spec) if k_spec else None
        self._pending = None
        added, diverged, truncated = self._readback(p)
        t_rb = time.perf_counter()
        if spec is not None and diverged:
            # reconcile: discard the speculative launch (its scattered K/V
            # sits beyond the logical length and is overwritten before it
            # can ever be read) and fall back to a synchronous tick
            self.state = self.state._replace(
                lengths=self.state.lengths - jnp.asarray(spec.advance))
            if not truncated:   # truncation already repositioned the rng
                self._rng = spec.rng_before
            _M_RECONCILE.inc(cause="eos-retire")
            spec = None
        if spec is not None:
            # speculation was right: the launch in flight IS the next tick
            self._pending = spec
        else:
            done += self._retire_finished()
            self._admit()
            if self.live:
                self._pending = self._launch_deferred()
        dt = time.perf_counter() - t0
        # device window estimate: the pending launch provably ran from
        # tick start to readback completion unless it was already ready
        # when the tick began; the freshly dispatched launch runs from
        # its dispatch to tick end (credited here, verified by the next
        # tick's is_ready probe)
        dev_s = 0.0 if ready0 else t_rb - t0
        if self._pending is not None:
            dev_s += time.perf_counter() - self._pending.t_dispatch
        self._note_tick(dt, added, min(dev_s, dt))
        self._journal_barrier(done)
        return done

    def flush_pipeline(self) -> List[Tuple[int, List[int]]]:
        """Quiesce the pipeline: block on any in-flight launch, run its
        deferred accounting, retire its finishers through the journal
        barrier.  The finishers are ALSO queued onto the next step()'s
        return so a driver loop polling step() never loses a completion.
        Safe no-op when nothing is in flight (or on a synchronous
        engine).  snapshot()/drain() call this first — a quiesced engine
        is the only thing worth serializing."""
        p = self._pending
        if p is None:
            return []
        self._pending = None
        added, _, _ = self._readback(p)
        done = self._retire_finished()
        if added:
            _M_TOKENS.inc(added)
        self._journal_barrier(done)
        self._flushed_done.extend(done)
        return done

    def _spec_round(self) -> int:
        """One speculative round for every (decoding) live slot: k draft
        proposals via single paged steps on the draft state, ONE ragged
        all-logits verify of [last | proposals] on the target, per-slot
        prefix acceptance, then a vectorized lengths rollback on both
        states.  Greedy; token-exact with the plain engine."""
        k = self.spec_k
        dp, dc = self.draft
        slots = len(self.slots)
        live_mask = np.asarray([r is not None for r in self.slots])
        # verify writes k+1 tokens per live slot into the TARGET state;
        # privatize any still-shared boundary page first (the draft pool
        # is never shared — draft prefill always acquires private pages)
        self._cow_barrier(np.where(live_mask, k + 1, 0))
        toks_dev = []
        cur = jnp.asarray(self._next_tok)
        bad_d = jnp.zeros(slots, bool)
        for _ in range(k):
            lg_d, self.dstate = paged_decode_step(dp, cur, self.dstate, dc)
            bad_d = bad_d | jnp.any(jnp.isnan(lg_d), axis=-1)
            cur = jnp.argmax(lg_d, axis=-1).astype(jnp.int32)
            toks_dev.append(cur)
        d_toks_dev = jnp.stack(toks_dev, axis=1)              # [slots, k]
        feed = jnp.concatenate(
            [jnp.asarray(self._next_tok)[:, None], d_toks_dev], axis=1)
        q_lens = jnp.asarray(np.where(live_mask, k + 1, 0).astype(np.int32))
        lg_t, self.state = ragged_model_step(
            self.params, feed, q_lens, self.state, self.cfg,
            attn=self._attn_for(k + 1), all_logits=True)
        # draft catch-up to base + k + 1, then the same rollback trims both
        _, self.dstate = paged_decode_step(
            dp, d_toks_dev[:, -1], self.dstate, dc)
        self.spec_rounds += 1
        _M_RB_LAUNCH.inc(kind="spec-verify")
        d_toks = np.asarray(d_toks_dev)
        choice = np.asarray(jnp.argmax(lg_t, axis=-1))        # [slots, k+1]
        bad = np.asarray(
            jnp.any(jnp.isnan(lg_t), axis=(1, 2)) | bad_d)
        undo = np.zeros(slots, np.int32)
        n_kept = 0
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            if bad[slot]:
                raise RuntimeError(
                    f"slot {slot} (rid {req.rid}) speculative logits are "
                    "NaN-poisoned: stepped without provisioned capacity")
            n_acc = 0
            while n_acc < k and d_toks[slot, n_acc] == choice[slot, n_acc]:
                n_acc += 1
            self.spec_proposed += k
            self.spec_accepted += n_acc
            new = ([int(x) for x in d_toks[slot, :n_acc]]
                   + [int(choice[slot, n_acc])])
            new = new[: req.max_new_tokens - len(req.tokens)]
            if self.eos_id is not None and self.eos_id in new:
                new = new[: new.index(self.eos_id) + 1]
            req.tokens += new
            if self.journal is not None:
                self.journal.tokens(req.rid, new)
            n_kept += len(new)
            _M_RB_DECODE.inc(len(new))
            self._next_tok[slot] = new[-1]
            undo[slot] = k + 1 - len(new)
        undo_dev = jnp.asarray(undo)
        self.state = self.state._replace(
            lengths=self.state.lengths - undo_dev)
        self.dstate = self.dstate._replace(
            lengths=self.dstate.lengths - undo_dev)
        return n_kept
