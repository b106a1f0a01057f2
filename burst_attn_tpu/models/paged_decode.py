"""Paged KV-cache serving path: shared page pool + ragged continuous
batching on top of ops/paged_attention.py.

models/decode.py allocates a dense [B, Nkv, max_seq, D] cache per layer —
worst-case memory per sequence, O(max_seq) decode compute, and batch slots
are all-or-nothing.  This module is the serving-shaped alternative:

  * `PagePool` (host-side, stateful): owns the free list of pool pages.
    Sequences acquire pages as they grow and release them on retirement —
    admission control falls out of `len(free)`.
  * `PagedState` (device pytree): per-layer page pools, the page table,
    per-sequence lengths, everything static-shaped — the host mutates the
    TABLE (tiny int32 arrays), never reshapes device buffers, so the jitted
    step functions never retrace as sequences come and go.
  * `paged_prefill` absorbs a prompt into freshly-acquired pages (flash
    attention over the contiguous prompt, then paged scatter of the rope'd
    K/V); `paged_decode_step` appends one token per live sequence and
    attends via the ragged paged kernel.  Sequences at different lengths
    batch in the same call (ragged), empty slots cost one predicated grid
    step per page slot.

The batch dimension is a fixed number of SLOTS (max concurrent sequences);
continuous batching = host assigns a finished slot's pages back to the free
list and prefillls a new prompt into that slot, while other slots keep
decoding.  Slot admission/retirement is host logic between steps — the
device arrays never change shape.

Reference parity: the reference has no serving layer at all (SURVEY.md §5
"checkpoint/resume: none (op library)"); this extends the framework the
same direction as models/decode.py but with pool semantics.  Kernel design
notes in ops/paged_attention.py.
"""

from collections import OrderedDict
from functools import partial
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax.sharding import PartitionSpec as P
from jax import shard_map

from .transformer import ModelConfig, _attn_out, _mlp, _qkv_proj, _rms_norm
from .decode import _flash_prompt_attention, sample_logits
from ..ops.paged_attention import (
    QUANT_DTYPES, paged_decode_attention, quantize_tokens,
)


def resolve_pool_dtype(quantize, default):
    """(pool storage dtype, canonical tag) for an init_paged_state-style
    `quantize` knob: False -> (default, None); True / "int8" -> int8;
    "fp8" -> float8_e4m3fn.  The tag is the string every downstream
    surface keys on (obs labels, checkpoint meta, kvplane wire meta)."""
    if not quantize:
        return default, None
    name = "int8" if quantize is True else str(quantize)
    if name not in QUANT_DTYPES:
        raise ValueError(f"quantize must be False, True, or one of "
                         f"{sorted(QUANT_DTYPES)}; got {quantize!r}")
    return QUANT_DTYPES[name][0], name


def _check_tp_mesh(cfg: ModelConfig, mesh):
    """Shared head-axis validation for the tp serving paths; returns the
    tp size (1 = run unsharded)."""
    if mesh is None or cfg.head_axis is None:
        return 1
    if cfg.head_axis not in mesh.shape:
        raise ValueError(
            f"head_axis {cfg.head_axis!r} is not an axis of the mesh "
            f"{dict(mesh.shape)}; pass mesh=None for single-device serving "
            "or set cfg.head_axis to a mesh axis")
    tp = mesh.shape.get(cfg.head_axis, 1)
    if tp > 1 and (cfg.n_kv_heads % tp or cfg.n_heads % tp):
        raise ValueError(
            f"n_heads {cfg.n_heads} / n_kv_heads {cfg.n_kv_heads} not "
            f"divisible by {cfg.head_axis!r} mesh size {tp}")
    return tp


def _prompt_attention_dispatch(q, k, v, cfg: ModelConfig, mesh):
    """Head-sharded prompt (prefill) attention under a tp mesh — same
    rationale as _paged_attention_dispatch: the Pallas flash call must be
    split explicitly."""
    if _check_tp_mesh(cfg, mesh) == 1:
        return _flash_prompt_attention(q, k, v, window=cfg.window)
    spec = P(None, cfg.head_axis, None, None)
    fn = shard_map(
        partial(_flash_prompt_attention, window=cfg.window),
        mesh=mesh,
        in_specs=(spec,) * 3,
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


def _paged_attention_dispatch(qg, kp, vp, ks, vs, table, lengths,
                              cfg: ModelConfig, mesh):
    """Route the paged kernel through a head-sharded shard_map when serving
    tensor-parallel (mesh given and cfg.head_axis present): the pool's kv
    heads split over tp, each shard walks its own pages — a Pallas call
    cannot be partitioned by GSPMD, so the split must be explicit.  The
    table/lengths ride in replicated.  Everything else in the step (qkv
    projections, MLP, logits) stays GSPMD-sharded by the params' specs."""
    if _check_tp_mesh(cfg, mesh) == 1:
        return paged_decode_attention(qg, kp, vp, table, lengths,
                                      k_scales=ks, v_scales=vs,
                                      window=cfg.window)
    spec4 = P(None, cfg.head_axis, None, None)
    spec3 = P(None, cfg.head_axis, None)
    quant = ks is not None
    in_specs = [spec4, spec4, spec4]
    args = [qg, kp, vp]
    if quant:
        in_specs += [spec3, spec3]
        args += [ks, vs]
    in_specs += [P(None, None), P(None)]
    args += [table, lengths]

    def shard(qg, kp, vp, *rest):
        if quant:
            ks_l, vs_l, table_l, lengths_l = rest
        else:
            ks_l, vs_l = None, None
            table_l, lengths_l = rest
        return paged_decode_attention(qg, kp, vp, table_l, lengths_l,
                                      k_scales=ks_l, v_scales=vs_l,
                                      window=cfg.window)

    fn = shard_map(
        shard, mesh=mesh, in_specs=tuple(in_specs), out_specs=spec4,
        check_vma=False,
    )
    return fn(*args)


class PagedState(NamedTuple):
    """Device-side paged cache (one pool per layer, table shared).
    Quantized serving (init_paged_state(quantize=True | "int8" | "fp8")):
    pools store 1 B/elem (int8 or fp8 e4m3fn) with per-token fp32 dequant
    scales beside the pages — half the bf16 pool memory, a quarter of
    fp32.  The scale banks are pool state exactly like the page bytes:
    CoW copies, checkpoints, and KV-plane shipments carry both or
    neither."""
    k_pages: Tuple[jax.Array, ...]  # each [P, Nkv, page, D]
    v_pages: Tuple[jax.Array, ...]
    page_table: jax.Array           # [slots, max_pages_per_seq] int32
    lengths: jax.Array              # [slots] int32 (0 = empty slot)
    k_scales: Optional[Tuple[jax.Array, ...]] = None  # each [P, Nkv, page]
    v_scales: Optional[Tuple[jax.Array, ...]] = None


class PagePool:
    """Host-side REFCOUNTED page allocator for a PagedState.

    Not a jax object: allocation decisions happen between jitted steps.
    `acquire(n)` pops page ids from the free list at refcount 1 (raises if
    exhausted — callers use `available` for admission control);
    `release(ids)` decrements and returns a page to the free list when its
    count reaches zero; `share(ids)` increments (prefix caching: the same
    physical page referenced from several sequences' table rows and/or the
    prefix cache).  The pool never touches device memory: pages are
    recycled by table rewrite, stale contents are simply never addressed.

    Every mutation runs through the PURE transition function
    `protocols.pool.step` — the same function burstcheck's model checker
    explores over all interleavings (proto-pool-conserved) — with
    `_free`/`_refs` kept as the mutable mirror of the machine state
    (checkpoint serialization and the fuzz integrity recount read them
    directly).
    """

    def __init__(self, n_pages: int, dtype: Optional[str] = None):
        # page 0 is RESERVED as the write sink for empty batch slots: the
        # jitted decode step must scatter *something* per slot (static
        # shapes), and routing dead slots' writes to a page no sequence can
        # own keeps live pages clobber-free without per-slot predication.
        self.n_pages = n_pages
        # the STORAGE dtype tag of the pools this allocator fronts:
        # None = full precision, "int8"/"fp8" = 1 B pages + scale banks.
        # Pure metadata here (the allocator never touches device memory),
        # but it is the single tag obs gauges label by, checkpoints pin,
        # and the KV plane asserts agreement on before landing pages.
        self.dtype = dtype
        self._free: List[int] = list(range(n_pages - 1, 0, -1))
        self._refs = [0] * n_pages

    def proto_state(self):
        """The allocator as the machine's immutable PoolState."""
        from ..protocols import pool as _pp

        return _pp.from_lists(self.n_pages, self._free, self._refs)

    def _step(self, event):
        from ..protocols import pool as _pp

        st, out = _pp.step(self.proto_state(), event)
        self._free = list(st.free)
        self._refs = list(st.refs)
        return out

    @property
    def available(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        """PHYSICAL pages currently held (each shared page counts once)."""
        return self.n_pages - 1 - len(self._free)

    @property
    def logical_refs(self) -> int:
        """Sum of refcounts — the pages the pool would need WITHOUT
        sharing.  logical_refs - in_use = pages saved by prefix sharing."""
        return sum(self._refs)

    @property
    def has_shared(self) -> bool:
        """True iff ANY page is held at refcount > 1 — the cheap gate the
        serving engine uses to skip the CoW barrier scan entirely when
        nothing is shared (the common cache-off / zero-overlap case)."""
        return any(r > 1 for r in self._refs)

    def refcount(self, i: int) -> int:
        return self._refs[int(i)]

    def acquire(self, n: int) -> List[int]:
        out = self._step(("acquire", int(n)))
        return list(out[0][1])

    def share(self, ids) -> None:
        """Add one reference to already-live pages (prefix reuse)."""
        self._step(("share", tuple(int(i) for i in ids)))

    def release(self, ids) -> None:
        # an over-release would put the page on the free list while another
        # sequence still references it — corrupt both, silently (the
        # machine validates the whole batch before mutating anything)
        self._step(("release", tuple(int(i) for i in ids)))


class PrefixCache:
    """Host-side page-aligned prefix cache (vLLM-style automatic prefix
    caching, restricted to FULL pages).

    Maps the rolling hash of each full-page token prefix to the pool page
    holding that page's K/V (one page id is valid across every layer's
    pool — the table is layer-shared).  The cache owns ONE pool reference
    per registered page, so cached pages survive their sequences retiring;
    `evict(n)` drops the n least-recently-used entries and their refs.

    Write discipline: the LEGACY full-prefill path (paged_prefill) never
    writes a shared page — decode appends target the column at
    lengths//page, beyond every full (cacheable) page.  The ragged engine
    additionally admits FULL-prompt hits by re-absorbing the prompt's last
    token through chunked prefill, whose K/V scatter targets the last
    shared page — that write goes through the copy-on-write barrier
    (serving/model.cow_pages) which privatizes the page first.  Eviction
    only frees a physical page when its refcount reaches 0.
    """

    def __init__(self, pool: PagePool):
        self._pool = pool
        self._pages: "dict[bytes, int]" = {}   # prefix hash -> page id
        # least recent first; OrderedDict keys give O(1) touch/remove
        # (a plain list made every lookup hit O(n) and evictions O(n^2))
        self._lru: "OrderedDict[bytes, None]" = OrderedDict()
        # chain structure: a lookup stops at the first miss, so an entry
        # whose PARENT is gone can never hit again — eviction must go
        # leaf-first or it orphans reachable descendants
        self._parent: "dict[bytes, Optional[bytes]]" = {}
        self._nkids: "dict[bytes, int]" = {}

    @staticmethod
    def chain(tokens, page: int, dtype: Optional[str] = None) -> List[bytes]:
        """Rolling hash per FULL page of `tokens` (1-D int array): entry i
        identifies the whole prefix tokens[:(i+1)*page].

        `dtype` is the pool's STORAGE dtype tag (PagePool.dtype) and is
        folded into the seed of the chain, making each entry a stable
        content key for the QUANTIZED page bytes: within one pool dtype
        the quantized representation is a deterministic function of the
        token prefix (quantize_tokens is pure), so two prompts share an
        entry iff their pages hold identical quantized bytes — and an
        entry minted against an int8 pool can never alias one minted
        against fp8 or full precision (the requantization hazard across
        checkpoint restores into a differently-typed pool).  dtype=None
        (full precision) keeps the pre-quantization chain byte-identical."""
        import hashlib

        toks = np.asarray(tokens, np.int32)
        out: List[bytes] = []
        h = b"" if dtype is None else f"pool:{dtype}".encode()
        for i in range(len(toks) // page):
            h = hashlib.sha1(h + toks[i * page:(i + 1) * page].tobytes()
                             ).digest()
            out.append(h)
        return out

    def __len__(self):
        return len(self._pages)

    def _touch(self, h: bytes):
        self._lru.move_to_end(h)

    def lookup(self, hashes: List[bytes]) -> List[int]:
        """Longest cached prefix of `hashes`; bumps the pool refcount of
        every returned page (caller owns the new references) and marks the
        entries recently used."""
        ids: List[int] = []
        for h in hashes:
            pid = self._pages.get(h)
            if pid is None:
                break
            ids.append(pid)
            self._touch(h)
        self._pool.share(ids)
        return ids

    def insert(self, hashes: List[bytes], page_ids) -> None:
        """Register a prompt's FULL hash chain (hashes[i]'s parent is
        hashes[i-1]); the cache takes one reference per NEWLY inserted
        page.  Already-present entries are touched (LRU refresh) only."""
        assert len(hashes) == len(page_ids)
        prev: Optional[bytes] = None
        for h, pid in zip(hashes, page_ids):
            if h in self._pages:
                self._touch(h)
            else:
                self._pool.share([int(pid)])
                self._pages[h] = int(pid)
                self._lru[h] = None
                self._parent[h] = prev
                self._nkids[h] = 0
                if prev is not None:
                    self._nkids[prev] += 1
            prev = h

    def evictable(self) -> int:
        """Upper bound on pages evict() could free right now: entries whose
        page only the cache references.  A refcount-1 parent blocked by a
        pinned child is counted but not currently droppable, so callers
        treat this as a shed heuristic, never a guarantee — hard admission
        calls evict() for real and rechecks."""
        return sum(1 for pid in self._pages.values()
                   if self._pool.refcount(pid) == 1)

    def to_meta(self) -> List[List[str]]:
        """JSON-able snapshot of the index: [hash_hex, page_id, parent_hex]
        per entry in LRU order (least recent first).  Pool refcounts are
        NOT included — the pool serializes its own `_refs` wholesale
        (serving/checkpoint._pool_meta), and this index's references are
        part of that total."""
        return [[h.hex(), str(self._pages[h]),
                 (self._parent[h] or b"").hex()]
                for h in self._lru]

    @classmethod
    def from_meta(cls, pool: PagePool, meta) -> "PrefixCache":
        """Rebuild an index captured by to_meta against an already-restored
        pool.  Does NOT call pool.share — the serialized refcounts already
        include this index's references (double-bumping them here would be
        exactly the leak the checkpoint fuzz hunts)."""
        cache = cls(pool)
        for h_hex, pid, parent_hex in meta:
            h = bytes.fromhex(h_hex)
            parent = bytes.fromhex(parent_hex) or None
            pid = int(pid)
            if pool.refcount(pid) < 1:
                raise ValueError(
                    f"prefix-cache meta references free page {pid}")
            cache._pages[h] = pid
            cache._lru[h] = None
            cache._parent[h] = parent
            cache._nkids.setdefault(h, 0)
            if parent is not None:
                cache._nkids[parent] = cache._nkids.get(parent, 0) + 1
        return cache

    def evict(self, n: int) -> int:
        """Free up to n pages by dropping entries, LRU-first among LEAVES
        (an entry with cached children is never dropped first: lookups
        stop at the first miss, so removing a chain root orphans every
        descendant while freeing one page).  Entries whose page a live
        sequence still shares are skipped — releasing them frees nothing
        and destroys reusable prefixes.  Returns pages actually freed."""
        freed = 0
        progress = True
        while freed < n and progress:
            progress = False
            for h in list(self._lru):
                if freed >= n:
                    break
                if self._nkids.get(h, 0) > 0:
                    continue  # not a leaf
                if self._pool.refcount(self._pages[h]) > 1:
                    continue  # shared with a live sequence
                del self._lru[h]
                self._pool.release([self._pages.pop(h)])
                parent = self._parent.pop(h)
                self._nkids.pop(h, None)
                if parent is not None and parent in self._nkids:
                    self._nkids[parent] -= 1
                freed += 1
                progress = True  # a parent may have become a leaf
        return freed


def _suffix_attention(q, k, v, t_pre, q_hi, kv_hi, window=None,
                      use_flash=None):
    """Causal attention of suffix queries (absolute positions t_pre..) over
    the full [cached prefix + suffix] context: one offset MaskSpec — col j
    visible from suffix row i iff j <= i + t_pre — instead of a separate
    kernel (the same five-scalar tile contract the ring rounds use).

    q/k may carry PADDED tail rows/cols (page-multiple shapes keep the
    enclosing jit's compile key at page granularity); the TRACED q_hi /
    kv_hi bounds keep them invisible — pad-row outputs are garbage the
    caller never reads."""
    from ..ops.masks import MaskSpec

    b, n, t_suf, d = q.shape
    s_kv = k.shape[2]
    spec = MaskSpec(jnp.int32(0), jnp.int32(q_hi), jnp.int32(kv_hi),
                    jnp.int32(1), jnp.int32(t_pre))
    if use_flash is None:
        use_flash = jax.default_backend() == "tpu"
    if use_flash:
        from ..ops.pallas_flash import flash_fwd
        from ..ops.tile import finalize

        # None carry: statically-empty initial state (no zeros round trip)
        m, lse, acc = flash_fwd(q, k, v, None, None, None, d**-0.5, spec,
                                window=window)
        return finalize(m, lse, acc, q.dtype)
    # CPU/tests: dense masked softmax (GQA via repeat; small shapes); the
    # visibility mask comes from the shared oracle (ops/masks.dense_mask)
    # so the band formula stays single-sourced with the kernels
    from ..ops.masks import dense_mask

    group = q.shape[1] // k.shape[1]
    kf = jnp.repeat(k, group, axis=1).astype(jnp.float32)
    vf = jnp.repeat(v, group, axis=1).astype(jnp.float32)
    s = jnp.einsum("bnid,bnjd->bnij", q.astype(jnp.float32), kf) * d**-0.5
    s = jnp.where(dense_mask(spec, t_suf, s_kv, window=window), s,
                  float("-inf"))
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked pad rows softmax to NaN; zero them so downstream
    # layer math (whose pad rows the caller ignores) stays finite
    p = jnp.where(jnp.isnan(p), 0.0, p)
    return jnp.einsum("bnij,bnjd->bnid", p, vf).astype(q.dtype)


def _suffix_attention_dispatch(q, k, v, t_pre, q_hi, kv_hi, cfg, mesh):
    """Head-sharded suffix attention under a tp mesh — same rationale as
    _prompt_attention_dispatch: the Pallas flash call cannot be split by
    GSPMD.  The traced q_hi/kv_hi bounds ride in replicated."""
    if _check_tp_mesh(cfg, mesh) == 1:
        return _suffix_attention(q, k, v, t_pre, q_hi=q_hi, kv_hi=kv_hi,
                                 window=cfg.window)
    spec = P(None, cfg.head_axis, None, None)
    fn = shard_map(
        lambda q_, k_, v_, qh, kh: _suffix_attention(
            q_, k_, v_, t_pre, q_hi=qh, kv_hi=kh, window=cfg.window),
        mesh=mesh,
        in_specs=(spec, spec, spec, P(), P()),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v, q_hi, kv_hi)


def init_paged_state(cfg: ModelConfig, *, slots: int, n_pages: int,
                     page: int = 128, max_pages_per_seq: int = 64,
                     quantize=False) -> Tuple[PagedState, PagePool]:
    """Fresh pool + allocator.  `page` must be a multiple of 128 (TPU lane
    tile); total pool capacity is n_pages * page tokens shared by all
    slots.  `quantize`: False = full-precision pools; True or "int8" =
    int8 pools; "fp8" = float8_e4m3fn pools — quantized pools store
    per-token fp32 dequant scales beside the pages."""
    if page % 128:
        raise ValueError(f"page size {page} must be a multiple of 128")
    shape = (n_pages, cfg.n_kv_heads, page, cfg.d_head)
    dt, tag = resolve_pool_dtype(quantize, cfg.dtype)
    k_pages = tuple(jnp.zeros(shape, dt) for _ in range(cfg.n_layers))
    v_pages = tuple(jnp.zeros(shape, dt) for _ in range(cfg.n_layers))
    table = jnp.zeros((slots, max_pages_per_seq), jnp.int32)
    lengths = jnp.zeros((slots,), jnp.int32)
    ks = vs = None
    if tag is not None:
        ks = tuple(jnp.ones(shape[:3], jnp.float32)
                   for _ in range(cfg.n_layers))
        vs = tuple(jnp.ones(shape[:3], jnp.float32)
                   for _ in range(cfg.n_layers))
    return (PagedState(k_pages, v_pages, table, lengths, ks, vs),
            PagePool(n_pages, dtype=tag))


def _gather_dequant_pages(pages, scales, idx, n_kv, d_head):
    """Gather pool pages page-contiguously, dequantizing when int8:
    idx [..., n] -> [..., n_kv, n*page, d_head].  The ONE place the
    dequant-gather convention lives (suffix prefill + multi-step read
    through it; a dtype/layout change lands in both or neither)."""
    g = pages[idx]
    if scales is not None:
        g = g.astype(jnp.float32) * scales[idx][..., None]
    g = jnp.moveaxis(g, -3, -4)
    return g.reshape(*g.shape[:-4], n_kv, g.shape[-3] * g.shape[-2], d_head)


def _scatter_pages(pages, new, page_ids, scales=None):
    """Write [1, Nkv, T, D] rope'd K/V into pool pages `page_ids` (device
    scatter; T padded to a whole number of pages by the caller).  With
    quantized pools pass the matching `scales` array: the chunks quantize
    per token into the pool's own dtype (int8 / fp8) and both arrays
    scatter TOGETHER in the same jitted program; returns (pages, scales).
    The page-and-scale atomicity here is what pool-quant-safe lint-proves
    on a live engine."""
    page = pages.shape[2]
    n = new.shape[2] // page
    # [n, Nkv, page, D] chunks in page order
    chunks = jnp.moveaxis(new[0], 1, 0).reshape(n, page, new.shape[1],
                                                new.shape[3])
    chunks = jnp.moveaxis(chunks, 2, 1)
    if scales is None:
        return pages.at[page_ids].set(chunks.astype(pages.dtype)), None
    q8, s = quantize_tokens(chunks, dtype=pages.dtype)
    return (pages.at[page_ids].set(q8),
            scales.at[page_ids].set(s))


def paged_prefill(params, tokens, state: PagedState, pool: PagePool,
                  slot: int, cfg: ModelConfig, mesh=None,
                  cache: Optional[PrefixCache] = None):
    """Absorb one prompt [T] into batch slot `slot`.

    Host-side wrapper: acquires ceil(T/page) pages, runs the jitted prompt
    pass (flash attention + paged K/V scatter), rewrites the slot's table
    row.  Returns (last-token logits [vocab] fp32, new PagedState); the
    acquired page ids are recorded in the returned state's table.

    `cache` (PrefixCache; bf16 or int8 pools — shared pages' dequant
    scales are pool state shared exactly like the K/V bytes): full pages whose
    token prefix is cached are REUSED — their K/V is never recomputed, the
    suffix runs a shorter prefill attending the cached context through an
    offset spec (_suffix_attention) — and this prompt's own full pages are
    registered for future requests.

    Tensor-parallel: pass the same `mesh` as paged_decode_step — the
    prompt's flash attention runs head-sharded through its own shard_map
    (_prompt_attention_dispatch) and the pool scatter follows the pools'
    sharding under GSPMD.
    """
    t = int(tokens.shape[0])
    page = state.k_pages[0].shape[2]
    max_pages = state.page_table.shape[1]
    n_need = -(-t // page)
    if n_need > max_pages:
        raise ValueError(f"prompt needs {n_need} pages > table width {max_pages}")
    if int(state.lengths[slot]) != 0:
        raise RuntimeError(
            f"slot {slot} is still live (len {int(state.lengths[slot])}); "
            "retire_slot first or its pages leak")
    if cache is not None:
        hashes = PrefixCache.chain(tokens, page, dtype=pool.dtype)
        # always leave >= 1 suffix token: the caller needs last-token logits
        hits = cache.lookup(hashes[: (t - 1) // page])
        if hits:
            t_pre = len(hits) * page
            suffix = tokens[t_pre:]
            t_suf = int(suffix.shape[0])
            n_suf = -(-t_suf // page)
            # page-multiple padding keeps the jit's compile key at page
            # granularity (varying prompt tails share one program); the
            # true length rides in as a traced scalar
            suffix = jnp.pad(suffix, (0, n_suf * page - t_suf))
            ids = []
            try:
                # inside the try: an exhausted-pool acquire must release
                # the lookup's hit references too, or they leak forever
                ids = pool.acquire(n_suf)
                logits, state = _paged_prefill_suffix_jit(
                    params, suffix[None, :], state,
                    jnp.asarray(hits, jnp.int32),
                    jnp.asarray(ids, jnp.int32), jnp.int32(slot),
                    jnp.int32(t_suf), cfg, t_pre, mesh)
            except Exception:
                pool.release(ids + hits)  # hits carry our lookup refs
                raise
            n_full = t // page
            # the FULL chain (hits included) so parent links are recorded
            cache.insert(hashes[:n_full],
                         hits + ids[: n_full - len(hits)])
            return logits[0], state
    ids = pool.acquire(n_need)
    try:
        logits, state = _paged_prefill_jit(
            params, tokens[None, :], state, jnp.asarray(ids, jnp.int32),
            jnp.int32(slot), cfg, mesh)
    except Exception:
        pool.release(ids)
        raise
    if cache is not None:
        cache.insert(hashes[: t // page], ids[: t // page])
    return logits[0], state


def _absorb_prompt(params, tokens, pos, state: PagedState, cfg,
                   layer_attn, layer_scatter):
    x = params["embed"].astype(cfg.dtype)[tokens]
    k_pools, v_pools, k_scs, v_scs = [], [], [], []
    for li, (p, kp, vp) in enumerate(zip(params["layers"], state.k_pages,
                                         state.v_pages)):
        q, k, v = _qkv_proj(p, x, pos, cfg)
        o = layer_attn(li, q, k, v)
        kp2, ks2, vp2, vs2 = layer_scatter(li, kp, vp, k, v)
        k_pools.append(kp2)
        v_pools.append(vp2)
        k_scs.append(ks2)
        v_scs.append(vs2)
        x = x + _attn_out(p, o)
        m, _ = _mlp(p, x, cfg, inference=True)
        x = x + m
    return _rms_norm(x, params["final_norm"]), k_pools, v_pools, k_scs, v_scs


def _write_table_row(state: PagedState, slot, row):
    return lax.dynamic_update_slice(
        state.page_table,
        jnp.pad(row, (0, state.page_table.shape[1] - row.shape[0]))[None, :],
        (slot, jnp.int32(0)),
    )


# `state` is donated (both prefill jits): serving deployments size the
# pools to fill HBM, so prefill must alias them in place — without donation
# every admission transiently needs 2x pool memory (old + new pools per
# layer) and a pool that fits would OOM on the first prompt.  The cost: if
# the jit fails at RUNTIME (post-donation), the caller's state is consumed
# and the release-and-reraise in paged_prefill only restores pool
# bookkeeping, not the state — trace/shape errors leave it retryable.
@partial(jax.jit, static_argnames=("cfg", "mesh"), donate_argnums=(2,))
def _paged_prefill_jit(params, tokens, state: PagedState, page_ids,
                       slot, cfg: ModelConfig, mesh=None):
    """slot is a TRACED int32 (one compile serves every slot); page_ids'
    static LENGTH keys the compile — one cache entry per prompt page count."""
    b, t = tokens.shape
    page = state.k_pages[0].shape[2]
    t_pad = -(-t // page) * page
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    quant = state.k_scales is not None

    def layer_attn(li, q, k, v):
        # attention consumes the full-precision K/V; only the POOL stores
        # the (possibly int8-quantized) copies
        return _prompt_attention_dispatch(q, k.astype(cfg.dtype),
                                          v.astype(cfg.dtype), cfg, mesh)

    def layer_scatter(li, kp, vp, k, v):
        pad = [(0, 0), (0, 0), (0, t_pad - t), (0, 0)]
        kp2, ks2 = _scatter_pages(
            kp, jnp.pad(k, pad), page_ids,
            state.k_scales[li] if quant else None)
        vp2, vs2 = _scatter_pages(
            vp, jnp.pad(v, pad), page_ids,
            state.v_scales[li] if quant else None)
        return kp2, ks2, vp2, vs2

    x, k_pools, v_pools, k_scs, v_scs = _absorb_prompt(
        params, tokens, pos, state, cfg, layer_attn, layer_scatter)
    logits = jnp.einsum("bsd,vd->bsv", x[:, -1:], params["lm_head"],
                        preferred_element_type=jnp.float32)[:, 0]
    table = _write_table_row(state, slot, page_ids)
    lengths = state.lengths.at[slot].set(t)
    return logits, PagedState(
        tuple(k_pools), tuple(v_pools), table, lengths,
        tuple(k_scs) if quant else None, tuple(v_scs) if quant else None)


# compile key: (cached-page count, suffix-page count) — the caller pads the
# suffix tokens to a page multiple and passes the true length as a TRACED
# scalar, so naturally varying prompt tails share one program
@partial(jax.jit, static_argnames=("cfg", "t_pre", "mesh"),
         donate_argnums=(2,))
def _paged_prefill_suffix_jit(params, tokens, state: PagedState, ctx_ids,
                              suf_ids, slot, t_suf, cfg: ModelConfig,
                              t_pre: int, mesh=None):
    """Prefill of a prompt whose first t_pre tokens' K/V already sit in
    cached pages (ctx_ids): compute q/k/v for the SUFFIX only (tokens is
    the suffix PADDED to a page multiple; t_suf the real length), attend
    the gathered cached context + suffix through one offset spec, scatter
    the suffix K/V into suf_ids, and point the slot's table row at
    [ctx_ids | suf_ids].  Shares the per-layer body (_absorb_prompt) with
    the full prefill."""
    b, t_pad = tokens.shape
    nkv, d_head = cfg.n_kv_heads, cfg.d_head
    quant = state.k_scales is not None
    pos = t_pre + jnp.broadcast_to(jnp.arange(t_pad, dtype=jnp.int32)[None],
                                   (b, t_pad))

    def layer_attn(li, q, k, v):
        # context dequantized through the shared gather (int8 shared pages'
        # scales are pool state, deterministic from token content — safe to
        # share across requests exactly like the K/V bytes); pad rows/cols
        # stay invisible through the traced q_hi/kv_hi bounds
        kc = _gather_dequant_pages(
            state.k_pages[li], state.k_scales[li] if quant else None,
            ctx_ids, nkv, d_head)[None]
        vc = _gather_dequant_pages(
            state.v_pages[li], state.v_scales[li] if quant else None,
            ctx_ids, nkv, d_head)[None]
        k_full = jnp.concatenate(
            [kc.astype(cfg.dtype), k.astype(cfg.dtype)], axis=2)
        v_full = jnp.concatenate(
            [vc.astype(cfg.dtype), v.astype(cfg.dtype)], axis=2)
        return _suffix_attention_dispatch(q, k_full, v_full, t_pre,
                                          q_hi=t_suf, kv_hi=t_pre + t_suf,
                                          cfg=cfg, mesh=mesh)

    def layer_scatter(li, kp, vp, k, v):
        kp2, ks2 = _scatter_pages(
            kp, k, suf_ids, state.k_scales[li] if quant else None)
        vp2, vs2 = _scatter_pages(
            vp, v, suf_ids, state.v_scales[li] if quant else None)
        return kp2, ks2, vp2, vs2

    x, k_pools, v_pools, k_scs, v_scs = _absorb_prompt(
        params, tokens, pos, state, cfg, layer_attn, layer_scatter)
    x_last = lax.dynamic_slice_in_dim(x, t_suf - 1, 1, axis=1)
    logits = jnp.einsum("bsd,vd->bsv", x_last, params["lm_head"],
                        preferred_element_type=jnp.float32)[:, 0]
    table = _write_table_row(state, slot, jnp.concatenate([ctx_ids, suf_ids]))
    lengths = state.lengths.at[slot].set(t_pre + t_suf)
    return logits, PagedState(
        tuple(k_pools), tuple(v_pools), table, lengths,
        tuple(k_scs) if quant else None, tuple(v_scs) if quant else None)


@partial(jax.jit, static_argnames=("cfg", "mesh"), donate_argnums=(2,))
def paged_decode_step(params, tokens, state: PagedState, cfg: ModelConfig,
                      mesh=None):
    """One decode step for EVERY live slot (ragged batch).

    tokens: [slots] int32 — next input token per slot (ignored for empty
    slots).  Every live slot must have room for one more token in its last
    page... or its NEXT page already in the table row (see
    `ensure_capacity`).  Returns ([slots, vocab] fp32 logits, new state).
    `mesh` + cfg.head_axis: tensor-parallel serving — the page pools split
    over the head axis (see _paged_attention_dispatch).
    """
    slots = tokens.shape[0]
    page = state.k_pages[0].shape[2]
    live = state.lengths > 0
    pos = jnp.where(live, state.lengths, 0)  # next position = current length
    x = params["embed"].astype(cfg.dtype)[tokens[:, None]]  # [slots, 1, d]
    group = cfg.n_heads // cfg.n_kv_heads

    # which (page, offset) receives the new token per slot
    slot_page = state.lengths // page          # page slot index in table row
    offset = state.lengths % page
    page_id = jnp.take_along_axis(state.page_table, slot_page[:, None],
                                  axis=1)[:, 0]
    # dead slots write into the reserved sink page 0 (see PagePool) so their
    # mandatory scatter never collides with a live page
    # a LIVE slot mapping to page 0 means the caller skipped ensure_capacity
    # at an exact page boundary: the new token would scatter into the sink
    # and attention would read sink garbage — per-sequence silent corruption.
    # A jitted fn can't raise, so poison that slot's logits with NaN below.
    boundary_unassigned = live & (page_id == 0)
    page_id = jnp.where(live, page_id, 0)

    quant = state.k_scales is not None
    k_pools, v_pools, k_scs, v_scs = [], [], [], []
    for li, (p, kp, vp) in enumerate(zip(params["layers"], state.k_pages,
                                         state.v_pages)):
        q, k, v = _qkv_proj(p, x, pos[:, None], cfg)
        # append: scatter each slot's new K/V row into its page
        k_row, v_row = k[:, :, 0], v[:, :, 0]
        ks = vs = None
        if quant:
            k8, k_s = quantize_tokens(k_row, dtype=kp.dtype)
            v8, v_s = quantize_tokens(v_row, dtype=vp.dtype)
            kp = kp.at[page_id, :, offset].set(k8)
            vp = vp.at[page_id, :, offset].set(v8)
            ks = state.k_scales[li].at[page_id, :, offset].set(k_s)
            vs = state.v_scales[li].at[page_id, :, offset].set(v_s)
        else:
            kp = kp.at[page_id, :, offset].set(k_row.astype(kp.dtype))
            vp = vp.at[page_id, :, offset].set(v_row.astype(vp.dtype))
        qg = q.reshape(slots, cfg.n_kv_heads, group, cfg.d_head)
        o = _paged_attention_dispatch(
            qg, kp, vp, ks, vs, state.page_table,
            state.lengths + live.astype(jnp.int32), cfg, mesh)
        o = o.reshape(slots, cfg.n_heads, 1, cfg.d_head)
        x = x + _attn_out(p, o)
        m, _ = _mlp(p, x, cfg, inference=True)
        x = x + m
        k_pools.append(kp)
        v_pools.append(vp)
        k_scs.append(ks)
        v_scs.append(vs)
    x = _rms_norm(x, params["final_norm"])
    logits = jnp.einsum("bsd,vd->bsv", x, params["lm_head"],
                        preferred_element_type=jnp.float32)[:, 0]
    logits = jnp.where(boundary_unassigned[:, None], jnp.nan, logits)
    lengths = state.lengths + live.astype(jnp.int32)
    return logits, PagedState(
        tuple(k_pools), tuple(v_pools), state.page_table, lengths,
        tuple(k_scs) if quant else None, tuple(v_scs) if quant else None)


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(2,))
def paged_multi_step(params, tokens, state: PagedState, cfg: ModelConfig):
    """Append T tokens to EVERY live slot in one pass (speculative
    verification / chunked decode): tokens [slots, T] -> ([slots, T,
    vocab] f32 logits, state with lengths += T for live slots).

    Attention dense-gathers each slot's pages (paged_decode_reference
    style): at speculative T (~4) the model matmuls dominate and the
    gather amortizes over T positions — the single-token hot path keeps
    the Pallas kernel.  The new tokens' K/V scatter into the pool FIRST,
    so the gathered context already contains them (no concat path).
    Capacity for all T tokens must be pre-assigned (provision_capacity);
    dead slots scatter into the sink page and emit garbage logits the
    caller ignores.  Speculative ROLLBACK is `rollback_tokens` — a pure
    lengths decrement, because entries past lengths are invisible; with
    int8 pools the rolled-back tokens' stale SCALES are equally invisible
    and the next append overwrites values and scales together."""
    quant = state.k_scales is not None
    slots, t = tokens.shape
    page = state.k_pages[0].shape[2]
    max_ctx = state.page_table.shape[1] * page
    group = cfg.n_heads // cfg.n_kv_heads
    live = state.lengths > 0
    base = jnp.where(live, state.lengths, 0)
    pos = base[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]  # [slots,T]
    # per-token destination pages (sink for dead slots)
    slot_ix = jnp.arange(slots)[:, None]
    pids = state.page_table[slot_ix, pos // page]
    # a LIVE slot mapping any position to page 0 means the caller skipped
    # provision_capacity: poison that slot's logits (same loud-failure
    # contract as paged_decode_step) instead of silently scattering into
    # the sink page and attending garbage
    boundary_unassigned = live & jnp.any(pids == 0, axis=1)
    pids = jnp.where(live[:, None], pids, 0)
    offs = pos % page
    col = jnp.arange(max_ctx, dtype=jnp.int32)[None, :]           # [1, ctx]
    x = params["embed"].astype(cfg.dtype)[tokens]                 # [S,T,dm]
    k_pools, v_pools, k_scs, v_scs = [], [], [], []
    for li, (p, kp, vp) in enumerate(zip(params["layers"], state.k_pages,
                                         state.v_pages)):
        q, k, v = _qkv_proj(p, x, pos, cfg)
        # scatter new K/V: [slots, T, Nkv, D] at ([slots,T] pages, offsets)
        k_rows = jnp.moveaxis(k, 1, 2)
        v_rows = jnp.moveaxis(v, 1, 2)
        ks = vs = None
        if quant:
            k8, k_s = quantize_tokens(k_rows, dtype=kp.dtype)
            v8, v_s = quantize_tokens(v_rows, dtype=vp.dtype)
            kp = kp.at[pids, :, offs].set(k8)
            vp = vp.at[pids, :, offs].set(v8)
            ks = state.k_scales[li].at[pids, :, offs].set(k_s)
            vs = state.v_scales[li].at[pids, :, offs].set(v_s)
        else:
            kp = kp.at[pids, :, offs].set(k_rows.astype(kp.dtype))
            vp = vp.at[pids, :, offs].set(v_rows.astype(vp.dtype))

        # gather each slot's full context (now including the new tokens)
        kc = _gather_dequant_pages(kp, ks, state.page_table,
                                   cfg.n_kv_heads, cfg.d_head)
        vc = _gather_dequant_pages(vp, vs, state.page_table,
                                   cfg.n_kv_heads, cfg.d_head)
        qg = q.reshape(slots, cfg.n_kv_heads, group, t, cfg.d_head)
        s = jnp.einsum("bngtd,bnjd->bngtj", qg.astype(jnp.float32),
                       kc.astype(jnp.float32)) * cfg.d_head**-0.5
        visible = col[:, None, :] <= pos[:, :, None]              # causal
        if cfg.window is not None:
            visible &= col[:, None, :] > pos[:, :, None] - cfg.window
        s = jnp.where(visible[:, None, None, :, :], s, float("-inf"))
        o = jnp.einsum("bngtj,bnjd->bngtd", jax.nn.softmax(s, axis=-1),
                       vc.astype(jnp.float32))
        o = o.reshape(slots, cfg.n_heads, t, cfg.d_head).astype(cfg.dtype)
        x = x + _attn_out(p, o)
        m, _ = _mlp(p, x, cfg, inference=True)
        x = x + m
        k_pools.append(kp)
        v_pools.append(vp)
        k_scs.append(ks)
        v_scs.append(vs)
    x = _rms_norm(x, params["final_norm"])
    logits = jnp.einsum("btd,vd->btv", x, params["lm_head"],
                        preferred_element_type=jnp.float32)
    logits = jnp.where(boundary_unassigned[:, None, None], jnp.nan, logits)
    lengths = state.lengths + t * live.astype(jnp.int32)
    return logits, PagedState(
        tuple(k_pools), tuple(v_pools), state.page_table, lengths,
        tuple(k_scs) if quant else None, tuple(v_scs) if quant else None)


def rollback_tokens(state: PagedState, slot: int, n: int) -> PagedState:
    """Host-side: un-append the last n tokens of `slot` (speculative
    rejection).  Pure lengths bookkeeping — entries past lengths are
    invisible and the next append overwrites them; pages stay assigned."""
    length = int(state.lengths[slot])
    if n < 0 or n >= length:
        # n == length would zero the slot while its table row still owns
        # pages: retire_slot early-returns on length 0 and the pages leak
        raise ValueError(f"cannot roll back {n} of {length} tokens "
                         "(at least one must remain; retire_slot frees)")
    return state._replace(lengths=state.lengths.at[slot].set(length - n))


def ensure_capacity(state: PagedState, pool: PagePool, slot: int) -> PagedState:
    """Host-side: guarantee slot has a page for its next token, acquiring
    one if its last page is full.  Call before paged_decode_step."""
    length = int(state.lengths[slot])
    page = state.k_pages[0].shape[2]
    if length % page != 0 or length == 0:
        return state  # room in the current page (or empty slot)
    slot_page = length // page
    if slot_page >= state.page_table.shape[1]:
        raise RuntimeError(f"slot {slot} exceeded max_pages_per_seq")
    if int(state.page_table[slot, slot_page]) != 0:
        # idempotent: a prior (possibly aborted) pass already assigned the
        # page — page 0 is the reserved sink, so 0 reliably means unassigned
        return state
    (new_id,) = pool.acquire(1)
    table = state.page_table.at[slot, slot_page].set(new_id)
    return state._replace(page_table=table)


def provision_capacity(state: PagedState, pool: PagePool, slot: int,
                       n_tokens: int) -> PagedState:
    """Host-side: pre-assign every page `slot` needs to absorb `n_tokens`
    MORE tokens, so a decode loop of that many steps needs no further
    host-side allocation (one host fetch here vs one `ensure_capacity`
    length sync per slot per step in the hot loop)."""
    if n_tokens <= 0:
        return state
    length = int(state.lengths[slot])
    if length == 0:
        raise RuntimeError(
            f"slot {slot} is empty; paged_prefill acquires its own pages — "
            "provisioning now would leak them when prefill rewrites the row")
    page = state.k_pages[0].shape[2]
    last = length + n_tokens - 1  # final position to be written
    need_through = last // page   # highest table column required
    if need_through >= state.page_table.shape[1]:
        raise RuntimeError(
            f"slot {slot}: {n_tokens} more tokens need table column "
            f"{need_through} >= max_pages_per_seq {state.page_table.shape[1]}")
    row = np.asarray(state.page_table[slot])  # one fetch for all columns
    missing = [p for p in range(need_through + 1) if row[p] == 0]
    if not missing:
        return state
    ids = pool.acquire(len(missing))
    table = state.page_table.at[slot, np.asarray(missing)].set(
        np.asarray(ids, dtype=np.int32))
    return state._replace(page_table=table)


def retire_slot(state: PagedState, pool: PagePool, slot: int) -> PagedState:
    """Host-side: release a finished sequence's pages and empty the slot."""
    length = int(state.lengths[slot])
    if length == 0:
        return state
    # release EVERY assigned page in the row, used or pre-acquired
    # (ensure_capacity adds one ahead; provision_capacity may add many) —
    # page 0 is the unassigned sentinel, so non-zero means acquired.
    # Zero the row so a later ensure/provision on the re-prefilled slot
    # can't mistake stale ids for assignments.
    row = np.asarray(state.page_table[slot])
    ids = [int(i) for i in row if i != 0]
    pool.release(ids)
    return state._replace(
        lengths=state.lengths.at[slot].set(0),
        page_table=state.page_table.at[slot].set(0))
