"""Ragged paged attention: ONE Pallas launch for a mixed prefill+decode
token batch against the paged KV pool.

ops/paged_attention.py serves exactly one query token per sequence per
launch — fine for pure decode, but a continuous-batching engine lives on
MIXED steps: some slots absorbing a prompt chunk (tens of query tokens),
others decoding (one), all against the same page pool.  Running prefill
and decode as separate programs forfeits the batch (two launches, two
sets of ragged predication, and the prefill chunk's MXU work cannot soak
up the decode slots' latency).  This kernel is the single-launch design
(PAPERS.md "Ragged Paged Attention"):

  * The grid walks `(sequence, kv-head, q-block, page-slot)`.  Each
    sequence brings its OWN query token count `q_lens[s]` (1 = decode,
    up to the chunk size = prefill); per-sequence page tables arrive via
    scalar prefetch and are consulted in the kv index maps, exactly like
    the decode kernel — each grid step DMAs one pool page.
  * GQA folds the query-head group INTO the q tile rows: block rows are
    laid out `token-major x group` (row r = token r//G, head r%G), so a
    decode step (1 token x G heads) and a prefill block (block_q tokens
    x G heads) are the same [rows, page] score tile shape.
  * Causality is enforced within each sequence: query token t of
    sequence s sits at absolute position `kv_lens[s] - q_lens[s] + t`
    and sees cached positions `<= ` that (sliding window optional).
    Page-slots wholly outside a block's visible band are predicated off
    and their DMAs clamped onto a live page (consecutive duplicate block
    indexes collapse into one fetch) — cost per sequence ∝ its length.
  * The inner online-softmax update is OP-FOR-OP the decode kernel's
    (same exp2 rebase, same masking order, same fp32 accumulation), so a
    decode row (q_len == 1) is BIT-IDENTICAL to paged_decode_attention —
    tested, not aspirational: the serving engine may route any step
    through either kernel and streams must not fork.

Interpret mode runs the same grid on CPU, which is how tier-1 proves the
mixed-batch parity (dense oracle + decode-kernel bit compare) off-TPU.
`ragged_supported()` is the capability probe: the serving engine falls
back to the dense-gather path (models/serving) with a labeled
`burst.fused_fallback{pass=serve}` counter instead of raising.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_flash import LOG2E, NEG_INF, VMEM_LIMIT, _interpret_default

# hard ceiling on the padded rows-per-block tile (block_q * group rounded
# to sublanes): past this the [rows, page] score tile plus the fp32
# accumulator stops fitting VMEM comfortably at page=128, d=128
_MAX_BLOCK_ROWS = 1024


def _ragged_kernel(
    table_ref, n_live_ref, kvlen_ref, qlen_ref, lo_ref,  # scalar prefetch
    *refs,
    scale, page, n_slots, bq, g, quant, window, emit_partials=False,
):
    if quant:
        q_ref, k_ref, v_ref, ks_ref, vs_ref = refs[:5]
        rest = refs[5:]
    else:
        q_ref, k_ref, v_ref = refs[:3]
        rest = refs[3:]
    if emit_partials:
        o_ref, m_ref, l_ref = rest[:3]
        m_scr, l_scr, acc_scr = rest[3:]
    else:
        o_ref = rest[0]
        m_scr, l_scr, acc_scr = rest[1:]
    s_ = pl.program_id(0)
    qi = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_len = qlen_ref[s_]
    kv_len = kvlen_ref[s_]
    q_start = kv_len - q_len          # absolute position of query token 0
    t0 = qi * bq                      # first query token of this block
    # absolute position of the block's LAST real token: everything at or
    # below it is potentially visible, pages wholly above it are dead.
    # (for q_len == 1 this reduces to the decode kernel's j < n_live test)
    p_max = q_start + jnp.minimum(q_len, t0 + bq) - 1
    live = (t0 < q_len) & (j * page <= p_max) & (j >= lo_ref[s_] // page)

    @pl.when(live)
    def _accum():
        q = q_ref[0, 0, :, :] * (scale * LOG2E)
        k_tile = k_ref[0, :, :]
        if quant:
            # int8 and fp8 e4m3 both embed EXACTLY in bf16 (8-bit mantissa
            # covers int8's 8 bits and e4m3's 4-bit mantissa); the fp32
            # column rescale below is the whole dequant for either dtype
            k_tile = k_tile.astype(jnp.bfloat16)
        s = jax.lax.dot_general(
            q, k_tile, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if quant:
            # per-token dequant as a column rescale (decode kernel's trick)
            s = s * ks_ref[0]
        pos = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # row r = query token t0 + r//g at absolute position qp; rows past
        # q_len are wrapper padding — their outputs are sliced away, so
        # they need no extra masking (their q rows are zeros / pad tokens
        # and every op below is row-independent)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        qp = q_start + t0 + row // g
        valid = pos <= qp
        if window is not None:
            valid &= pos >= qp - window + 1
        s = jnp.where(valid, s, NEG_INF)
        m_prev, l_prev = m_scr[:], l_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.where(m_prev >= m_new, 1.0, jnp.exp2(m_prev - m_new))
        p = jnp.exp2(s - m_new)
        p = jnp.where(valid, p, 0.0)
        m_scr[:] = m_new
        l_scr[:] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        if quant:
            pv = jax.lax.dot_general(
                (p * vs_ref[0]).astype(jnp.bfloat16),
                v_ref[0, :, :].astype(jnp.bfloat16),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        else:
            pv = jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0, :, :], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        acc_scr[:] = acc_scr[:] * alpha + pv

    @pl.when(j == n_slots - 1)
    def _finish():
        if emit_partials:
            # split-k contract (models/dist_decode._merge, base-2 domain):
            # hand back the UNNORMALIZED accumulator plus the (m, l)
            # running-softmax state so the caller can LSE-merge this
            # partial with another band's.  m/l broadcast across the lane
            # tile; the host reads lane 0.
            o_ref[0, 0, :, :] = acc_scr[:]
            m_ref[0, 0, :, :] = jnp.broadcast_to(m_scr[:], m_ref.shape[2:])
            l_ref[0, 0, :, :] = jnp.broadcast_to(l_scr[:], l_ref.shape[2:])
        else:
            # fully-masked blocks (idle slot / past-q_len block) emit zeros
            l = jnp.where(l_scr[:] > 0, l_scr[:], 1.0)
            o_ref[0, 0, :, :] = (acc_scr[:] / l).astype(o_ref.dtype)


def _block_rows(block_q: int, group: int) -> int:
    """Padded rows per q block: block_q tokens x group heads, rounded up
    to the 8-sublane tile (>= 8, matching _pad_group for block_q == 1)."""
    return max(8, -(-(block_q * group) // 8) * 8)


def ragged_supported(*, n_kv_heads, n_q_heads, q_tokens, d_head, page,
                     quantized=False, block_q=8, interpret=None):
    """Capability probe: None when ragged_paged_attention can serve this
    shape, else a human-readable reason whose PREFIX is a stable key (the
    serving engine maps it to a bounded fallback-counter label): probe
    first, fall back loudly."""
    if interpret is None:
        interpret = _interpret_default()
    if q_tokens < 1:
        return f"empty q chunk: q_tokens {q_tokens} < 1"
    if n_q_heads % n_kv_heads:
        return (f"GQA group mismatch: {n_q_heads} query heads not a "
                f"multiple of {n_kv_heads} kv heads")
    if page % 128:
        return f"page size {page} is not a multiple of the 128 lane tile"
    group = n_q_heads // n_kv_heads
    bq = max(1, min(block_q, q_tokens))
    rows = _block_rows(bq, group)
    if rows > _MAX_BLOCK_ROWS:
        return (f"q-block rows {rows} (block_q {bq} x group {group}, "
                f"padded) exceed the {_MAX_BLOCK_ROWS}-row tile budget")
    # VMEM plan: q + o + acc tiles (fp32) plus a double-buffered k/v page
    kv_bytes = 1 if quantized else 2
    plan = (rows * d_head * 4 * 3          # q, o, acc
            + rows * (page + 2) * 4        # scores + m/l columns
            + 4 * page * d_head * kv_bytes)  # k/v pages, double buffered
    if plan > VMEM_LIMIT:
        return (f"VMEM plan {plan} bytes exceeds the {VMEM_LIMIT} budget "
                f"(page {page}, d_head {d_head}, rows {rows})")
    if not interpret and d_head % 128:
        # compiled Mosaic wants lane-aligned head dims; interpret mode
        # (CPU tier-1) has no such constraint
        return f"head dim {d_head} is not lane-aligned (128) for Mosaic"
    return None


def _fold_groups(q, n_kv, group, n_qblk, bq, rows):
    """[S, Nq, QT, D] -> [S, Nkv, n_qblk*rows, D] token-major x group row
    layout, zero-padded to bq tokens per block and `rows` sublanes."""
    s, _, qt, d = q.shape
    qtp = n_qblk * bq
    q = q.reshape(s, n_kv, group, qt, d)
    if qtp != qt:
        q = jnp.pad(q, [(0, 0), (0, 0), (0, 0), (0, qtp - qt), (0, 0)])
    q = jnp.moveaxis(q, 2, 3)                       # [S, Nkv, QTp, G, D]
    q = q.reshape(s, n_kv, n_qblk, bq * group, d)
    if rows != bq * group:
        q = jnp.pad(q, [(0, 0), (0, 0), (0, 0), (0, rows - bq * group),
                        (0, 0)])
    return q.reshape(s, n_kv, n_qblk * rows, d)


def _unfold_groups(o, n_q, group, n_qblk, bq, rows, qt):
    """Inverse of _fold_groups: [S, Nkv, n_qblk*rows, D] -> [S, Nq, QT, D]."""
    s, n_kv, _, d = o.shape
    o = o.reshape(s, n_kv, n_qblk, rows, d)[:, :, :, :bq * group]
    o = o.reshape(s, n_kv, n_qblk * bq, group, d)
    o = jnp.moveaxis(o, 3, 2)                       # [S, Nkv, G, QTp, D]
    return o.reshape(s, n_q, n_qblk * bq, d)[:, :, :qt]


def ragged_paged_attention(q, k_pages, v_pages, page_table, q_lens, kv_lens,
                           *, k_scales=None, v_scales=None, window=None,
                           scale=None, block_q=8, interpret=None,
                           ctx_lo=None, emit_partials=False):
    """Mixed prefill+decode ragged attention against a paged KV pool.

    q          [S, Nq, QT, D]   query tokens per slot; slot s's token t is
                                the token at absolute position
                                kv_lens[s] - q_lens[s] + t.  Rows at or
                                past q_lens[s] are padding (outputs there
                                are garbage the caller must ignore).
    k_pages    [P, Nkv, page, D]  shared pool — the new tokens' K/V must
    v_pages    [P, Nkv, page, D]  already be scattered in (the serving
                                  step scatters BEFORE attending)
    page_table [S, n_slots] int32 pool page per (slot, table column)
    q_lens     [S] int32        query tokens this launch (0 = idle slot;
                                1 = decode; >1 = prefill chunk)
    kv_lens    [S] int32        total live tokens INCLUDING this launch's
    window     static int       sliding-window band per query position
    k_scales / v_scales         per-token fp32 dequant scales for 1 B/elem
                                (int8 or fp8-e4m3) pools; either quantized
                                dtype rides the same bf16-embed + column
                                rescale, so the kernel never branches on it
    block_q    static int       query tokens per grid block

    ctx_lo / emit_partials are the split-k hooks the grouped shared-prefix
    front-end (ragged_paged_attention_grouped) drives; plain callers leave
    them at their defaults and the traced program is unchanged:

    ctx_lo     [S] int32        PAGE-ALIGNED per-slot lower context bound —
                                pool positions below it are excluded (their
                                page-slots predicated off, exactly the `lo`
                                page-skip the window path uses)
    emit_partials  static bool  return the unnormalized split-k partial
                                (acc [S,Nq,QT,D], m [S,Nq,QT,1],
                                l [S,Nq,QT,1], all fp32, base-2 softmax
                                domain) instead of the normalized output

    Returns [S, Nq, QT, D] in q's dtype.  A pure-decode batch (QT == 1)
    is bit-identical to paged_decode_attention on the same pool.
    """
    s, n_q, qt, d = q.shape
    n_kv = k_pages.shape[1]
    page = k_pages.shape[2]
    n_slots = page_table.shape[1]
    if n_q % n_kv:
        raise ValueError(f"{n_q} query heads not grouped by {n_kv} kv heads")
    group = n_q // n_kv
    if scale is None:
        scale = d**-0.5
    if interpret is None:
        interpret = _interpret_default()
    quant = k_scales is not None
    if quant != (v_scales is not None):
        raise ValueError("k_scales and v_scales must be given together")

    bq = max(1, min(block_q, qt))
    n_qblk = -(-qt // bq)
    rows = _block_rows(bq, group)
    q_rows = _fold_groups(q, n_kv, group, n_qblk, bq, rows)

    q_lens = q_lens.astype(jnp.int32)
    kv_lens = kv_lens.astype(jnp.int32)
    n_live = -(-kv_lens // page)
    if window is None:
        lo = jnp.zeros_like(kv_lens)
    else:
        # lower edge of query token 0's band (the widest in the batch);
        # per-row edges re-tighten inside the kernel.  q_len == 1 reduces
        # to the decode kernel's max(len - window, 0).
        lo = jnp.maximum(kv_lens - q_lens - window + 1, 0)
    if ctx_lo is not None:
        # page-aligned exclusion of a shared-prefix band: whole pages drop
        # out of the `live` predicate, so no sub-page masking is needed
        lo = jnp.maximum(lo, ctx_lo.astype(jnp.int32))

    def q_map(s_, h, qi, j, table, n_live_, kvlen_, qlen_, lo_):
        return (s_, h, qi, 0)

    def kv_map(s_, h, qi, j, table, n_live_, kvlen_, qlen_, lo_):
        # clamp dead page-slots into the live band (duplicate consecutive
        # indexes collapse into one DMA); empty slots stay in range
        slot = jnp.clip(j, lo_[s_] // page, jnp.maximum(n_live_[s_] - 1, 0))
        return (table[s_, slot], h, 0, 0)

    kernel = functools.partial(
        _ragged_kernel, scale=scale, page=page, n_slots=n_slots,
        bq=bq, g=group, quant=quant, window=window,
        emit_partials=emit_partials,
    )
    in_specs = [
        pl.BlockSpec((1, 1, rows, d), q_map),
        pl.BlockSpec((None, 1, page, d), kv_map),
        pl.BlockSpec((None, 1, page, d), kv_map),
    ]
    inputs = [page_table, n_live, kv_lens, q_lens, lo,
              q_rows, k_pages, v_pages]
    if quant:
        def sc_map(s_, h, qi, j, table, n_live_, kvlen_, qlen_, lo_):
            return kv_map(s_, h, qi, j, table, n_live_, kvlen_, qlen_,
                          lo_)[:3] + (0,)

        in_specs.append(pl.BlockSpec((None, 1, 1, page), sc_map))
        in_specs.append(pl.BlockSpec((None, 1, 1, page), sc_map))
        inputs += [k_scales[:, :, None, :], v_scales[:, :, None, :]]
    out_spec = pl.BlockSpec((1, 1, rows, d), q_map)
    out_shape = jax.ShapeDtypeStruct((s, n_kv, n_qblk * rows, d), q.dtype)
    if emit_partials:
        # acc stays fp32 (unnormalized); m/l ride in d-wide lane tiles
        f32 = functools.partial(jax.ShapeDtypeStruct,
                                (s, n_kv, n_qblk * rows, d))
        out_shape = (f32(jnp.float32), f32(jnp.float32), f32(jnp.float32))
        out_spec = (out_spec, out_spec, out_spec)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(s, n_kv, n_qblk, n_slots),
        in_specs=in_specs,
        out_specs=out_spec,
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT,
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(*inputs)
    unfold = functools.partial(_unfold_groups, n_q=n_q, group=group,
                               n_qblk=n_qblk, bq=bq, rows=rows, qt=qt)
    if emit_partials:
        acc, m, l = o
        return unfold(acc), unfold(m)[..., :1], unfold(l)[..., :1]
    return unfold(o)


def ragged_paged_attention_grouped(
        q, k_pages, v_pages, page_table, q_lens, kv_lens, *,
        group_id, shared_table, shared_lens,
        k_scales=None, v_scales=None, window=None, scale=None,
        block_q=8, interpret=None):
    """Shared-prefix grouped variant: score each group's shared pages ONCE,
    LSE-merge with every member's private-suffix partial.

    Co-batched requests admitted through the prefix cache pin the SAME
    physical pages for their common prompt prefix.  The plain launch walks
    every slot's full page table, re-fetching (and re-scoring) those pages
    per member.  Here the pool gather for the shared band happens once per
    GROUP (`k_pages[shared_table]` — G x n_sh pages instead of S x n_slots),
    members score against the group buffer, and the result is merged with
    the private band exactly the way models/dist_decode._merge folds split-k
    partials — in the kernel's base-2 softmax domain, so the merge algebra
    matches the one-launch online softmax op for op.

    group_id     [S] int32      group index per slot; slots whose group has
                                shared_lens == 0 degenerate to the plain
                                launch result (merge with an empty partial)
    shared_table [G, n_sh] int32  pool pages of each group's shared prefix
                                (page-0 padded past its length)
    shared_lens  [G] int32      shared tokens per group — MUST be a page
                                multiple (the private band's page-skip is
                                whole-page)

    Every member's shared pages must be a prefix of its own page table
    (the admission path guarantees this: hit pages are assigned before
    private pages), and causal masking is applied per query row, so a
    query INSIDE the shared band (mid-prefill after a partial hit) still
    sees exactly positions <= its own.

    Returns [S, Nq, QT, D] in q's dtype — numerically equal to the plain
    launch up to split-k merge reassociation (parity-tested, not bitwise).
    """
    s, n_q, qt, d = q.shape
    n_kv = k_pages.shape[1]
    page = k_pages.shape[2]
    group = n_q // n_kv
    n_sh = shared_table.shape[1]
    if scale is None:
        scale = d**-0.5
    group_id = group_id.astype(jnp.int32)
    shared_lens = shared_lens.astype(jnp.int32)
    ctx_lo = shared_lens[group_id]

    # private band: the one-launch kernel, pages below the shared boundary
    # predicated off, partials handed back unnormalized
    acc_p, m_p, l_p = ragged_paged_attention(
        q, k_pages, v_pages, page_table, q_lens, kv_lens,
        k_scales=k_scales, v_scales=v_scales, window=window, scale=scale,
        block_q=block_q, interpret=interpret,
        ctx_lo=ctx_lo, emit_partials=True)

    # shared band: ONE pool gather per group, then a broadcast view per
    # member.  The quant path mirrors the kernel's precision op for op
    # (k scored as bf16 with a post-dot column rescale, p*v folded through
    # bf16) so grouped-vs-plain parity holds at merge-reassociation level
    # rather than dequant level.
    quant = k_scales is not None
    g_n = shared_table.shape[0]

    def _flat(pages, width=d):
        t = jnp.moveaxis(pages, 2, 1)          # [G, Nkv, n_sh, page, ...]
        return t.reshape(g_n, n_kv, n_sh * page, *t.shape[4:])[group_id]

    k_s = _flat(k_pages[shared_table])         # [S, Nkv, Tsh, D]
    v_s = _flat(v_pages[shared_table])
    qg = q.reshape(s, n_kv, group, qt, d).astype(jnp.float32)
    qg = qg * (scale * LOG2E)                  # base-2 domain, as the kernel
    if quant:
        k_cols = _flat(k_scales[shared_table][..., None])[..., 0]
        v_cols = _flat(v_scales[shared_table][..., None])[..., 0]
        sc = jnp.einsum("bngtd,bnjd->bngtj", qg, k_s.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
        sc = sc * k_cols[:, :, None, None, :]
    else:
        sc = jnp.einsum("bngtd,bnjd->bngtj", qg, k_s.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
    qp = (kv_lens - q_lens)[:, None] + jnp.arange(qt)[None, :]   # [S, QT]
    col = jnp.arange(n_sh * page)
    valid = (col[None, None, :] <= qp[:, :, None])
    valid &= col[None, None, :] < shared_lens[group_id][:, None, None]
    if window is not None:
        valid &= col[None, None, :] >= qp[:, :, None] - window + 1
    sc = jnp.where(valid[:, None, None, :, :], sc, NEG_INF)
    m_s = jnp.max(sc, axis=-1, keepdims=True)        # [S,Nkv,G,QT,1]
    p = jnp.where(valid[:, None, None, :, :], jnp.exp2(sc - m_s), 0.0)
    l_s = jnp.sum(p, axis=-1, keepdims=True)
    if quant:
        acc_s = jnp.einsum(
            "bngtj,bnjd->bngtd",
            (p * v_cols[:, :, None, None, :]).astype(jnp.bfloat16),
            v_s.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
    else:
        acc_s = jnp.einsum("bngtj,bnjd->bngtd", p, v_s.astype(jnp.float32),
                           preferred_element_type=jnp.float32)
    m_s = m_s.reshape(s, n_q, qt, 1)
    l_s = l_s.reshape(s, n_q, qt, 1)
    acc_s = acc_s.reshape(s, n_q, qt, d)

    # split-k merge (dist_decode._merge in base 2, -inf guarded the way
    # the kernel guards its alpha rebase)
    m_g = jnp.maximum(m_p, m_s)
    a_p = jnp.where(m_p >= m_g, 1.0, jnp.exp2(m_p - m_g))
    a_s = jnp.where(m_s >= m_g, 1.0, jnp.exp2(m_s - m_g))
    l_g = l_p * a_p + l_s * a_s
    acc_g = acc_p * a_p + acc_s * a_s
    o = acc_g / jnp.where(l_g > 0, l_g, 1.0)
    return o.astype(q.dtype)


def ragged_paged_reference(q, k_pages, v_pages, page_table, q_lens, kv_lens,
                           *, k_scales=None, v_scales=None, window=None,
                           scale=None):
    """jnp oracle: dense-gathers every slot's pages and runs masked
    softmax with the per-row causal band.  O(S·n_slots·page) memory —
    tests only.  Padding rows (t >= q_lens) and idle slots emit zeros."""
    if k_scales is not None:
        k_pages = k_pages.astype(jnp.float32) * k_scales[..., None]
        v_pages = v_pages.astype(jnp.float32) * v_scales[..., None]
    s, n_q, qt, d = q.shape
    n_kv = k_pages.shape[1]
    page = k_pages.shape[2]
    n_slots = page_table.shape[1]
    group = n_q // n_kv
    if scale is None:
        scale = d**-0.5
    k = k_pages[page_table]  # [S, n_slots, Nkv, page, D]
    v = v_pages[page_table]
    k = jnp.moveaxis(k, 2, 1).reshape(s, n_kv, n_slots * page, d)
    v = jnp.moveaxis(v, 2, 1).reshape(s, n_kv, n_slots * page, d)
    qg = q.reshape(s, n_kv, group, qt, d)
    sc = jnp.einsum("bngtd,bnjd->bngtj", qg.astype(jnp.float32),
                    k.astype(jnp.float32)) * scale
    qp = (kv_lens - q_lens)[:, None] + jnp.arange(qt)[None, :]  # [S, QT]
    col = jnp.arange(n_slots * page)[None, None, :]
    valid = col <= qp[:, :, None]
    if window is not None:
        valid &= col >= (qp[:, :, None] - window + 1)
    valid &= (jnp.arange(qt)[None, :] < q_lens[:, None])[:, :, None]
    sc = jnp.where(valid[:, None, None, :, :], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    p = jnp.where(valid[:, None, None, :, :], p, 0.0)  # masked rows -> 0
    o = jnp.einsum("bngtj,bnjd->bngtd", p, v.astype(jnp.float32))
    return o.reshape(s, n_q, qt, d).astype(q.dtype)
