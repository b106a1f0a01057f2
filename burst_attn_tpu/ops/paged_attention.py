"""Ragged paged-attention decode kernel (TPU serving-side native kernel).

The reference is a training-time op library; its inference story stops at
"call the op".  A complete framework serves, and serving on TPU wants a
PAGED KV cache: the dense [B, Nkv, max_seq, D] cache the basic decoder uses
(models/decode.py) allocates worst-case memory per sequence and pays
O(max_seq) attention compute per decode step regardless of the actual
context length.  This module provides the kernel half of the paged design
(models/paged_decode.py holds the pool/cache manager):

  * KV lives in a shared pool of fixed-size pages `[n_pages, Nkv, page, D]`.
    A sequence owns a list of pages (its row of the page table); memory
    scales with TOKENS IN USE, not max_seq, and sequences of wildly
    different lengths batch together (ragged batching).
  * One decode step attends each sequence's single new query against its
    own pages only.  The Pallas grid walks `(batch, kv-head, page-slot)`;
    the PAGE TABLE is delivered via scalar prefetch and consulted in the
    kv index maps, so each grid step DMAs exactly the pool page it needs —
    the gather never materializes a contiguous copy of the cache.
  * Ragged lengths: slots past a sequence's live page count are clamped to
    its last live page (Pallas collapses consecutive identical block
    indexes into one fetch) and skipped by predication; the final partial
    page is masked by position.  Cost per sequence ∝ its length.

GQA folds the query-head group into the kernel's q tile: q arrives
[B, Nkv, G, D] (G = n_heads / n_kv_heads query rows per kv head) and each
grid step computes a [G, page] score tile — at G=8, d=128 this is a real
MXU tile, not a matvec.

Reference parity anchor: the closest reference analogue is the flash-attn
CUDA decode path (burst_utils.py:149-176 drives the same kernels at T=1);
paged layout + ragged batching are TPU-first extensions (no reference
equivalent — see PAPERS.md "Ragged Paged Attention").
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_flash import LOG2E, NEG_INF, VMEM_LIMIT, _interpret_default


def _pad_group(q):
    """Pad the query-group dim to the 8-sublane minimum tile."""
    g = q.shape[2]
    gp = max(8, -(-g // 8) * 8)
    if gp != g:
        q = jnp.pad(q, [(0, 0), (0, 0), (0, gp - g), (0, 0)])
    return q, gp


def _decode_kernel(
    table_ref, n_live_ref, len_ref, lo_ref,  # scalar prefetch
    *refs,
    scale, page, n_slots, quant,
):
    if quant:
        (q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    live = (j < n_live_ref[b]) & (j >= lo_ref[b] // page)

    @pl.when(live)
    def _accum():
        q = q_ref[0, 0, :, :] * (scale * LOG2E)
        k_tile = k_ref[0, :, :]
        if quant:
            k_tile = k_tile.astype(jnp.bfloat16)
        s = jax.lax.dot_general(
            q, k_tile, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if quant:
            # per-token dequant folds into a column rescale of the scores:
            # q . (k_t * s_t) = (q . k_t) * s_t.  The matmuls run in bf16
            # (int8 casts exactly — |v| <= 127 — and fp8 e4m3's 4-bit
            # mantissa embeds in bf16's 8); quantization buys MEMORY, not
            # MXU throughput here.  One [G, page] multiply on the VPU.
            s = s * ks_ref[0]  # [1, page] broadcast over [G, page]
        # mask the final partial page's tail and (sliding window) the
        # positions below the window's lower edge
        pos = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = (pos < len_ref[b]) & (pos >= lo_ref[b])
        s = jnp.where(valid, s, NEG_INF)
        m_prev, l_prev = m_scr[:], l_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.where(m_prev >= m_new, 1.0, jnp.exp2(m_prev - m_new))
        p = jnp.exp2(s - m_new)
        p = jnp.where(valid, p, 0.0)
        m_scr[:] = m_new
        l_scr[:] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        if quant:
            # symmetric trick on the v side: p @ (v_t * s_t) = (p * s_t) @ v_t
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
        # empty sequences (l == 0) emit zeros rather than NaN
        l = jnp.where(l_scr[:] > 0, l_scr[:], 1.0)
        o_ref[0, 0, :, :] = (acc_scr[:] / l).astype(o_ref.dtype)


# 1 B/elem pool storage dtypes and the full-range absmax each scale maps
# onto: int8 rounds into [-127, 127]; fp8 e4m3fn casts into +-448 (the
# format's largest finite).  Both dequantize as a per-token column rescale
# inside the kernels, so they share every downstream code path.
QUANT_DTYPES = {
    "int8": (jnp.int8, 127.0),
    "fp8": (jnp.float8_e4m3fn, 448.0),
}


def _quant_range(dtype):
    """(canonical name, full-scale range) for a 1 B pool dtype."""
    dt = jnp.dtype(dtype)
    for name, (cand, rng) in QUANT_DTYPES.items():
        if dt == jnp.dtype(cand):
            return name, rng
    raise ValueError(f"unsupported quantized pool dtype {dt!r} "
                     f"(one of {sorted(QUANT_DTYPES)})")


def quantize_tokens(x, dtype=jnp.int8):
    """Per-token symmetric quantization of [..., T, D] K/V rows into a
    1 B/elem pool dtype: returns (quantized values, f32 scales [..., T]).
    scale = max|x| / range per token (127 for int8, 448 for fp8 e4m3fn);
    zero rows get scale 1 (they dequantize to exact zeros).  int8 rounds
    and clips; fp8 casts directly (the cast IS the rounding)."""
    name, rng = _quant_range(dtype)
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    s = jnp.where(amax > 0, amax / rng, 1.0)
    xs = x.astype(jnp.float32) / s[..., None]
    if name == "int8":
        q = jnp.clip(jnp.round(xs), -rng, rng).astype(jnp.int8)
    else:
        q = xs.astype(jnp.float8_e4m3fn)
    return q, s


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                           k_scales=None, v_scales=None,
                           window=None, scale=None, interpret=None):
    """One ragged decode step against a paged KV pool.

    q          [B, Nkv, G, D]   one new token per sequence, query heads
                                grouped under their kv head (G >= 1)
    k_pages    [P, Nkv, page, D]  shared pool (page = tokens per page,
    v_pages    [P, Nkv, page, D]   a multiple of 128)
    page_table [B, S] int32     pool page id per (sequence, slot); slots
                                at or past ceil(len/page) are ignored
    lengths    [B] int32        live tokens per sequence (0 = empty)
    window     static int       sliding-window attention: the new token (at
                                position lengths-1) sees only the last
                                `window` positions — pages fully below the
                                band are skipped, so cost ∝ window
    k_scales / v_scales  [P, Nkv, page] f32: per-token dequant scales for
                QUANTIZED pools (quantize_tokens: int8 or fp8 e4m3fn —
                the kernel is dtype-agnostic, both cast exactly to bf16)
                — both or neither.  The dequant rides the matmuls as
                column rescales; pool memory halves vs bf16 (1 B + 4 B
                scale per 128·2B token), quarters vs fp32.

    Returns [B, Nkv, G, D] attention output in q's dtype.
    """
    b, n_kv, g, d = q.shape
    page = k_pages.shape[2]
    n_slots = page_table.shape[1]
    if scale is None:
        scale = d**-0.5
    if interpret is None:
        interpret = _interpret_default()
    q, gp = _pad_group(q)

    n_live = -(-lengths // page)  # pages in use per sequence
    # lower edge of the visible band (matches models/decode.py:108-111:
    # the query at position len-1 sees positions >= len - window)
    if window is None:
        lo = jnp.zeros_like(lengths)
    else:
        lo = jnp.maximum(lengths - window, 0)

    def q_map(b_, h, j, table, n_live_, len_, lo_):
        return (b_, h, 0, 0)

    def kv_map(b_, h, j, table, n_live_, len_, lo_):
        # clamp dead slots into the live band: consecutive duplicate
        # indexes collapse into a single DMA.  max(n_live-1, 0) keeps empty
        # sequences in range (their steps are fully predicated off).
        slot = jnp.clip(j, lo_[b_] // page, jnp.maximum(n_live_[b_] - 1, 0))
        return (table[b_, slot], h, 0, 0)

    quant = k_scales is not None
    if quant != (v_scales is not None):
        raise ValueError("k_scales and v_scales must be given together")
    kernel = functools.partial(
        _decode_kernel, scale=scale, page=page, n_slots=n_slots, quant=quant,
    )
    in_specs = [
        pl.BlockSpec((1, 1, gp, d), q_map),
        pl.BlockSpec((None, 1, page, d), kv_map),
        pl.BlockSpec((None, 1, page, d), kv_map),
    ]
    inputs = [page_table, n_live, lengths, lo, q, k_pages, v_pages]
    if quant:
        def sc_map(b_, h, j, table, n_live_, len_, lo_):
            return kv_map(b_, h, j, table, n_live_, len_, lo_)[:3] + (0,)

        # scales reshape to [P, Nkv, 1, page] so the block's LAST TWO dims
        # are (1, page) — legal Mosaic tiling for any Nkv (a [P, Nkv, page]
        # block would put the size-1 block dim against Nkv)
        in_specs.append(pl.BlockSpec((None, 1, 1, page), sc_map))
        in_specs.append(pl.BlockSpec((None, 1, 1, page), sc_map))
        inputs += [k_scales[:, :, None, :], v_scales[:, :, None, :]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, n_kv, n_slots),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, gp, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((gp, 1), jnp.float32),
            pltpu.VMEM((gp, 1), jnp.float32),
            pltpu.VMEM((gp, d), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_kv, gp, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT,
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*inputs)
    return o[:, :, :g, :]


def paged_decode_reference(q, k_pages, v_pages, page_table, lengths,
                           window=None, scale=None,
                           k_scales=None, v_scales=None):
    """jnp oracle for the kernel: gathers each sequence's pages into a
    contiguous cache and runs dense masked attention.  O(B·S·page) memory —
    tests only.  Quantized pools (int8/fp8) dequantize with the per-token
    scales first."""
    if k_scales is not None:
        k_pages = k_pages.astype(jnp.float32) * k_scales[..., None]
        v_pages = v_pages.astype(jnp.float32) * v_scales[..., None]
    b, n_kv, g, d = q.shape
    page = k_pages.shape[2]
    n_slots = page_table.shape[1]
    if scale is None:
        scale = d**-0.5
    k = k_pages[page_table]  # [B, S, Nkv, page, D]
    v = v_pages[page_table]
    k = jnp.moveaxis(k, 2, 1).reshape(b, n_kv, n_slots * page, d)
    v = jnp.moveaxis(v, 2, 1).reshape(b, n_kv, n_slots * page, d)
    s = jnp.einsum("bngd,bnjd->bngj", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    pos = jnp.arange(n_slots * page)[None, :]
    valid = pos < lengths[:, None]
    if window is not None:
        valid = valid & (pos >= jnp.maximum(lengths - window, 0)[:, None])
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(valid[:, None, None, :], p, 0.0)  # all-masked rows -> 0
    return jnp.einsum("bngj,bnjd->bngd", p, v.astype(jnp.float32)).astype(q.dtype)
