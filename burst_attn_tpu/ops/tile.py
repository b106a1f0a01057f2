"""Pure-jnp online-softmax attention tile with carry-in state.

One ring round of FlashAttention-style attention: given carry state
(m = running row max, lse = running log-sum-exp, acc = unnormalized output
accumulator) from previous rounds, fold in the contribution of one KV block.

This is the numerics oracle for the framework — the TPU-native analogue of
the reference's pure-torch tile (burst_attn/burst_utils.py:42-101) and of the
carry-in Triton kernel (burst_attn/lao.py:67-213).  It runs on any backend
(CPU included), is exactly what the Pallas kernels must reproduce, and is the
default backend for simulated-mesh tests.

Conventions (all differ deliberately from the reference's torch layout mix):
  q, k    : [B, N, S, D]  ("bnsd"; contiguous [S, D] per head — TPU friendly)
  v       : [B, N, S, Dv] Dv = D, or v's own width (latent attention: q, k
                          192 wide, v 128); acc, o, do and dv are Dv wide
  m, lse  : [B, N, S]     float32, initialized to -inf
  acc     : [B, N, S, Dv] float32, initialized to 0, unnormalized
  final   : o = acc * exp(m - lse)   (guarded for fully-masked rows)

GQA: N query heads, Nk kv heads with N % Nk == 0; kv head g serves query
heads [g*G, (g+1)*G).
"""

from functools import partial

import jax
import jax.numpy as jnp

from .masks import MaskSpec, dense_mask

NEG_INF = float("-inf")


def init_state(batch, heads, seq, dim):
    m = jnp.full((batch, heads, seq), NEG_INF, dtype=jnp.float32)
    lse = jnp.full((batch, heads, seq), NEG_INF, dtype=jnp.float32)
    acc = jnp.zeros((batch, heads, seq, dim), dtype=jnp.float32)
    return m, lse, acc


def _expand_kv(x, n_q_heads):
    """Repeat kv heads to match query heads (GQA)."""
    n_kv = x.shape[1]
    if n_kv == n_q_heads:
        return x
    assert n_q_heads % n_kv == 0, f"GQA needs Nq % Nk == 0, got {n_q_heads} % {n_kv}"
    return jnp.repeat(x, n_q_heads // n_kv, axis=1)


def _with_segments(mask, segments):
    """Intersect a [s_q, s_kv] structural mask with the packed-sequence
    (segment-ids) equality mask.  segments = (q_seg [B, s_q], kv_seg
    [B, s_kv]) int32; tokens attend only within their own segment.  Returns
    a [B, 1, s_q, s_kv] mask (batch-dependent)."""
    if segments is None:
        return mask
    q_seg, kv_seg = segments
    return (mask[None, None] &
            (q_seg[:, None, :, None] == kv_seg[:, None, None, :]))


def _rows(x, rng, axis=2):
    """Static row range [lo, hi) of x along `axis`; None = all of it."""
    if x is None or rng is None:
        return x
    return jax.lax.slice_in_dim(x, rng[0], rng[1], axis=axis)


def _seg_rows(segments, q_range, kv_range):
    if segments is None:
        return None
    return _rows(segments[0], q_range, 1), _rows(segments[1], kv_range, 1)


def fwd_on_ranges(fn, q, k, v, m, lse, acc, scale, spec, *, segments=None,
                  q_range=None, kv_range=None):
    """The SLICED form of a forward round that covers only the q rows
    `q_range` and the kv columns `kv_range` (static (lo, hi) pairs; `spec`
    is local to them): slice, run `fn(q, k, v, m, lse, acc, scale, spec,
    segments=)` on the slices, write the updated rows back into the state.
    What every tile without an in-place sub-range form does (this oracle;
    pallas_flash.flash_fwd where its grid cannot take the ranges), and the
    definition of what the in-place form must return."""
    out = fn(_rows(q, q_range), _rows(k, kv_range), _rows(v, kv_range),
             _rows(m, q_range), _rows(lse, q_range), _rows(acc, q_range),
             scale, spec, segments=_seg_rows(segments, q_range, kv_range))
    if q_range is None:
        return out
    return tuple(
        jax.lax.dynamic_update_slice_in_dim(full, part, q_range[0], axis=2)
        for full, part in zip((m, lse, acc), out))


def _pad_rows(g, rng, s):
    """Contribution of the rows `rng` laid into s zero rows (axis 2)."""
    if rng is None:
        return g
    pad = [(0, 0)] * g.ndim
    pad[2] = (rng[0], s - rng[1])
    return jnp.pad(g, pad)


def bwd_on_ranges(fn, do, q, k, v, delta, lse, scale, spec, *, segments=None,
                  q_range=None, kv_range=None, carry=None):
    """The SLICED form of a backward round (see fwd_on_ranges): `fn(do, q,
    k, v, delta, lse, scale, spec, segments=)` on the slices, each gradient
    padded with zeros to its full length, and dk, dv added to `carry` =
    (dk, dv) where one is given.  Returns (dq, dk, dv), full-size float32."""
    dq, dk, dv = fn(_rows(do, q_range), _rows(q, q_range), _rows(k, kv_range),
                    _rows(v, kv_range), _rows(delta, q_range),
                    _rows(lse, q_range), scale, spec,
                    segments=_seg_rows(segments, q_range, kv_range))
    dq = _pad_rows(dq, q_range, q.shape[2])
    dk = _pad_rows(dk, kv_range, k.shape[2])
    dv = _pad_rows(dv, kv_range, k.shape[2])
    if carry is not None:
        dk, dv = carry[0] + dk, carry[1] + dv
    return dq, dk, dv


def tile_fwd(q, k, v, m, lse, acc, scale, spec: MaskSpec, window=None,
             segments=None, q_range=None, kv_range=None):
    """One online-softmax round; returns updated (m, lse, acc).
    `window` (static): sliding-window lower bound, see masks.dense_mask.
    `segments`: packed-sequence ids, see _with_segments.
    `q_range` / `kv_range`: the round covers only those rows / columns of
    the full arrays (fwd_on_ranges); rows outside keep their state."""
    if q_range is not None or kv_range is not None:
        return fwd_on_ranges(
            partial(tile_fwd, window=window), q, k, v, m, lse, acc, scale,
            spec, segments=segments, q_range=q_range, kv_range=kv_range)
    s_q, s_kv = q.shape[2], k.shape[2]
    k = _expand_kv(k, q.shape[1])
    v = _expand_kv(v, q.shape[1])
    mask = _with_segments(dense_mask(spec, s_q, s_kv, window), segments)

    s = jnp.einsum("bnid,bnjd->bnij", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    s = jnp.where(mask, s, NEG_INF)

    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # alpha rescales the old accumulator; rows where m stays -inf keep alpha=1
    # (their acc is 0 anyway) to avoid -inf - -inf = nan.
    alpha = jnp.where(m >= m_new, 1.0, jnp.exp(m - m_new))
    p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
    l_step = jnp.sum(p, axis=-1)

    acc = acc * alpha[..., None] + jnp.einsum(
        "bnij,bnjd->bnid", p, v, preferred_element_type=jnp.float32
    )
    prior = jnp.where(jnp.isneginf(lse), 0.0, jnp.exp(lse - m_new))
    total = prior + l_step
    lse_new = jnp.where(total > 0, m_new + jnp.log(total), NEG_INF)
    return m_new, lse_new, acc


def finalize(m, lse, acc, dtype):
    """Normalize the accumulator: o = acc * exp(m - lse)."""
    o_scale = jnp.where(jnp.isneginf(lse), 0.0, jnp.exp(m - lse))
    return (acc * o_scale[..., None]).astype(dtype)


def tile_bwd(do, q, k, v, delta, lse, scale, spec: MaskSpec, window=None,
             segments=None, q_range=None, kv_range=None, carry=None):
    """One backward ring round; returns this round's (dq, dk, dv) in float32.
    With `carry` = (dk, dv) the last two are the carry plus this round's;
    `q_range` / `kv_range` as in tile_fwd (bwd_on_ranges).

    delta = sum(o * do, axis=-1) [B, N, S] float32 (precomputed once — the
    reference's optimize_bwd_comm quantity, burst_attn_interface.py:269-278).
    lse is the FINAL log-sum-exp of the query rows, so p = exp(s - lse) is the
    true softmax probability; masked entries are forced to zero.
    """
    if q_range is not None or kv_range is not None or carry is not None:
        return bwd_on_ranges(
            partial(tile_bwd, window=window), do, q, k, v, delta, lse, scale,
            spec, segments=segments, q_range=q_range, kv_range=kv_range,
            carry=carry)
    n_q = q.shape[1]
    n_kv = k.shape[1]
    s_q, s_kv = q.shape[2], k.shape[2]
    kx = _expand_kv(k, n_q)
    vx = _expand_kv(v, n_q)
    mask = _with_segments(dense_mask(spec, s_q, s_kv, window), segments)

    s = jnp.einsum("bnid,bnjd->bnij", q, kx, preferred_element_type=jnp.float32)
    s = s * scale
    p = jnp.where(mask, jnp.exp(s - lse[..., None]), 0.0)

    do32 = do.astype(jnp.float32)
    dv = jnp.einsum("bnij,bnid->bnjd", p, do32, preferred_element_type=jnp.float32)
    dp = jnp.einsum("bnid,bnjd->bnij", do32, vx, preferred_element_type=jnp.float32)
    ds = p * (dp - delta[..., None]) * scale
    dq = jnp.einsum("bnij,bnjd->bnid", ds, kx, preferred_element_type=jnp.float32)
    dk = jnp.einsum("bnij,bnid->bnjd", ds, q, preferred_element_type=jnp.float32)

    if n_kv != n_q:
        g = n_q // n_kv
        dk = dk.reshape(dk.shape[0], n_kv, g, s_kv, -1).sum(axis=2)
        dv = dv.reshape(dv.shape[0], n_kv, g, s_kv, -1).sum(axis=2)
    return dq, dk, dv


@partial(jax.jit, static_argnames=("causal", "window"))
def single_device_attention(q, k, v, scale=None, causal=False, window=None,
                            segment_ids=None):
    """Full attention on one device via the tile (a one-round "ring").
    `segment_ids` [B, S] int32 packs multiple sequences into one row:
    attention never crosses a segment boundary."""
    from .masks import round_spec

    if scale is None:
        scale = q.shape[-1] ** -0.5
    if window is not None and not causal:
        raise ValueError("window attention requires causal=True")
    b, n, s, d = q.shape
    spec = round_spec(jnp.int32(0), jnp.int32(0), s, k.shape[2], causal, "contig")
    m, lse, acc = init_state(b, n, s, v.shape[-1])
    segs = None if segment_ids is None else (segment_ids, segment_ids)
    m, lse, acc = tile_fwd(q, k, v, m, lse, acc, scale, spec, window=window,
                           segments=segs)
    return finalize(m, lse, acc, q.dtype)
