"""Pallas TPU flash-attention kernel family with ring carry-in state.

The native-kernel layer of the framework: the TPU equivalent of the
reference's two GPU kernel backends — the flash-attn v2 CUDA kernels
(reference burst_attn/burst_utils.py:149-248) and the carry-in Triton kernel
(reference burst_attn/lao.py:67-213, whose forward ACCEPTS previous
(m, lse, acc_o) so the online softmax continues across ring rounds).

Design (see SURVEY.md §2.3, §7):

  * `flash_fwd` — one ring round.  Takes carry state (m, lse, acc) and folds
    in one KV block's contribution, exactly like lao.py's `_fwd_kernel`
    carry-in args (M_in, Lse_in, O_in; lao.py:107-114) but expressed as a
    Pallas grid over (batch, head, q-block, kv-block) with the running
    (m, l, acc) held in VMEM scratch across the innermost kv iterations.
  * `flash_bwd` — one backward ring round, split into a dq kernel and a
    dk/dv kernel (both deterministic — no atomics, unlike lao.py's
    atomic-add dq path, lao.py:473-482).  Takes the precomputed
    delta = sum(o*do) (the reference's optimize_bwd_comm quantity /
    flash-attn softmax_d input, burst_utils.py:195-229) and the FINAL lse.
  * Every ring round is the SAME compiled kernel, parameterized by the five
    runtime MaskSpec scalars (ops/masks.py) delivered via scalar prefetch;
    index maps clamp the kv-block index so fully-masked blocks are neither
    fetched nor computed (the TPU analogue of the reference's 3-way causal
    case split, burst_attn_interface.py:221-235).

State layout.  The per-row softmax stats (m, lse, delta) are logically
[B, N, S] float32.  Mosaic requires the last two block dims to be
(8k, 128k)-aligned or equal to the array dims, and the lane-replicated
[B, N, S, 128] layout used by stock kernels inflates HBM 128x — untenable at
ring scale (B·N·S grows to millions of rows).  We instead reshape to
[B, N, S/LP, LP] (LP = 128 when possible; a free, layout-preserving reshape)
and give each (batch, head) program the whole head's stats as one block
(block dims == array dims, always legal).  In-kernel, rows for one q-block
are unpacked (LP lanes -> bq sublanes) with an exact repeat+select, and
packed back with a native lane-reducing reshape.
"""

import functools
import logging
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import tuning
from .masks import MaskSpec, unit_of

logger = logging.getLogger("burst_attn_tpu")
# re-exported here for kernel users; defined in ops/tuning.py so jnp-only
# paths (burst.py's backend fallback) can resolve blocks without importing
# this module
from .tuning import resolve_blocks  # noqa: F401

NEG_INF = float("-inf")
# stand-in for -inf lse rows in the backward kernels: exp(s - BIG_LSE)
# underflows to exactly 0 for any finite score s
BIG_LSE = 1e30
# The kernels run the online softmax in base 2: log2(e) is folded into the
# q-block scaling so every transcendental is a bare exp2 (exp(x) lowers to
# exp2(x*log2e) + a mul on the VPU; the kernels are VPU-bound so the dropped
# [bq, bkv] multiplies are measurable).  All kernel INTERFACES stay in the
# natural-log domain (m, lse), converted on the [bq, 1] columns at init/finish.
LOG2E = 1.4426950408889634
LN2 = 0.6931471805599453
# Mosaic's default scoped-VMEM budget is 16 MiB; v5e has far more physical
# VMEM and the larger budget admits 2048-wide kv blocks.  BURST_VMEM_LIMIT
# (bytes, read at import) exists for cliff experiments: the limit bounds how
# aggressively Mosaic double-buffers, so it interacts with the block-area
# cliff law in ops/tuning.py.
VMEM_LIMIT = int(os.environ.get("BURST_VMEM_LIMIT", 100 * 1024 * 1024))


def _interpret_default():
    return jax.default_backend() != "tpu"


def _tri_disabled():
    """BURST_NO_TRI=1 turns the wrapped-diagonal causal grids off globally
    (escape hatch: the rectangular grids are the longer-validated path).
    Checked at trace time; "", "0", and "false" mean off (triangular on)."""
    return os.environ.get("BURST_NO_TRI", "").strip().lower() not in ("", "0", "false")


def _fwd_loop_default():
    """BURST_FWD_LOOP=1 makes flash_fwd's fori_loop sub-block sweep
    (`loop_sweep`) the default.  Exists so the cliff-break experiment
    (sweep_blocks --fwd-loop; docs §3) can be PROMOTED for a bench run
    without a code edit — if the loop sweep legalizes
    4096-wide kv blocks, rerun `BURST_FWD_LOOP=1 BURST_ALLOW_CLIFF=1
    python bench.py` with retuned blocks before changing defaults."""
    return os.environ.get("BURST_FWD_LOOP", "").strip().lower() not in ("", "0", "false")


def _bwd_loop_default():
    """BURST_BWD_LOOP=1 makes the tri backward's fori_loop sub-block sweep
    the default — same promotion mechanism as BURST_FWD_LOOP (see
    _fwd_loop_default): if the loop body's buffer reuse moves the bwd VMEM
    cliff (sweep_blocks --bwd ...xtrix1024 with it set), rerun bench with
    retuned bwd blocks before changing ops/tuning.py defaults."""
    return os.environ.get("BURST_BWD_LOOP", "").strip().lower() not in ("", "0", "false")


def _pick_block(seq: int, block: int) -> int:
    """Largest block <= `block` that divides seq (seq lengths are powers of
    two in practice, so this is normally min(block, seq))."""
    block = min(block, seq)
    while seq % block:
        block -= 1
    return block


# Ragged sequence support: flash_fwd/flash_bwd pad awkward sequence lengths
# up to a multiple of the packed-stats lane width, so _pick_block always has
# a >= 128-ish divisor to work with instead of silently degrading to a
# near-1 block (and a catastrophic grid) on prime/odd lengths.  The pad
# region is masked out for free: MaskSpec row/col bounds stay in true
# coordinates, so padded rows/cols fail `rows < q_hi` / `cols < kv_hi` in
# every kernel mask, and callers slice the outputs back.
_SEQ_ALIGN = 128


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _padded_len(s: int, block: int) -> int:
    """Sequence length the kernel should actually run at: `s` itself when the
    requested block tiles it exactly, or when it fits one small (sub-align)
    block; otherwise the next 128-aligned length, with the pad masked out.
    (A 128-aligned s is its own ceiling, so good-divisor cases like
    s=2176/block=2048 fall through unchanged.)"""
    if s % block == 0 or (block >= s and s <= _SEQ_ALIGN):
        return s
    return _ceil_to(s, _SEQ_ALIGN)


def _pad_seq(x, s_pad: int, fill=0.0):
    """Pad dim 2 (sequence) of [B, N, S, ...] up to s_pad with `fill`."""
    s = x.shape[2]
    if s == s_pad:
        return x
    pad = [(0, 0)] * x.ndim
    pad[2] = (0, s_pad - s)
    return jnp.pad(x, pad, constant_values=fill)


def _pad_seg(seg, s_pad: int, fill):
    """Pad dim 1 (sequence) of a [B, S] segment-id array with `fill`.

    The sentinels (-1 q-side, -2 kv-side) keep the two pads from matching
    each other; real-vs-pad pairs are already dead structurally — the spec's
    q_hi/kv_hi bounds stay in TRUE coordinates, so any block touching pad
    rows/cols takes the masked path and the bounds test kills those pairs
    regardless of ids.  Callers should still use non-negative segment ids
    (negatives are reserved for padding; see flash_attention docstring)."""
    s = seg.shape[1]
    if s == s_pad:
        return seg
    return jnp.pad(seg, [(0, 0), (0, s_pad - s)], constant_values=fill)


def _spec_array(spec: MaskSpec):
    return jnp.stack(
        [
            jnp.asarray(spec.q_lo, jnp.int32),
            jnp.asarray(spec.q_hi, jnp.int32),
            jnp.asarray(spec.kv_hi, jnp.int32),
            jnp.asarray(spec.causal, jnp.int32),
            jnp.asarray(spec.offset, jnp.int32),
        ]
    )


def _in_units(wnd, *tokens):
    """(window, *tokens) in the mask's units: as given where `wnd` is a token
    window or None (the code every call traced before units existed), each
    divided by the block length where it is a masks.BlockUnits (tile origins
    and sizes are whole blocks: flash_fwd / flash_bwd check it)."""
    unit, wnd = unit_of(wnd)
    if unit == 1:
        return (wnd, *tokens)
    return (wnd, *(t // unit for t in tokens))


def _unit_index(x, unit):
    """Token indices (non-negative int32) -> mask-unit indices."""
    if unit == 1:
        return x
    if unit & (unit - 1) == 0:
        return jax.lax.shift_right_logical(x, unit.bit_length() - 1)
    return x // unit


def _block_mask(spec_ref, r0, c0, bq, bkv, wnd=None, seg=None):
    """[bq, bkv] bool mask for the tile at rows r0.., cols c0.. (True=attend).

    `wnd` is the STATIC sliding-window width (None = unlimited); when None
    the generated code is identical to the pre-window kernels — windowed
    runs are the only ones that pay for the extra band term.  `seg` =
    (q_seg [bq, 1], kv_seg [1, bkv]) packed-sequence id tiles: attention
    never crosses a segment boundary (the broadcast compare is the only
    cost, and only on blocks that take the masked path).  A BlockUnits `wnd`
    holds each (row, col) to the spec by its block (see masks.BlockUnits)."""
    unit, wnd = unit_of(wnd)
    rows = _unit_index(
        r0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0), unit)
    cols = _unit_index(
        c0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1), unit)
    q_lo, q_hi, kv_hi = spec_ref[0], spec_ref[1], spec_ref[2]
    causal, offset = spec_ref[3], spec_ref[4]
    m = (rows >= q_lo) & (rows < q_hi) & (cols < kv_hi)
    m = m & ((causal == 0) | (cols <= rows + offset))
    if wnd is not None:
        m = m & (cols > rows + offset - wnd)
    if seg is not None:
        m = m & (seg[0] == seg[1])
    return m


def _seg_uniform_eq(qs, ks):
    """Scalar: True iff both segment tiles are single-segment AND equal —
    the condition under which a structurally-full block needs no segment
    masking (the fast path stays fast on the unpacked interior)."""
    return ((jnp.max(qs) == jnp.min(qs)) & (jnp.max(ks) == jnp.min(ks))
            & (jnp.max(qs) == jnp.max(ks)))


def _block_has_work(spec_ref, r0, c0, bq, bkv, wnd=None):
    wnd, r0, c0, bq, bkv = _in_units(wnd, r0, c0, bq, bkv)
    q_lo, q_hi, kv_hi = spec_ref[0], spec_ref[1], spec_ref[2]
    causal, offset = spec_ref[3], spec_ref[4]
    ok = (r0 < q_hi) & (r0 + bq > q_lo) & (c0 < kv_hi)
    ok = ok & ((causal == 0) | (c0 <= r0 + bq - 1 + offset))
    if wnd is not None:
        # union of the rows' visible bands is [r0+offset-wnd+1, ...): a
        # block wholly left of it is dead
        ok = ok & (c0 + bkv - 1 > r0 + offset - wnd)
    return ok


def _block_full(spec_ref, r0, c0, bq, bkv, wnd=None):
    """True iff every (row, col) of the tile is visible — the fast path can
    skip mask construction and the elementwise selects entirely.  On a causal
    64-block grid ~97% of live blocks are interior, and the kernels are
    VPU-bound, so this matters more than any matmul tuning."""
    wnd, r0, c0, bq, bkv = _in_units(wnd, r0, c0, bq, bkv)
    q_lo, q_hi, kv_hi = spec_ref[0], spec_ref[1], spec_ref[2]
    causal, offset = spec_ref[3], spec_ref[4]
    ok = (r0 >= q_lo) & (r0 + bq <= q_hi) & (c0 + bkv <= kv_hi)
    ok = ok & ((causal == 0) | (c0 + bkv - 1 <= r0 + offset))
    if wnd is not None:
        # intersection of the rows' bands starts at r0+bq-1+offset-wnd+1
        ok = ok & (c0 > r0 + bq - 1 + offset - wnd)
    return ok


def _kv_jmax(spec_ref, i, bq, bkv, n_kv_blocks, wnd=None):
    """Last useful kv-block index for q-block i (for DMA index clamping).
    `wnd` only says which units the spec is in."""
    _, bq, bkv = _in_units(wnd, bq, bkv)
    kv_hi, causal, offset = spec_ref[2], spec_ref[3], spec_ref[4]
    hi = jnp.where(causal > 0, jnp.minimum(kv_hi, i * bq + bq + offset), kv_hi)
    return jnp.clip((hi + bkv - 1) // bkv - 1, 0, n_kv_blocks - 1)


def _q_imin(spec_ref, j, bq, bkv, n_q_blocks, wnd=None):
    """First useful q-block index for kv-block j (bwd dk/dv clamping).
    `wnd` only says which units the spec is in."""
    _, bq, bkv = _in_units(wnd, bq, bkv)
    q_lo, causal, offset = spec_ref[0], spec_ref[3], spec_ref[4]
    lo = jnp.where(causal > 0, jnp.maximum(q_lo, j * bkv - offset), q_lo)
    return jnp.clip(lo // bq, 0, n_q_blocks - 1)


def _kv_jmin(spec_ref, i, bq, bkv, n_kv_blocks, wnd):
    """First useful kv-block index for q-block i under a sliding window
    (DMA clamping: left-of-band blocks are dead, and clamping their fetch
    index to the first live block makes them free — same trick as _kv_jmax
    on the causal side).  Row i*bq's band starts at r0 + offset - wnd + 1."""
    wnd, bq, bkv = _in_units(wnd, bq, bkv)
    offset = spec_ref[4]
    lo = i * bq + offset - wnd + 1
    return jnp.clip(lo // bkv, 0, n_kv_blocks - 1)


def _q_imax(spec_ref, j, bq, bkv, n_q_blocks, wnd):
    """Last useful q-block index for kv-block j under a sliding window:
    rows beyond c0 + bkv - 1 + wnd - 1 - offset have their whole band left
    of this kv block."""
    wnd, bq, bkv = _in_units(wnd, bq, bkv)
    offset = spec_ref[4]
    hi = j * bkv + bkv - 1 + wnd - 1 - offset
    return jnp.clip(hi // bq, 0, n_q_blocks - 1)


# ---------------------------------------------------------------------------
# packed-stats helpers (see "State layout" in the module docstring)


def _read_rows(state_ref, i, bq, lp):
    """Rows [i*bq, (i+1)*bq) of a packed [1, 1, S/lp, lp] stats ref -> (bq, 1)."""
    rows = bq // lp
    pack = state_ref[0, 0, pl.ds(i * rows, rows), :]
    if lp == 1:
        return pack
    rep = jnp.repeat(pack, bq // rows, axis=0)  # (bq, lp); row t = pack[t//lp]
    t_lane = jax.lax.broadcasted_iota(jnp.int32, (bq, lp), 0) % lp
    c_idx = jax.lax.broadcasted_iota(jnp.int32, (bq, lp), 1)
    return jnp.sum(jnp.where(t_lane == c_idx, rep, 0.0), axis=1, keepdims=True)


def _write_rows(state_ref, i, col, bq, lp):
    """Inverse of _read_rows: store (bq, 1) into rows of the packed ref."""
    rows = bq // lp
    state_ref[0, 0, pl.ds(i * rows, rows), :] = jnp.reshape(col, (rows, lp))


def _pack(x, lp):
    """[B, N, S] -> [B, N, S/lp, lp] (free, layout-preserving reshape)."""
    b, n, s = x.shape
    return x.reshape(b, n, s // lp, lp)


def _gqa_group(n: int, n_kv: int) -> int:
    assert n % n_kv == 0, f"GQA needs Nq % Nk == 0, got {n} % {n_kv}"
    return n // n_kv


def _make_index_maps(bq, bkv, nqb, nkb, group, wnd=None, q_off=0, kv_off=0):
    """Shared fwd/bwd(dq) index maps over the (batch, head, q-block, kv-block)
    grid; kv fetches are clamped to the [first, last] useful block so
    fully-masked blocks are never DMA'd (the lower clamp only exists under a
    sliding window — without one, block 0 is always live causally).
    q_off / kv_off: the grid's first block in the full arrays (a sub-range
    round: the clamps stay local to the range, the offset is added last)."""

    def q_map(b_, h, i, j, sp):
        return (b_, h, _shift(i, q_off), 0)

    def kv_map(b_, h, i, j, sp):
        j_eff = jnp.minimum(j, _kv_jmax(sp, i, bq, bkv, nkb, wnd))
        if unit_of(wnd)[1] is not None:
            j_eff = jnp.maximum(j_eff, _kv_jmin(sp, i, bq, bkv, nkb, wnd))
        return (b_, h // group, _shift(j_eff, kv_off), 0)

    def state_map(b_, h, i, j, sp):
        return (b_, h, 0, 0)

    return q_map, kv_map, state_map


def _unpack(x):
    b, n, r, lp = x.shape
    return x.reshape(b, n, r * lp)


# ---------------------------------------------------------------------------
# sub-range rounds.  A ring round of the zigzag case split touches half of a
# shard: all q rows against the first half of kv, or the second half of the
# q rows against all of kv.  flash_fwd / flash_bwd take that as STATIC row
# ranges `q_range` / `kv_range` = (lo, hi) of the FULL arrays; the rect
# grids cover only the range (index maps add its first block), so nothing
# is sliced before the kernel or padded after it.  `spec` is local to the
# range.  Where the kernel that would run cannot take a range, the call is
# ops/tile.py's sliced form of the same round.


def _range_len(rng, s):
    return s if rng is None else rng[1] - rng[0]


def _shift(i, off):
    """Block index i of a range's grid, in the full array.  A call with no
    range (off 0) traces the index it traced before ranges existed."""
    return i + off if off else i


def _whole_blocks(rng, s, block) -> bool:
    """Whether the row range is made of whole `block`-row blocks of the full
    length-s array (the grid's own block: _pick_block of the range's
    length), so that an index map can reach it by a block offset."""
    n = _range_len(rng, s)
    if _padded_len(n, block) != n:
        return False
    blk = _pick_block(n, block)
    return (0 if rng is None else rng[0]) % blk == 0 and s % blk == 0


def fwd_covers_ranges(s_q, s_kv, q_range, kv_range, *, block_q, block_kv,
                      triangular=False) -> bool:
    """Whether flash_fwd's grid covers the ranges in place (its rectangular
    grid, whole blocks) or the call takes the sliced form; a call with no
    range always runs on the full arrays.  Static: the ring counts its
    rounds by it (parallel/burst.py, burst.inplace_rounds)."""
    if q_range is None and kv_range is None:
        return True
    return (not triangular
            and _whole_blocks(q_range, s_q, block_q)
            and _whole_blocks(kv_range, s_kv, block_kv))


class DiagPath(NamedTuple):
    """What serves the tiles the causal diagonal cuts in a forward or a
    backward call that promises `triangular` (fwd_diag_path, bwd_diag_path)."""

    # "sub": the tile's live sub-squares only (_fwd_kernel._sweep_diag,
    # _bwd_cut_tile); "whole": its whole area on the masked path
    path: str
    tiles: int  # such tiles a (batch, head): the q blocks of the pass's grid
    edge: Optional[int]  # the sub-square edge where path == "sub"


def fwd_diag_path(s_q, s_kv, *, block_q, block_kv, triangular, window=None,
                  segments=False, q_range=None, kv_range=None,
                  diag_block=None, loop_sweep=False) -> Optional[DiagPath]:
    """Whether flash_fwd's diagonal tiles (q block i against kv block i of a
    call whose caller promises `triangular`: statically full-window causal,
    offset 0 or -1) compute their live sub-squares only, or their whole
    area on the masked path.  None where no such promise is made.  Static:
    flash_fwd chooses its kernel body by it, and the ring counts its
    dispatches by it (parallel/burst.py, flash.diag_tiles).

    The sub-square sweep needs what makes the dead, full and cut squares of
    a diagonal tile known at trace time: no sliding window (a
    masks.BlockUnits unit is fine), no `segments`, exact tiling with
    block_q == block_kv, and an edge `diag_block` (None: the generation's,
    ops/tuning.py; 0 turns the sweep off) that divides the tile, is smaller
    than it and is whole mask units.  The fori_loop sweep (`loop_sweep`,
    BURST_FWD_LOOP) keeps the whole tile.  A call with ranges is judged on
    the rows they cover: that is what its sliced form runs on."""
    s_q, s_kv = _range_len(q_range, s_q), _range_len(kv_range, s_kv)
    if not triangular or s_q != s_kv:
        return None
    sq_pad = _padded_len(s_q, block_q)
    bq = _pick_block(sq_pad, block_q)
    bkv = _pick_block(_padded_len(s_kv, block_kv), block_kv)
    nqb = sq_pad // bq
    unit, win = unit_of(window)
    if diag_block is None:
        diag_block = tuning.block_defaults().diag_block
    sub = (sq_pad == s_q and win is None and not segments
           and not (loop_sweep or _fwd_loop_default()) and bq == bkv
           and 0 < diag_block < bkv and bkv % diag_block == 0
           and diag_block % unit == 0)
    return DiagPath("sub", nqb, diag_block) if sub else DiagPath(
        "whole", nqb, None)


def _bwd_diag_edge(kernel, s_q, s_kv, *, block_q, block_kv, triangular,
                   window, segments, diag_block, loop_sweep):
    """The sub-square edge of the cut blocks of ONE fused backward launch on
    exact tiles (`kernel` "tri" or "rect"), or None where the blocks the
    diagonal cuts keep the whole tile (bwd_diag_path has the conditions)."""
    bq, bkv = _pick_block(s_q, block_q), _pick_block(s_kv, block_kv)
    unit, win = unit_of(window)
    sub = (bool(triangular) and kernel in ("tri", "rect") and s_q == s_kv
           and win is None and not segments and not loop_sweep
           and bkv % bq == 0 and 0 < diag_block < bkv
           and bq % diag_block == 0 and diag_block % unit == 0)
    return diag_block if sub else None


def bwd_diag_path(n, n_kv, s_q, s_kv, d, *, block_q, block_kv, triangular,
                  window=None, segments=False, q_range=None, kv_range=None,
                  d_v=None, interpret=None, fused=None, block_kv_compute=None,
                  diag_block=None, loop_sweep=False) -> Optional[DiagPath]:
    """fwd_diag_path's twin for flash_bwd: whether the q blocks the causal
    diagonal cuts (with the backward's block_kv = ratio * block_q, `ratio` of
    them a kv block) compute their live sub-squares only (_bwd_cut_tile), or
    their whole area on the masked path.  None where the caller makes no
    `triangular` promise.  `tiles` counts the cut q blocks a (batch, head).
    Static: flash_bwd chooses its kernel body by it, and the ring counts its
    dispatches by it (parallel/burst.py, flash.diag_tiles{pass=bwd}).

    The sub-square sweep lives in the two fused kernels (the wrapped-diagonal
    one and the rectangular one with its in-place dq; the split kernels keep
    the whole tile: _bwd_kernel_of on these shapes decides, so off the chip
    the default is "whole") and needs what fwd_diag_path's needs: no sliding
    window (a masks.BlockUnits unit is fine), no `segments`, exact tiling
    with block_kv a multiple of block_q, and an edge `diag_block` (None: the
    generation's, ops/tuning.py; 0 turns the sweep off) that divides
    block_q, is smaller than block_kv and is whole mask units.  The
    fori_loop sweep (`loop_sweep`, BURST_BWD_LOOP) keeps the whole tile.  A
    call with ranges or a carry is judged on the rows it covers: that is
    what the kernel it reaches runs on, in place or sliced."""
    s_q, s_kv = _range_len(q_range, s_q), _range_len(kv_range, s_kv)
    if not triangular or s_q != s_kv:
        return None
    if interpret is None:
        interpret = _interpret_default()
    sq_pad = _padded_len(s_q, block_q)
    nqb = sq_pad // _pick_block(sq_pad, block_q)
    if diag_block is None:
        diag_block = tuning.block_defaults().diag_block
    edge = None
    if sq_pad == s_q and _padded_len(s_kv, block_kv) == s_kv:
        kernel = _bwd_kernel_of(
            n, n_kv, s_q, s_kv, d, block_q=block_q, block_kv=block_kv,
            interpret=interpret, fused=fused, triangular=triangular,
            window=window, block_kv_compute=block_kv_compute, d_v=d_v)
        edge = _bwd_diag_edge(
            kernel, s_q, s_kv, block_q=block_q, block_kv=block_kv,
            triangular=triangular, window=window, segments=segments,
            diag_block=diag_block,
            loop_sweep=loop_sweep or _bwd_loop_default())
    return DiagPath("sub" if edge else "whole", nqb, edge)


# ---------------------------------------------------------------------------
# forward


def _lcm(a, b):
    import math

    return a * b // math.gcd(a, b)


def fwd_band_nb(bq, bkv, window):
    """Exact max kv-block count a q-row-block's sliding-window band can
    intersect, over the alignments the band contract can produce
    (r0 = i*bq, offset in {0, -1} — and, shift-invariantly, any offset
    ≡ 0 (mod bkv): the windowed contig ring's live rounds pass offset
    r*s with bkv | s, which lands on the off=0 alignment class; keep the
    enumeration in residues, never absolute offsets).  A closed-form upper bound
    ((bq+window-2)//bkv + 2) overcounts by one at every aligned config —
    e.g. window=4K, bq=bkv=2048 intersects at most 3 blocks, not 4 — and a
    permanently-dead extra grid step per row is exactly the overhead the
    band grid exists to remove."""
    best = 0
    for r0 in range(0, _lcm(bq, bkv), bq):  # residues cycle at lcm
        for off in (0, -1):
            jmin = (r0 + off - window + 1) // bkv
            jmax = (r0 + bq - 1 + off) // bkv
            best = max(best, jmax - jmin + 1)
    return best


def bwd_band_nb(bq, bkv, window):
    """Exact max q-block count whose band can reach a kv block (the fused
    bwd sweep length), over reachable alignments c0 = j*bkv, offset 0/-1.
    Mirror of fwd_band_nb with the roles swapped (_q_imin/_q_imax)."""
    best = 0
    for c0 in range(0, _lcm(bq, bkv), bkv):
        for off in (0, -1):
            imin = (c0 - off) // bq          # first causal q row's block
            imax = (c0 + bkv - 1 + window - 1 - off) // bq
            best = max(best, imax - imin + 1)
    return best


def _tri_coords(nqb, r):
    """Wrapped-diagonal coordinates for the static-causal triangular grid,
    generalized to TALL q blocks: block_q = r * block_kv.

    Grid dims (b, h, p, j') with p in [0, nqb/2), j' in [0, (nqb+1)*r):
    row pair p covers q-block p (kv-blocks 0..(p+1)*r-1, segment A =
    j' < (p+1)*r) then q-block nqb-1-p (kv-blocks 0..(nqb-p)*r-1,
    segment B) — (p+1)*r + (nqb-p)*r = (nqb+1)*r steps, ALL live.  The
    rectangular grid spends ~half its steps on clamped/dead causal blocks
    (~1.9us each of pure grid overhead on v5e at seq=64K, where causal fwd
    measured 150 TFLOPs/s vs 172 non-causal — the all-live grid closes
    most of that gap; measured values in README.md's performance section
    and sweep_blocks output).

    Why tall blocks: at fixed block AREA (the measured VMEM cliff bound,
    docs/design.md §3) the kernel's K/V streaming traffic scales as
    1/block_q — each kv block fetched serves more query rows — while the
    grid STEP COUNT, the diagonal's masked fraction (2/(nqb+1)), and the
    pipeline's scoped-VMEM demand are all r-invariant.  At seq=64K the
    2048x2048 forward moves ~16.9 GB of K/V (HBM-bound at ~819 GB/s);
    4096x1024 moves half that for the same step count.

    Per segment the last r steps overlap the diagonal and take the masked
    path (the `masked` return); every earlier step is statically full
    under offset 0/-1.  Requires block_q % block_kv == 0 and an even
    q-block count."""
    p_ = pl.program_id(2)
    j_ = pl.program_id(3)
    lena = (p_ + 1) * r
    segb = j_ >= lena
    i = jnp.where(segb, nqb - 1 - p_, p_)
    jrel = jnp.where(segb, j_ - lena, j_)
    seg_len = jnp.where(segb, (nqb - p_) * r, lena)
    is_init = (j_ == 0) | (j_ == lena)
    is_fin = (j_ == lena - 1) | (j_ == (nqb + 1) * r - 1)
    masked = jrel >= seg_len - r
    return i, jrel, is_init, is_fin, masked


def _fwd_kernel(
    spec_ref,
    q_ref, k_ref, v_ref,
    *rest,
    scale, bq, bkv, bkv_compute, lp, n_kv_blocks, cast_p, tri, wnd=None,
    seg=False, emit_o=False, loop=False, ablate=None, band_nb=None,
    carry=True, tri_r=1, q_off=0, keep_rows=False, diag=None,
):
    # q_off: this call's first q-row block in the FULL state arrays (a
    # sub-range round, see flash_fwd); the mask arithmetic below stays local
    # to the range.  keep_rows: the range leaves some of a head's rows out.
    # diag: the edge of the diagonal sweep's row chunks where tile i == j
    # takes it (fwd_diag_path decides, statically), else None.
    if carry:
        m_in_ref, lse_in_ref, acc_in_ref = rest[:3]
        rest = rest[3:]
    if seg:
        qseg_ref, kvseg_ref = rest[0], rest[1]
        rest = rest[2:]
    m_out_ref, lse_out_ref, acc_out_ref, m_scr, l_scr, acc_scr = rest
    if tri:
        nqb = n_kv_blocks // tri_r  # s_q == s_kv; bq == tri_r * bkv
        i, j, is_init, is_fin, tri_masked = _tri_coords(nqb, tri_r)
    elif band_nb is not None:
        # band grid (see flash_fwd): dim 3 walks only the <=band_nb kv
        # blocks that can intersect q-block i's sliding-window band, instead
        # of all n_kv_blocks — the all-live-steps idea of the tri grid
        # applied to the window structure
        i = pl.program_id(2)
        c = pl.program_id(3)
        j = _kv_jmin(spec_ref, i, bq, bkv, n_kv_blocks, wnd) + c
        is_init = c == 0
        is_fin = c == band_nb - 1
    else:
        i = pl.program_id(2)
        j = pl.program_id(3)
        is_init = j == 0
        is_fin = j == n_kv_blocks - 1
    r0 = i * bq
    c0 = j * bkv

    if keep_rows:
        # the packed m/lse OUT block is the whole head's, written back whole:
        # seed it with the carry so the rows this call never visits keep it
        @pl.when((pl.program_id(2) == 0) & (pl.program_id(3) == 0))
        def _keep():
            m_out_ref[...] = m_in_ref[...]
            lse_out_ref[...] = lse_in_ref[...]

    @pl.when(is_init)
    def _init():
        if carry:
            m0 = _read_rows(m_in_ref, _shift(i, q_off), bq, lp)
            lse0 = _read_rows(lse_in_ref, _shift(i, q_off), bq, lp)
            # scratch m is kept in the base-2 scaled domain (see LOG2E note)
            m_scr[:] = m0 * LOG2E
            # linear-scale running sum relative to m: l = exp(lse - m);
            # 0 if empty
            l_scr[:] = jnp.where(m0 == NEG_INF, 0.0, jnp.exp(lse0 - m0))
            acc_scr[:] = acc_in_ref[0, 0, :, :]
        else:
            # statically-empty carry (single-device / first ring round):
            # the empty state is a constant, so the [bq, d] f32 acc-in DMA
            # per row visit — and XLA's materialization of the whole
            # [B, N, S, D] zeros input — never happen (measured-relevant:
            # that is ~2 GB of dead HBM traffic per 64K-seq forward)
            m_scr[:] = jnp.full_like(m_scr, NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

    if tri:
        # every tri step is live; only the r diagonal-overlap blocks at each
        # segment's end are partially masked (r = 1: exactly the final step)
        fast_cond = ~tri_masked
        masked_cond = tri_masked
    else:
        live = _block_has_work(spec_ref, r0, c0, bq, bkv, wnd) & (
            j <= _kv_jmax(spec_ref, i, bq, bkv, n_kv_blocks, wnd)
        )
        full = _block_full(spec_ref, r0, c0, bq, bkv, wnd)
        fast_cond = live & full
        masked_cond = live & ~full
    if seg:
        # packed sequences: only blocks wholly inside ONE shared segment may
        # skip masking; mixed blocks join the masked path (cheap scalar test)
        qs_tile = qseg_ref[0, :, :]   # [bq, 1]
        ks_tile = kvseg_ref[0, :, :]  # [1, bkv]
        seg_ok = _seg_uniform_eq(qs_tile, ks_tile)
        was_live = fast_cond | masked_cond
        fast_cond = fast_cond & seg_ok
        masked_cond = was_live & ~fast_cond

    # scale (and the base-2 conversion) folded into the [bq, d] q block
    # (one small mul, hoisted out of the sub-block loop) instead of the
    # [bq, bkv] score matrix — the kernel is VPU-bound, not MXU-bound
    q = q_ref[0, 0, :, :] * (scale * LOG2E)

    def _qk(q_rows, cs):
        return jax.lax.dot_general(
            q_rows, k_ref[0, 0, cs, :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def _score(u):
        return _qk(q, pl.ds(u * bkv_compute, bkv_compute))

    def _softmax(s, mask, m_prev, l_prev):
        """VPU half of one sub-block fold: returns (m_new, l_new, alpha, p)."""
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.where(m_prev >= m_new, 1.0, jnp.exp2(m_prev - m_new))
        p = jnp.exp2(s - m_new)
        if mask is not None:
            # guards the all-masked-row nan (s = m_new = -inf)
            p = jnp.where(mask, p, 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        return m_new, l_new, alpha, p.astype(v_ref.dtype) if cast_p else p

    def _p_v(p, cs):
        return jax.lax.dot_general(
            p, v_ref[0, 0, cs, :], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def _pv(u, p):
        return _p_v(p, pl.ds(u * bkv_compute, bkv_compute))

    def _sweep_loop(masked):
        """lax.fori_loop variant of _sweep: the pend (alpha, p) rides the
        loop CARRY instead of Python-unrolled values.

        Why this exists: Mosaic allocates the unrolled pipeline's
        intermediates SSA-style — every stage's [bq, bkc] f32 tiles stay
        live for the whole body, so scoped-VMEM demand grows with
        n_sub·bq·bkc = bq·bkv (the measured block-area cliff,
        docs/design.md §3).  A fori_loop body reuses its buffers per
        iteration, capping demand at ~2 stages independent of bkv — the
        experiment that could admit bkv=4096 and halve the grid's step
        count.  Selected by flash_fwd's loop_sweep flag."""
        m0 = m_scr[:]
        l0 = l_scr[:]
        acc0 = acc_scr[:]
        n_sub = bkv // bkv_compute

        def mask_of(u):
            if not masked:
                return None
            unit, win = unit_of(wnd)
            cols = _unit_index(
                c0 + u * bkv_compute
                + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv_compute), 1),
                unit)
            rows = _unit_index(r0 + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bkv_compute), 0), unit)
            q_lo, q_hi, kv_hi = spec_ref[0], spec_ref[1], spec_ref[2]
            causal, offset = spec_ref[3], spec_ref[4]
            mk = (rows >= q_lo) & (rows < q_hi) & (cols < kv_hi)
            mk = mk & ((causal == 0) | (cols <= rows + offset))
            if win is not None:
                mk = mk & (cols > rows + offset - win)
            if seg:
                ks_u = jax.lax.dynamic_slice(
                    ks_tile, (0, u * bkv_compute), (1, bkv_compute))
                mk = mk & (qs_tile == ks_u)
            return mk

        def step_body(u, carry):
            m_c, l_c, acc_c, alpha_p, p_p = carry
            cs = pl.ds(u * bkv_compute, bkv_compute)
            s_u = jax.lax.dot_general(
                q, k_ref[0, 0, cs, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            # fold the carried pend FIRST: its pv matmul is independent of
            # this iteration's VPU chain and queues right behind s_u
            cs_prev = pl.ds((u - 1) * bkv_compute, bkv_compute)
            acc_c = acc_c * alpha_p + jax.lax.dot_general(
                p_p, v_ref[0, 0, cs_prev, :], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_c, l_c, alpha, p = _softmax(s_u, mask_of(u), m_c, l_c)
            return m_c, l_c, acc_c, alpha, p

        # iteration 0 outside the loop (no pend to fold yet)
        m1, l1, alpha1, p1 = _softmax(_score(0), mask_of(0), m0, l0)
        if n_sub > 1:
            m1, l1, acc1, alpha_last, p_last = jax.lax.fori_loop(
                1, n_sub, step_body, (m1, l1, acc0, alpha1, p1))
            u_last = n_sub - 1
        else:
            acc1, alpha_last, p_last, u_last = acc0, alpha1, p1, 0
        acc1 = acc1 * alpha_last + _pv(u_last, p_last)
        m_scr[:], l_scr[:], acc_scr[:] = m1, l1, acc1

    def _sweep(masked):
        """Three-stage software pipeline over compute sub-blocks (splash-style
        bkv vs bkv_compute).  With in-order issue and async MXU execution, the
        stagger means no MXU op ever waits on the VPU softmax chain:

            issue s(u+1)      [MXU]  — independent of everything in flight
            softmax(u)        [VPU]  — consumes s(u), overlaps s(u+1)
            acc += pv(u-1)    [MXU]  — its p tile was finished LAST iteration

        The acc update is deferred one sub-block (the alpha rescale composes:
        acc_u = acc_{u-1}*alpha_u + pv_u applied one step late), drained after
        the loop.  State (m, l, acc) is loop-carried by VALUE and written back
        to scratch once per grid step.  With a single sub-block
        (bkv_compute == bkv) this degenerates to the plain serial fold."""
        m, l, acc = m_scr[:], l_scr[:], acc_scr[:]
        n_sub = bkv // bkv_compute
        s_cur = _score(0)
        pend = None  # (u, alpha, p) awaiting its pv matmul + acc fold
        for u in range(n_sub):
            s_next = _score(u + 1) if u + 1 < n_sub else None
            mask = (
                _block_mask(spec_ref, r0, c0 + u * bkv_compute, bq,
                            bkv_compute, wnd,
                            seg=(qs_tile,
                                 ks_tile[:, u * bkv_compute:
                                         (u + 1) * bkv_compute]) if seg
                            else None)
                if masked else None
            )
            if ablate == "nosoftmax":
                # perf-debug ONLY (wrong numerics): p := s, softmax chain
                # skipped — times the MXU/pipeline ceiling with zero VPU work
                alpha, p = jnp.float32(1.0), (
                    s_cur.astype(v_ref.dtype) if cast_p else s_cur)
            else:
                m, l, alpha, p = _softmax(s_cur, mask, m, l)
            if pend is not None:
                acc = acc * pend[1] + _pv(pend[0], pend[2])
            pend = (u, alpha, p)
            s_cur = s_next
        # NOTE on the step-tail drain: deferring this final pv across the
        # grid step (the backward's _flush_dk trick) was measured on v5e and
        # REGRESSES fwd 157.6 -> 123.7 TFLOPs/s — the [bq, bkc] p stash
        # write/read costs more than the drained bubble.  The nosoftmax
        # ablation (sweep_blocks --ablate-fwd) bounds the whole VPU chain's
        # exposure at ~8% (206 ms vs 223 ms at seq=64K): the fwd ceiling is
        # per-grid-step overhead, not softmax scheduling.
        acc = acc * pend[1] + _pv(pend[0], pend[2])
        m_scr[:], l_scr[:], acc_scr[:] = m, l, acc

    def _sweep_diag():
        """The tile the causal diagonal cuts (i == j, bq == bkv), in row
        chunks of `diag` rows: under the caller's promise (full-window
        causal, offset 0 or -1, see flash_fwd) row chunk r sees all of the
        columns left of its own square, none right of it, and the square
        itself through the diagonal.  So chunk r folds columns
        [0, (r+1)*diag) in ONE fold: the columns left of the square without
        a mask (no iota, no select), the square under _block_mask with the
        real spec scalars (so offset -1 and block units stay exact), and
        the dead squares not at all.  One running max a row for the whole
        tile where _sweep takes one a compute sub-block, so the result
        differs from sweep(True)'s by rounding.  The three-stage stagger is
        _sweep's, over row chunks: the next chunk's scores are issued before
        this chunk's softmax, and its pv one chunk late.  Measured against
        the column-major form (column chunk u folded into rows [u*diag, bq),
        _sweep's order): the 8,192-row call reads 4.07 / 4.32 / 4.22 ms that
        way at 1024 / 512 / 256 and 4.19 / 3.76 / 3.53 this way (4.76 whole;
        PERF.md section 6, PR 32): a narrow column chunk pays the [rows, 1]
        state updates, which fill 1 lane of 128, once a chunk."""
        e = diag

        def scores(r):
            qr = q[r * e:(r + 1) * e]
            return (_qk(qr, pl.ds(0, r * e)) if r else None,
                    _qk(qr, pl.ds(r * e, e)))

        def softmax(r, s_full, s_cut):
            rows = pl.ds(r * e, e)
            mask = _block_mask(spec_ref, r0 + r * e, c0 + r * e, e, e, wnd)
            m_prev, l_prev = m_scr[rows, :], l_scr[rows, :]
            s_cut = jnp.where(mask, s_cut, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s_cut, axis=1, keepdims=True))
            if s_full is not None:
                m_new = jnp.maximum(m_new,
                                    jnp.max(s_full, axis=1, keepdims=True))
            alpha = jnp.where(m_prev >= m_new, 1.0, jnp.exp2(m_prev - m_new))
            # the select guards the all-masked-row nan (s = m_new = -inf)
            p_cut = jnp.where(mask, jnp.exp2(s_cut - m_new), 0.0)
            l_new = l_prev * alpha + jnp.sum(p_cut, axis=1, keepdims=True)
            p_full = None
            if s_full is not None:
                p_full = jnp.exp2(s_full - m_new)
                l_new = l_new + jnp.sum(p_full, axis=1, keepdims=True)
            m_scr[rows, :], l_scr[rows, :] = m_new, l_new
            if cast_p:
                p_cut = p_cut.astype(v_ref.dtype)
                if p_full is not None:
                    p_full = p_full.astype(v_ref.dtype)
            return alpha, p_full, p_cut

        def fold_pv(r, alpha, p_full, p_cut):
            rows = pl.ds(r * e, e)
            pv = _p_v(p_cut, rows)
            if p_full is not None:
                pv = pv + _p_v(p_full, pl.ds(0, r * e))
            acc_scr[rows, :] = acc_scr[rows, :] * alpha + pv

        s_cur = scores(0)
        pend = None  # (r, alpha, p_full, p_cut) awaiting its pv + acc fold
        for r in range(bq // e):
            s_next = scores(r + 1) if (r + 1) * e < bq else None
            out = softmax(r, *s_cur)
            if pend is not None:
                fold_pv(*pend)
            pend = (r, *out)
            s_cur = s_next
        fold_pv(*pend)

    sweep = _sweep_loop if loop else _sweep

    @pl.when(fast_cond)
    def _compute_fast():
        sweep(False)

    if diag is None:
        @pl.when(masked_cond)
        def _compute_masked():
            sweep(True)
    elif tri:
        # tri_r == 1: the masked step IS the tile with i == j
        @pl.when(masked_cond)
        def _compute_diag():
            _sweep_diag()
    else:
        @pl.when(masked_cond & (i != j))
        def _compute_masked():
            sweep(True)

        @pl.when(masked_cond & (i == j))
        def _compute_diag():
            _sweep_diag()

    @pl.when(is_fin)
    def _finish():
        m = m_scr[:] * LN2  # back to the natural-log domain
        l = l_scr[:]
        _write_rows(m_out_ref, _shift(i, q_off), m, bq, lp)
        lse = jnp.where(l > 0, m + jnp.log(l), NEG_INF)
        _write_rows(lse_out_ref, _shift(i, q_off), lse, bq, lp)
        if emit_o:
            # fused finalize: o = acc * exp(m - lse) = acc / l — emit the
            # normalized output in the caller's dtype and skip the separate
            # [B,N,S,D]-f32 finalize pass (and its HBM round trip) entirely
            acc_out_ref[0, 0, :, :] = jnp.where(
                l > 0, acc_scr[:] / l, 0.0).astype(acc_out_ref.dtype)
        else:
            acc_out_ref[0, 0, :, :] = acc_scr[:]


def flash_fwd(q, k, v, m, lse, acc, scale, spec: MaskSpec, *,
              block_q=1024, block_kv=1024, block_kv_compute=None,
              interpret=None, cast_p=True, triangular=False, window=None,
              segments=None, emit_o=False, loop_sweep=False, _ablate=None,
              q_range=None, kv_range=None, diag_block=None):
    """One online-softmax ring round on TPU.  Same contract as
    ops/tile.py:tile_fwd: returns updated (m, lse, acc).

    A carried (m, lse, acc) is ALIASED to the outputs: in a ring the round
    updates the scan's carry where it lies.  With `q_range` / `kv_range`
    (see "sub-range rounds" above) the grid covers only those rows of the
    full arrays, and the rows of the state outside `q_range` keep the
    carry's contents because they are the same buffer.

    m = lse = acc = None declares a STATICALLY EMPTY carry (the state a
    fresh init_state would hold): the kernel skips the three state inputs
    entirely and seeds its scratch from constants, eliminating both XLA's
    materialization of the [B,N,S,D] f32 zeros accumulator and the
    per-row-visit acc-in DMA — ~2 GB of dead HBM traffic per 64K-seq
    single-device forward.

    q [B,N,S,D]; k [B,Nk,Skv,D], v [B,Nk,Skv,Dv] (GQA when Nk < N); m, lse
    [B,N,S] f32; acc [B,N,S,Dv] f32.  Dv is v's own last axis (latent
    attention: q, k 192 wide, v, acc and o 128): the kernel body is
    specialised on the static shapes and Dv == D is the program it always
    was; no operand is padded to the other's width.  `spec` scalars may be
    traced values.
    `block_kv_compute` (<= block_kv) sets the in-kernel compute sub-block
    width (see _fwd_kernel._sweep); the default min(block_kv, 1024) is the
    measured v5e optimum (two pipelined sub-blocks per 2048 memory block:
    150 vs 134 TFLOPs/s plain at seq=64K; 512 regresses).

    `triangular=True` selects the wrapped-diagonal all-live grid (see
    _tri_coords) — valid ONLY when the caller statically knows `spec` is
    full-window causal: q_lo=0, q_hi=S, kv_hi=S, causal, offset in {0, -1}
    (at block granularity both offsets have work confined to kv-block
    j <= q-block i with only the diagonal block partial, which is what the
    grid assumes; the diagonal's mask itself uses the real spec scalars, so
    both offsets compute correctly — the striped ring rounds rely on this).
    With `window` set, triangular=True instead selects the BAND grid, whose
    precondition is wider: offset in {0, -1} OR any offset ≡ 0 (mod bkv) —
    the windowed contig ring's live rounds have offset r*s with bkv | s,
    and the band width enumeration is shift-invariant at block-aligned
    offsets (the kernel's _kv_jmin/_kv_jmax read the traced offset; see
    fwd_band_nb).  Do NOT tighten either grid to absolute offsets.
    Falls back to the rectangular grid when the square-tiling preconditions
    don't hold.

    Under the same promise the tile the diagonal cuts (q block i against kv
    block i; block_q == block_kv, no window, no segments: fwd_diag_path)
    computes its live sub-squares only, in row chunks of `diag_block` rows
    (_fwd_kernel._sweep_diag; None: the generation's, ops/tuning.py; 0: the
    whole tile on the masked path), on the triangular grid and, where that
    cannot be formed (one q block, an odd count), on the rectangular one.
    Measured on the v5e (benchmarks/sweep_tile_calls.py --edges; kernel
    device ms of one call, 32 heads x 128, bf16, the row's 2048 x 2048
    tiles): the 8,192-row call (10 tiles a head, 4 diagonal) 4.76 whole,
    4.19 / 3.76 / 3.53 / 3.51 / 3.59 at 1024 / 512 / 256 / 128 / 64 (32 / 8
    and 32 / 4 heads, block units of 4, offset 0 and -1, empty and carried
    state: the same to 0.01); the 1,024-row call x batch 8 (one 1024 x 1024
    tile a head) 1.526 whole, 1.119 / 0.944 / 0.920 / 1.134 at 512 / 256 /
    128 / 64.  A diagonal 2048 x 2048 tile of 32 heads, with its init and
    finish: 0.61 ms whole, 0.30 at 256 and at 128; a full one 0.386.  The
    generation's edge is 256, not 128: the sweep is unrolled in Python, and
    its trace is part of a program's set-up (ops/tuning.py has both costs).

    The defaults and switches are resolved here; the kernel launch on exact
    tiles (_fwd_launch) is traced behind ONE jit (_fwd_launch_traced) whose
    static keywords are everything but the arrays, so that the layers of a
    model, and jax.checkpoint's recomputed forward, share one trace of the
    kernel and one lowered function.  XLA inlines the call.
    """
    if interpret is None:
        interpret = _interpret_default()
    if not loop_sweep and _ablate is None and _fwd_loop_default():
        loop_sweep = True  # BURST_FWD_LOOP promotion (see _fwd_loop_default)
    if _ablate is not None and loop_sweep:
        raise ValueError("_ablate has no loop_sweep variant — the ablation "
                         "would silently time the full softmax chain")
    if diag_block is None:
        diag_block = tuning.block_defaults().diag_block
    # everything the kernel launch reads from the environment is resolved
    # here and rides its static keywords: the cached trace answers for
    # nothing else
    return _flash_fwd_body(
        q, k, v, m, lse, acc, spec, segments, scale=scale, block_q=block_q,
        block_kv=block_kv, block_kv_compute=block_kv_compute,
        interpret=interpret, cast_p=cast_p, triangular=triangular,
        window=window, emit_o=emit_o, loop_sweep=loop_sweep, _ablate=_ablate,
        q_range=q_range, kv_range=kv_range, diag_block=diag_block,
        no_tri=_tri_disabled())


def _flash_fwd_body(q, k, v, m, lse, acc, spec, segments, *, scale, block_q,
                    block_kv, block_kv_compute, interpret, cast_p, triangular,
                    window, emit_o, loop_sweep, _ablate, q_range, kv_range,
                    diag_block, no_tri):
    """flash_fwd with its defaults resolved: the sliced and the padded form
    around the kernel, then the launch on exact tiles (_fwd_launch)."""
    static = dict(
        scale=scale, block_q=block_q, block_kv=block_kv,
        block_kv_compute=block_kv_compute, interpret=interpret, cast_p=cast_p,
        window=window, emit_o=emit_o, loop_sweep=loop_sweep, _ablate=_ablate,
        diag_block=diag_block, no_tri=no_tri)
    carry = m is not None
    assert (lse is None) == (acc is None) == (not carry), \
        "m, lse, acc must be all None (empty carry) or all present"
    assert carry or q_range is None, "a q_range round updates a carried state"
    if not fwd_covers_ranges(
            q.shape[2], k.shape[2], q_range, kv_range, block_q=block_q,
            block_kv=block_kv, triangular=triangular):
        from .tile import fwd_on_ranges

        def on_slices(q, k, v, m, lse, acc, scale, spec, segments):
            return _flash_fwd_body(
                q, k, v, m, lse, acc, spec, segments, triangular=triangular,
                q_range=None, kv_range=None, **static)

        return fwd_on_ranges(
            on_slices, q, k, v, m, lse, acc, scale, spec, segments=segments,
            q_range=q_range, kv_range=kv_range)
    b, n, sq_full, d = q.shape
    n_kv, skv_full = k.shape[1], k.shape[2]
    # the lengths the grid covers; the arrays keep their full length
    s_q, s_kv = _range_len(q_range, sq_full), _range_len(kv_range, skv_full)
    group = _gqa_group(n, n_kv)
    sq_pad, skv_pad = _padded_len(s_q, block_q), _padded_len(s_kv, block_kv)
    if sq_pad != s_q or skv_pad != s_kv:
        # ragged lengths: pad, run, slice back (spec bounds stay in true
        # coordinates so the pad region is masked; tri grids assume exact
        # full-window tiling, so the padded call is rectangular)
        if segments is not None:
            # pad ids never match each other or any real segment
            segments = (_pad_seg(segments[0], sq_pad, -1),
                        _pad_seg(segments[1], skv_pad, -2))
        m2, lse2, acc2 = _flash_fwd_body(
            _pad_seq(q, sq_pad), _pad_seq(k, skv_pad), _pad_seq(v, skv_pad),
            _pad_seq(m, sq_pad, float("-inf")) if carry else None,
            (_pad_seq(lse, sq_pad, float("-inf")) if carry else None),
            _pad_seq(acc, sq_pad) if carry else None,
            spec, segments, triangular=False, q_range=None, kv_range=None,
            **static)
        return m2[:, :, :s_q], lse2[:, :, :s_q], acc2[:, :, :s_q]
    lp = _pick_block(_pick_block(s_q, block_q), 128)
    inputs = [_spec_array(spec), q, k, v]
    if carry:
        inputs += [_pack(m, lp), _pack(lse, lp), acc]
    if segments is not None:
        q_seg, kv_seg = segments
        # ids as [B, S, 1] (q rows along sublanes) / [B, 1, S] (kv along
        # lanes) so the in-kernel compare broadcasts without relayout
        inputs.append(jnp.asarray(q_seg, jnp.int32)[:, :, None])
        inputs.append(jnp.asarray(kv_seg, jnp.int32)[:, None, :])
    m_new, lse_new, acc_new = _fwd_launch_traced(
        *inputs, carry=carry, seg=segments is not None,
        triangular=triangular, q_range=q_range, kv_range=kv_range, **static)
    return _unpack(m_new), _unpack(lse_new), acc_new


def _fwd_launch(spec_arr, q, k, v, *rest, carry, seg, scale, block_q,
                block_kv, block_kv_compute, interpret, cast_p, triangular,
                window, emit_o, loop_sweep, _ablate, q_range, kv_range,
                diag_block, no_tri):
    """The forward kernel on exact tiles: `rest` is the packed (m, lse) and
    acc where `carry`, then the segment ids as [B, S, 1] / [B, 1, S] where
    `seg`; every keyword static.  Returns the packed (m, lse) and acc (or o
    under emit_o).  q and k are `d` wide, v, acc and o `d_v` wide."""
    b, n, sq_full, d = q.shape
    d_v = v.shape[-1]
    n_kv, skv_full = k.shape[1], k.shape[2]
    # the lengths the grid covers; the arrays keep their full length
    s_q, s_kv = _range_len(q_range, sq_full), _range_len(kv_range, skv_full)
    group = _gqa_group(n, n_kv)
    bq = _pick_block(s_q, block_q)
    bkv = _pick_block(s_kv, block_kv)
    if block_kv_compute is None:
        block_kv_compute = min(bkv, 1024)
    bkc = _pick_block(bkv, block_kv_compute)
    lp = _pick_block(bq, 128)
    nqb = s_q // bq
    nkb = s_kv // bkv
    q_off = q_range[0] // bq if q_range is not None else 0
    kv_off = kv_range[0] // bkv if kv_range is not None else 0
    # a masks.BlockUnits window: the spec and `win` count blocks of `unit`
    # tokens, and every tile (the compute sub-blocks too) is whole blocks
    unit, win = unit_of(window)
    _check_unit_tiles(window, bq, bkc)
    tri = (bool(triangular) and win is None and not no_tri
           and bq % bkv == 0 and s_q == s_kv and nqb % 2 == 0 and nqb >= 2)
    tri_r = bq // bkv if tri else 1  # tall-q aspect (see _tri_coords)
    # band grid: the window analogue of the tri grid.  A q-block's band can
    # intersect at most band_nb kv blocks (exact max over the reachable
    # alignments r0 = i*bq and offsets {0,-1}), so the kv grid dim shrinks
    # from nkb to band_nb — at window=4K/seq=64K/bkv=2048 that is 3 steps
    # per row instead of 32, and per-grid-step overhead is what dominates
    # small-window runs (measured 53 band-TFLOPs/s at window=4K vs 158
    # full-causal, results/results_window.jsonl).  Same caller contract as
    # tri (static full-window causal, offset 0/-1), which `triangular=True`
    # already promises.
    band_nb = None
    if bool(triangular) and win is not None and not no_tri:
        nb = min(nkb, fwd_band_nb(bq // unit, bkv // unit, win))
        if nb < nkb:
            band_nb = nb
    if tri:
        def q_map(b_, h, p, jp, sp):
            return (b_, h, jnp.where(jp >= (p + 1) * tri_r, nqb - 1 - p, p), 0)

        def kv_map(b_, h, p, jp, sp):
            lena = (p + 1) * tri_r
            return (b_, h // group, jnp.where(jp >= lena, jp - lena, jp), 0)

        def state_map(b_, h, p, jp, sp):
            return (b_, h, 0, 0)

        grid = (b, n, nqb // 2, (nqb + 1) * tri_r)
    elif band_nb is not None:
        def q_map(b_, h, i, c, sp):
            return (b_, h, i, 0)

        def kv_map(b_, h, i, c, sp):
            j = _kv_jmin(sp, i, bq, bkv, nkb, window) + c
            j_eff = jnp.minimum(j, _kv_jmax(sp, i, bq, bkv, nkb, window))
            return (b_, h // group, j_eff, 0)

        def state_map(b_, h, i, c, sp):
            return (b_, h, 0, 0)

        grid = (b, n, nqb, band_nb)
    else:
        q_map, kv_map, state_map = _make_index_maps(
            bq, bkv, nqb, nkb, group, wnd=window, q_off=q_off, kv_off=kv_off)
        grid = (b, n, nqb, nkb)
    path = None if _ablate is not None else fwd_diag_path(
        s_q, s_kv, block_q=block_q, block_kv=block_kv, triangular=triangular,
        window=window, segments=seg, diag_block=diag_block,
        loop_sweep=loop_sweep)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, bq=bq, bkv=bkv, bkv_compute=bkc, lp=lp,
        n_kv_blocks=nkb, cast_p=cast_p, tri=tri, wnd=window,
        seg=seg, emit_o=emit_o, loop=loop_sweep,
        ablate=_ablate, band_nb=band_nb, carry=carry, tri_r=tri_r,
        q_off=q_off, keep_rows=s_q != sq_full,
        diag=None if path is None else path.edge,
    )
    state_block = pl.BlockSpec((1, 1, sq_full // lp, lp), state_map)
    in_specs = [
        pl.BlockSpec((1, 1, bq, d), q_map),
        pl.BlockSpec((1, 1, bkv, d), kv_map),
        pl.BlockSpec((1, 1, bkv, d_v), kv_map),
    ]
    if carry:
        in_specs += [state_block, state_block,
                     pl.BlockSpec((1, 1, bq, d_v), q_map)]
    if seg:
        in_specs.append(pl.BlockSpec(
            (1, bq, 1), lambda b_, h, i, j, sp: (b_, q_map(b_, h, i, j, sp)[2], 0)))
        in_specs.append(pl.BlockSpec(
            (1, 1, bkv), lambda b_, h, i, j, sp: (b_, 0, kv_map(b_, h, i, j, sp)[2])))
    out_shape = [
        jax.ShapeDtypeStruct((b, n, sq_full // lp, lp), jnp.float32),
        jax.ShapeDtypeStruct((b, n, sq_full // lp, lp), jnp.float32),
        # emit_o: the third output is the NORMALIZED o in q's dtype (fused
        # finalize, see _finish) instead of the raw f32 accumulator
        jax.ShapeDtypeStruct((b, n, sq_full, d_v),
                             q.dtype if emit_o else jnp.float32),
    ]
    # the carried state is updated where it lies (flattened inputs: spec, q,
    # k, v, then m, lse, acc).  Each acc block is read before its one write
    # and never again, so the alias needs no separation argument; emit_o's
    # third output is another dtype and cannot share acc's buffer
    aliases = {4: 0, 5: 1, 6: 2} if carry and not emit_o else {}
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            state_block,
            state_block,
            pl.BlockSpec((1, 1, bq, d_v), q_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d_v), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        # a band grid runs under a name of its own (same prefix): a trace
        # shows a windowed call's time beside the other forward calls'
        name="burst_flash_fwd" + ("_band" if band_nb is not None else ""),
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        # q-block dim must be "arbitrary": the packed m/lse out blocks are
        # shared by every q-block of a head, so a megacore split over dim 2
        # would race the partial writes.
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT,
            dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(spec_arr, q, k, v, *rest)


# One trace and one lowered body a DISTINCT forward call, not one a call site
# (PR 33).  Pallas traces a kernel body anew at every pallas_call, a model
# makes one a layer (and jax.checkpoint's forward again), and _sweep_diag is a
# Python-unrolled loop: trace + lower of jax.grad over four
# jax.checkpoint(burst_attn) blocks at 1 x 8,192 rows for a described v5e
# (benchmarks/trace_cost.py, this repo's CPU host) reads 0.46-0.57 s with the
# whole-tile body, 0.96 with the sub-square sweep at an edge of 128 traced a
# site (PR 32), and 0.54-0.61 at 256 behind this jit (PERF.md section 6).
# Every layer's call has the same static keywords and avals, so the later ones
# hit jit's trace cache and lower to calls of one func.func; XLA inlines it.
# The jit holds the kernel launch and nothing else: what XLA fuses around a
# call (slices, pads, the state's packing, the ring's finalize) must meet the
# graph it met before, or the program around the kernel moves (with the
# state's unpacking inside, the four-chip ring compiled one copy and 32,256
# bytes of temporaries away from the parent's).
_fwd_launch_traced = jax.jit(_fwd_launch, static_argnames=(
    "carry", "seg", "scale", "block_q", "block_kv", "block_kv_compute",
    "interpret", "cast_p", "triangular", "window", "emit_o", "loop_sweep",
    "_ablate", "q_range", "kv_range", "diag_block", "no_tri"))


# ---------------------------------------------------------------------------
# backward: dq kernel


def _dq_kernel(
    spec_ref,
    do_ref, q_ref, k_ref, v_ref, delta_ref, lse_ref,
    *rest,
    scale, bq, bkv, lp, n_kv_blocks, wnd=None, seg=False,
):
    if seg:
        qseg_ref, kvseg_ref = rest[0], rest[1]
        rest = rest[2:]
    dq_ref, dq_scr, lse_scr, delta_scr = rest
    i = pl.program_id(2)
    j = pl.program_id(3)
    r0 = i * bq
    c0 = j * bkv

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        lse = _read_rows(lse_ref, i, bq, lp)
        # fully-masked rows have lse = -inf; substituting a large positive
        # value makes p = exp2(s - BIG) underflow to 0 without an elementwise
        # select over the [bq, bkv] tile.  lse converted to base 2 (LOG2E).
        lse_scr[:] = jnp.where(lse == NEG_INF, BIG_LSE, lse * LOG2E)
        delta_scr[:] = _read_rows(delta_ref, i, bq, lp)

    live = _block_has_work(spec_ref, r0, c0, bq, bkv, wnd) & (
        j <= _kv_jmax(spec_ref, i, bq, bkv, n_kv_blocks, wnd)
    )
    full = _block_full(spec_ref, r0, c0, bq, bkv, wnd)
    seg_tiles = None
    if seg:
        # mixed-segment blocks lose only the fast path; dead-block pruning
        # (live) keys on the causal structure and stays valid
        seg_tiles = (qseg_ref[0, :, :], kvseg_ref[0, :, :])
        full = full & _seg_uniform_eq(*seg_tiles)

    def _accum(mask):
        q = q_ref[0, 0, :, :] * (scale * LOG2E)
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        # dp is independent of the softmax: issue it before the VPU chain
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        p = jnp.exp2(s - lse_scr[:])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        # the trailing *scale of ds is deferred to _finish (constant across j)
        ds = p * (dp - delta_scr[:])
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(live & full)
    def _compute_fast():
        _accum(None)

    @pl.when(live & ~full)
    def _compute_masked():
        _accum(_block_mask(spec_ref, r0, c0, bq, bkv, wnd, seg=seg_tiles))

    @pl.when(j == n_kv_blocks - 1)
    def _finish():
        dq_ref[0, 0, :, :] = dq_scr[:] * scale


# ---------------------------------------------------------------------------
# backward: dk/dv kernel
#
# Grid innermost dimension iterates over (gqa-group, q-block) pairs so each
# (batch, kv-head, kv-block) program accumulates contributions from every
# query head it serves — the group reduction of ops/tile.py:tile_bwd done
# in-kernel without atomics.


def _dkdv_kernel(
    spec_ref,
    do_ref, q_ref, k_ref, v_ref, delta_ref, lse_ref,
    *rest,
    scale, bq, bkv, lp, n_q_blocks, group, wnd=None, seg=False,
):
    if seg:
        qseg_ref, kvseg_ref = rest[0], rest[1]
        rest = rest[2:]
    dk_ref, dv_ref, dk_scr, dv_scr = rest
    j = pl.program_id(2)
    t = pl.program_id(3)
    iq = t % n_q_blocks
    r0 = iq * bq
    c0 = j * bkv

    @pl.when(t == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    live = _block_has_work(spec_ref, r0, c0, bq, bkv, wnd) & (
        iq >= _q_imin(spec_ref, j, bq, bkv, n_q_blocks, wnd)
    )
    full = _block_full(spec_ref, r0, c0, bq, bkv, wnd)
    seg_tiles = None
    if seg:
        seg_tiles = (qseg_ref[0, :, :], kvseg_ref[0, :, :])
        full = full & _seg_uniform_eq(*seg_tiles)

    def _accum(mask):
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        lse_row = _read_rows(lse_ref, iq, bq, lp)
        lse_row = jnp.where(lse_row == NEG_INF, BIG_LSE, lse_row * LOG2E)
        delta_row = _read_rows(delta_ref, iq, bq, lp)

        s = jax.lax.dot_general(
            q * (scale * LOG2E), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dp is independent of the softmax: issue it before the VPU chain
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        p = jnp.exp2(s - lse_row)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        # trailing *scale of ds deferred to _finish; dk uses the RAW q block
        ds = p * (dp - delta_row)
        # dv += p^T @ do
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dk += ds^T @ q
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(live & full)
    def _compute_fast():
        _accum(None)

    @pl.when(live & ~full)
    def _compute_masked():
        _accum(_block_mask(spec_ref, r0, c0, bq, bkv, wnd, seg=seg_tiles))

    @pl.when(t == n_q_blocks * group - 1)
    def _finish():
        dk_ref[0, 0, :, :] = dk_scr[:] * scale
        dv_ref[0, 0, :, :] = dv_scr[:]


# ---------------------------------------------------------------------------
# shared fused-backward tile body (used by both the rectangular and the
# wrapped-diagonal fused kernels, which differ only in scheduling and in
# where dq accumulates — threaded in via `dq_update`)


def _flush_dk(dk_scr, ds_pend, q_pend, pend_flag):
    """Deferred dk accumulation for the previous live step's ds tile.
    Issued at step START, before this step's s/dp matmuls, so the MXU
    queue [dk, s, dp, dv] is entirely independent of this step's VPU
    p/ds chain: p is ready when dv's turn comes (one matmul after its
    dependency s), and ds is ready when the final dq issues — no MXU op
    waits on the VPU in steady state.  dv is NOT deferred: its operand p
    is finished two matmul-slots before dv's queue position, so deferring
    it only adds scratch-stash traffic.  (Measured on v5e at seq=64K:
    no deferral 166.5 TFLOPs/s; dv+dk deferred 169.6; flush nested after
    s/dp instead of step start 165.2.)"""
    dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
        ds_pend[:], q_pend[:], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    pend_flag[0] = 0


def _bwd_accum_tile(
    do_ref, q_ref, k_ref, v_ref, delta_ref, lse_ref,
    dv_scr, ds_pend, q_pend, pend_flag,
    iq, mask, *, scale, bq, lp, dq_update,
):
    """One fused-backward block pair: s/dp matmuls, p/ds VPU chain, inline
    dv accumulation, dq via `dq_update(ds, k)`, and the dk pend stash (in
    the bf16 the matmul would cast to anyway — numerics unchanged; the next
    step's _flush_dk issues it behind that step's own s/dp)."""
    q = q_ref[0, 0, :, :]
    k = k_ref[0, 0, :, :]
    v = v_ref[0, 0, :, :]
    do = do_ref[0, 0, :, :]
    lse_row = _read_rows(lse_ref, iq, bq, lp)
    lse_row = jnp.where(lse_row == NEG_INF, BIG_LSE, lse_row * LOG2E)
    delta_row = _read_rows(delta_ref, iq, bq, lp)

    # s and dp are independent MXU ops issued back to back; the VPU
    # p/ds chain overlaps them and the flush matmul queued before
    s = jax.lax.dot_general(
        q * (scale * LOG2E), k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    p = jnp.exp2(s - lse_row)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta_row)
    dq_update(ds, k)
    ds_pend[:] = ds.astype(q.dtype)
    q_pend[:] = q
    pend_flag[0] = 1


def _bwd_cut_tile(
    do_ref, q_ref, k_ref, v_ref, delta_ref, lse_ref, dv_scr, dk_scr,
    iq, pos, mask_of, *, scale, bq, edge, lp, dq_update,
):
    """One fused-backward block pair that the causal diagonal cuts, in its
    live sub-squares only (bwd_diag_path decides, statically).  `pos`
    (traced) is how many q blocks this one lies below the kv block's first
    row: under the caller's promise (full-window causal, offset 0 or -1, in
    tokens or in whole mask units) its rows see all of the kv block's
    columns [0, pos * bq), the square [pos * bq, (pos + 1) * bq) through the
    diagonal and nothing right of it.  ONE body serves every position:

      * the square, at the traced column offset pos * bq, in column chunks
        of `edge` columns (_bwd_accum_tile_sub's order): chunk u meets the
        rows of its own sub-square under `mask_of(u)` (_block_mask with the
        real spec scalars, so offset -1 and block units stay exact), all the
        rows below it at once without a mask (no iota, no select) and the
        rows above it not at all.  dv / dk of a chunk are summed as values
        and added to the scratch once; dq's rows are summed as values too;
      * the columns left of the square one block_q-wide piece at a time,
        each whole and without a mask under its own pl.when (piece `at`
        exists where pos >= at).

    The sums are the whole tile's in another order (float32 accumulation,
    bf16 operands where they are bf16 there).  dk is folded HERE and
    pend_flag left alone (0: the step's flush ran first): a cut block is the
    last live step of its sweep, and its pairs are too few to hide a stash's
    write and read behind.  dq leaves through `dq_update(rows, first)`:
    first the square's assembled rows (a store, where the kernel adds dq in
    place: the visit's one read of the aliased input), then each left
    piece's on top of it, all inside the one grid step: a visit stays a
    visit.

    Measured on the v5e (benchmarks/sweep_tile_calls.py --edges --bwd; kernel
    device ms of one call in the row's 1024 x 2048 blocks, 32 query heads x
    128, bf16; PERF.md section 6, PR 35).  The 8,192-row call at 32 / 8
    heads (the rectangular kernel; 6 full and 4 cut tile-units of
    2048 x 2048): 10.27 whole, then by edge 512 / 256 / 128: 8.57 / 8.43 /
    8.55 this way and 8.61 / 8.49 / 8.62 in row chunks (the forward's order:
    chunk r against the r sub-squares left of its own at once; each chunk
    then reads, adds to and writes back its columns' dv / dk scratch rows);
    at 32 / 32 heads (the triangular kernel) 9.91 whole, 8.47 / 8.33 / 8.45
    and 8.51 / 8.39 / 8.52; the 1,024-row call x batch 8 2.147 whole, 1.320
    at 256 (rows: 1.374); 16,384 rows at 192 / 128 54.28 whole, 49.46
    (49.72); 65,536 rows 506.9 whole, 494.2 (494.7).  A cut tile-unit:
    1.05-1.08 ms whole, 0.62-0.65 at 256 (a full one 0.95-0.99).

    Why one traced position and not a body a position: Mosaic gives every
    pl.when body its own intermediates, and past the scoped-VMEM budget a
    kernel runs three times slower (the block-area cliff of ops/tuning.py).
    A body a position, beside a whole-tile masked body kept for blocks that
    cannot occur, sat on that brink at an edge of 256: the 8,192-row call
    read 8.46 ms at 32 / 8 heads but 27.3 in block units and 27.5 at an edge
    of 512, and the 16,384-row call at 192 / 128 95.2.  This form reads the
    same to 0.01 ms with VMEM_LIMIT at 88 MiB as at 100."""
    e = edge
    n_chunks = bq // e
    base = pl.multiple_of(pos * bq, bq)
    qs_scale = scale * LOG2E
    lse_row = _read_rows(lse_ref, iq, bq, lp)
    lse_row = jnp.where(lse_row == NEG_INF, BIG_LSE, lse_row * LOG2E)
    delta_row = _read_rows(delta_ref, iq, bq, lp)

    def chain(rows, cols, mask):
        """p and ds of the rows x cols piece, dp issued beside the scores."""
        s = jax.lax.dot_general(
            q_ref[0, 0, rows, :] * qs_scale, k_ref[0, 0, cols, :],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do_ref[0, 0, rows, :], v_ref[0, 0, cols, :],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        p = jnp.exp2(s - lse_row[rows])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        return p, p * (dp - delta_row[rows])

    def grads(rows, cols, mask):
        """(dv, dk) of the columns and dq of the rows from one piece."""
        p, ds = chain(rows, cols, mask)
        do, q, k_c = (do_ref[0, 0, rows, :], q_ref[0, 0, rows, :],
                      k_ref[0, 0, cols, :])
        t_dims = (((0,), (0,)), ((), ()))  # a^T @ b
        return (
            jax.lax.dot_general(p.astype(do.dtype), do, t_dims,
                                preferred_element_type=jnp.float32),
            jax.lax.dot_general(ds.astype(q.dtype), q, t_dims,
                                preferred_element_type=jnp.float32),
            jax.lax.dot_general(ds.astype(k_c.dtype), k_c,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32))

    def add_to(scr, cols, x):
        scr[cols, :] = scr[cols, :] + x

    dq_rows = [None] * n_chunks

    def add_dq(r, x):
        dq_rows[r] = x if dq_rows[r] is None else dq_rows[r] + x

    for u in range(n_chunks):
        cols = pl.ds(base + u * e, e)
        dv_u, dk_u, dq_u = grads(slice(u * e, (u + 1) * e), cols, mask_of(u))
        add_dq(u, dq_u)
        if u + 1 < n_chunks:
            dv_b, dk_b, dq_b = grads(slice((u + 1) * e, bq), cols, None)
            dv_u, dk_u = dv_u + dv_b, dk_u + dk_b
            for r in range(u + 1, n_chunks):
                add_dq(r, dq_b[(r - u - 1) * e:(r - u) * e])
        add_to(dv_scr, cols, dv_u)
        add_to(dk_scr, cols, dk_u)
    dq_update(jnp.concatenate(dq_rows, axis=0), True)
    for at in range(1, k_ref.shape[2] // bq):
        @pl.when(pos >= at)
        def _left(at=at):
            cols = slice((at - 1) * bq, at * bq)
            dv_l, dk_l, dq_l = grads(slice(0, bq), cols, None)
            add_to(dv_scr, cols, dv_l)
            add_to(dk_scr, cols, dk_l)
            dq_update(dq_l, False)


# ---------------------------------------------------------------------------
# backward: fused kernel (dq + dk + dv in one pass)
#
# The split dq/dkdv kernels each recompute s and dp — 7 matmuls and 2
# softmax-exp passes per block pair where 5 and 1 suffice.  The fused kernel
# keeps dk/dv in VMEM scratch (kv-block-major grid) and accumulates dq
# IN PLACE in HBM via input_output_aliasing (the megablox gmm pattern):
# each visit reads the aliased dq block, adds this block's contribution, and
# writes it back.  Two structural rules make this race-free:
#   * q-blocks iterate DESCENDING within each kv sweep, so a dq block
#     written in sweep j is re-read in sweep j+1 exactly one full sweep
#     (nqb*group grid steps) later — far outside the pipeline's prefetch
#     lookahead.  Ascending order would re-read the last diagonal block only
#     one step after its write.
#   * index-map clamping maps skipped (masked) steps onto the first live
#     block, so consecutive duplicate indices collapse into one
#     fetch/flush; duplicate visits rewrite identical content.
# Gated on n_q_blocks * group >= 4 (below that the split kernels are used;
# the separation argument needs a reasonably long sweep).  Measured on the
# v5e (PR 22, libtpu 0.0.34): forced at a 2-step sweep of 512x512 blocks, dq
# comes back off by 0.07-0.19 (dk/dv exact); 3-step sweeps and 2-step sweeps
# of 256x256 blocks agree with the split kernels to 1e-7.


def _bwd_fused_iq(spec_ref, j, c, bq, bkv, n_q_blocks, wnd):
    """Shared kernel/index-map iq schedule for the fused bwd sweep: descend
    from the sweep's bottom-most useful q block.  Without a window that is
    n_q_blocks-1; with one it is _q_imax (rows below it have their whole
    band left of kv block j), floored at imin so an empty column still
    yields a deterministic (passthrough-written) block.  Returns
    (iq_clamped, clamped): clamped steps revisit imin's block and must not
    write dq.  Note the band preserves the descending-separation argument:
    a block written at step c_w of sweep j re-appears in sweep j+1 at
    c_r = c_w + (imax(j+1) - imax(j)) >= c_w, i.e. a full sweep later."""
    imin = _q_imin(spec_ref, j, bq, bkv, n_q_blocks, wnd)
    if unit_of(wnd)[1] is None:
        imax = n_q_blocks - 1
    else:
        imax = jnp.maximum(_q_imax(spec_ref, j, bq, bkv, n_q_blocks, wnd),
                           imin)
    iq_raw = imax - c
    return jnp.maximum(iq_raw, imin), iq_raw < imin


def _bwd_fused_kernel(
    spec_ref,
    do_ref, q_ref, k_ref, v_ref, delta_ref, lse_ref, dq_in_ref,
    *rest,
    scale, bq, bkv, lp, n_q_blocks, group, nbq, wnd=None, seg=False,
    carry=False, q_off=0, diag=None,
):
    # carry: dk, dv of the rounds before arrive as inputs aliased to the
    # outputs, and _finish adds this round's to them.  q_off: the sweep's
    # first q block in the full delta / lse arrays (a sub-range round, see
    # flash_bwd); iq, r0, c0 and the masks stay local to the range.
    # diag: the sub-square edge where the blocks the diagonal cuts take
    # _bwd_cut_tile (bwd_diag_path decides, statically), else None.
    if carry:
        dk_in_ref, dv_in_ref = rest[0], rest[1]
        rest = rest[2:]
    if seg:
        qseg_ref, kvseg_ref = rest[0], rest[1]
        rest = rest[2:]
    (dq_out_ref, dk_ref, dv_ref,
     dk_scr, dv_scr, ds_pend, q_pend, pend_flag) = rest
    j = pl.program_id(2)
    t = pl.program_id(3)
    # descending within the (possibly window-banded) sweep of nbq steps
    iq, clamped = _bwd_fused_iq(spec_ref, j, t % nbq, bq, bkv, n_q_blocks,
                                wnd)
    r0 = iq * bq
    c0 = j * bkv

    @pl.when(t == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)
        pend_flag[0] = 0

    # clamped steps revisit block imin, whose live visit came just before
    # them in the descending sweep; they must not touch dq_out or they'd
    # overwrite that visit's accumulation with the stale dq_in buffer
    live = _block_has_work(spec_ref, r0, c0, bq, bkv, wnd) & ~clamped
    full = _block_full(spec_ref, r0, c0, bq, bkv, wnd)
    if seg:
        # packed sequences: only single-segment-matching blocks skip the
        # mask (same classification as _fwd_kernel)
        qs_tile = qseg_ref[0, :, :]   # [bq, 1]
        ks_tile = kvseg_ref[0, :, :]  # [1, bkv]
        seg_ok = _seg_uniform_eq(qs_tile, ks_tile)
        fast_cond = live & full & seg_ok
        masked_cond = live & ~(full & seg_ok)
    else:
        fast_cond = live & full
        masked_cond = live & ~full

    @pl.when(pend_flag[0] == 1)
    def _flush_prev():
        _flush_dk(dk_scr, ds_pend, q_pend, pend_flag)

    def _dq_update(ds, k):
        # in-place dq accumulation (ds*scale deferred to the caller's epilog
        # would lose the per-visit accumulation — apply it here instead)
        dq_out_ref[0, 0, :, :] = dq_in_ref[0, 0, :, :] + scale * jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def _accum(mask):
        _bwd_accum_tile(
            do_ref, q_ref, k_ref, v_ref, delta_ref, lse_ref,
            dv_scr, ds_pend, q_pend, pend_flag,
            _shift(iq, q_off), mask, scale=scale, bq=bq, lp=lp,
            dq_update=_dq_update,
        )

    @pl.when(fast_cond)
    def _compute_fast():
        _accum(None)

    if diag is None:
        @pl.when(masked_cond)
        def _compute_masked():
            _accum(_block_mask(spec_ref, r0, c0, bq, bkv, wnd,
                               seg=(qs_tile, ks_tile) if seg else None))
    else:
        # under the caller's promise the masked blocks are exactly the
        # bkv // bq that the diagonal cuts, `pos` q blocks below kv block
        # j's first row; no whole-tile masked body is kept beside the cut
        # one (its intermediates would count against the same VMEM, see
        # _bwd_cut_tile).  The visit of the dq block is the whole tile's:
        # one read of the aliased input, one block written back
        def _dq_store(dq_rows, first):
            prev = dq_in_ref if first else dq_out_ref
            dq_out_ref[0, 0, :, :] = prev[0, 0, :, :] + scale * dq_rows

        @pl.when(masked_cond)
        def _compute_cut():
            pos = iq - j * (bkv // bq)
            _bwd_cut_tile(
                do_ref, q_ref, k_ref, v_ref, delta_ref, lse_ref, dv_scr,
                dk_scr, _shift(iq, q_off), pos,
                lambda r: _block_mask(
                    spec_ref, r0 + r * diag, c0 + pos * bq + r * diag,
                    diag, diag, wnd),
                scale=scale, bq=bq, edge=diag, lp=lp, dq_update=_dq_store)

    @pl.when(~live & ~clamped)
    def _passthrough():
        # an unclamped dead block (fully-masked column / row range) gets its
        # own buffer flush at the next index change; keep its content valid
        dq_out_ref[0, 0, :, :] = dq_in_ref[0, 0, :, :]

    @pl.when(t == nbq * group - 1)
    def _finish():
        # drain: this sweep's last live step just stashed its pend tiles
        @pl.when(pend_flag[0] == 1)
        def _drain():
            _flush_dk(dk_scr, ds_pend, q_pend, pend_flag)

        if carry:
            # rounded to float32 HERE, as the sliced form rounds this
            # round's dk before it adds the carry (an add fused with the
            # multiply would round once, and the two forms would differ)
            dk_scr[:] = dk_scr[:] * scale
        else:
            dk_ref[0, 0, :, :] = dk_scr[:] * scale
            dv_ref[0, 0, :, :] = dv_scr[:]

    if carry:
        @pl.when(t == nbq * group - 1)
        def _fold():
            # the carry's block index is constant over the sweep: one fetch
            dk_ref[0, 0, :, :] = dk_in_ref[0, 0, :, :] + dk_scr[:]
            dv_ref[0, 0, :, :] = dv_in_ref[0, 0, :, :] + dv_scr[:]


def _flush_dk_sub(dk_scr, ds_pend, q_pend, pend_flag, bkvc):
    """Sub-block-width deferred dk flush (tri kernel): pend_flag holds
    (flag, sub-block index); the dk rows are a dynamic slice of the kv-block
    scratch.  Same scheduling argument as _flush_dk — the matmul issues at
    the NEXT step's start, ahead of that step's VPU dependencies."""
    rows = pl.ds(pend_flag[1] * bkvc, bkvc)
    dk_scr[rows, :] = dk_scr[rows, :] + jax.lax.dot_general(
        ds_pend[:], q_pend[:], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    pend_flag[0] = 0


def _bwd_accum_tile_sub(
    do_ref, q_ref, k_ref, v_ref, delta_ref, lse_ref,
    dv_scr, dk_scr, ds_pend, q_pend, pend_flag,
    iq, masked, mask_of, *, scale, bq, bkvc, n_sub, lp, dq_update,
):
    """Fused-backward block pair with the kv block split into compute
    sub-blocks — the backward analogue of _fwd_kernel._sweep.

    Why: the un-sub-blocked tile keeps four [bq, bkv] f32 intermediates
    (s, dp, p, ds) live at once, which is what pins the backward's VMEM
    cliff one power of two below the forward's (see ops/tuning.py).
    Splitting the kv dimension into n_sub pieces shrinks the live
    intermediates to [bq, bkvc], buying the same grid-step count at double
    the kv block — fewer steps, same math.

    Scheduling per sub-block u: s(u) and dp(u) issue back to back on the
    MXU; the VPU p/ds chain for u overlaps dv(u)'s matmul (its operand p is
    ready one slot earlier) and dk(u-1)'s — dk is deferred ONE SUB-BLOCK
    in-step (plain values, the q operand is the same all step) and one
    GRID STEP for the final sub-block (scratch stash, _flush_dk_sub).  dq
    accumulates across sub-blocks in a [bq, d] f32 value and folds into the
    resident output buffer once per step."""
    q = q_ref[0, 0, :, :]
    do = do_ref[0, 0, :, :]
    lse_row = _read_rows(lse_ref, iq, bq, lp)
    lse_row = jnp.where(lse_row == NEG_INF, BIG_LSE, lse_row * LOG2E)
    delta_row = _read_rows(delta_ref, iq, bq, lp)
    qs = q * (scale * LOG2E)
    dq_acc = None
    prev = None  # (u, ds cast) awaiting its dk matmul
    for u in range(n_sub):
        rows = slice(u * bkvc, (u + 1) * bkvc)
        k_u = k_ref[0, 0, rows, :]
        v_u = v_ref[0, 0, rows, :]
        s = jax.lax.dot_general(
            qs, k_u, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v_u, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        p = jnp.exp2(s - lse_row)
        if masked:
            p = jnp.where(mask_of(u), p, 0.0)
        dv_scr[rows, :] = dv_scr[rows, :] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_row)
        dq_u = jax.lax.dot_general(
            ds.astype(k_u.dtype), k_u, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dq_acc = dq_u if dq_acc is None else dq_acc + dq_u
        if prev is not None:
            pu, pds = prev
            prows = slice(pu * bkvc, (pu + 1) * bkvc)
            dk_scr[prows, :] = dk_scr[prows, :] + jax.lax.dot_general(
                pds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        prev = (u, ds.astype(q.dtype))
    dq_update(dq_acc)
    ds_pend[:] = prev[1]
    q_pend[:] = q
    pend_flag[0] = 1
    pend_flag[1] = prev[0]


def _bwd_accum_tile_sub_loop(
    do_ref, q_ref, k_ref, v_ref, delta_ref, lse_ref,
    dv_scr, dk_scr, ds_pend, q_pend, pend_flag,
    iq, masked, mask_of, *, scale, bq, bkvc, n_sub, lp, dq_update,
):
    """lax.fori_loop variant of _bwd_accum_tile_sub — the backward analogue
    of _fwd_kernel._sweep_loop.

    Why this exists: the unrolled sub-block loop's intermediates are
    allocated SSA-style, so scoped-VMEM demand grows with n_sub·bq·bkvc =
    bq·bkv — the measured backward cliff at 1024x2048 area, which
    sub-blocking alone did NOT move (docs/design.md §3's negative result:
    the round-2 `_bwd_accum_tile_sub` experiment).  A fori_loop body
    reuses its buffers per iteration, capping demand at ~2 stages
    independent of bkv — the experiment that could admit 4096-wide kv
    blocks and halve the backward's grid-step count.  Selected by
    flash_bwd's loop_sweep flag (BURST_BWD_LOOP promotes it).

    Scheduling: same dk deferral as the unrolled variant — dk(u-1) rides
    the loop CARRY (its ds tile, cast to the matmul dtype) and issues at
    the top of iteration u, ahead of u's VPU chain; the final sub-block's
    dk crosses the grid step through the ds_pend/q_pend scratch stash
    exactly like the unrolled path (_flush_dk_sub reads pend_flag[1]).
    `mask_of` here must accept a TRACED u (the tri kernel passes its
    iota-based builder, not the static-slice closure)."""
    q = q_ref[0, 0, :, :]
    do = do_ref[0, 0, :, :]
    lse_row = _read_rows(lse_ref, iq, bq, lp)
    lse_row = jnp.where(lse_row == NEG_INF, BIG_LSE, lse_row * LOG2E)
    delta_row = _read_rows(delta_ref, iq, bq, lp)
    qs = q * (scale * LOG2E)

    def one(u, prev_ds, dq_acc, fold_prev):
        rows = pl.ds(u * bkvc, bkvc)
        k_u = k_ref[0, 0, rows, :]
        v_u = v_ref[0, 0, rows, :]
        s = jax.lax.dot_general(
            qs, k_u, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v_u, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if fold_prev:
            # the carried pend's dk: independent of this iteration's VPU
            # chain, queues right behind s/dp
            prows = pl.ds((u - 1) * bkvc, bkvc)
            dk_scr[prows, :] = dk_scr[prows, :] + jax.lax.dot_general(
                prev_ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        p = jnp.exp2(s - lse_row)
        if masked:
            p = jnp.where(mask_of(u), p, 0.0)
        dv_scr[rows, :] = dv_scr[rows, :] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_row)
        dq_acc = dq_acc + jax.lax.dot_general(
            ds.astype(k_u.dtype), k_u, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return ds.astype(q.dtype), dq_acc

    dq0 = jnp.zeros((bq, q_ref.shape[-1]), jnp.float32)
    ds_last, dq_acc = one(0, None, dq0, False)
    if n_sub > 1:
        def body(u, carry):
            prev_ds, dq_c = carry
            return one(u, prev_ds, dq_c, True)

        ds_last, dq_acc = jax.lax.fori_loop(
            1, n_sub, body, (ds_last, dq_acc))
    dq_update(dq_acc)
    ds_pend[:] = ds_last
    q_pend[:] = q
    pend_flag[0] = 1
    pend_flag[1] = n_sub - 1


def _bwd_fused_tri_kernel(
    spec_ref,
    do_ref, q_ref, k_ref, v_ref, delta_ref, lse_ref,
    *rest,
    scale, bq, bkv, bkvc, lp, nqb, nkb, ratio, seg=False, loop=False,
    diag=None,
):
    """Wrapped-diagonal causal backward (static full-window causal with
    offset 0 or -1 — see the flash_fwd docstring's triangular contract —
    and group=1).

    Grid (b, h, p, c) with p in [0, nkb/2), c in [0, C] where
    C = 2*nqb - ratio*(nkb-1), ratio = bkv//bq: pair p processes kv-block
    nkb-1-p (segment A: its live q-blocks, descending) then kv-block p
    (segment B) — every step computes a live block, eliminating the
    rectangular grid's ~half dead steps.  dq accumulates IN the whole-head
    output buffer (constant block index -> VMEM-resident until the head
    changes), so there is no in-place HBM aliasing and no write/read
    separation constraint.  dk/dv write at segment ends through an output
    index map lagged one step (jsel(c-1)), with one trailing no-compute step
    (c == C) to flush the final dk pend and write segment B's dk/dv.

    `diag`: the sub-square edge where the `ratio` blocks the diagonal cuts
    at each segment's end take _bwd_cut_tile (bwd_diag_path decides,
    statically), else None: the whole tile on the masked path.
    """
    if seg:
        qseg_ref, kvseg_ref = rest[0], rest[1]
        rest = rest[2:]
    (dq_ref, dk_ref, dv_ref,
     dk_scr, dv_scr, ds_pend, q_pend, pend_flag) = rest
    p = pl.program_id(2)
    c = pl.program_id(3)
    j_hi = nkb - 1 - p
    len_a = nqb - ratio * j_hi
    ncols = 2 * nqb - ratio * (nkb - 1)
    seg_b = c >= len_a
    iq = jnp.where(seg_b, nqb - 1 - (c - len_a), nqb - 1 - c)
    jk = jnp.where(seg_b, p, j_hi)
    r0 = iq * bq
    c0 = jk * bkv

    compute = c < ncols

    @pl.when((p == 0) & (c == 0))
    def _init_head():
        dq_ref[0, 0, :, :] = jnp.zeros_like(dq_ref[0, 0, :, :])
        pend_flag[0] = 0

    # flush the previous step's deferred dk BEFORE this step's matmuls and
    # before any segment reinit (the pend belongs to the previous segment's
    # kv block when c == len_a)
    @pl.when(pend_flag[0] == 1)
    def _flush_prev():
        _flush_dk_sub(dk_scr, ds_pend, q_pend, pend_flag, bkvc)

    # segment writeout: at c == len_a write segment A's dk/dv (out index map
    # lags one step, so the block still points at kv j_hi); at c == ncols
    # (the trailing step) write segment B's
    @pl.when((c == len_a) | (c == ncols))
    def _writeout():
        dk_ref[0, 0, :, :] = dk_scr[:] * scale
        dv_ref[0, 0, :, :] = dv_scr[:]

    @pl.when((c == 0) | (c == len_a))
    def _init_seg():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    # the diagonal blocks are the trailing `ratio` steps of each segment
    full = jnp.where(seg_b, c < ncols - ratio, c < len_a - ratio)
    if seg:
        # packed sequences: a structurally-full block still needs masking
        # unless both tiles share one segment (same as the fwd tri grid —
        # seg only widens which steps take the masked path)
        qs_tile = qseg_ref[0, :, :]   # [bq, 1]
        ks_tile = kvseg_ref[0, :, :]  # [1, bkv]
        full = full & _seg_uniform_eq(qs_tile, ks_tile)

    def _dq_update(dq_acc):
        # dq accumulates straight into the resident whole-head out buffer
        rows = pl.ds(iq * bq, bq)
        dq_ref[0, 0, rows, :] = dq_ref[0, 0, rows, :] + scale * dq_acc

    def _mask_of(u):
        # u is a Python int (the sub-block loop is unrolled): static slice
        seg_u = (qs_tile, ks_tile[:, u * bkvc:(u + 1) * bkvc]) if seg else None
        return _block_mask(spec_ref, r0, c0 + u * bkvc, bq, bkvc, seg=seg_u)

    def _mask_of_dyn(u):
        # traced u (the fori_loop sweep): same shared predicate —
        # _block_mask takes traced r0/c0 everywhere already; only the seg
        # tile needs a dynamic slice instead of the unrolled static one
        seg_u = None
        if seg:
            seg_u = (qs_tile,
                     jax.lax.dynamic_slice(ks_tile, (0, u * bkvc), (1, bkvc)))
        return _block_mask(spec_ref, r0, c0 + u * bkvc, bq, bkvc, seg=seg_u)

    def _accum(masked):
        accum = _bwd_accum_tile_sub_loop if loop else _bwd_accum_tile_sub
        accum(
            do_ref, q_ref, k_ref, v_ref, delta_ref, lse_ref,
            dv_scr, dk_scr, ds_pend, q_pend, pend_flag,
            iq, masked, _mask_of_dyn if loop else _mask_of,
            scale=scale, bq=bq, bkvc=bkvc, n_sub=bkv // bkvc, lp=lp,
            dq_update=_dq_update,
        )

    @pl.when(compute & full)
    def _compute_fast():
        _accum(False)

    if diag is None:
        @pl.when(compute & ~full)
        def _compute_masked():
            _accum(True)
    else:
        @pl.when(compute & ~full)
        def _compute_cut():
            # `pos` q blocks below its kv block's first row
            pos = iq - jk * ratio
            _bwd_cut_tile(
                do_ref, q_ref, k_ref, v_ref, delta_ref, lse_ref, dv_scr,
                dk_scr, iq, pos,
                lambda r: _block_mask(
                    spec_ref, r0 + r * diag, c0 + pos * bq + r * diag,
                    diag, diag),
                scale=scale, bq=bq, edge=diag, lp=lp,
                dq_update=lambda dq_rows, first: _dq_update(dq_rows))


def _bwd_tri_launch(spec_arr, do, q, k, v, delta_p, lse_p, *seg_ids, scale,
                    block_q, block_kv, interpret, block_kv_compute,
                    loop_sweep, diag):
    """The wrapped-diagonal backward kernel on exact tiles: delta and lse
    packed, then the segment ids as [B, S, 1] / [B, 1, S] where the call has
    them; every keyword static."""
    b, n, s_q, d = q.shape
    s_kv, d_v = k.shape[2], v.shape[-1]
    bq = _pick_block(s_q, block_q)
    bkv = _pick_block(s_kv, block_kv)
    if block_kv_compute is None:
        bkvc = bkv
    else:
        bkvc = _pick_block(bkv, block_kv_compute)
    lp = _pick_block(bq, 128)
    nqb = s_q // bq
    nkb = s_kv // bkv
    ratio = bkv // bq
    ncols = 2 * nqb - ratio * (nkb - 1)

    def iq_of(p, c):
        j_hi = nkb - 1 - p
        len_a = nqb - ratio * j_hi
        i = jnp.where(c >= len_a, nqb - 1 - (c - len_a), nqb - 1 - c)
        return jnp.clip(i, 0, nqb - 1)

    def q_map(b_, h, p, c, sp):
        return (b_, h, iq_of(p, c), 0)

    def kv_map(b_, h, p, c, sp):
        return (b_, h, jnp.where(c >= nqb - ratio * (nkb - 1 - p), p, nkb - 1 - p), 0)

    def kv_out_map(b_, h, p, c, sp):
        # lagged one step so the c == len_a / c == ncols writeouts land on
        # the segment that just ended
        cl = jnp.maximum(c, 1) - 1
        return (b_, h, jnp.where(cl >= nqb - ratio * (nkb - 1 - p), p, nkb - 1 - p), 0)

    def state_map(b_, h, p, c, sp):
        return (b_, h, 0, 0)

    def dq_map(b_, h, p, c, sp):
        return (b_, h, 0, 0)

    state_block = pl.BlockSpec((1, 1, s_q // lp, lp), state_map)
    in_specs = [
        pl.BlockSpec((1, 1, bq, d_v), q_map),
        pl.BlockSpec((1, 1, bq, d), q_map),
        pl.BlockSpec((1, 1, bkv, d), kv_map),
        pl.BlockSpec((1, 1, bkv, d_v), kv_map),
        state_block,
        state_block,
    ]
    if seg_ids:
        in_specs.append(pl.BlockSpec(
            (1, bq, 1),
            lambda b_, h, p, c, sp: (b_, q_map(b_, h, p, c, sp)[2], 0)))
        in_specs.append(pl.BlockSpec(
            (1, 1, bkv),
            lambda b_, h, p, c, sp: (b_, 0, kv_map(b_, h, p, c, sp)[2])))
    return pl.pallas_call(
        functools.partial(
            _bwd_fused_tri_kernel, scale=scale, bq=bq, bkv=bkv, bkvc=bkvc,
            lp=lp, nqb=nqb, nkb=nkb, ratio=ratio, seg=bool(seg_ids),
            loop=loop_sweep, diag=diag,
        ),
        name="burst_flash_bwd_tri",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n, nkb // 2, ncols + 1),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, s_q, d), dq_map),
                pl.BlockSpec((1, 1, bkv, d), kv_out_map),
                pl.BlockSpec((1, 1, bkv, d_v), kv_out_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((bkv, d), jnp.float32),
                pltpu.VMEM((bkv, d_v), jnp.float32),
                pltpu.VMEM((bq, bkvc), q.dtype),
                pltpu.VMEM((bq, d), q.dtype),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, n, s_q, d), jnp.float32),
            jax.ShapeDtypeStruct((b, n, s_kv, d), jnp.float32),
            jax.ShapeDtypeStruct((b, n, s_kv, d_v), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT,
            dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(spec_arr, do, q, k, v, delta_p, lse_p, *seg_ids)


def _check_unit_tiles(window, bq, bkv):
    unit, _ = unit_of(window)
    if bq % unit or bkv % unit:
        raise ValueError(
            f"mask blocks of {unit} tokens must divide the kernel's tiles "
            f"({bq} x {bkv})")


def bwd_band_nbq(bq, bkv, nqb, window):
    """Static q-block count of a fused-bwd window band sweep (exact over
    reachable alignments, see bwd_band_nb); nqb when no window."""
    unit, win = unit_of(window)
    if win is None:
        return nqb
    return min(nqb, bwd_band_nb(bq // unit, bkv // unit, win))


def _bwd_rect_launch(spec_arr, do, q, k, v, delta_p, lse_p, dq0, *rest,
                     scale, block_q, block_kv, interpret, window, q_range,
                     kv_range, carry, seg, diag):
    """The fused rectangular backward kernel on exact tiles: delta and lse
    packed, the zeros dq accumulates into, then (dk, dv) of the rounds
    before where `carry`, then the segment ids as [B, S, 1] / [B, 1, S]
    where `seg`; every keyword static."""
    b, n, sq_full, d = q.shape
    n_kv, skv_full, d_v = k.shape[1], k.shape[2], v.shape[-1]
    # the lengths the grid covers; the arrays keep their full length
    s_q, s_kv = _range_len(q_range, sq_full), _range_len(kv_range, skv_full)
    group = _gqa_group(n, n_kv)
    bq = _pick_block(s_q, block_q)
    bkv = _pick_block(s_kv, block_kv)
    lp = _pick_block(bq, 128)
    nqb = s_q // bq
    nkb = s_kv // bkv
    q_off = q_range[0] // bq if q_range is not None else 0
    kv_off = kv_range[0] // bkv if kv_range is not None else 0
    _check_unit_tiles(window, bq, bkv)
    # window: sweep only the q blocks whose band can touch kv block j
    nbq = bwd_band_nbq(bq, bkv, nqb, window)

    def qh_of(h, t):
        return h * group + t // nbq

    def bq_map(b_, h, j, t, sp):
        iq, _ = _bwd_fused_iq(sp, j, t % nbq, bq, bkv, nqb, window)
        return (b_, qh_of(h, t), _shift(iq, q_off), 0)

    def bstate_map(b_, h, j, t, sp):
        return (b_, qh_of(h, t), 0, 0)

    def bkv_map(b_, h, j, t, sp):
        return (b_, h, _shift(j, kv_off), 0)

    bstate_block = pl.BlockSpec((1, 1, sq_full // lp, lp), bstate_map)
    in_specs = [
        pl.BlockSpec((1, 1, bq, d_v), bq_map),
        pl.BlockSpec((1, 1, bq, d), bq_map),
        pl.BlockSpec((1, 1, bkv, d), bkv_map),
        pl.BlockSpec((1, 1, bkv, d_v), bkv_map),
        bstate_block,
        bstate_block,
        pl.BlockSpec((1, 1, bq, d), bq_map),
    ]
    # flattened input index 7 = dq0 (after the scalar-prefetch spec array)
    aliases = {7: 0}
    if carry:
        # each dk/dv block is read before its sweep and written after it,
        # once: the alias needs no separation argument (unlike dq's)
        in_specs += [pl.BlockSpec((1, 1, bkv, w), bkv_map) for w in (d, d_v)]
        aliases.update({8: 1, 9: 2})
    if seg:
        # seg ids come LAST so the alias indices above stay stable
        in_specs.append(pl.BlockSpec(
            (1, bq, 1),
            lambda b_, h, j, t, sp: (b_, bq_map(b_, h, j, t, sp)[2], 0)))
        in_specs.append(pl.BlockSpec(
            (1, 1, bkv), lambda b_, h, j, t, sp: (b_, 0, _shift(j, kv_off))))
    return pl.pallas_call(
        functools.partial(
            _bwd_fused_kernel, scale=scale, bq=bq, bkv=bkv, lp=lp,
            n_q_blocks=nqb, group=group, nbq=nbq, wnd=window,
            seg=seg, carry=carry, q_off=q_off, diag=diag,
        ),
        # the banded sweep under its own name, as the forward's band grid
        name="burst_flash_bwd_" + ("band" if nbq < nqb else "rect"),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n_kv, nkb, nbq * group),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, bq, d), bq_map),
                pl.BlockSpec((1, 1, bkv, d), bkv_map),
                pl.BlockSpec((1, 1, bkv, d_v), bkv_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((bkv, d), jnp.float32),
                pltpu.VMEM((bkv, d_v), jnp.float32),
                # deferred-flush pend tiles (see _flush_dk);
                # q.dtype matches the casts the stash performs
                pltpu.VMEM((bq, bkv), q.dtype),
                pltpu.VMEM((bq, d), q.dtype),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, n, sq_full, d), jnp.float32),
            jax.ShapeDtypeStruct((b, n_kv, skv_full, d), jnp.float32),
            jax.ShapeDtypeStruct((b, n_kv, skv_full, d_v), jnp.float32),
        ],
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT,
            dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(spec_arr, do, q, k, v, delta_p, lse_p, dq0, *rest)


def _bwd_launch(*arrays, kernel, **static):
    """One fused backward kernel ("tri" or "rect") on exact tiles."""
    if kernel == "tri":
        return _bwd_tri_launch(*arrays, **static)
    return _bwd_rect_launch(*arrays, **static)


# flash_fwd's arrangement (see _fwd_launch_traced) for the two fused backward
# kernels: one trace and one lowered body a DISTINCT backward call, not one a
# layer.  _bwd_cut_tile is a Python-unrolled loop a position, and a model
# makes one backward call a layer: trace + lower of jax.grad over four
# jax.checkpoint(burst_attn) blocks at 1 x 8,192 rows for a described v5e
# (benchmarks/trace_cost.py, this repo's CPU host; ops/tuning.py has the
# numbers by edge).  The jit holds the kernel launch and nothing else: the
# spec's stacking, the state's packing, the zeros dq accumulates into and a
# range round's zero carry stay outside it, in the order the kernels' callers
# always made them.  The split kernels (two launches, bodies that did not
# grow) stay in line in flash_bwd.
_bwd_launch_traced = jax.jit(_bwd_launch, static_argnames=(
    "kernel", "scale", "block_q", "block_kv", "interpret", "window",
    "q_range", "kv_range", "carry", "seg", "block_kv_compute", "loop_sweep",
    "diag"))


def _flash_bwd_fused_tri(do, q, k, v, delta, lse, scale, spec, *,
                         block_q, block_kv, interpret, block_kv_compute=None,
                         segments=None, loop_sweep=False, diag=None):
    lp = _pick_block(_pick_block(q.shape[2], block_q), 128)
    arrays = [_spec_array(spec), do, q, k, v, _pack(delta, lp),
              _pack(lse, lp)]
    if segments is not None:
        arrays.append(jnp.asarray(segments[0], jnp.int32)[:, :, None])
        arrays.append(jnp.asarray(segments[1], jnp.int32)[:, None, :])
    return _bwd_launch_traced(
        *arrays, kernel="tri", scale=scale, block_q=block_q,
        block_kv=block_kv, interpret=interpret,
        block_kv_compute=block_kv_compute, loop_sweep=loop_sweep, diag=diag)


def _flash_bwd_fused(do, q, k, v, delta, lse, scale, spec, *,
                     block_q, block_kv, interpret, window=None,
                     segments=None, q_range=None, kv_range=None, carry=None,
                     diag=None):
    b, n, sq_full, d = q.shape
    n_kv, skv_full, d_v = k.shape[1], k.shape[2], v.shape[-1]
    lp = _pick_block(_pick_block(_range_len(q_range, sq_full), block_q), 128)
    # full-size: a q_range round visits only its rows and the rest stay zero
    dq0 = jnp.zeros((b, n, sq_full, d), jnp.float32)
    arrays = [_spec_array(spec), do, q, k, v, _pack(delta, lp),
              _pack(lse, lp), dq0]
    if carry is None and _range_len(kv_range, skv_full) != skv_full:
        # the kv blocks outside the range are never written: they are zeros
        dk0 = jnp.zeros((b, n_kv, skv_full, d), jnp.float32)
        carry = (dk0, dk0 if d_v == d else jnp.zeros(
            (b, n_kv, skv_full, d_v), jnp.float32))
    if carry is not None:
        arrays += list(carry)
    if segments is not None:
        arrays.append(jnp.asarray(segments[0], jnp.int32)[:, :, None])
        arrays.append(jnp.asarray(segments[1], jnp.int32)[:, None, :])
    return _bwd_launch_traced(
        *arrays, kernel="rect", scale=scale, block_q=block_q,
        block_kv=block_kv, interpret=interpret, window=window,
        q_range=q_range, kv_range=kv_range, carry=carry is not None,
        seg=segments is not None, diag=diag)


def _tri_bwd_other_residents(bq, bkv, d, itemsize=2, bkvc=None, d_v=None):
    """Estimated VMEM held by everything EXCEPT the whole-head dq output in
    the triangular fused bwd kernel: double-buffered input blocks (do, q,
    k, v) and dk/dv f32 output blocks, plus the ds/q deferral stashes
    (sub-block width when block_kv_compute is set).  Packed delta/lse
    blocks are negligible next to these.  q, k, dq, dk are `d` wide; do, v,
    dv `d_v` (None: d)."""
    if bkvc is None:
        bkvc = bkv
    d_v = d if d_v is None else d_v
    blocks = 2 * (bq * (d + d_v) * itemsize      # do, q
                  + bkv * (d + d_v) * itemsize   # k, v
                  + bkv * (d + d_v) * 4)         # dk, dv out (f32)
    scratch = bq * bkvc * itemsize + bq * d * itemsize  # ds stash, q stash
    return blocks + scratch


def tri_bwd_supported(s_q, s_kv, n, n_kv, d, *, block_q, block_kv,
                      block_kv_compute=None, d_v=None) -> bool:
    """Whether flash_bwd(triangular=True) will actually use the
    wrapped-diagonal kernel (vs silently falling back to the rectangular
    fused kernel): group=1 only, square even block tiling, and the
    whole-head dq output buffer must fit the VMEM budget.

    The dq budget is derived from VMEM_LIMIT minus an estimate of the other
    residents, at half utilization — Mosaic's own overheads aren't modeled,
    so the gate errs conservative.  Nothing catches a compile failure behind
    it: tests/test_tpu_compile.py compiles the largest shape it admits
    (plain and packed) for the v5e, and a shape Mosaic refuses must be
    excluded HERE, statically."""
    bq = _pick_block(s_q, block_q)
    bkv = _pick_block(s_kv, block_kv)
    nkb = s_kv // bkv
    # clamp the sub-block exactly as _flash_bwd_fused_tri will, so the
    # estimate charges the scratch the kernel actually allocates
    bkvc = None if block_kv_compute is None else _pick_block(bkv, block_kv_compute)
    dq_budget = VMEM_LIMIT // 2 - _tri_bwd_other_residents(
        bq, bkv, d, bkvc=bkvc, d_v=d_v)
    return (
        n == n_kv and s_q == s_kv and bkv % bq == 0
        and nkb % 2 == 0 and nkb >= 2
        and s_q * d * 4 <= dq_budget
    )


def _bwd_kernel_of(n, n_kv, s_q, s_kv, d, *, block_q, block_kv, interpret,
                   fused=None, triangular=False, window=None,
                   block_kv_compute=None, d_v=None) -> str:
    """The kernel flash_bwd runs a round of these (whole-block) lengths on:
    "tri", "rect" (the fused rectangular one) or "split".  `d`: the width of
    q and k; `d_v`: of v (None: d)."""
    bq = _pick_block(s_q, block_q)
    bkv = _pick_block(s_kv, block_kv)
    explicit_split = fused is False
    if window is not None:
        # the wrapped-diagonal tri grid assumes full-window causality (and
        # masks in tokens: a masks.BlockUnits window declines it too); a
        # window instead takes the BANDED fused sweep.  Segments ride BOTH
        # fused kernels' masked paths (round-2 verdict item 5 — neither
        # mode downgrades to the 7-matmul split kernels any more).
        triangular = False
    if fused is None:
        fused = (not interpret and bwd_band_nbq(bq, bkv, s_q // bq, window)
                 * _gqa_group(n, n_kv) >= 4)
    if (bool(triangular) and not explicit_split and not _tri_disabled()
            and tri_bwd_supported(s_q, s_kv, n, n_kv, d, block_q=bq,
                                  block_kv=bkv,
                                  block_kv_compute=block_kv_compute,
                                  d_v=d_v)):
        return "tri"
    return "rect" if fused else "split"


def bwd_folds_carry(n, n_kv, s_q, s_kv, d, q_range, kv_range, *, block_q,
                    block_kv, interpret=None, fused=None, triangular=False,
                    window=None, block_kv_compute=None, d_v=None) -> bool:
    """Whether flash_bwd takes a round's `q_range` / `kv_range` / `carry` in
    the kernel: exactly where it takes the fused rectangular kernel, decided
    on the RANGE's own lengths (the in-place dq argument needs the range's
    sweep length, see _bwd_fused_kernel), over whole blocks.  Everywhere
    else the call is the sliced form (ops/tile.bwd_on_ranges).  Static: the
    ring counts its rounds by it (parallel/burst.py, burst.inplace_rounds)."""
    if interpret is None:
        interpret = _interpret_default()
    if not (_whole_blocks(q_range, s_q, block_q)
            and _whole_blocks(kv_range, s_kv, block_kv)):
        return False
    return _bwd_kernel_of(
        n, n_kv, _range_len(q_range, s_q), _range_len(kv_range, s_kv), d,
        block_q=block_q, block_kv=block_kv, interpret=interpret, fused=fused,
        triangular=triangular, window=window,
        block_kv_compute=block_kv_compute, d_v=d_v) == "rect"


def flash_bwd(do, q, k, v, delta, lse, scale, spec: MaskSpec, *,
              block_q=1024, block_kv=1024, interpret=None, fused=None,
              triangular=False, window=None, segments=None,
              block_kv_compute=None, loop_sweep=False,
              q_range=None, kv_range=None, carry=None, diag_block=None):
    """One backward ring round on TPU.  Same contract as ops/tile.py:tile_bwd:
    returns (dq [B,N,S,D], dk [B,Nk,Skv,D], dv [B,Nk,Skv,Dv]) in float32.
    q and k are D wide; v and do are Dv wide (their own last axes: the
    kernels are specialised on the static shapes, Dv == D is the program it
    always was).

    `carry` = (dk, dv) float32 of the rounds before: the returned dk, dv are
    the carry plus this round's.  `q_range` / `kv_range` (see "sub-range
    rounds" above): the round covers only those rows of the full arrays; dq
    is zero outside `q_range`, and dk, dv outside `kv_range` are the
    carry's.  Where the fused rectangular kernel runs (bwd_folds_carry) the
    carry is aliased to the outputs and the kernel adds into it; everywhere
    else this is the sliced form, one call signature either way.

    delta = sum(o*do, -1) [B,N,S] f32 (precomputed; reference
    burst_attn_interface.py:269-278); lse is the FINAL log-sum-exp.

    `fused` selects the single-pass dq+dk+dv kernel (default on real TPU when
    the sweep is long enough for its aliasing-separation argument; see
    _bwd_fused_kernel — an explicit fused=True is honoured below that gate
    too, and is then NOT race-free on hardware).  The split kernels remain for interpret mode and
    short sweeps.  `triangular=True` selects the wrapped-diagonal causal
    grid (same caller contract as flash_fwd's triangular: full-window
    causal, offset 0 or -1) when tri_bwd_supported() holds; an explicit
    fused=False takes precedence so the split kernels can always be
    A/B-compared.  `block_kv_compute` (tri path only) splits the kv block
    into compute sub-blocks — see _bwd_accum_tile_sub.

    Under the `triangular` promise the q blocks the diagonal cuts (with
    block_kv = ratio * block_q, `ratio` of them a kv block) compute their
    live sub-squares only, in both fused kernels (bwd_diag_path has the
    conditions, _bwd_cut_tile the body; `diag_block`: the sub-square edge,
    None: the generation's `diag_block`, ops/tuning.py; 0: the whole
    tile on the masked path).  The defaults and switches are resolved here;
    a fused kernel's launch on exact tiles is traced behind ONE jit
    (_bwd_launch_traced), as flash_fwd's is.
    """
    if interpret is None:
        interpret = _interpret_default()
    if not loop_sweep and _bwd_loop_default():
        loop_sweep = True  # BURST_BWD_LOOP promotion (see _bwd_loop_default)
    if diag_block is None:
        diag_block = tuning.block_defaults().diag_block
    b, n, s_q, d = q.shape
    n_kv, s_kv, d_v = k.shape[1], k.shape[2], v.shape[-1]
    group = _gqa_group(n, n_kv)

    def diag_of(kernel, rows_q, rows_kv):
        return _bwd_diag_edge(
            kernel, rows_q, rows_kv, block_q=block_q, block_kv=block_kv,
            triangular=triangular, window=window,
            segments=segments is not None, diag_block=diag_block,
            loop_sweep=loop_sweep)

    if q_range is not None or kv_range is not None or carry is not None:
        kw = dict(block_q=block_q, block_kv=block_kv, interpret=interpret,
                  fused=fused, triangular=triangular, window=window,
                  block_kv_compute=block_kv_compute)
        if bwd_folds_carry(n, n_kv, s_q, s_kv, d, q_range, kv_range, d_v=d_v,
                           **kw):
            return _flash_bwd_fused(
                do, q, k, v, delta, lse, scale, spec, block_q=block_q,
                block_kv=block_kv, interpret=interpret, window=window,
                segments=segments, q_range=q_range, kv_range=kv_range,
                carry=carry, diag=diag_of(
                    "rect", _range_len(q_range, s_q),
                    _range_len(kv_range, s_kv)))
        from .tile import bwd_on_ranges

        return bwd_on_ranges(
            functools.partial(flash_bwd, loop_sweep=loop_sweep,
                              diag_block=diag_block, **kw),
            do, q, k, v, delta, lse, scale, spec, segments=segments,
            q_range=q_range, kv_range=kv_range, carry=carry)
    sq_pad, skv_pad = _padded_len(s_q, block_q), _padded_len(s_kv, block_kv)
    if sq_pad != s_q or skv_pad != s_kv:
        # ragged lengths: pad, run, slice back (see flash_fwd).  lse pads
        # with 0 (not -inf) so the kernels' exp(s - lse) stays finite before
        # the mask select zeroes the padded rows' contributions.
        if segments is not None:
            segments = (_pad_seg(segments[0], sq_pad, -1),
                        _pad_seg(segments[1], skv_pad, -2))
        dq, dk, dv = flash_bwd(
            _pad_seq(do, sq_pad), _pad_seq(q, sq_pad),
            _pad_seq(k, skv_pad), _pad_seq(v, skv_pad),
            _pad_seq(delta, sq_pad), _pad_seq(lse, sq_pad),
            scale, spec, block_q=block_q, block_kv=block_kv,
            interpret=interpret, fused=fused, triangular=False, window=window,
            segments=segments, block_kv_compute=block_kv_compute,
        )
        return dq[:, :, :s_q], dk[:, :, :s_kv], dv[:, :, :s_kv]
    bq = _pick_block(s_q, block_q)
    bkv = _pick_block(s_kv, block_kv)
    lp = _pick_block(bq, 128)
    nqb = s_q // bq
    nkb = s_kv // bkv
    _check_unit_tiles(window, bq, bkv)
    kernel = _bwd_kernel_of(
        n, n_kv, s_q, s_kv, d, block_q=block_q, block_kv=block_kv,
        interpret=interpret, fused=fused, triangular=triangular,
        window=window, block_kv_compute=block_kv_compute, d_v=d_v)
    if kernel == "tri":
        return _flash_bwd_fused_tri(
            do, q, k, v, delta, lse, scale, spec,
            block_q=block_q, block_kv=block_kv, interpret=interpret,
            block_kv_compute=block_kv_compute, segments=segments,
            loop_sweep=loop_sweep, diag=diag_of("tri", s_q, s_kv),
        )
    if kernel == "rect":
        return _flash_bwd_fused(
            do, q, k, v, delta, lse, scale, spec,
            block_q=block_q, block_kv=block_kv, interpret=interpret,
            window=window, segments=segments,
            diag=diag_of("rect", s_q, s_kv),
        )

    # ---- dq ----
    q_map, kv_map, state_map = _make_index_maps(bq, bkv, nqb, nkb, group,
                                                wnd=window)
    state_block = pl.BlockSpec((1, 1, s_q // lp, lp), state_map)
    dq_in_specs = [
        pl.BlockSpec((1, 1, bq, d_v), q_map),
        pl.BlockSpec((1, 1, bq, d), q_map),
        pl.BlockSpec((1, 1, bkv, d), kv_map),
        pl.BlockSpec((1, 1, bkv, d_v), kv_map),
        state_block,
        state_block,
    ]
    dq_inputs = [_spec_array(spec), do, q, k, v, _pack(delta, lp),
                 _pack(lse, lp)]
    if segments is not None:
        q_seg3 = jnp.asarray(segments[0], jnp.int32)[:, :, None]
        kv_seg3 = jnp.asarray(segments[1], jnp.int32)[:, None, :]
        dq_in_specs.append(pl.BlockSpec(
            (1, bq, 1), lambda b_, h, i, j, sp: (b_, q_map(b_, h, i, j, sp)[2], 0)))
        dq_in_specs.append(pl.BlockSpec(
            (1, 1, bkv), lambda b_, h, i, j, sp: (b_, 0, kv_map(b_, h, i, j, sp)[2])))
        dq_inputs += [q_seg3, kv_seg3]
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, bq=bq, bkv=bkv, lp=lp, n_kv_blocks=nkb,
            wnd=window, seg=segments is not None,
        ),
        name="burst_flash_bwd_dq",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n, nqb, nkb),
            in_specs=dq_in_specs,
            out_specs=pl.BlockSpec((1, 1, bq, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((bq, d), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, n, s_q, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT,
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*dq_inputs)

    # ---- dk/dv ----
    def qh_of(h, t):
        return h * group + t // nqb

    def bq_map(b_, h, j, t, sp):
        iq = jnp.maximum(t % nqb, _q_imin(sp, j, bq, bkv, nqb, window))
        if unit_of(window)[1] is not None:
            iq = jnp.minimum(iq, _q_imax(sp, j, bq, bkv, nqb, window))
        return (b_, qh_of(h, t), iq, 0)

    def bstate_map(b_, h, j, t, sp):
        return (b_, qh_of(h, t), 0, 0)

    def bkv_map(b_, h, j, t, sp):
        return (b_, h, j, 0)

    bstate_block = pl.BlockSpec((1, 1, s_q // lp, lp), bstate_map)
    dkdv_in_specs = [
        pl.BlockSpec((1, 1, bq, d_v), bq_map),
        pl.BlockSpec((1, 1, bq, d), bq_map),
        pl.BlockSpec((1, 1, bkv, d), bkv_map),
        pl.BlockSpec((1, 1, bkv, d_v), bkv_map),
        bstate_block,
        bstate_block,
    ]
    dkdv_inputs = [_spec_array(spec), do, q, k, v, _pack(delta, lp),
                   _pack(lse, lp)]
    if segments is not None:
        dkdv_in_specs.append(pl.BlockSpec(
            (1, bq, 1),
            lambda b_, h, j, t, sp: (b_, bq_map(b_, h, j, t, sp)[2], 0)))
        dkdv_in_specs.append(pl.BlockSpec(
            (1, 1, bkv), lambda b_, h, j, t, sp: (b_, 0, j)))
        dkdv_inputs += [q_seg3, kv_seg3]
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkdv_kernel, scale=scale, bq=bq, bkv=bkv, lp=lp,
            n_q_blocks=nqb, group=group, wnd=window,
            seg=segments is not None,
        ),
        name="burst_flash_bwd_dkdv",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n_kv, nkb, nqb * group),
            in_specs=dkdv_in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, bkv, d), bkv_map),
                pl.BlockSpec((1, 1, bkv, d_v), bkv_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((bkv, d), jnp.float32),
                pltpu.VMEM((bkv, d_v), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, n_kv, s_kv, d), jnp.float32),
            jax.ShapeDtypeStruct((b, n_kv, s_kv, d_v), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT,
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*dkdv_inputs)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# single-device flash attention (the "flash" benchmark baseline, and a
# standalone fused attention op — reference role: flash_attn_func on one GPU,
# test/test_burst.py:175-184)


def flash_attention(q, k, v, scale=None, causal=False, block_q=None, block_kv=None,
                    block_q_bwd=None, block_kv_bwd=None, block_kv_compute=None,
                    window=None, segment_ids=None):
    """Fused single-device flash attention.  q,k,v [B,N,S,D] -> o [B,N,S,D].

    Block sizes default per TPU generation from ops/tuning.py (v5e measured
    optimum: fwd 2048x2048 with 1024-wide compute sub-blocks, fused backward
    1024x2048); the bwd blocks never default larger than the fwd blocks so a
    caller who shrinks the fwd blocks for VMEM keeps that budget in bwd.
    block_kv_compute splits the fwd kv memory block into compute sub-blocks
    (see flash_fwd).

    `window` (static int) enables sliding-window attention: each query
    attends to its last `window` positions (inclusive of itself); requires
    causal=True.  Both directions run BAND grids (fwd band_nb /
    bwd _bwd_fused_iq): the grid enumerates only blocks intersecting the
    band, so cost scales with window, not sequence.

    `segment_ids` [B, S] int32 (non-negative; negatives are reserved for
    internal padding) packs multiple documents into one row — attention
    never crosses a segment boundary.  Blocks wholly inside one segment
    keep the fast path; only boundary-straddling blocks pay for the id
    compare, in the forward and in the fused (tri or rect) backward
    alike."""
    if segment_ids is None:
        return _flash_attention_plain(q, k, v, scale, causal, block_q,
                                      block_kv, block_q_bwd, block_kv_bwd,
                                      block_kv_compute, window)
    return _flash_attention_seg(q, k, v, segment_ids, scale, causal, block_q,
                                block_kv, block_q_bwd, block_kv_bwd,
                                block_kv_compute, window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash_attention_plain(q, k, v, scale=None, causal=False, block_q=None,
                           block_kv=None, block_q_bwd=None, block_kv_bwd=None,
                           block_kv_compute=None, window=None):
    o, _ = _flash_attention_fwd_impl(q, k, v, scale, causal, block_q, block_kv,
                                     block_kv_compute, window)
    return o


def _flash_attention_fwd_impl(q, k, v, scale, causal, block_q, block_kv,
                              block_kv_compute=None, window=None,
                              segment_ids=None):
    from .masks import round_spec

    b, n, s, d = q.shape
    if scale is None:
        scale = d**-0.5
    if window is not None and not causal:
        raise ValueError("window attention requires causal=True")
    block_q, block_kv, _, _, block_kv_compute = resolve_blocks(
        block_q, block_kv, block_kv_compute=block_kv_compute,
        s_q=s, s_kv=k.shape[2], window=window)
    # single-device: the windowed spec is the plain causal spec (delta = 0);
    # the static `window` is what narrows the band
    spec = round_spec(jnp.int32(0), jnp.int32(0), s, k.shape[2], causal, "contig")
    segs = None if segment_ids is None else (segment_ids, segment_ids)
    # m = lse = acc = None: statically-empty carry — no zeros materialization,
    # no acc-in DMA (see flash_fwd docstring)
    _, lse, o = flash_fwd(
        q, k, v, None, None, None, scale, spec, block_q=block_q, block_kv=block_kv,
        block_kv_compute=block_kv_compute,
        # the spec here is statically known to be plain full-window causal,
        # exactly the triangular grid's precondition (tri declines windows;
        # segment masking composes with the tri grid — the in-kernel seg_ok
        # test just widens which blocks take the masked path).  emit_o fuses
        # the finalize into the kernel's last visit of each q block: no
        # one-round ring carry is needed here, so the raw f32 accumulator
        # never has to reach HBM
        triangular=causal, window=window, segments=segs, emit_o=True,
    )
    return o, lse


def _flash_attention_vjp_fwd(q, k, v, scale, causal, block_q, block_kv,
                             block_q_bwd, block_kv_bwd, block_kv_compute,
                             window):
    o, lse = _flash_attention_fwd_impl(q, k, v, scale, causal, block_q, block_kv,
                                       block_kv_compute, window)
    return o, (q, k, v, o, lse)


def _flash_attention_vjp_bwd(scale, causal, block_q, block_kv, block_q_bwd,
                             block_kv_bwd, block_kv_compute, window, res, do):
    from .masks import round_spec

    q, k, v, o, lse = res
    d = q.shape[-1]
    if scale is None:
        scale = d**-0.5
    _, _, block_q_bwd, block_kv_bwd, _ = resolve_blocks(
        block_q, block_kv, block_q_bwd, block_kv_bwd,
        s_q=q.shape[2], s_kv=k.shape[2], window=window)
    spec = round_spec(jnp.int32(0), jnp.int32(0), q.shape[2], k.shape[2], causal, "contig")
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    dq, dk, dv = flash_bwd(
        do, q, k, v, delta, lse, scale, spec,
        block_q=block_q_bwd, block_kv=block_kv_bwd,
        # statically known plain full-window causal here (same as the fwd)
        triangular=causal, window=window,
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_attention_plain.defvjp(_flash_attention_vjp_fwd, _flash_attention_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
def _flash_attention_seg(q, k, v, segment_ids, scale=None, causal=False,
                         block_q=None, block_kv=None, block_q_bwd=None,
                         block_kv_bwd=None, block_kv_compute=None, window=None):
    o, _ = _flash_attention_fwd_impl(q, k, v, scale, causal, block_q, block_kv,
                                     block_kv_compute, window, segment_ids)
    return o


def _flash_attention_seg_vjp_fwd(q, k, v, segment_ids, scale, causal, block_q,
                                 block_kv, block_q_bwd, block_kv_bwd,
                                 block_kv_compute, window):
    o, lse = _flash_attention_fwd_impl(q, k, v, scale, causal, block_q,
                                       block_kv, block_kv_compute, window,
                                       segment_ids)
    return o, (q, k, v, segment_ids, o, lse)


def _flash_attention_seg_vjp_bwd(scale, causal, block_q, block_kv, block_q_bwd,
                                 block_kv_bwd, block_kv_compute, window, res,
                                 do):
    import numpy as np

    from .masks import round_spec

    q, k, v, segment_ids, o, lse = res
    d = q.shape[-1]
    if scale is None:
        scale = d**-0.5
    _, _, block_q_bwd, block_kv_bwd, _ = resolve_blocks(
        block_q, block_kv, block_q_bwd, block_kv_bwd,
        s_q=q.shape[2], s_kv=k.shape[2], window=window)
    spec = round_spec(jnp.int32(0), jnp.int32(0), q.shape[2], k.shape[2],
                      causal, "contig")
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    dq, dk, dv = flash_bwd(
        do, q, k, v, delta, lse, scale, spec,
        # statically plain full-window causal; segments compose with the
        # tri bwd kernel (a window instead selects the banded fused sweep)
        triangular=causal, window=window,
        block_q=block_q_bwd, block_kv=block_kv_bwd,
        segments=(segment_ids, segment_ids),
    )
    # integer inputs carry symbolic-zero (float0) cotangents
    dseg = np.zeros(segment_ids.shape, dtype=jax.dtypes.float0)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), dseg)


_flash_attention_seg.defvjp(_flash_attention_seg_vjp_fwd,
                            _flash_attention_seg_vjp_bwd)
