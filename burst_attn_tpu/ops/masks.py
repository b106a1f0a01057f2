"""Per-round attention mask specifications for ring attention.

The reference implements causal load balancing with three *structural* code
paths per ring round (full / first-half-KV / second-half-Q for the zigzag
layout, and a shift-by-one tensor slicing for the striped layout — see
burst_attn/burst_attn_interface.py:221-235, :303-367, :454-475 in the
reference).  On TPU we instead parameterize ONE uniform attention tile by five
runtime scalars, so every ring round is the same traced computation (scan
body) and XLA/Pallas can skip the masked-out work via dynamic loop bounds:

    q_lo, q_hi : active query-row range [q_lo, q_hi)    (local indices)
    kv_hi      : active key/value-column range [0, kv_hi)
    causal     : 1 if a causal constraint applies
    offset     : col j visible from row i  iff  j <= i + offset

This reproduces the reference's case analysis exactly:

zigzag layout (rank p holds global chunks p and 2W-1-p, concatenated):
  * kv_part == q_part : plain causal on the local layout (offset 0).  Valid
    because both halves are internally contiguous and the first half precedes
    the second globally.
  * kv_part <  q_part : kv's first half is entirely in the local q's past and
    its second half entirely in the future -> full q x first-half kv,
    non-causal (reference's `split_kv` branch).
  * kv_part >  q_part : local q's first half sees nothing; its second half
    sees everything -> second-half q x full kv, non-causal.

striped layout (rank p holds global tokens p, p+W, p+2W, ...):
  local token i on rank a is global a + i*W; causality  b + jW <= a + iW
  reduces to  j <= i  when  b <= a  (offset 0) and  j <= i-1  otherwise
  (offset -1) — the reference's shift-by-one slicing
  (burst_attn_interface.py:463-475) expressed as a mask.

contig layout (plain contiguous chunks, the naive causal ring): kv_part <
q_part -> full, == -> causal, > -> fully masked.  Not load balanced; kept as
the ring-attention baseline (reference benchmarks/ring_attn.py).
"""

from typing import NamedTuple, Optional

import numpy as np
import jax.numpy as jnp

LAYOUTS = ("contig", "zigzag", "striped")


class MaskSpec(NamedTuple):
    """Runtime scalars (all int32) describing one ring round's mask."""

    q_lo: jnp.ndarray
    q_hi: jnp.ndarray
    kv_hi: jnp.ndarray
    causal: jnp.ndarray
    offset: jnp.ndarray


class BlockUnits(NamedTuple):
    """A tile's STATIC `window` argument when its mask acts on blocks of
    `block` tokens, not on tokens: the pair (row i, col j) is visible iff the
    five-scalar spec admits (i // block, j // block), with `window` counted
    in blocks too (None = unlimited).  The spec's scalars are then in blocks
    as well.  A causal spec in these units is block-causal (`offset` 0: a
    row sees every column up to its own block's end; -1: strictly earlier
    blocks), and `window=1` on top is block-diagonal: the three live
    quadrants of a block-diffusion stream (`bd_quadrants`).  The kernels
    need `block` to divide their tile sizes."""

    block: int
    window: Optional[int] = None


def unit_of(window):
    """(tokens a mask unit spans, the window in those units) of a tile's
    static `window`: an int or None is a token window (unit 1)."""
    if isinstance(window, BlockUnits):
        return window.block, window.window
    return 1, window


def _i32(x):
    return jnp.asarray(x, dtype=jnp.int32)


def full_spec(s_q: int, s_kv: int) -> MaskSpec:
    return MaskSpec(_i32(0), _i32(s_q), _i32(s_kv), _i32(0), _i32(0))


def round_spec(q_part, kv_part, s_q: int, s_kv: int, causal: bool, layout: str,
               window=None) -> MaskSpec:
    """Mask spec for one ring round.

    q_part / kv_part: global partition ids (traced int32 scalars) of the
    sequence chunks held by the query side and key/value side of this round.
    s_q / s_kv: static local sub-sequence lengths.  causal/layout: static.

    `window` (static int, None = unlimited) adds a sliding-window lower
    bound: each query attends to at most `window` keys ending at its causal
    position.  Supported for the "contig" layout only — in natural token
    order every ring round is the band `j <= i + delta` with `delta =
    (q_part - kv_part) * s` (a traced offset), so one offset-form spec plus
    the static window covers all rounds.  The zigzag/striped permutations
    interleave two token ranges per shard, which breaks the single-band
    structure a 5-scalar spec can express.
    """
    if window is not None:
        if layout != "contig":
            raise ValueError(
                f"window attention supports layout='contig' only, got "
                f"{layout!r} (the zigzag/striped load-balancing permutations "
                "break the band structure)")
        if not causal:
            raise ValueError("window attention requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        delta = (_i32(q_part) * s_q - _i32(kv_part) * s_kv).astype(jnp.int32)
        return MaskSpec(_i32(0), _i32(s_q), _i32(s_kv), _i32(1), delta)
    if not causal:
        return full_spec(s_q, s_kv)
    if layout == "zigzag":
        assert s_q % 2 == 0 and s_kv % 2 == 0, "zigzag needs even local seqlen"
        eq = q_part == kv_part
        q_lo = jnp.where(kv_part > q_part, s_q // 2, 0).astype(jnp.int32)
        kv_hi = jnp.where(kv_part < q_part, s_kv // 2, s_kv).astype(jnp.int32)
        return MaskSpec(q_lo, _i32(s_q), kv_hi, eq.astype(jnp.int32), _i32(0))
    elif layout == "striped":
        offset = jnp.where(kv_part <= q_part, 0, -1).astype(jnp.int32)
        return MaskSpec(_i32(0), _i32(s_q), _i32(s_kv), _i32(1), offset)
    elif layout == "contig":
        q_hi = jnp.where(kv_part > q_part, 0, s_q).astype(jnp.int32)
        return MaskSpec(_i32(0), q_hi, _i32(s_kv), (q_part == kv_part).astype(jnp.int32), _i32(0))
    else:
        raise ValueError(f"unknown layout {layout!r}; expected one of {LAYOUTS}")


def spec_live(spec: MaskSpec, window=None):
    """Traced bool scalar: does ANY (row, col) of this round's tile attend?

    False for a contig-causal ring's future rounds (q_hi == 0) and for
    windowed rounds whose whole band lies outside the resident kv chunk —
    the ring caller wraps the tile computation in `lax.cond` on this and
    skips the kernel launch entirely on dead rounds.  With window << seq a
    contig windowed ring of W devices has only ~ceil(window/chunk)+1 live
    rounds per device; every other round previously ran a full grid of
    masked-out blocks."""
    live = (spec.q_hi > spec.q_lo) & (spec.kv_hi > 0)
    # causal: some row must see col 0 (the earliest col of the chunk)
    live = live & ((spec.causal == 0) | (spec.q_hi - 1 + spec.offset >= 0))
    if window is not None:
        # band union over rows starts at q_lo + offset - window + 1; the
        # round is dead when that already exceeds the last kv col
        live = live & (spec.q_lo + spec.offset - window + 1 <= spec.kv_hi - 1)
    return live


def spec_pair_count(spec: MaskSpec, s_q: int, s_kv: int, window=None):
    """Traced f32 scalar: number of attending (row, col) pairs of one
    round's tile — the closed-ish form of `dense_mask(...).sum()` without
    materializing the [s_q, s_kv] mask (an O(s_q) row sweep instead of
    O(s_q * s_kv) booleans).

    This is the devstats mask-occupancy numerator (obs/devstats.py): per
    ring round, each live row i contributes the clamped width of its
    visible column interval [max(0, i + offset - window + 1),
    min(kv_hi - 1, i + offset)] (the causal/window band), or the full
    [0, kv_hi) range when the round is non-causal.  Asserted equal to the
    dense-mask sum in tests/test_devstats.py."""
    rows = jnp.arange(s_q, dtype=jnp.int32)
    in_row = (rows >= spec.q_lo) & (rows < spec.q_hi)
    hi = jnp.where(spec.causal > 0,
                   jnp.minimum(spec.kv_hi - 1, rows + spec.offset),
                   spec.kv_hi - 1)
    lo = jnp.zeros_like(rows)
    if window is not None:
        lo = jnp.where(spec.causal > 0,
                       jnp.maximum(lo, rows + spec.offset - window + 1), lo)
    n = jnp.clip(hi - lo + 1, 0, s_kv)
    return jnp.sum(jnp.where(in_row, n, 0)).astype(jnp.float32)


def _host_round_pairs(layout: str, q_part: int, kv_part: int, s: int,
                      causal: bool, window=None) -> int:
    """Host (numpy) twin of `spec_pair_count(round_spec(...))` for CONCRETE
    partition ids.  live_delta_table runs from inside traced callers
    (under shard_map), where even constant jnp ops become tracers — so the
    occupancy table needs an all-host evaluation.
    Mirrors round_spec's spec algebra field by field; pinned equal to the
    traced closed form and the dense-mask sum in tests/test_masks.py."""
    if window is not None:
        if layout != "contig":
            raise ValueError(
                f"window attention supports layout='contig' only, got "
                f"{layout!r}")
        if not causal:
            raise ValueError("window attention requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        q_lo, q_hi, kv_hi, caus, off = 0, s, s, 1, (q_part - kv_part) * s
    elif not causal:
        q_lo, q_hi, kv_hi, caus, off = 0, s, s, 0, 0
    elif layout == "zigzag":
        q_lo = s // 2 if kv_part > q_part else 0
        kv_hi = s // 2 if kv_part < q_part else s
        q_hi, caus, off = s, int(kv_part == q_part), 0
    elif layout == "striped":
        q_lo, q_hi, kv_hi, caus = 0, s, s, 1
        off = 0 if kv_part <= q_part else -1
    elif layout == "contig":
        q_lo, kv_hi, off = 0, s, 0
        q_hi = 0 if kv_part > q_part else s
        caus = int(q_part == kv_part)
    else:
        raise ValueError(f"unknown layout {layout!r}; expected one of {LAYOUTS}")
    rows = np.arange(s, dtype=np.int64)
    in_row = (rows >= q_lo) & (rows < q_hi)
    hi = np.minimum(kv_hi - 1, rows + off) if caus else np.full_like(rows, kv_hi - 1)
    lo = np.zeros_like(rows)
    if window is not None and caus:
        lo = np.maximum(lo, rows + off - window + 1)
    n = np.clip(hi - lo + 1, 0, s)
    return int(np.sum(np.where(in_row, n, 0)))


def live_delta_table(layout: str, s: int, world: int, *, causal: bool,
                     window=None, max_segment_len=None):
    """Per-ring-offset occupancy of a schedule: `live[delta]` is True iff
    ANY device's round at ring offset `delta` (q_part - kv_part = delta mod
    world, equal local chunk lengths `s`) attends at least one (row, col)
    pair.  This is `spec_pair_count` — the closed-form per-round occupancy —
    evaluated over the whole ring, and it is what the schedule compiler
    (parallel/schedule.py) consumes to ELIDE dead rounds: a False entry
    means no consume/send/recv/credit op for that offset anywhere on the
    ring, so the compiled program simply omits the round.

    `max_segment_len` (static int, contig layout only) adds the packed-
    segment reach bound: two chunks `delta` apart hold tokens at least
    `(delta-1)*s + 1` positions apart (adjacent chunks touch at distance 1),
    and tokens of one segment are at most `max_segment_len - 1` apart — so
    offsets past the bound cannot share a segment on any device.  It is a
    CONTRACT about the ids the caller will feed (not validated per batch
    under jit — document, don't trace); zigzag/striped interleave token
    ranges per shard, so no per-offset segment bound exists there and the
    argument is ignored for those layouts.

    Offset 0 (the self round) is always live.  All inputs are concrete
    host ints; the result is a host tuple of bools.
    """
    if world < 1:
        raise ValueError(f"need world >= 1, got {world}")
    live = [True]
    for delta in range(1, world):
        if not causal:
            alive = True
        else:
            alive = any(
                _host_round_pairs(layout, p, (p - delta) % world, s,
                                  True, window=window) > 0
                for p in range(world))
        if (alive and max_segment_len is not None and layout == "contig"):
            # min token distance between chunks delta apart vs the max
            # within-segment distance; without causality the kv chunk also
            # sits (world - delta) chunks AHEAD on wrapping devices, so the
            # live set is a prefix+suffix band and `live_round_prefix`
            # correctly refuses to truncate it
            dist = (delta - 1) * s + 1
            if not causal:
                dist = min(dist, (world - delta - 1) * s + 1)
            alive = dist <= max_segment_len - 1
        live.append(bool(alive))
    return tuple(live)


def live_round_prefix(layout: str, s: int, world: int, *, causal: bool,
                      window=None, max_segment_len=None) -> int:
    """Static live-round count when the live offsets form a PREFIX
    {0..K}: returns K + 1, or `world` (no truncation) when the live set is
    not a prefix (zigzag/striped, or any non-band structure).  This is the
    `r_live` the schedule compiler and the scan ring's static truncation
    share — contig windowed rings reproduce the historical closed form
    min(world, (s + window - 2) // s + 1) (asserted in tests)."""
    live = live_delta_table(layout, s, world, causal=causal, window=window,
                            max_segment_len=max_segment_len)
    k = max(i for i, alive in enumerate(live) if alive)
    if all(live[:k + 1]):
        return k + 1
    return world


def dense_mask(spec: MaskSpec, s_q: int, s_kv: int, window=None) -> jnp.ndarray:
    """Materialize the [s_q, s_kv] boolean mask (True = attend).

    Used by the jnp tile (the numerics oracle) and by tests; the Pallas
    kernels compute the same predicate block-wise with dynamic loop bounds.
    `window` (static) keeps only the last `window` visible columns of each
    row's causal range: cols > rows + offset - window.  A `BlockUnits`
    window evaluates the same predicate on (row // block, col // block).
    """
    unit, window = unit_of(window)
    rows = jnp.arange(s_q, dtype=jnp.int32)[:, None]
    cols = jnp.arange(s_kv, dtype=jnp.int32)[None, :]
    if unit != 1:
        rows, cols = rows // unit, cols // unit
    m = (rows >= spec.q_lo) & (rows < spec.q_hi) & (cols < spec.kv_hi)
    causal_ok = jnp.where(spec.causal > 0, cols <= rows + spec.offset, True)
    if window is not None:
        causal_ok = causal_ok & (cols > rows + spec.offset - window)
    return m & causal_ok


# ---------------------------------------------------------------------------
# block diffusion.  Training by diffusion over blocks runs a layer on a stream
# of 2L tokens, the NOISED copy [0, L) then the CLEAN copy [L, 2L) of one
# L-token document, both with positions 0..L-1.  With b(i) = i // B:
#   noised query i sees noised key j iff b(j) == b(i),
#                      clean key j  iff b(j) <  b(i);
#   clean query i sees clean key j  iff b(j) <= b(i), and no noised key.
# Three of the four quadrants are live, each a causal spec in BlockUnits; the
# fourth (clean x noised) is empty and nothing visits it.


class Quadrant(NamedTuple):
    """One live quadrant as a tile call: static row ranges of the stream, the
    spec (in blocks, local to the ranges) and the tile's `window`.  Every
    quadrant's spec keeps the static contract of the kernels' all-live grids
    (whole ranges, causal, offset 0 or -1), so each call asks for them
    (`triangular=True`): the two unwindowed ones get the triangular grid,
    the block-diagonal one, whose window is one block, the band grid."""

    q_range: tuple
    kv_range: tuple
    spec: MaskSpec
    window: BlockUnits


def bd_quadrants(seq: int, block: int):
    """The live quadrants of a block-diffusion stream of `seq` = 2L tokens,
    in the order that lets a carried state chain them: clean x clean, then
    the noised rows' two (clean keys first)."""
    if seq % 2 or (seq // 2) % block:
        raise ValueError(
            f"a block-diffusion stream is [noised; clean] of one document: "
            f"its length {seq} must be twice a multiple of the block length "
            f"{block}")
    half = seq // 2
    nb = half // block
    noised, clean = (0, half), (half, seq)

    def causal(offset):
        return MaskSpec(_i32(0), _i32(nb), _i32(nb), _i32(1), _i32(offset))

    return (
        Quadrant(clean, clean, causal(0), BlockUnits(block)),
        Quadrant(noised, clean, causal(-1), BlockUnits(block)),
        Quadrant(noised, noised, causal(0), BlockUnits(block, 1)),
    )


def bd_dense_mask(seq: int, block: int) -> np.ndarray:
    """The [2L, 2L] boolean mask of the rule above, written from the rule and
    not from `bd_quadrants`: the oracle the quadrants are tested against."""
    half = seq // 2
    i = np.arange(seq)[:, None]
    j = np.arange(seq)[None, :]
    bi, bj = (i % half) // block, (j % half) // block
    q_noised, k_noised = i < half, j < half
    return np.where(q_noised,
                    np.where(k_noised, bj == bi, bj < bi),
                    ~k_noised & (bj <= bi))


def bd_pairs(seq: int, block: int) -> int:
    """Visible (query, key) pairs of the stream: L^2 + L*B, against the 2L^2
    of one causal sweep over it."""
    half = seq // 2
    return half * half + half * block
