"""Kernel block sizes: one measured row per TPU generation, and the rule
that fits it to one tile call.

SURVEY.md §7 build order item 2 calls for a "block-size autotuning table per
TPU generation": the measured optimum differs per chip (VMEM size, MXU/VPU
ratio), and the v5e numbers baked into the kernel defaults were found with
`benchmarks/sweep_blocks.py`.  This table keys those measurements by
`jax.devices()[0].device_kind` so other generations get a sane starting
point and a re-sweep has one place to record results.

Values are (fwd block_q, fwd block_kv, fwd block_kv_compute,
bwd block_q, bwd block_kv).  The v5e row is measured (seq=64K, 32 heads,
d=128, causal bf16); other rows start from the v5e optimum scaled by VMEM
headroom and are marked estimated until swept on hardware.

A row is what a LONG, UNWINDOWED call wants.  `resolve_blocks` is handed
what a tile call can see statically (the rows of q and of kv it covers, its
static window) and `call_row` fits the row to it: a call under a band far
narrower than the row's tiles gets tiles of the band's width.  Blocks a
caller sets win over both; the VMEM-cliff clamp applies to whatever was
resolved.
"""

import logging
from typing import NamedTuple, Optional

import jax

from .masks import unit_of

logger = logging.getLogger("burst_attn_tpu")


class BlockTable(NamedTuple):
    fwd_block_q: int
    fwd_block_kv: int
    fwd_block_kv_compute: Optional[int]
    bwd_block_q: int
    bwd_block_kv: int
    measured: bool  # False = extrapolated, re-sweep on hardware
    # VMEM-cliff clamp budgets (elements of q-block x kv-block area a fwd /
    # bwd grid step may keep live before throughput collapses ~3x).  The
    # v5e values are MEASURED (benchmarks/cliff_probe.py); other
    # generations scale them by their larger per-core VMEM and inherit
    # measured=False until a sweep pins them.
    fwd_cliff_area: int = 2048 * 2048
    bwd_cliff_area: int = 1024 * 2048
    # Tile edge under a narrow band (call_row): the least square tile a
    # banded call takes.  Below it the grid's steps (about 0.35 us each on
    # the v5e) cost more than the dead area a smaller tile saves.  MEASURED
    # on the v5e (benchmarks/sweep_tile_calls.py; the numbers are at
    # call_row); the other generations inherit it unswept.
    band_block: int = 512
    # Sub-square edge of the diagonal sweeps.  The forward's (pallas_flash
    # fwd_diag_path / _fwd_kernel._sweep_diag): a causal call's diagonal tile
    # is computed in row chunks of this many rows, the squares above the
    # diagonal skipped and only the ones it cuts masked.  0 = the whole tile
    # on the masked path.  Chosen on the kernel AND on what its body costs to
    # trace, which lands in a cell's setup_s (the body is a Python-unrolled
    # loop of tile / edge chunks).  MEASURED on the v5e (benchmarks/
    # sweep_tile_calls.py --edges, PRs 32 and 33; kernel ms of the 8,192-row
    # call / of the 1,024-row call x 8, then host seconds to trace + lower
    # one call site for a described v5e, benchmarks/trace_cost.py):
    #   0 (whole) 4.76 / 1.526, 0.11 s    512  3.76 / 1.119, 0.16 s
    #   256       3.53 / 0.944, 0.25 s    128  3.51 / 0.920, 0.37 s
    #   1024      4.19           64   3.59 / 1.134
    # 256 keeps 98 % of 128's gain at 8,192 rows and 96 % at 1,024 for two
    # thirds of its trace (PR 32 shipped 128 traced once a layer and was
    # refused on setup_s; since PR 33 the body is traced once a distinct
    # call, so the edge is paid once a program length, not once a layer).
    # The same edge serves the BACKWARD's cut blocks (pallas_flash
    # bwd_diag_path / _bwd_cut_tile: the q blocks the diagonal cuts, the
    # square it passes through in column chunks of this many columns).
    # MEASURED on the v5e (sweep_tile_calls.py --edges --bwd, PR 35; kernel
    # ms of the 8,192-row call at 32 / 8 heads, rectangular kernel / at
    # 32 / 32, triangular kernel / of the 1,024-row call x 8):
    #   0 (whole) 10.27 / 9.91 / 2.147    512  8.57 / 8.47
    #   256        8.43 / 8.33 / 1.320    128  8.55 / 8.45
    # Host seconds to trace + lower jax.grad over four jax.checkpoint(
    # burst_attn) blocks at 1 x 8,192 rows, 32 / 8 heads, for a described v5e
    # (trace_cost.py, this repo's CPU host, least of three to five): the
    # parent of PR 35 0.53-0.57; the backward's launch behind flash_bwd's one
    # jit with the whole tile 0.48; with the cut blocks at 256 0.55-0.60 (at
    # 8 x 1,024 rows 0.49-0.51 against 0.50-0.54): the one trace takes back
    # what the body adds.
    # The other generations inherit it unswept.
    diag_block: int = 256


class ResolvedBlocks(NamedTuple):
    """Uniform return of resolve_blocks(): always all five fields, so call
    sites never branch on arity (callers that don't use the compute
    sub-block just ignore the last field)."""

    block_q: int
    block_kv: int
    block_q_bwd: int
    block_kv_bwd: int
    block_kv_compute: Optional[int]


_TABLE = {
    # measured with benchmarks/sweep_blocks.py on one v5e chip (rounds
    # 2-3); see docs/design.md §3 for the cliff analysis
    "v5e": BlockTable(2048, 2048, 1024, 1024, 2048, True),
    # v4/v5p have roughly twice the v5e per-core VMEM, so the area at which
    # a grid step's live blocks spill — the cliff — should sit one power of
    # two higher; block shapes stay at the v5e optimum until swept
    "v5p": BlockTable(2048, 2048, 1024, 1024, 2048, False,
                      fwd_cliff_area=2 * 2048 * 2048,
                      bwd_cliff_area=2 * 1024 * 2048),
    "v4": BlockTable(2048, 2048, 1024, 1024, 2048, False,
                     fwd_cliff_area=2 * 2048 * 2048,
                     bwd_cliff_area=2 * 1024 * 2048),
    # v6e (Trillium): bigger MXU, comparable VMEM — keep the v5e budgets
    "v6": BlockTable(2048, 2048, 1024, 1024, 2048, False),
}

# Ordered (substring, canonical row) aliases over the device_kind strings JAX
# runtimes actually report — "TPU v5 lite" / "TPU v5e" (v5e), "TPU v5p" and
# sometimes bare "TPU v5" (v5p), "TPU v6 lite" / "TPU v6e" / Trillium, "TPU
# v4".  Order matters: the v5e spellings must be tried before the bare "v5"
# catch-all, and "v5p" before "v5".
_KIND_ALIASES = (
    ("v5 lite", "v5e"),
    ("v5litepod", "v5e"),
    ("v5e", "v5e"),
    ("v5p", "v5p"),
    ("v5", "v5p"),
    ("v6 lite", "v6"),
    ("v6e", "v6"),
    ("trillium", "v6"),
    ("v6", "v6"),
    ("v4", "v4"),
)

_DEFAULT = BlockTable(2048, 2048, 1024, 1024, 2048, False)

_logged_kinds = set()


def _log_resolution(kind: str, canonical: Optional[str], row: BlockTable,
                    platform: str) -> None:
    """Log each device kind's table resolution once per process — an
    unmeasured row on real TPU hardware means the defaults are
    extrapolations and a re-sweep (benchmarks/sweep_blocks.py) is due
    (round-1 verdict item 8)."""
    if kind in _logged_kinds:
        return
    _logged_kinds.add(kind)
    if platform != "tpu":
        return  # off-TPU the values only affect tiling granularity
    if row.measured:
        logger.info("kernel blocks for %r: measured row %r", kind, canonical)
    else:
        logger.warning(
            "kernel blocks for %r resolved to %r row (NOT measured on this "
            "generation — defaults extrapolated from the v5e sweep; run "
            "`python -m benchmarks.sweep_blocks` and record the optimum in "
            "burst_attn_tpu/ops/tuning.py)",
            kind, canonical,
        )


def generations():
    """Every named generation row of the tuning table plus the "default"
    fallback, in sorted order — the static iteration domain of the cost
    verifier (analysis/costmodel.py), which must prove budgets for rows a
    CPU lint host can never resolve through jax.devices()."""
    return tuple(sorted(_TABLE)) + ("default",)


def generation_row(kind: str) -> BlockTable:
    """The BlockTable row for a canonical generation name (or "default"),
    with no device in hand — the device-free twin of block_defaults()."""
    if kind == "default":
        return _DEFAULT
    if kind not in _TABLE:
        raise KeyError(
            f"unknown generation {kind!r}; expected one of {generations()}")
    return _TABLE[kind]


def canonical_kind(device=None):
    """Canonical generation name ("v5e"/"v5p"/"v4"/"v6") for a device's
    device_kind, or None when unrecognized — the one place device-kind
    strings are interpreted (consumers: the block table here, peak-FLOPs
    tables in benchmarks)."""
    if device is None:
        devs = jax.devices()
        if not devs:
            return None
        device = devs[0]
    kind = getattr(device, "device_kind", "").lower()
    for key, canonical in _KIND_ALIASES:
        if key in kind:
            return canonical
    return None


def block_defaults(device=None) -> BlockTable:
    """Best-known kernel blocks for `device` (default: first jax device).

    A TPU whose device_kind the table does not know is an error: blocks
    guessed for it would be timed as if they were tuned.  Off-TPU (CPU
    interpret runs) the values only affect tiling granularity, not
    correctness; the default row is returned.
    """
    if device is None:
        devs = jax.devices()
        if not devs:
            return _DEFAULT
        device = devs[0]
    kind = getattr(device, "device_kind", "").lower()
    platform = getattr(device, "platform", "")
    canonical = canonical_kind(device)
    if canonical is None and platform == "tpu":
        raise ValueError(
            f"no kernel block row for TPU device kind {kind!r}; sweep it "
            f"(benchmarks/sweep_blocks.py) and add the row to "
            f"burst_attn_tpu/ops/tuning.py (known: {sorted(_TABLE)})")
    row = _TABLE[canonical] if canonical else _DEFAULT
    _log_resolution(kind, canonical, row, platform)
    return row


# Measured VMEM-cliff law (benchmarks/cliff_probe.py on v5e, traces under
# results/cliff_traces/): a fwd grid step whose q-block x kv-block AREA
# exceeds the generation's budget collapses ~3x (57 TFLOPs/s at 2048x4096
# on v5e — at EVERY compute-sub-block size, so it is not score
# materialization or pipeline overlap; halving bq to 1024 recovers 142).
# The backward's per-step residency is larger (5 matmul operands + dk/dv
# scratch), so its cliff sits one power of two lower.  It's a cliff, not a
# slope — exceeding the budget is never a trade-off worth making, hence a
# clamp rather than a warning.  The budgets live in the per-generation
# BlockTable rows (a v5p with twice the VMEM must not be clamped to v5e's
# areas); the CPU's _DEFAULT row carries the v5e-measured values through the
# BlockTable field defaults.


def _cliff_ok():
    """BURST_ALLOW_CLIFF=1 disables the clamp (sweeps/probes must be able
    to measure the cliff configs themselves)."""
    import os

    return os.environ.get("BURST_ALLOW_CLIFF", "") not in ("", "0")


def _clamp_cliff(bq: int, bkv: int, area: int, which: str):
    if bq * bkv <= area or _cliff_ok():
        return bq, bkv
    new_bkv = max(area // bq, 128)
    logger.warning(
        "%s blocks %dx%d exceed the measured VMEM-cliff area (%d); clamping "
        "kv block to %d (see results/cliff_probe.jsonl; BURST_ALLOW_CLIFF=1 to "
        "measure cliff configs anyway)", which, bq, bkv, area, new_bkv)
    return bq, new_bkv


def _pow2_ceil(x: int) -> int:
    return 1 << max(x - 1, 0).bit_length()


def call_row(t: BlockTable, s_q=None, s_kv=None, window=None):
    """(fwd block_q, fwd block_kv, bwd block_q, bwd block_kv) that the
    generation's row `t` gives ONE tile call, from what the call can see
    statically: the rows of q and of kv it covers and its `window` (tokens,
    or a masks.BlockUnits).  One rule in rows and band width; the measured
    rows are its fixed points, and it names no model and no shape.
    Measured on the v5e with benchmarks/sweep_tile_calls.py (PR 29: device
    ms of the kernel of one call, d_head 128, bf16, the second of two
    sweeps that agree to 0.01 ms; PERF.md section 6).

    Band width.  On the band grids a q tile visits the kv tiles its band of
    `win * unit` tokens can touch: the band plus about one tile edge.  Under
    a band far narrower than the row's tiles nearly all of that is dead, and
    what is visited takes the masked path, which the VPU bounds.  So the
    tile shrinks to the band's width rounded up to a power of two, and not
    below `t.band_block`, where grid steps take over; a band as wide as the
    row's tiles keeps them (window 4096 at 64K: the row).  Fixed points, at
    8,192 rows.  A band of 4 tokens, 32 / 4 heads (the block-diagonal call
    of a block-diffusion stream): forward 3.05 ms in the row's 2048 x 2048
    on the rectangular grid, 3.02 on the band grid, then 2.10 / 1.77 / 2.00
    / 2.48 in squares of 1024 / 512 / 256 / 128 (256 x 512: 2.00,
    512 x 256: 2.82); backward 4.48 in the row's 1024 x 2048, then 2.43 /
    1.64 / 1.72 / 2.81.  A window of 256 tokens, 32 / 8 heads: forward 4.38
    in the row's tiles, 2.84 / 2.13 / 2.56 in 1024 / 512 / 256; backward
    6.11, 4.39 / 2.77 / 2.64.  A window of 1,024: forward 4.38, 2.84 in
    1024, 2.95 in 512; backward 6.11, 4.39 in 1024, 3.96 in 512 (the rule's
    1024 is not the backward's best there; it is 28 % under the row).

    Rows.  An unwindowed call keeps the row at every length measured: no
    departure was 3 % faster on the call.  At 8,192 rows (32 / 4 and 32 / 8
    heads) the forward reads 4.76 ms in 2048 x 2048 and 4.78 in
    1024 x 1024 (9 tile-units of area for 10, four times the steps), 5.69 in
    2048 x 1024, 7.24 in 4096 x 1024, 7.70 in 512 x 512; the backward 10.22
    / 10.31 / 10.27 in 1024 x 2048 and 9.93 / 10.03 / 9.99 in 1024 x 1024
    (2.7-2.9 % faster, under the bar), 10.47 in 512 x 2048, 10.70 in
    512 x 1024, 10.78 in 2048 x 1024, 12.53 in 512 x 512.  At 1,024 rows
    (batch 8, 32 / 8 heads), where the row's tiles are clamped to one
    1024 x 1024 a head: forward 1.53 against 1.63 in 512 x 512 (the
    triangular grid, 3 of 4 quarter tiles) and 2.91 in 256 x 256; backward
    2.15 against 2.13 in 512 x 512, 2.32 in 512 x 1024, 2.88 in 256 x 512.
    So `s_q` and `s_kv` change nothing today; they are what the next
    measured departure keys on.

    Head widths (PR 34: q and k `d_qk` wide, v `d_v`; the kernels read them
    off the arrays).  The row stands at 192 / 128 too, so the widths are not
    arguments of this rule.  At 16,384 rows x 32 / 32 heads (the last rows
    of results/sweep_tile_calls.jsonl; the forward with the diagonal tiles
    whole, as the tile sizes were always swept): forward 22.33 ms in
    2048 x 2048 against 23.50 in 1024 x 1024, 24.32 in 1024 x 2048, 25.84
    in 2048 x 1024 (128 / 128 beside it: 15.68); the triangular backward
    54.28 in 1024 x 2048 against 52.44 in 1024 x 1024 (-3.4 %), 54.49 in
    512 x 2048 and 58.93 in 2048 x 1024, which takes the rectangular kernel
    (128 / 128: 35.12 against 34.29, -2.4 %).  The backward's 1024 x 1024 is
    the 2.4-3.4 % it has been at every length and width measured: a property
    of that kernel (ROADMAP S9), not of a width, and no row of its own.  The
    192-deep score product goes to the MXU as ONE operand: fed as a 128 + 64
    pair of products summed, the forward read 20.66 ms against 19.39 (edge
    256) and the backward 54.41 against 54.28.
    """
    del s_q, s_kv  # see Rows
    row = (t.fwd_block_q, t.fwd_block_kv, t.bwd_block_q, t.bwd_block_kv)
    unit, win = unit_of(window)
    if win is not None:
        edge = max(t.band_block, _pow2_ceil(win * unit))
        row = tuple(min(b, edge) for b in row)
    return row


def resolve_blocks(block_q=None, block_kv=None, block_q_bwd=None,
                   block_kv_bwd=None, block_kv_compute=None,
                   device=None,
                   table: Optional[BlockTable] = None, *,
                   s_q=None, s_kv=None, window=None) -> ResolvedBlocks:
    """Fill unspecified kernel block sizes from the per-generation table
    and, where the caller gives it, the tile call's own geometry (`s_q`,
    `s_kv`: the rows the call covers; `window`: its static window; see
    call_row).  Without geometry the row stands as it is.

    The bwd defaults never exceed the (resolved) fwd blocks, so a caller who
    shrinks the fwd blocks for VMEM keeps that budget in bwd; likewise the
    compute sub-block never exceeds the kv memory block.  Explicit configs
    past the generation's measured VMEM cliff are clamped (see
    _clamp_cliff; budgets come from the device's BlockTable row).  Always
    returns a 5-field ResolvedBlocks; callers without a compute sub-block
    ignore the last field.  `table` bypasses the device probe with an
    explicit BlockTable row: how the static cost verifier resolves every
    generation's blocks through the same defaulting from a host with no TPU.
    """
    t = block_defaults(device) if table is None else table
    fwd_q, fwd_kv, bwd_q, bwd_kv = call_row(t, s_q, s_kv, window)
    bq = fwd_q if block_q is None else block_q
    bkv = fwd_kv if block_kv is None else block_kv
    bqb = min(bwd_q, bq) if block_q_bwd is None else block_q_bwd
    bkvb = min(bwd_kv, bkv) if block_kv_bwd is None else block_kv_bwd
    bq, bkv = _clamp_cliff(bq, bkv, t.fwd_cliff_area, "fwd")
    bqb, bkvb = _clamp_cliff(bqb, bkvb, t.bwd_cliff_area, "bwd")
    if block_kv_compute is None:
        block_kv_compute = (bkv if t.fwd_block_kv_compute is None
                            else min(t.fwd_block_kv_compute, bkv))
    else:
        block_kv_compute = min(block_kv_compute, bkv)
    return ResolvedBlocks(bq, bkv, bqb, bkvb, block_kv_compute)
