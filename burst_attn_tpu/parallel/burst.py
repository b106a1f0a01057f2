"""Burst (ring) attention — the core distributed op.

TPU-native rebuild of the reference's OpBurstAttn / OpBurstAttnStrip
(burst_attn/burst_attn_interface.py:161-613):

  torch.autograd.Function            -> jax.custom_vjp (burst_attn_shard)
  Python ring loop + CUDA streams +
    double buffers (comm.py:267-301) -> lax.scan whose body issues the
                                        collective-permute BEFORE the tile
                                        compute; XLA's async collective
                                        permute gives the comm/compute
                                        overlap, the scan carry is the
                                        double buffer
  NCCL P2P ring                      -> lax.ppermute on a named mesh axis
  double ring (intra-node ring nested
    in inter-node ring, inter hop
    prefetched one intra-cycle early
    on its own stream, comm.py:221-254) -> a static Python loop over inter
                                        cycles: the inter-axis ppermute of
                                        the cycle base is issued at cycle
                                        start and consumed at cycle end, so
                                        XLA has the whole intra cycle to
                                        hide the DCN hop
  dq add-and-forward ring
    (comm.py:187-218)                -> dq_intra rotates with the q-side
                                        payload; at each cycle boundary it is
                                        folded into an inter-ring running sum
                                        and restarted at zero; one final
                                        inter+intra hop returns dq home
  causal zigzag 3-way case split /
    striped shift-by-one slicing     -> one uniform tile parameterized by
                                        runtime MaskSpec scalars (ops/masks.py)

Data conventions: per-shard q, k, v are [B, N, S_local, D] ("bnsd"), where the
global sequence is permuted into layout order (parallel/layouts.py) and
chunked device-major over (inter, intra): partition id = inter_rank *
intra_size + intra_rank.
"""

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from jax import shard_map
from jax.lax import axis_size

from .. import obs
from ..obs import devstats
from ..ops import tile as jnp_tile
from ..ops.masks import (full_spec, live_round_prefix, round_spec, spec_live,
                         spec_pair_count)
from .ring import (ppermute_by, ppermute_next, my_partition,
                   partition_at_round, ring_round_counts,
                   wire_dequantize, wire_quantize)

# -- obs dispatch instrumentation (host boundary only — see _note_dispatch).
# These counters advance when a program is DISPATCHED (once per trace under
# jit, once per call eagerly): the unit for "which path did the ring take",
# not per-step execution counts (docs/observability.md, "per-trace").
_M_DISPATCH = obs.counter(
    "burst.dispatch", "ring dispatches by backend and tile")
_M_INPLACE = obs.counter(
    "burst.inplace_rounds",
    "scheduled scan-ring rounds after the self round, by pass and by where "
    "the round folds into its carry: path=kernel (the tile writes into the "
    "carry in place) or path=xla (slice / run / add around the tile)")
_M_DIAG = obs.counter(
    "flash.diag_tiles",
    "tiles the causal diagonal cuts (a batch and head each; pass=fwd: q "
    "block i against kv block i, pass=bwd: the backward's q blocks) of the "
    "dispatch's tile calls that promise a full-window causal mask, by pass "
    "and by what serves them: path=sub (the kernel computes the tile's live "
    "sub-squares only) or path=whole (its whole area on the masked path)")


@dataclass(frozen=True)
class BurstConfig:
    """Static configuration for burst attention.

    Mirrors the kwargs of the reference's burst_attn_func
    (burst_attn_interface.py:135-158); process_group/double_group become mesh
    axis names, the flash/triton/math backend switch becomes jnp vs pallas,
    and `deterministic` is always true on TPU (XLA reductions are
    deterministic) — kept for API parity.
    """

    causal: bool = False
    layout: str = "zigzag"  # "zigzag" | "striped" | "contig" (causal schedule)
    scale: Optional[float] = None  # default 1/sqrt(head_dim)
    intra_axis: str = "sp"
    inter_axis: Optional[str] = None  # set for the hierarchical double ring
    # the tile of a round: "jnp" (the float32 oracle) | "pallas" (the flash
    # kernels, ops/pallas_flash.py)
    backend: str = "jnp"
    optimize_bwd_comm: bool = True  # rotate delta=sum(o*do) [B,N,S] f32, not o
    # kernel blocks; None = resolved per tile CALL by resolved_blocks() in
    # the tile dispatch, from the per-TPU-generation table and the call's
    # own geometry (ops/tuning.py resolve_blocks: rows covered, band width),
    # with bwd blocks never defaulting larger than the fwd ones (a caller
    # who tunes block_q/block_kv down for VMEM keeps that budget in the
    # backward too).  A block set here wins over both.
    block_q: Optional[int] = None
    block_kv: Optional[int] = None
    block_q_bwd: Optional[int] = None
    block_kv_bwd: Optional[int] = None
    deterministic: bool = True
    # Sliding-window (band) causal attention: each query sees its last
    # `window` positions.  Contig layout only (ops/masks.round_spec explains
    # why the load-balancing permutations can't express a band); rounds
    # wholly outside the band are dead and skipped block-wise.
    window: Optional[int] = None
    # Packed-segment length bound: a PROMISE that no segment in the
    # segment_ids the caller feeds spans more than this many tokens.  It is
    # a CONTRACT, not a runtime check (ids are traced values — validating
    # per batch would defeat jit): with contig-causal single rings the
    # occupancy compiler uses it to ELIDE ring rounds whose chunk distance
    # exceeds the bound (ops/masks.live_delta_table), exactly like `window`
    # elides rounds past the band.  Ids that break the promise silently
    # drop attention pairs.  Ignored by zigzag/striped (their token
    # interleaving defeats any per-round distance bound) and by non-causal
    # rings (wrap-around makes the live set a non-prefix band).
    max_segment_len: Optional[int] = None
    # Wire precision of the ROTATING ring payloads (ROADMAP item 5): None
    # ships the caller's dtypes bit-exactly; "int8"/"fp8" quantize the fwd
    # K/V blocks, the bwd q-side bundle (delta|o, do, q — lse stays fp32)
    # and the fp32 dq partials to 1 B/elem, with per-block fp32 SCALAR
    # scales riding the same payload (extra pytree leaves in the rotating
    # tuple).  fp32 ACCUMULATION is never touched — every quantized tensor
    # is rescaled before any dot/add, like ops/ragged_paged.py's int8 pool
    # path — so the cost is a pinned quantization tolerance, not a different
    # algorithm.  Resident tensors and the purely-local math never see the
    # wire dtype.
    wire_dtype: Optional[str] = None
    # Structural causal scheduling (reference burst_attn_interface.py:221-235,
    # :303-367): zigzag rounds dispatch through a 3-way lax.cond whose
    # branches run statically-sliced dense tiles (full q x half kv / half q x
    # full kv) or a triangular-grid causal tile, instead of one uniform
    # masked tile whose rectangular grid is ~half dead steps.  Striped rounds
    # use the triangular grid directly (every round is full-window causal).
    case_split: bool = True
    # Block-diffusion training mask (ops/masks.bd_quadrants): the sequence is
    # the stream [noised; clean] of one document and this is its block
    # length.  It replaces `causal` / `window`; one shard only (burst_attn
    # refuses a ring: _bd_fwd says what is missing).
    block_diffusion: Optional[int] = None

    def __post_init__(self):
        # validate here, not only in burst_attn(): direct BurstConfig users
        # (burst_attn_shard inside their own shard_map, the pp trainer)
        # must hit the same wall — a window on a zigzag/striped ring would
        # silently band the PERMUTED local order
        if self.window is not None:
            if self.layout != "contig":
                raise ValueError(
                    "window attention requires layout='contig' (the "
                    "zigzag/striped load-balancing permutations break the "
                    f"band structure); got layout={self.layout!r}")
            if not self.causal:
                raise ValueError("window attention requires causal=True")
            if self.window < 1:
                raise ValueError(f"window must be >= 1, got {self.window}")
        if self.block_diffusion is not None:
            if self.block_diffusion < 1:
                raise ValueError(
                    f"block_diffusion (the block length) must be >= 1, got "
                    f"{self.block_diffusion}")
            if self.window is not None:
                raise ValueError(
                    "block_diffusion is a mask kind of its own and takes no "
                    "sliding window")
        if self.max_segment_len is not None and self.max_segment_len < 1:
            raise ValueError(
                f"max_segment_len must be >= 1, got {self.max_segment_len}")
        if self.wire_dtype not in (None, "int8", "fp8"):
            raise ValueError(
                f"wire_dtype must be None, 'int8' or 'fp8', got "
                f"{self.wire_dtype!r}")
        if self.backend not in ("jnp", "pallas"):
            raise ValueError(
                "backend must be 'jnp' or 'pallas' (burst_attn also takes "
                f"'auto': pallas on a TPU, jnp elsewhere), got "
                f"{self.backend!r}")

    def resolved_blocks(self, s_q=None, s_kv=None, window=None):
        """ResolvedBlocks with None fields filled by ops/tuning.py — the one
        source of block defaults — for a tile call that covers `s_q` query
        and `s_kv` key rows under the static `window` (no geometry: the
        per-TPU-generation row as it stands)."""
        from ..ops.tuning import resolve_blocks

        return resolve_blocks(self.block_q, self.block_kv,
                              self.block_q_bwd, self.block_kv_bwd,
                              s_q=s_q, s_kv=s_kv, window=window)


# ---------------------------------------------------------------------------
# tile dispatch


def _call_blocks(cfg, s_q, s_kv, q_range, kv_range, window):
    """The blocks of ONE tile call, resolved from what it covers: the rows
    of its `q_range` / `kv_range` (the whole length-s arrays without one)
    under its static window."""
    def rows(s, rng):
        return s if rng is None else rng[1] - rng[0]

    return cfg.resolved_blocks(rows(s_q, q_range), rows(s_kv, kv_range),
                               window)


def _tile_fwd(cfg, q, k, v, m, lse, acc, scale, spec, triangular=False,
              segments=None, q_range=None, kv_range=None, window=None):
    """One forward round folded into the carried (m, lse, acc).  `q_range` /
    `kv_range`: static (lo, hi) rows of the FULL arrays the round covers
    (`spec` is local to them); the state's other rows come back untouched.
    Both tiles take the same call; which form it lowers to (the kernel on
    the carry in place, or slice / run / write back) is the tile's own
    static decision (_round_in_kernel reads the same one).  `window`: the
    tile's static window where it is not cfg.window (a masks.BlockUnits)."""
    window = cfg.window if window is None else window
    if cfg.backend == "pallas":
        from ..ops import pallas_flash

        rb = _call_blocks(cfg, q.shape[2], k.shape[2], q_range, kv_range,
                          window)
        bq, bkv = rb.block_q, rb.block_kv
        return pallas_flash.flash_fwd(
            q, k, v, m, lse, acc, scale, spec,
            block_q=bq, block_kv=bkv, triangular=triangular,
            window=window, segments=segments,
            q_range=q_range, kv_range=kv_range,
        )
    if m is None:
        # jnp oracle has no None-carry fast path; materialize the empty
        # state it stands for (CPU-only — XLA folds the constants anyway)
        b, n, s, _ = q.shape
        m, lse, acc = jnp_tile.init_state(b, n, s, v.shape[-1])
    return jnp_tile.tile_fwd(q, k, v, m, lse, acc, scale, spec,
                             window=window, segments=segments,
                             q_range=q_range, kv_range=kv_range)


def _tile_bwd(cfg, do, q, k, v, delta, lse, scale, spec, triangular=False,
              segments=None, q_range=None, kv_range=None, carry=None,
              window=None):
    """One backward round: (dq, dk, dv) float32, full-size.  dq is this
    round's alone (it rides a ring: the caller adds it to the arriving
    partial); dk, dv are `carry` = (dk, dv) plus this round's where a carry
    is given.  Ranges and `window` as in _tile_fwd."""
    window = cfg.window if window is None else window
    if cfg.backend == "pallas":
        from ..ops import pallas_flash

        rb = _call_blocks(cfg, q.shape[2], k.shape[2], q_range, kv_range,
                          window)
        bq, bkv = rb.block_q_bwd, rb.block_kv_bwd
        return pallas_flash.flash_bwd(
            do, q, k, v, delta, lse, scale, spec, block_q=bq, block_kv=bkv,
            triangular=triangular, window=window, segments=segments,
            q_range=q_range, kv_range=kv_range, carry=carry,
        )
    return jnp_tile.tile_bwd(do, q, k, v, delta, lse, scale, spec,
                             window=window, segments=segments,
                             q_range=q_range, kv_range=kv_range, carry=carry)


def _round_tiles(cfg, s, s_kv):
    """The tile calls a round AFTER the self round can make, as static
    (triangular, q_range, kv_range) triples in the forward's roles (the
    backward swaps nothing: its q side is the rotating one, its ranges the
    same rows).  The zigzag case split picks one of two at run time; every
    other schedule has one shape of round."""
    if cfg.causal and cfg.case_split and s_kv == s:
        if cfg.layout == "zigzag":
            half = s // 2
            return [(False, None, (0, half)), (False, (half, s), None)]
        if cfg.layout == "striped":
            return [(True, None, None)]
    band = cfg.layout == "contig" and cfg.causal and cfg.window is not None
    return [(band, None, None)]


def _promised_fwd_calls(cfg, s, s_kv, rounds, seg):
    """The forward tile calls of one dispatch that promise `triangular`, as
    static (calls, rows, window, segments) of _tile_fwd's arguments: the
    self round (_fwd_impl's tri0), the later rounds whose tile says so
    (_round_tiles), or the three quadrants of a block-diffusion stream
    (_bd_fwd)."""
    if cfg.block_diffusion is not None:
        from ..ops.masks import bd_quadrants

        return [(1, quad.q_range[1] - quad.q_range[0], quad.window, False)
                for quad in bd_quadrants(s, cfg.block_diffusion)]
    if not (cfg.causal and s_kv == s):
        return []
    later = sum(tri for tri, _, _ in _round_tiles(cfg, s, s_kv))
    return [(1 + later * (rounds - 1), s, cfg.window, seg)]


def _promised_bwd_calls(cfg, s, s_kv, rounds, seg):
    """_promised_fwd_calls for the backward tile calls: the quadrants of a
    block-diffusion stream (_bd_bwd), the zigzag split's own round and
    every round of the striped one (_bwd_impl's compute; a contig ring's
    backward promises nothing, its forward's self round does)."""
    if cfg.block_diffusion is not None:
        return _promised_fwd_calls(cfg, s, s_kv, rounds, seg)
    if not (cfg.causal and cfg.case_split and s_kv == s
            and cfg.layout in ("zigzag", "striped")):
        return []
    return [(rounds if cfg.layout == "striped" else 1, s, cfg.window, seg)]


def _diag_tiles(cfg, q_shape, k_shape, rounds, seg, d_v):
    """{(pass, path): diagonal tiles} of one dispatch's tile calls that
    promise `triangular`, by the tile entry's own static choice
    (pallas_flash.fwd_diag_path / bwd_diag_path, on the blocks _tile_fwd /
    _tile_bwd resolve).  A backward tile is a q block of the backward's own
    tiling that the diagonal cuts."""
    if cfg.backend != "pallas":
        return {}
    from ..ops import pallas_flash

    (b, n, s, d), (_, n_kv, s_kv, _) = q_shape, k_shape
    tiles = {}
    for pass_, promised in (("fwd", _promised_fwd_calls),
                            ("bwd", _promised_bwd_calls)):
        for calls, rows, window, segs in promised(cfg, s, s_kv, rounds, seg):
            rb = cfg.resolved_blocks(rows, rows, window)
            if pass_ == "fwd":
                path = pallas_flash.fwd_diag_path(
                    rows, rows, block_q=rb.block_q, block_kv=rb.block_kv,
                    triangular=True, window=window, segments=segs)
            else:
                path = pallas_flash.bwd_diag_path(
                    n, n_kv, rows, rows, d, block_q=rb.block_q_bwd,
                    block_kv=rb.block_kv_bwd, triangular=True, window=window,
                    segments=segs, d_v=d_v)
            key = (pass_, path.path)
            tiles[key] = tiles.get(key, 0) + calls * b * n * path.tiles
    return tiles


def _round_in_kernel(cfg, pass_, q_shape, k_shape, d_v) -> bool:
    """Whether a round after the self round folds into its carry INSIDE the
    kernel (the forward's state, the backward's dk / dv) on these per-shard
    shapes, or takes the sliced / added form in XLA: the tile entry's own
    static gate (pallas_flash.fwd_covers_ranges / bwd_folds_carry), asked
    for every tile call the round can make."""
    if cfg.backend != "pallas":
        return False
    from ..ops import pallas_flash

    (_, n, s, d), (_, n_kv, s_kv, _) = q_shape, k_shape

    def folds(tri, q_rng, kv_rng):
        # the blocks the tile call itself resolves (_tile_fwd / _tile_bwd)
        rb = _call_blocks(cfg, s, s_kv, q_rng, kv_rng, cfg.window)
        if pass_ == "fwd":
            return pallas_flash.fwd_covers_ranges(
                s, s_kv, q_rng, kv_rng, block_q=rb.block_q,
                block_kv=rb.block_kv, triangular=tri)
        return pallas_flash.bwd_folds_carry(
            n, n_kv, s, s_kv, d, q_rng, kv_rng, block_q=rb.block_q_bwd,
            block_kv=rb.block_kv_bwd,
            # the forward's band grid has no backward twin to ask for
            triangular=tri and cfg.window is None, window=cfg.window,
            d_v=d_v)

    return all(folds(*tile) for tile in _round_tiles(cfg, s, s_kv))


def _sizes(cfg):
    intra = axis_size(cfg.intra_axis)
    inter = axis_size(cfg.inter_axis) if cfg.inter_axis is not None else 1
    return inter, intra


def _r_live(cfg, s, s_kv, n_inter, n_intra):
    """Static live-round count of a truncatable SINGLE contig ring (shared
    by fwd and bwd — the two passes' truncation must stay in lockstep with
    ops/masks' occupancy algebra).  Both the window band and the
    max_segment_len reach bound produce a live-round PREFIX on contig
    causal rings (masks.live_round_prefix; windowed rings reproduce the
    historical closed form min(W, (s + window - 2) // s + 1)).  n_intra =
    no truncation."""
    if ((cfg.window is not None or cfg.max_segment_len is not None)
            and cfg.layout == "contig" and cfg.causal
            and n_inter == 1 and n_intra > 1 and s_kv == s):
        return live_round_prefix(
            "contig", s, n_intra, causal=True, window=cfg.window,
            max_segment_len=cfg.max_segment_len)
    return n_intra


# ---------------------------------------------------------------------------
# block diffusion (one shard)
#
# The stream [noised; clean] has three live quadrants (ops/masks.bd_quadrants)
# and each is one call of the tile every other mask runs, on the halves of
# q / k / v it covers, with a block-unit causal spec: clean x clean stands
# alone; the noised rows fold their clean keys (strictly earlier blocks) and
# then their own block into one carried state.  The clean x noised quadrant
# has no call, so nothing visits it: L^2 + L*B pairs of work, where one
# causal sweep over the stream would do 2L^2.
#
# Not over a ring: with the stream cut into contiguous shards the noised
# half's shards would have no clean keys of their own and the clean half's
# no noised rows; a layout that interleaves both halves of each block range
# on every shard (and a round schedule over it) is ROADMAP work.


def _rows_of(x, rng):
    return lax.slice_in_dim(x, rng[0], rng[1], axis=2)


def _bd_fwd(q, k, v, cfg: BurstConfig):
    """(o, lse) of the block-diffusion mask on one shard's whole stream."""
    from ..ops.masks import bd_quadrants

    s, d = q.shape[2], q.shape[3]
    scale = cfg.scale if cfg.scale is not None else d**-0.5
    clean, below, diagonal = bd_quadrants(s, cfg.block_diffusion)

    def fold(quad, state):
        return _tile_fwd(cfg, _rows_of(q, quad.q_range),
                         _rows_of(k, quad.kv_range), _rows_of(v, quad.kv_range),
                         *state, scale, quad.spec, triangular=True,
                         window=quad.window)

    empty = (None, None, None)
    with jax.named_scope("obs.bd.clean"):
        m_c, lse_c, acc_c = fold(clean, empty)
    with jax.named_scope("obs.bd.noised"):
        m_n, lse_n, acc_n = fold(diagonal, fold(below, empty))
    o = jnp.concatenate([jnp_tile.finalize(m_n, lse_n, acc_n, q.dtype),
                         jnp_tile.finalize(m_c, lse_c, acc_c, q.dtype)], axis=2)
    return o, jnp.concatenate([lse_n, lse_c], axis=2)


def _bd_bwd(cfg: BurstConfig, q, k, v, o, lse, do):
    """(dq, dk, dv), float32, of _bd_fwd: the same three tile calls; dk / dv
    of the clean keys are carried from the clean rows' call into the noised
    rows', and the noised rows' two dq are added."""
    from ..ops.masks import bd_quadrants

    s, d = q.shape[2], q.shape[3]
    scale = cfg.scale if cfg.scale is not None else d**-0.5
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    clean, below, diagonal = bd_quadrants(s, cfg.block_diffusion)

    def grads(quad, carry=None):
        qr, kr = quad.q_range, quad.kv_range
        return _tile_bwd(cfg, _rows_of(do, qr), _rows_of(q, qr),
                         _rows_of(k, kr), _rows_of(v, kr),
                         lax.slice_in_dim(delta, *qr, axis=2),
                         lax.slice_in_dim(lse, *qr, axis=2), scale, quad.spec,
                         triangular=True, carry=carry, window=quad.window)

    with jax.named_scope("obs.bd.clean"):
        dq_c, *dkv_c = grads(clean)
    with jax.named_scope("obs.bd.noised"):
        dq_below, dk_c, dv_c = grads(below, carry=tuple(dkv_c))
        dq_diag, dk_n, dv_n = grads(diagonal)
    return (jnp.concatenate([dq_below + dq_diag, dq_c], axis=2),
            jnp.concatenate([dk_n, dk_c], axis=2),
            jnp.concatenate([dv_n, dv_c], axis=2))


# ---------------------------------------------------------------------------
# forward


def _fwd_impl(q, k, v, cfg: BurstConfig, seg=None, collect=False):
    """Ring forward. Per-shard shapes q [B,N,S,D], k/v [B,Nk,S,D].

    Reference call stack SURVEY.md §3.1 / burst_attn_interface.py:170-253.
    Returns (o, lse) with o [B,N,S,D] in q.dtype, lse [B,N,S] f32 — plus a
    per-shard obs.devstats.DevStats as a third element when `collect`.

    `collect` is a STATIC flag: every stats equation sits behind
    `if collect`, so the collect=False trace is bit-identical to a build
    without devstats at all (proved by burstlint `devstats-pure`).

    `seg` [B, S] int32 (optional): packed-sequence ids for the LOCAL shard,
    in the same layout order as q/k/v.  The kv-side ids ride the KV ring
    (one extra tiny int32 array in the rotating payload); the q-side ids
    stay resident.  Attention never crosses a segment boundary.
    """
    if cfg.block_diffusion is not None:
        return _bd_fwd(q, k, v, cfg)

    b, n, s, d = q.shape
    scale = cfg.scale if cfg.scale is not None else d**-0.5
    n_inter, n_intra = _sizes(cfg)
    wire = cfg.wire_dtype
    # obs.ring.* named scopes: the ring's ops under the part of the
    # schedule they serve, in the executable's op_names and so on the
    # profiler's timeline (docs/observability.md) — metadata only, no
    # equations
    with jax.named_scope("obs.ring.entry"):
        part_me = my_partition(cfg.intra_axis, cfg.inter_axis)

    def compute(st, kv_c, r):
        kv_part = partition_at_round(r, cfg.intra_axis, cfg.inter_axis)
        if wire is not None:
            # rescale-on-consume: dequantize the rotating payload to the
            # compute dtype BEFORE any tile math — the fp32 accumulation
            # below never sees the wire dtype
            if seg is not None:
                k8, ksc, v8, vsc, kvseg_c = kv_c
            else:
                k8, ksc, v8, vsc = kv_c
                kvseg_c = None
            k_c = wire_dequantize(k8, ksc, k.dtype)
            v_c = wire_dequantize(v8, vsc, v.dtype)
        elif seg is not None:
            k_c, v_c, kvseg_c = kv_c
        else:
            k_c, v_c = kv_c
            kvseg_c = None
        segs = None if seg is None else (seg, kvseg_c)
        s_kv = k_c.shape[2]
        if cfg.causal and cfg.case_split and cfg.layout == "zigzag" and s_kv == s:
            # structural split (reference burst_attn_interface.py:221-235).
            # Its third case, the own partition, is round 0 alone (peeled
            # below: a ring visits each partition once), so a round here is
            # one of two.  Both hand the tile the FULL arrays and the rows
            # the round covers (_round_tiles): no slice of k / v / the
            # state before the kernel, no update-slice after it
            half = s // 2
            (_, _, kv_first), (_, q_second, _) = _round_tiles(cfg, s, s_kv)

            def past_case(st):
                # kv's first half entirely in the local past: dense half-kv
                return _tile_fwd(cfg, q, k_c, v_c, *st, scale,
                                 full_spec(s, half), segments=segs,
                                 kv_range=kv_first)

            def future_case(st):
                # only the local q's second half attends (to all of kv)
                return _tile_fwd(cfg, q, k_c, v_c, *st, scale,
                                 full_spec(s - half, s_kv), segments=segs,
                                 q_range=q_second)

            return lax.cond(kv_part < part_me, past_case, future_case, st)
        if cfg.causal and cfg.case_split and cfg.layout == "striped" and s_kv == s:
            # every striped round is full-window causal (offset 0 or -1):
            # the triangular grid applies round-independently
            spec = round_spec(part_me, kv_part, s, s_kv, True, "striped")
            return _tile_fwd(cfg, q, k_c, v_c, *st, scale, spec,
                             triangular=True, segments=segs)
        spec = round_spec(part_me, kv_part, s, s_kv, cfg.causal, cfg.layout,
                          window=cfg.window)
        if cfg.layout == "contig" and cfg.causal:
            # contig-causal rings have provably dead rounds (futures; with a
            # window also everything beyond the band's reach): skip the
            # whole kernel launch, not just its blocks (ops/masks.spec_live).
            # Windowed LIVE rounds also take the BAND grid: every live
            # round's offset is a nonneg multiple of the chunk length
            # (delta = r*s, and blocks divide s or flash_fwd's ragged path
            # declines the grid), so delta ≡ 0 (mod bkv) and the band
            # alignment enumeration behind fwd_band_nb applies round-
            # independently — the kernel's _kv_jmin/_kv_jmax read the
            # traced offset.  The bwd side has been banded since round 3
            # (bwd_band_nbq in the rect fused sweep).
            band = cfg.window is not None
            return lax.cond(
                spec_live(spec, cfg.window),
                lambda st_: _tile_fwd(cfg, q, k_c, v_c, *st_, scale, spec,
                                      triangular=band, segments=segs),
                lambda st_: st_,
                st)
        return _tile_fwd(cfg, q, k_c, v_c, *st, scale, spec, segments=segs)

    def round_tally(r):
        # devstats (collect only): one round's (live, attended pairs) from
        # the UNIFORM mask spec — by construction the same attended set the
        # case-split branches compute (ops/masks.py module docstring), so
        # the tally is layout-exact without touching the kernels.
        kv_part = partition_at_round(r, cfg.intra_axis, cfg.inter_axis)
        sp_u = round_spec(part_me, kv_part, s, k.shape[2], cfg.causal,
                          cfg.layout, window=cfg.window)
        return (spec_live(sp_u, cfg.window).astype(jnp.int32),
                spec_pair_count(sp_u, s, k.shape[2], cfg.window))

    def tally_add(dv, r):
        live, pairs = round_tally(r)
        return dv[0] + live, dv[1] + pairs

    # Static round truncation (windowed single ring): round r's kv offset is
    # delta = r*s for r <= me and negative (future, dead) past that, so
    # every round >= r_live is dead on EVERY device — don't run them and
    # don't pay their kv permutes.  (The double ring keeps the per-round
    # lax.cond skip instead: its visit order interleaves inter hops, so the
    # live set is not a prefix.)
    r_live = _r_live(cfg, s, k.shape[2], n_inter, n_intra)

    if wire is None:
        kv = (k, v) if seg is None else (k, v, seg)
    else:
        # Quantize ONCE at ring entry with per-(batch, kv-head) scalar
        # scales (amax over the local (s, d) chunk): the KV payload rotates
        # unchanged, so quantize-at-entry is exactly quantize-on-send on
        # every hop.  The peeled self round below still reads the resident
        # full-precision k/v — only bytes that actually cross a link are
        # quantized.  The int32 seg ids ride unquantized.
        with jax.named_scope("obs.ring.entry"):
            k8, ksc = wire_quantize(k, wire, (2, 3))
            v8, vsc = wire_quantize(v, wire, (2, 3))
        kv = (k8, ksc, v8, vsc) if seg is None else (k8, ksc, v8, vsc, seg)
    kv_base = kv

    # Round 0 is ALWAYS the self round (partition_at_round(0) == part_me:
    # c = s = 0 in ring.py:81-94), so it is peeled out of the scan with a
    # STATICALLY EMPTY carry: the kernel seeds its state from constants
    # (flash_fwd m=lse=acc=None) instead of reading a materialized
    # init_state — the [B,N,S,D] f32 zeros accumulator never exists in
    # HBM.  Self-rounds are also exactly the full-window-causal specs the
    # triangular / band grids require, so every layout's round 0 gets the
    # all-live grid, including contig (whose later rounds are
    # offset-shifted and stay rectangular).
    segs0 = None if seg is None else (seg, seg)
    spec0 = round_spec(part_me, part_me, s, k.shape[2], cfg.causal,
                       cfg.layout, window=cfg.window)
    tri0 = cfg.causal and k.shape[2] == s
    with jax.named_scope("obs.ring.round0_self"):
        state = _tile_fwd(cfg, q, k, v, None, None, None, scale, spec0,
                          triangular=tri0, segments=segs0)
        if collect:
            # devstats accumulators ride NEXT TO the state; every touch is
            # behind `if collect` so the stats-off trace stays bit-identical
            dv = tally_add((jnp.int32(0), jnp.float32(0.0)), jnp.int32(0))
            rounds_exec = 1

    for c in range(n_inter):
        if c < n_inter - 1:
            # prefetch next cycle's base one full intra-cycle early
            # (reference: comm.py:229-237); consumed at the cycle boundary.
            kv_base_next = ppermute_next(kv_base, cfg.inter_axis)
        start = 1 if c == 0 else 0  # cycle 0's round 0 was peeled above
        if c == 0 and r_live == 1:
            # the peel was cycle 0's only live round; no intra permutes
            if c < n_inter - 1:
                kv = kv_base = kv_base_next
            continue
        if c == 0:
            kv = ppermute_next(kv, cfg.intra_axis)  # round-0 send
        if r_live - 1 > start:

            def body(carry, s_idx, c=c):
                if collect:
                    kv_c, st, dv_c = carry
                else:
                    kv_c, st = carry
                kv_next = ppermute_next(kv_c, cfg.intra_axis)  # overlaps compute
                st = compute(st, kv_c, c * n_intra + s_idx)
                if collect:
                    dv_c = tally_add(dv_c, c * n_intra + s_idx)
                    return (kv_next, st, dv_c), None
                return (kv_next, st), None

            with jax.named_scope(f"obs.ring.cycle{c}.scan_rounds"):
                if collect:
                    (kv, state, dv), _ = lax.scan(
                        body, (kv, state, dv), jnp.arange(start, r_live - 1))
                    rounds_exec += r_live - 1 - start
                else:
                    (kv, state), _ = lax.scan(body, (kv, state),
                                              jnp.arange(start, r_live - 1))
        # last round of the cycle: no intra send (reference comm.py:238-251)
        with jax.named_scope(f"obs.ring.cycle{c}.last_round"):
            state = compute(state, kv, jnp.int32(c * n_intra + r_live - 1))
            if collect:
                dv = tally_add(dv, jnp.int32(c * n_intra + r_live - 1))
                rounds_exec += 1
        if c < n_inter - 1:
            kv = kv_base = kv_base_next
    m, lse, acc = state
    with jax.named_scope("obs.ring.finalize"):
        o = jnp_tile.finalize(m, lse, acc, q.dtype)
    if collect:
        qam = jnp.float32(0.0)
        if wire is not None:
            # finite-range gauge: the largest |value| the wire quantizer
            # mapped to its top code this dispatch (saturating blocks show
            # up as a growing gauge, not silent clipping)
            qam = jnp.maximum(jnp.max(jnp.abs(k.astype(jnp.float32))),
                              jnp.max(jnp.abs(v.astype(jnp.float32))))
        stats = devstats.ring_stats(
            rounds=rounds_exec, rounds_live=dv[0], attn_pairs=dv[1],
            total_pairs=float(rounds_exec) * s * k.shape[2], head_dim=d,
            rounds_elided=n_inter * n_intra - rounds_exec,
            m=m, lse=lse, acc=acc, quant_absmax=qam)
        return o, lse, stats
    return o, lse


# ---------------------------------------------------------------------------
# backward


def _bwd_impl(cfg: BurstConfig, q, k, v, o, lse, do, seg=None):
    """Communication-optimized ring backward (SURVEY.md §3.2).

    K, V stay resident; the query-side payload (delta|o, do, q, lse) rotates
    like KV did in forward; dq rides a concurrent accumulating ring and is
    returned home by one extra hop (burst_attn_interface.py:255-398).
    With packed sequences (`seg`), the q-side ids rotate with the payload
    while the resident kv side keeps the local ids.
    """
    if cfg.block_diffusion is not None:
        return _bd_bwd(cfg, q, k, v, o, lse, do)

    b, n, s, d = q.shape
    scale = cfg.scale if cfg.scale is not None else d**-0.5
    n_inter, n_intra = _sizes(cfg)
    wire = cfg.wire_dtype
    # obs.ring.bwd.* named scopes, as the forward's: entry, each cycle's
    # first round, payload jump, scan and last round, the return home
    with jax.named_scope("obs.ring.bwd.delta"):
        part_me = my_partition(cfg.intra_axis, cfg.inter_axis)
        delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                        axis=-1)
        if cfg.optimize_bwd_comm:
            # ring payload shrinks by a factor of head_dim
            # (reference burst_attn_interface.py:269-278)
            payload = (delta, do, q, lse)
        else:
            payload = (o, do, q, lse)
        if seg is not None:
            payload = payload + (seg,)
        if wire is not None:
            # quantize the q-side bundle once at ring entry (it rotates
            # unchanged): per-(batch, head) scalar scales; lse stays fp32
            # (its absolute accuracy sets every softmax rescale downstream)
            first_p, do_p, q_p, lse_p = payload[:4]
            f8, fsc = wire_quantize(first_p, wire,
                                    (2,) if cfg.optimize_bwd_comm else (2, 3))
            do8, dosc = wire_quantize(do_p, wire, (2, 3))
            q8, qsc = wire_quantize(q_p, wire, (2, 3))
            payload = (f8, fsc, do8, dosc, q8, qsc, lse_p) + payload[4:]
        dq_intra = jnp.zeros(q.shape, jnp.float32)
        dq_inter = jnp.zeros(q.shape, jnp.float32)

    if wire is None:
        dq_hop = ppermute_next
    else:
        def dq_hop(g, axis):
            # the dq add-and-forward ring: quantize-before-send with a
            # REFRESHED per-(batch, head) scale (the partial grew by one
            # local contribution since the last hop), dequantize-after-
            # receive back to fp32 — the fold itself stays full precision
            g8, gsc = wire_quantize(g, wire, (2, 3))
            g8, gsc = ppermute_next((g8, gsc), axis)
            return wire_dequantize(g8, gsc, jnp.float32)

    dkv = None  # [dk, dv]: resident, folded by the tile round after round

    def compute(pay, r, dkv, own=False):
        """One round: (dq of this round, dk, dv with this round folded in).
        dkv = (dk, dv) of the rounds before, resident like k and v, or None
        ahead of the first.  The tile adds into it (in the kernel where
        _round_in_kernel says so); dq is NOT carried this way: it rides the
        ring, and a kernel that took the arriving partial as its carry
        would wait for the hop that today hides under it.  `own` (static):
        round 0, the one round whose payload is this device's own."""
        q_part = partition_at_round(r, cfg.intra_axis, cfg.inter_axis)
        # roles flip vs forward: the rotating payload is the query side,
        # local k/v are resident.
        if wire is not None:
            f8, fsc, do8, dosc, q8, qsc, lse_r = pay[:7]
            qseg_r = pay[7] if seg is not None else None
            first = wire_dequantize(
                f8, fsc, jnp.float32 if cfg.optimize_bwd_comm else o.dtype)
            do_r = wire_dequantize(do8, dosc, do.dtype)
            q_r = wire_dequantize(q8, qsc, q.dtype)
        elif seg is not None:
            first, do_r, q_r, lse_r, qseg_r = pay
        else:
            first, do_r, q_r, lse_r = pay
            qseg_r = None
        segs = None if seg is None else (qseg_r, seg)
        if cfg.optimize_bwd_comm:
            delta_r = first
        else:
            delta_r = jnp.sum(first.astype(jnp.float32) * do_r.astype(jnp.float32), axis=-1)
        if cfg.causal and cfg.case_split and cfg.layout == "zigzag":
            # structural split, bwd roles (reference :303-367): the own
            # partition in round 0 and there alone, one of two half-shard
            # cases in every other round.  Those as in the forward: full
            # arrays plus the rows the round covers, dk / dv written into
            # the carry's own blocks
            half = s // 2
            (_, _, kv_first), (_, q_second, _) = _round_tiles(cfg, s, s)
            if own:
                spec = round_spec(part_me, part_me, s, s, True, "zigzag")
                return _tile_bwd(cfg, do_r, q_r, k, v, delta_r, lse_r, scale,
                                 spec, triangular=True, segments=segs,
                                 carry=dkv)

            def kv_past_case(dkv):
                # resident kv precedes the rotated q side: only kv's first
                # half participates -> dense tile over those columns
                return _tile_bwd(cfg, do_r, q_r, k, v, delta_r, lse_r, scale,
                                 full_spec(s, half), segments=segs,
                                 kv_range=kv_first, carry=dkv)

            def q_future_case(dkv):
                # only the rotated q side's second half attends
                return _tile_bwd(cfg, do_r, q_r, k, v, delta_r, lse_r, scale,
                                 full_spec(s - half, s), segments=segs,
                                 q_range=q_second, carry=dkv)

            return lax.cond(part_me < q_part, kv_past_case, q_future_case,
                            dkv)
        if cfg.causal and cfg.case_split and cfg.layout == "striped":
            spec = round_spec(q_part, part_me, s, s, True, "striped")
            return _tile_bwd(cfg, do_r, q_r, k, v, delta_r, lse_r, scale, spec,
                             triangular=True, segments=segs, carry=dkv)
        # cross-attention (s_kv_local != s): the resident kv side's length
        # comes from k, not from the rotating q payload
        spec = round_spec(q_part, part_me, s, k.shape[2], cfg.causal,
                          cfg.layout, window=cfg.window)
        if cfg.layout == "contig" and cfg.causal:
            # dead-round skip, bwd roles (fwd comment above): a zero dq and
            # the carry as it came, without touching the kernels
            def dead(dkv):
                if dkv is None:
                    dkv = (jnp.zeros(k.shape, jnp.float32),
                           jnp.zeros(v.shape, jnp.float32))
                return (jnp.zeros((b, n, s, d), jnp.float32), *dkv)

            return lax.cond(
                spec_live(spec, cfg.window),
                lambda dkv: _tile_bwd(cfg, do_r, q_r, k, v, delta_r, lse_r,
                                      scale, spec, segments=segs, carry=dkv),
                dead, dkv)
        return _tile_bwd(cfg, do_r, q_r, k, v, delta_r, lse_r, scale, spec,
                         segments=segs, carry=dkv)

    # Static round truncation, bwd roles (fwd comment in _fwd_impl): with the
    # q side rotating, round r's offset is delta = -r*s (dead causally) for
    # r <= me and (W-r)*s past the wrap — so the LIVE rounds are round 0
    # plus a tail of r_live-1 rounds at the end of the schedule.  The
    # payload jumps the dead middle in ONE ppermute (an arbitrary
    # permutation costs one collective regardless of hop count) instead of
    # paying a q-sized transfer per dead round.  Round 0's dq (the OWN
    # chunk's gradient) does not ride along at all — a full circle would
    # return it exactly where it started — it is held out in dq_home and
    # folded in after the ring's return-home hop.
    r_live = _r_live(cfg, s, k.shape[2], n_inter, n_intra)
    truncated = r_live < n_intra
    dq_home = None

    pay_base = payload
    for c in range(n_inter):
        first = ("obs.ring.bwd.round0_self" if c == 0
                 else f"obs.ring.bwd.cycle{c}.first_round")
        with jax.named_scope(first):
            if c < n_inter - 1:
                pay_base_next = ppermute_next(pay_base, cfg.inter_axis)
            if c > 0:
                # cycle boundary: fold the intra accumulator into the
                # inter-ring running sum (add-and-forward, reference
                # comm.py:187-218) and restart the intra accumulator at zero.
                dq_inter = dq_hop(dq_inter + dq_intra, cfg.inter_axis)
                dq_intra = jnp.zeros_like(dq_intra)
            # ---- first round of the cycle (r = c*I): no dq rotation ----
            dqc, *dkv = compute(payload, jnp.int32(c * n_intra), dkv,
                                own=c == 0)
            if truncated:
                dq_home = dqc
            else:
                dq_intra = dq_intra + dqc
        if r_live > 1:
            # start == 1 without truncation; the jump is a single hop then.
            # dq_intra is still all-zero at the jump when truncated
            # (rotation-invariant), so only the payload travels.
            start = n_intra - (r_live - 1)
            with jax.named_scope(f"obs.ring.bwd.cycle{c}.send0"):
                payload = ppermute_by(payload, cfg.intra_axis, start)
            if n_intra - 1 > start:

                def body(carry, s_idx, c=c):
                    pay, dq_i, dkv_c = carry
                    pay_next = ppermute_next(pay, cfg.intra_axis)
                    # dq leaves with the payload it accumulated for; the
                    # arriving dq belongs to the payload we hold this round.
                    dq_rot = dq_hop(dq_i, cfg.intra_axis)
                    dqc, *dkv_c = compute(pay, c * n_intra + s_idx, dkv_c)
                    # the one full-size float32 add left in a round
                    return (pay_next, dq_rot + dqc, dkv_c), None

                with jax.named_scope(f"obs.ring.bwd.cycle{c}.scan_rounds"):
                    (payload, dq_intra, dkv), _ = lax.scan(
                        body, (payload, dq_intra, dkv),
                        jnp.arange(start, n_intra - 1)
                    )
            # ---- last round of the cycle: rotate dq but not the payload ----
            with jax.named_scope(f"obs.ring.bwd.cycle{c}.last_round"):
                dq_rot = dq_hop(dq_intra, cfg.intra_axis)
                dqc, *dkv = compute(payload,
                                    jnp.int32(c * n_intra + n_intra - 1), dkv)
                dq_intra = dq_rot + dqc
        if c < n_inter - 1:
            payload = pay_base = pay_base_next

    # final return-home hops (reference burst_attn_interface.py:391-396,
    # comm.py:206-216): fold, one inter hop, one intra hop; then the
    # held-out round-0 dq (truncated rings only — it never traveled).
    with jax.named_scope("obs.ring.bwd.return_home"):
        dq = dq_inter + dq_intra
        if cfg.inter_axis is not None:
            dq = dq_hop(dq, cfg.inter_axis)
        if r_live > 1:
            dq = dq_hop(dq, cfg.intra_axis)
        if dq_home is not None:
            dq = dq + dq_home
    dk, dv = dkv
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp


def burst_attn_shard(q, k, v, cfg: BurstConfig, segment_ids=None,
                     collect_stats: bool = False):
    """Burst attention on per-shard arrays — call inside shard_map.

    q: [B, N, S_local, D]; k, v: [B, Nk, Skv_local, D] (GQA when Nk < N;
    Skv_local != S_local is CROSS-attention — non-causal contig only).
    segment_ids: optional [B, S_local] int32 packed-sequence ids for the
    LOCAL shard, in the same layout order as q/k/v (use
    layouts.to_layout(ids, layout, world, axis=1) for zigzag/striped).
    Returns o: [B, N, S_local, D] in q.dtype — or (o, DevStats) with
    per-shard in-graph ring telemetry when `collect_stats`
    (obs/devstats.py; the stats ride the forward, gradients are untouched).
    """
    if q.shape[2] != k.shape[2] and (
            cfg.causal or cfg.window is not None or segment_ids is not None):
        # causal cross-lengths have no defined diagonal alignment (and the
        # zigzag/striped bwd case splits assume equal shards); the single
        # segment_ids array covers both sides only when lengths match.
        # Fail here, loudly — the fwd would otherwise run and the bwd die
        # inside a lax.cond with an opaque shape error.
        raise ValueError(
            f"cross-attention (s_q {q.shape[2]} != s_kv {k.shape[2]}) "
            "supports non-causal contig without segment_ids only")
    if collect_stats:
        if segment_ids is None:
            return _burst_attn_shard_stats(q, k, v, cfg)
        return _burst_attn_shard_stats_seg(q, k, v, segment_ids, cfg)
    if segment_ids is None:
        return _burst_attn_shard_plain(q, k, v, cfg)
    return _burst_attn_shard_seg(q, k, v, segment_ids, cfg)


def _grads_out(grads, q, k, v):
    """The backward's float32 (dq, dk, dv) in the inputs' dtypes."""
    with jax.named_scope("obs.ring.bwd.out"):
        return tuple(g.astype(x.dtype) for g, x in zip(grads, (q, k, v)))


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _burst_attn_shard_plain(q, k, v, cfg: BurstConfig):
    o, _ = _fwd_impl(q, k, v, cfg)
    return o


def _vjp_fwd(q, k, v, cfg):
    o, lse = _fwd_impl(q, k, v, cfg)
    return o, (q, k, v, o, lse)


def _vjp_bwd(cfg, residuals, do):
    q, k, v, o, lse = residuals
    return _grads_out(_bwd_impl(cfg, q, k, v, o, lse, do), q, k, v)


_burst_attn_shard_plain.defvjp(_vjp_fwd, _vjp_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _burst_attn_shard_seg(q, k, v, seg, cfg: BurstConfig):
    o, _ = _fwd_impl(q, k, v, cfg, seg=seg)
    return o


def _seg_vjp_fwd(q, k, v, seg, cfg):
    o, lse = _fwd_impl(q, k, v, cfg, seg=seg)
    return o, (q, k, v, seg, o, lse)


def _seg_vjp_bwd(cfg, residuals, do):
    import numpy as np

    q, k, v, seg, o, lse = residuals
    grads = _grads_out(_bwd_impl(cfg, q, k, v, o, lse, do, seg=seg), q, k, v)
    return *grads, np.zeros(seg.shape, dtype=jax.dtypes.float0)


_burst_attn_shard_seg.defvjp(_seg_vjp_fwd, _seg_vjp_bwd)


# stats-collecting twins: (o, DevStats) outputs, IDENTICAL backward.  The
# stats are forward-only telemetry — their cotangents are dropped and the
# residuals/bwd math are byte-for-byte the plain path's, so grads under
# collect_stats=True equal the plain grads bit-for-bit
# (tests/test_devstats.py asserts this on the 8-dev mesh).


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _burst_attn_shard_stats(q, k, v, cfg: BurstConfig):
    o, _, stats = _fwd_impl(q, k, v, cfg, collect=True)
    return o, stats


def _stats_vjp_fwd(q, k, v, cfg):
    o, lse, stats = _fwd_impl(q, k, v, cfg, collect=True)
    return (o, stats), (q, k, v, o, lse)


def _stats_vjp_bwd(cfg, residuals, cts):
    do, _dstats = cts  # stats are telemetry: cotangent ignored
    q, k, v, o, lse = residuals
    return _grads_out(_bwd_impl(cfg, q, k, v, o, lse, do), q, k, v)


_burst_attn_shard_stats.defvjp(_stats_vjp_fwd, _stats_vjp_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _burst_attn_shard_stats_seg(q, k, v, seg, cfg: BurstConfig):
    o, _, stats = _fwd_impl(q, k, v, cfg, seg=seg, collect=True)
    return o, stats


def _stats_seg_vjp_fwd(q, k, v, seg, cfg):
    o, lse, stats = _fwd_impl(q, k, v, cfg, seg=seg, collect=True)
    return (o, stats), (q, k, v, seg, o, lse)


def _stats_seg_vjp_bwd(cfg, residuals, cts):
    import numpy as np

    do, _dstats = cts
    q, k, v, seg, o, lse = residuals
    grads = _grads_out(_bwd_impl(cfg, q, k, v, o, lse, do, seg=seg), q, k, v)
    return *grads, np.zeros(seg.shape, dtype=jax.dtypes.float0)


_burst_attn_shard_stats_seg.defvjp(_stats_seg_vjp_fwd, _stats_seg_vjp_bwd)


# ---------------------------------------------------------------------------
# global-array wrapper


def _note_dispatch(cfg: BurstConfig, mesh, q_shape, k_shape, batch_axes,
                   head_axes, *, seg, d_v) -> None:
    """Record one ring dispatch in the obs registry (burst.dispatch /
    burst.inplace_rounds / flash.diag_tiles).  `seg`: whether the call
    carries segment ids; `d_v`: v's width.  The rounds and hops a step
    runs are read off the device trace by scope (`obs.ring.*`), not
    counted here.

    Host-boundary code: called from burst_attn BEFORE shard_map, never from
    inside the traced shard program (burstlint `obs-jit-safe`)."""

    def _size_of(axes):
        if axes is None:
            return 1
        axes = axes if isinstance(axes, (tuple, list)) else (axes,)
        prod = 1
        for a in axes:
            if a is not None:
                prod *= mesh.shape.get(a, 1)
        return prod

    n_intra = mesh.shape.get(cfg.intra_axis, 1)
    n_inter = (mesh.shape.get(cfg.inter_axis, 1)
               if cfg.inter_axis is not None else 1)
    world = n_inter * n_intra
    b_div, h_div = _size_of(batch_axes), _size_of(head_axes)
    q_local = (max(1, q_shape[0] // b_div), max(1, q_shape[1] // h_div),
               max(1, q_shape[2] // world), q_shape[3])
    k_local = (max(1, k_shape[0] // b_div), max(1, k_shape[1] // h_div),
               max(1, k_shape[2] // world), k_shape[3])
    _M_DISPATCH.inc(backend=cfg.backend, tile=cfg.backend,
                    d_qk=str(q_shape[3]), d_v=str(d_v))
    r_live = _r_live(cfg, q_local[2], k_local[2], n_inter, n_intra)
    rounds, _, _ = ring_round_counts(n_inter, n_intra, r_live)
    # of the rounds after the self round, which fold into their carry inside
    # the kernel: the tile entry's own static gate, per pass
    for pass_ in ("fwd", "bwd"):
        if rounds > 1:
            in_kernel = _round_in_kernel(cfg, pass_, q_local, k_local, d_v)
            _M_INPLACE.inc(rounds - 1, **{
                "pass": pass_, "path": "kernel" if in_kernel else "xla"})
    for (pass_, path), tiles in _diag_tiles(cfg, q_local, k_local, rounds,
                                            seg, d_v).items():
        _M_DIAG.inc(tiles, **{"pass": pass_, "path": path})


def _resolve_backend(backend: str) -> str:
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return backend


def burst_attn(
    q,
    k,
    v,
    *,
    mesh,
    seq_axes=("sp",),
    causal: bool = False,
    layout: str = "zigzag",
    scale: Optional[float] = None,
    backend: str = "auto",
    optimize_bwd_comm: bool = True,
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
    block_q_bwd: Optional[int] = None,
    block_kv_bwd: Optional[int] = None,
    batch_axes=None,
    head_axes=None,
    case_split: bool = True,
    window: Optional[int] = None,
    segment_ids=None,
    max_segment_len: Optional[int] = None,
    wire_dtype: Optional[str] = None,
    collect_stats: bool = False,
    block_diffusion: Optional[int] = None,
) -> jax.Array:
    """Burst attention on global arrays [B, N, S, D]; S must already be in
    layout order (parallel/layouts.to_layout) for causal runs.  v may have a
    width of its own (q, k [.., D], v and the result [.., Dv]: latent
    attention's 192 / 128); the default scale is D ** -0.5.

    seq_axes: mesh axis name(s) the sequence is sharded over — ("sp",) for a
    single ring or ("inter", "intra") for the hierarchical double ring.
    batch_axes / head_axes: mesh axis name(s) batch / heads are sharded over
    (data / tensor parallelism riding alongside the sequence ring — the
    reference's process_group mechanism, burst_attn_interface.py:144-145).
    segment_ids: optional [B, S] int32 packed-sequence ids (non-negative),
    permuted into the SAME layout order as the sequence; attention never
    crosses a segment boundary — the kv-side ids ride the KV ring.
    max_segment_len: optional PROMISE that no segment spans more than this
    many tokens (a contract, not a runtime check — see
    BurstConfig.max_segment_len); contig-causal single rings use it to
    statically elide ring rounds no segment can reach.
    wire_dtype: "int8" | "fp8" | None — quantize the ROTATING ring payloads
    (fwd K/V, bwd bundle, dq partials; lse exempt) with per-block fp32
    scales riding the same transport; None = bit-exact wire.  fp32
    accumulation is untouched; tests/test_wire_quant.py pins the tolerances.
    collect_stats: return `(o, obs.devstats.DevStats)` instead of `o` —
    in-graph ring telemetry with a leading per-device axis of length
    `world` (batch/head replica groups are pre-reduced in-graph).  Fold it
    into the host registry with `stats.publish()` AFTER the step; gradients
    through `o` are bit-identical to the collect_stats=False path.
    block_diffusion: the block length B of the block-diffusion training mask
    (ops/masks.bd_quadrants): the sequence is the stream [noised; clean] of
    one document, 2L tokens.  It replaces `causal`; one sequence shard only.
    """
    if isinstance(seq_axes, str):
        seq_axes = (seq_axes,)
    if len(seq_axes) == 1:
        inter_axis, intra_axis = None, seq_axes[0]
    elif len(seq_axes) == 2:
        inter_axis, intra_axis = seq_axes
    else:
        raise ValueError(f"seq_axes must have 1 or 2 names, got {seq_axes}")
    if block_diffusion is not None:
        world = 1
        for a in seq_axes:
            world *= mesh.shape.get(a, 1)
        if world > 1:
            raise ValueError(
                f"block_diffusion runs on one sequence shard, got {world} "
                f"over {seq_axes}: the ring has no layout yet that gives "
                "every shard noised rows AND the clean keys they read (a "
                "contiguous cut of [noised; clean] leaves half the shards "
                "without either), nor a round schedule over one")
        if segment_ids is not None or collect_stats:
            raise ValueError(
                "block_diffusion takes no segment_ids and collects no ring "
                "stats (one document a stream, no ring)")
    # window validation lives in BurstConfig.__post_init__ (constructed
    # below); the blocks stay as the caller gave them (None = unset): each
    # tile call resolves its own from its geometry (cfg.resolved_blocks)
    cfg = BurstConfig(
        causal=causal,
        layout=layout,
        scale=scale,
        intra_axis=intra_axis,
        inter_axis=inter_axis,
        backend=_resolve_backend(backend),
        optimize_bwd_comm=optimize_bwd_comm,
        block_q=block_q,
        block_kv=block_kv,
        block_q_bwd=block_q_bwd,
        block_kv_bwd=block_kv_bwd,
        case_split=case_split,
        window=window,
        max_segment_len=max_segment_len,
        wire_dtype=wire_dtype,
        block_diffusion=block_diffusion,
    )
    _note_dispatch(cfg, mesh, q.shape, k.shape, batch_axes, head_axes,
                   seg=segment_ids is not None, d_v=v.shape[3])
    seq_spec = seq_axes if len(seq_axes) > 1 else intra_axis
    spec = P(batch_axes, head_axes, seq_spec, None)
    if collect_stats:
        # stats come back stacked over the ring axis (leading axis length
        # world); batch/head replica groups are reduced IN-GRAPH so every
        # ring position reports one consistent value no matter how many
        # dp/tp shards ride alongside (devstats.cross_reduce)
        def _flat(axes):
            if axes is None:
                return ()
            axes = axes if isinstance(axes, (tuple, list)) else (axes,)
            return tuple(a for a in axes
                         if a is not None and mesh.shape.get(a, 1) > 1)

        extra_axes = _flat(batch_axes) + _flat(head_axes)
        stats_spec = jax.tree.map(
            lambda _: P(seq_spec), devstats.DevStats(*devstats.DevStats._fields))

        def run_stats(q, k, v, seg=None):
            o, st = burst_attn_shard(q, k, v, cfg, seg, collect_stats=True)
            # custom_vjp outputs look differentiable to an OUTER grad trace
            # (the in-call stop_gradient is opaque to it); re-severing here
            # keeps the pmax/pmin cross-reduce off the autodiff path
            st = jax.tree.map(lax.stop_gradient, st)
            st = devstats.cross_reduce(st, extra_axes)
            return o, devstats.expand_device_axis(st)

        if segment_ids is not None:
            seg_spec = P(batch_axes, seq_spec)
            fn = shard_map(
                run_stats, mesh=mesh,
                in_specs=(spec, spec, spec, seg_spec),
                out_specs=(spec, stats_spec), check_vma=False,
            )
            return fn(q, k, v, jnp.asarray(segment_ids, jnp.int32))
        fn = shard_map(
            run_stats, mesh=mesh, in_specs=(spec, spec, spec),
            out_specs=(spec, stats_spec), check_vma=False,
        )
        return fn(q, k, v)
    if segment_ids is not None:
        seg_spec = P(batch_axes, seq_spec)
        fn = shard_map(
            lambda q, k, v, seg: burst_attn_shard(q, k, v, cfg, seg),
            mesh=mesh,
            in_specs=(spec, spec, spec, seg_spec),
            out_specs=spec,
            check_vma=False,
        )
        return fn(q, k, v, jnp.asarray(segment_ids, jnp.int32))
    fn = shard_map(
        partial(burst_attn_shard, cfg=cfg),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


# ---------------------------------------------------------------------------
# reference-style aliases (burst_attn_interface.py:109-158 parity)


def burst_attn_func(
    q,
    k,
    v,
    softmax_scale=None,
    flash: str = "auto",
    causal: bool = False,
    optimize_bwd_comm: bool = True,
    deterministic: bool = True,
    *,
    mesh,
    seq_axes=("sp",),
):
    """Reference-parity entry point: zigzag-half causal layout.

    `flash` selects the tile backend ("auto" | "pallas" | "jnp"), replacing
    the reference's "cuda"/"triton"/math switch.  `deterministic` is accepted
    for parity; the TPU path is always deterministic.
    """
    del deterministic
    return burst_attn(
        q, k, v, mesh=mesh, seq_axes=seq_axes, causal=causal, layout="zigzag",
        scale=softmax_scale, backend=flash, optimize_bwd_comm=optimize_bwd_comm,
    )


def burst_attn_func_striped(
    q,
    k,
    v,
    softmax_scale=None,
    flash: str = "auto",
    causal: bool = False,
    optimize_bwd_comm: bool = True,
    deterministic: bool = True,
    *,
    mesh,
    seq_axes=("sp",),
):
    """Reference-parity entry point: striped causal layout
    (burst_attn_interface.py:109, OpBurstAttnStrip)."""
    del deterministic
    return burst_attn(
        q, k, v, mesh=mesh, seq_axes=seq_axes, causal=causal, layout="striped",
        scale=softmax_scale, backend=flash, optimize_bwd_comm=optimize_bwd_comm,
    )
