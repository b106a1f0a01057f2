"""Fused on-device ring attention: the whole R-round forward ring in ONE
Pallas kernel, with neighbor KV rotation done by in-kernel inter-chip RDMA
(`pltpu.make_async_remote_copy`) instead of per-round `lax.ppermute`
collectives between per-round kernel launches.

Why.  The scan-based ring (parallel/burst._fwd_impl) realizes BurstAttention's
comm/compute overlap as "XLA hopefully schedules the async collective-permute
behind the next round's pallas_call" — every round pays a kernel relaunch plus
an XLA collective boundary, and the overlap is a compiler scheduling outcome,
not a property of the program.  Here the overlap is owned by the kernel by
construction.

Schedule IR.  Since the schedule-compiler refactor this kernel contains NO
topology logic of its own: it interprets a compiled `RingProgram`
(parallel/schedule.py), delivered as an int32 scalar-prefetch table whose
per-round rows say which (bank, slot) compute consumes, whether its recv
semaphores must be awaited, which send channels fire (src bank/slot, dst
slot), and the per-slot capacity-credit ops.  One kernel body therefore runs
every topology the compiler can emit:

  uni     the classic single ring (one slot bank, chunks travel W-1 cw hops)
  bidi    counter-rotating bidirectional ring: chunks for offsets
          1..ceil((W-1)/2) arrive clockwise, 1..floor((W-1)/2) counter-
          clockwise, interleaved — per-DIRECTION slot banks and DMA
          semaphores, both ICI directions carrying traffic concurrently,
          and every transfer gets TWO rounds of compute to hide under.
  double  the hierarchical double ring: the next cycle's base chunk leaves
          on the inter channel ONE FULL INTRA-CYCLE before its consume,
          into a dedicated prefetch bank — BurstAttention's signature
          trick, previously scan-only.  Runs on a two-axis
          ("inter", "intra") mesh or factored onto a flat ring axis.

Every program is simulation-proven by burstlint before trust (analysis/
oracle.verify_ring_program: delivery of the declared rotation, exactly-once
consumption, per-slot overwrite-before-read safety against a maximally-
ahead sender, prefetch distance >= one intra cycle).

Slot choreography per round (first grid step): wait the consume slot's recv
semaphores if the table says a chunk landed remotely, then start every
flagged channel send — the transfer is in flight for the entire round-r
compute sweep.  Capacity credits are PER SLOT (`free` is a semaphore array
per bank): a send whose dst slot is being reused takes that slot's credit;
the slot's last reader granted it to the bank's writer at its own round
end.  Multi-axis meshes are safe because every RDMA target is a full
LOGICAL device id computed from ALL mesh axis indices with only the ring
coordinate varied (parallel/ring.device_roles) — extra pp/tp/dp axes can
never alias ring traffic.

Compute path.  Per grid step (r, b, h, i) the kernel folds q-block i against
the WHOLE resident KV chunk: the chunk is copied HBM-slot -> VMEM once per
(round, batch, kv-head) and every q-block sweeps it from VMEM.  m/l row
stats live VMEM-resident for the entire kernel (packed [B, N, S/lp, lp]),
the [bq, D] f32 accumulator round-trips an HBM scratch between rounds with
the load overlapped, rounds merge split-k style.  Masks reuse the SAME
per-round `ops/masks.round_spec` scalars the scan ring computes — the
partition each round holds comes from the program's rotation schedule.

Interpret mode.  jax's dma_start discharge rule emulates remote copies over
a single named mesh axis, so THIS kernel — same banks, same compiled
schedule, same masks — runs on a simulated CPU mesh (tests/
test_fused_ring.py, tests/test_fused_topologies.py; double-ring schedules
run factored onto the flat axis there).  Remote semaphore signals are not
emulated, so the capacity handshake and the startup barrier are statically
gated on `interpret`; a TWO-axis mesh cannot be discharged at all, which is
why `supported` declines multi-axis/two-axis-double configs in interpret
mode only — on hardware they run fused.

The BACKWARD has its own kernel (ops/fused_ring_bwd.py) interpreting the
compiled backward program, gated by the same predicate with pass_="bwd".
"""

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.lax import axis_size

from .masks import live_round_prefix, round_spec, spec_live, spec_pair_count
from .pallas_flash import (
    LN2,
    LOG2E,
    NEG_INF,
    VMEM_LIMIT,
    _block_full,
    _block_has_work,
    _block_mask,
    _pick_block,
    _seg_uniform_eq,
    _spec_array,
    _unpack,
)
from .tuning import resolve_fused
from ..parallel import schedule as sched_ir
from ..parallel.ring import device_roles, ring_coords, wire_quantize

# barrier-semaphore namespace for the startup neighbor barrier; any stable
# id distinct from other collective pallas kernels in the same program works
_COLLECTIVE_ID = 13


def interpret_enabled() -> bool:
    """BURST_FUSED_INTERPRET=1 lets `backend="fused_ring"` run the REAL fused
    kernel under the pallas interpreter off-TPU (the parity tests set this);
    default off-TPU behavior is the scan-ring fallback, because the
    interpreted ring is orders of magnitude slower than the jnp scan path."""
    return os.environ.get("BURST_FUSED_INTERPRET", "").strip().lower() not in (
        "", "0", "false")


def hw_trace_forced() -> bool:
    """BURST_FUSED_ASSUME_TPU=1 makes the dispatch/kernels TRACE the
    hardware program off-TPU (full semaphore choreography, no interpret
    gate) — for burstlint's structural checks of topologies the interpret
    discharge cannot execute (two-axis double rings, multi-axis meshes).
    Tracing never runs the program; executing such a trace off-TPU fails."""
    return os.environ.get("BURST_FUSED_ASSUME_TPU", "").strip().lower() not in (
        "", "0", "false")


def _extra_named_axes(intra_axis: str, inter_axis=None):
    """Other size>1 named axes bound in the current trace (shard_map scope).

    Ring traffic addresses neighbors by LOGICAL device id; with extra
    partitioned axes that id must be computed from every axis index
    (parallel/ring.device_roles), which needs the mesh's axis order — so
    the gate below requires `cfg.mesh_axes` whenever this returns a
    non-empty list.  Returns None when the axis-env API is unavailable
    (reported as its own distinct reason, not as a multi-axis decline)."""
    try:
        from jax._src.core import get_axis_env

        sizes = dict(get_axis_env().axis_sizes)
    except Exception:  # noqa: BLE001 — private-API probe; absence != error
        return None
    skip = {intra_axis, inter_axis}
    return [a for a, sz in sizes.items()
            if a is not None and a not in skip and sz and sz > 1]


def resolve_topology(cfg, n_intra: int, n_inter: int = 1):
    """(topology, n_inter, n_intra) the fused kernels will run for cfg.

    A real inter axis (or cfg.fused_seq_factor on a flat ring) selects the
    double ring; `fused_topology="bidi"` opts the flat ring into the
    counter-rotating schedule (worlds < 3 degrade to uni — there is no
    second direction to use); default is uni."""
    if cfg.fused_seq_factor is not None:
        f_i, f_s = cfg.fused_seq_factor
        if n_inter > 1:
            raise ValueError("fused_seq_factor is for flat ring axes; this "
                             "config already has an inter axis")
        if f_i * f_s != n_intra:
            raise ValueError(
                f"fused_seq_factor {cfg.fused_seq_factor} does not tile the "
                f"ring axis ({n_intra} devices)")
        return ("double", f_i, f_s) if f_i > 1 else ("uni", 1, n_intra)
    if n_inter > 1:
        return "double", n_inter, n_intra
    topo = cfg.fused_topology
    if topo in ("auto", "uni"):
        return "uni", 1, n_intra
    if topo == "bidi":
        return ("bidi" if n_intra >= 3 else "uni"), 1, n_intra
    if topo == "double":
        # double requested without an inter axis or factor: nothing to nest
        return "uni", 1, n_intra
    raise ValueError(f"unknown fused_topology {topo!r}")


def occupancy_r_live(cfg, world: int, s):
    """Static live-round prefix the occupancy compiler should truncate the
    schedule to, or None for a dense program.  Windowed and length-bounded
    packed-segment contig-causal rings have a closed-form live set
    {0..r_live-1} (masks.live_round_prefix); handing it to the schedule
    compiler ELIDES the dead rounds outright — no RDMA, no KV sweep, no
    slot traffic.  `s` is the per-shard sequence length (None when the
    caller has no shape in hand, e.g. a shape-free structural probe)."""
    seg_l = getattr(cfg, "max_segment_len", None)
    if s is None or (cfg.window is None and seg_l is None):
        return None
    r_live = live_round_prefix(cfg.layout, s, world, causal=cfg.causal,
                               window=cfg.window, max_segment_len=seg_l)
    return None if r_live >= world else r_live


def _compile_for(cfg, topology: str, n_inter: int, n_intra: int,
                 pass_: str = "fwd", s=None):
    rf = resolve_fused(cfg.fused_block_q, cfg.fused_block_kv,
                       cfg.fused_kv_slots,
                       block_q_bwd=getattr(cfg, "fused_block_q_bwd", None),
                       block_kv_bwd=getattr(cfg, "fused_block_kv_bwd", None),
                       bwd_slots=getattr(cfg, "fused_bwd_slots", None),
                       ccw_slots=getattr(cfg, "fused_ccw_slots", None),
                       bwd_ccw_slots=getattr(cfg, "fused_bwd_ccw_slots",
                                             None),
                       wire_dtype=getattr(cfg, "wire_dtype", None))
    r_live = occupancy_r_live(cfg, n_inter * n_intra, s)
    # the program carries the wire dtype so expected_remote_dma and the
    # byte accounting describe the SAME transfers the kernels emit (each
    # quantized operand send fires a second remote copy for its scale)
    if pass_ == "fwd":
        return sched_ir.compile_fwd(topology, n_intra, n_inter,
                                    slots=rf.kv_slots, slots1=rf.ccw_slots,
                                    r_live=r_live, wire=rf.wire_dtype)
    return sched_ir.compile_bwd(topology, n_intra, n_inter,
                                slots=rf.bwd_slots, slots1=rf.bwd_ccw_slots,
                                dq_slots=rf.bwd_slots, r_live=r_live,
                                wire=rf.wire_dtype)


def supported(cfg, q_shape, k_shape, has_segments: bool, *,
              interpret=None, world=None, extra_axes=None, n_inter=None,
              pass_="fwd"):
    """None if the fused ring can run this config, else a reason string the
    dispatch logs / the tests assert on.  By default must be called at
    trace time (inside shard_map) — the axis-env and mesh-size probes read
    the trace context.  Passing `world` (ring axis size), `n_inter`
    (inter axis size) and `extra_axes` (other partitioned mesh axes)
    explicitly makes the predicate host-callable with PER-SHARD shapes:
    the obs dispatch instrumentation (parallel/burst._note_dispatch)
    evaluates the same gate the traced dispatch runs, so the
    `burst.dispatch`/`burst.fused_fallback` counters cannot drift from the
    real decision logic.

    `pass_` ("fwd" | "bwd") selects which kernel's gate to evaluate: the
    structural constraints are shared, but each pass has its own blocks and
    VMEM plan, so a shard can be fused in one pass and fall back in the
    other — parallel/burst._bwd_impl runs this with pass_="bwd" at its
    single dispatch point."""
    if pass_ not in ("fwd", "bwd"):
        raise ValueError(f"pass_ must be 'fwd' or 'bwd', got {pass_!r}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu" and not hw_trace_forced()
    if interpret and not interpret_enabled():
        return "off-TPU (set BURST_FUSED_INTERPRET=1 to run interpreted)"
    # sliding window and packed segments are fused configs since the
    # occupancy compiler: the window is a static band the sweeps predicate
    # on, segment ids ride a gathered side table, and dead rounds are
    # ELIDED from the program — the only windowed decline left is the
    # degenerate r_live == 1 bwd (via the schedule-compiler probe below)
    b, n, s, d = q_shape
    if k_shape[2] != s:
        return "cross-attention shard lengths"
    if world is None:
        world = axis_size(cfg.intra_axis)
    if n_inter is None:
        if cfg.inter_axis is None:
            n_inter = 1
        else:
            try:
                n_inter = axis_size(cfg.inter_axis)
            except Exception:  # noqa: BLE001 — axis not bound in this trace
                return (f"double ring inter axis {cfg.inter_axis!r} is not "
                        "bound in this trace")
    if world * n_inter < 2:
        return "world < 2 (nothing to rotate)"
    try:
        topology, t_inter, t_intra = resolve_topology(cfg, world, n_inter)
    except ValueError as e:
        return f"topology config invalid: {e}"
    if interpret and cfg.inter_axis is not None and n_inter > 1:
        # jax's dma_start discharge emulates a single named axis only; the
        # two-axis double ring runs fused on hardware (or factored onto a
        # flat axis in tests) but must decline under emulation
        return ("interpret-mode remote DMA is single-axis (two-axis double "
                "ring runs fused on hardware)")
    extra = _extra_named_axes(cfg.intra_axis, cfg.inter_axis) \
        if extra_axes is None else list(extra_axes)
    if extra is None:
        # distinct from the multi-axis decline: the axis env could not be
        # probed at all, so ring isolation is unprovable — misattributing
        # this as "multi-axis" would skew the fallback counters
        return "axis env unavailable (cannot prove ring isolation)"
    if extra:
        if interpret:
            return ("interpret-mode remote DMA is single-axis (multi-axis "
                    "mesh runs fused on hardware)")
        mesh_names = {a for a, _ in (cfg.mesh_axes or ())}
        missing = [a for a in extra if a not in mesh_names]
        if missing:
            return (f"ring axis must be the only partitioned axis in scope "
                    f"(found {extra}; pass mesh_axes via burst_attn to "
                    "prove ring isolation)")
    try:
        prog = _compile_for(cfg, topology, t_inter, t_intra, pass_, s=s)
    except sched_ir.ScheduleError as e:
        return f"schedule compiler declined: {e}"
    rf = resolve_fused(cfg.fused_block_q, cfg.fused_block_kv,
                       cfg.fused_kv_slots,
                       block_q_bwd=getattr(cfg, "fused_block_q_bwd", None),
                       block_kv_bwd=getattr(cfg, "fused_block_kv_bwd", None),
                       bwd_slots=getattr(cfg, "fused_bwd_slots", None),
                       ccw_slots=getattr(cfg, "fused_ccw_slots", None),
                       bwd_ccw_slots=getattr(cfg, "fused_bwd_ccw_slots",
                                             None),
                       wire_dtype=getattr(cfg, "wire_dtype", None))
    del prog
    wi = rf.wire_itemsize  # rotating-payload tiles: 1 B/elem when quantized
    if pass_ == "bwd":
        # VMEM plan, bwd roles: resident k+v chunk, fp32 dk/dv accumulators,
        # the per-step bundle tiles (q, do, delta|o, lse, arriving dq, local
        # dq, inter-held dq) — 4-byte worst case (rotating tiles priced at
        # the wire itemsize), so an oversized shard falls back instead of
        # failing Mosaic allocation mid-ring
        bqb = _pick_block(s, rf.block_q_bwd)
        vmem = 2 * s * d * 4 + 2 * s * d * 4 + 3 * bqb * d * wi \
            + 4 * bqb * d * 4
        if vmem > rf.vmem_budget:
            return (f"VMEM plan {vmem} bytes exceeds fused budget "
                    f"{rf.vmem_budget} (bwd)")
        return None
    # VMEM plan: resident k+v chunk (wire itemsize — they arrive over the
    # ring), packed m/l stats, acc staging — counted against the
    # per-generation budget so an oversized shard falls back instead of
    # failing Mosaic allocation mid-ring
    bq = _pick_block(s, rf.block_q)
    vmem = 2 * s * d * wi + 2 * b * n * s * 4 + 3 * bq * d * 4
    if vmem > rf.vmem_budget:
        return (f"VMEM plan {vmem} bytes exceeds fused budget "
                f"{rf.vmem_budget}")
    return None


# ---------------------------------------------------------------------------
# packed m/l stats access ([B, N, S/lp, lp] refs — pallas_flash's packed
# layout with explicit (batch, head) indices instead of pre-blocked refs)


def _stat_read(ref, b_, h, i, bq, lp):
    """Rows [i*bq, (i+1)*bq) of a packed [B, N, S/lp, lp] stats ref -> (bq, 1)."""
    rows = bq // lp
    pack = ref[b_, h, pl.ds(i * rows, rows), :]
    if lp == 1:
        return pack
    rep = jnp.repeat(pack, lp, axis=0)  # (bq, lp); row t = pack[t // lp]
    t_lane = jax.lax.broadcasted_iota(jnp.int32, (bq, lp), 0) % lp
    c_idx = jax.lax.broadcasted_iota(jnp.int32, (bq, lp), 1)
    return jnp.sum(jnp.where(t_lane == c_idx, rep, 0.0), axis=1, keepdims=True)


def _stat_write(ref, b_, h, i, col, bq, lp):
    rows = bq // lp
    ref[b_, h, pl.ds(i * rows, rows), :] = jnp.reshape(col, (rows, lp))


def dma_sem_wait(sem_view, ref):
    """Retire one completed DMA on a DMA semaphore: `tpu.wait_dma` with the
    transfer-sized ref (descriptor form — `pltpu.semaphore_wait` only
    admits REGULAR/barrier semaphore avals at trace time, so a DMA-sem
    wait spelled that way traces under the interpreter's int16 stand-in
    but fails the hardware trace; burstlint's BURST_FUSED_ASSUME_TPU
    census caught exactly that).  The ref must cover the same elements as
    the transfer(s) being retired — wait_dma blocks until the semaphore
    holds the ref's size, then decrements by it, which is also what the
    interpret discharge rules do (dma_start adds sizes, dma_wait
    subtracts them)."""
    pltpu.make_async_copy(ref, ref, sem_view).wait()


# ---------------------------------------------------------------------------
# static program views the kernel codegen branches on


def kernel_statics(prog):
    """The compiled program's static structure: which banks are consumed,
    which channels send (and from which src banks), where credits flow.
    Python-level — this decides which code the kernel EMITS, so the traced
    program (and burstlint's remote-DMA census, schedule.expected_remote_
    dma) is a function of the program alone."""
    rows = prog.rows
    R = prog.n_rounds
    consume_banks = tuple(sorted({rows["consume_bank"][r] for r in range(R)}))
    ch_active = tuple(ch for ch in range(prog.n_banks)
                      if any(rows[f"send{ch}"][r] for r in range(R)))
    src_banks0 = tuple(sorted({rows["src_bank0"][r] for r in range(R)
                               if rows["send0"][r]})) or (0,)
    grant_banks = tuple(b for b in range(prog.n_banks)
                        if any(rows[f"grant{b}"][r] for r in range(R)))
    take_chs = tuple(ch for ch in ch_active
                     if any(rows[f"take{ch}"][r] for r in range(R)))
    return dict(consume_banks=consume_banks, ch_active=ch_active,
                src_banks0=src_banks0, grant_banks=grant_banks,
                take_chs=take_chs)


_SENDC = {0: (sched_ir.SEND0, sched_ir.SRC_SLOT0, sched_ir.DST_SLOT0,
              sched_ir.TAKE0, sched_ir.META_CH0_DST),
          1: (sched_ir.SEND1, sched_ir.SRC_SLOT1, sched_ir.DST_SLOT1,
              sched_ir.TAKE1, sched_ir.META_CH1_DST)}
_GRANTC = {0: (sched_ir.GRANT0, sched_ir.META_CH0_SRC),
           1: (sched_ir.GRANT1, sched_ir.META_CH1_SRC)}


# ---------------------------------------------------------------------------
# kernel


def _fused_fwd_kernel(
    sched_ref,
    q_ref, k_hbm, v_hbm,
    *rest,
    prog, statics, scale, bq, bkv, lp, nqb, nkb, group, n_b, n_h, hw_sync,
    collect, wnd, has_seg, wire,
):
    """One grid step = q-block i of head h, batch b_, ring round r.

    sched_ref is the [R + 1, FWD_COLS] prefetch table: rows 0..R-1 hold the
    per-round mask scalars (cols 0..4, ops/masks.round_spec) plus the
    compiled program's op columns (parallel/schedule.py col constants);
    row R holds the traced neighbor ids (META_* slots).

    `collect` (static) appends one more OUTPUT before the scratch refs: a
    [n_banks, max_slots] int32 SMEM array counting, per (bank, slot), how
    many rounds consumed a chunk there — the devstats slot-reuse counter
    with its per-direction rows (obs/devstats.py, dir=cw|ccw labels).
    Pure scalar writes at round boundaries; the compute/DMA choreography
    is untouched, so stats-off and stats-on kernels produce bit-identical
    o/lse.

    Semaphore ledger (everything drains to zero; DMA sems count transfer
    sizes — dma_sem_wait retires a slot-sized transfer):
      krecv/vrecv[bank][slot]  +1 transfer per arriving send, -1 at the
                               consuming round's first grid step
      ksend/vsend[bank][slot]  +1 transfer per outgoing send (by dst
                               slot), -1 at the same round's last grid
                               step (drain)
      free[bank][slot] (hw)    per-SLOT capacity credit: the slot's last
                               reader signals the bank's writer (GRANT
                               column = slot + 1); a send whose TAKE flag
                               is set waits its dst slot's credit first.
                               Grants emitted == takes consumed, per slot
                               (compiler-checked, oracle-proven).
    """
    R = prog.n_rounds
    n_banks = prog.n_banks
    rest = list(rest)
    # remaining positional refs: [kscale, vscale] inputs when wire, [segq,
    # sega] inputs when has_seg, then the two outputs, the optional stats
    # output, then the scratch refs
    if wire is not None:
        ksc_hbm = rest.pop(0)    # [B, Nk, 1, 1] f32 per-chunk scales
        vsc_hbm = rest.pop(0)
    if has_seg:
        segq_ref = rest.pop(0)   # [1, s, 1] VMEM block: LOCAL segment ids
        sega_hbm = rest.pop(0)   # [B, world, 1, s] ANY: every shard's ids
    o_ref = rest.pop(0)
    lse_ref = rest.pop(0)
    if collect:
        slot_use_ref = rest.pop(0)
    kbufs, vbufs = [], []
    for _ in range(n_banks):
        kbufs.append(rest.pop(0))
        vbufs.append(rest.pop(0))
    kscbufs, vscbufs = [], []
    if wire is not None:
        # fp32 scale sub-banks: same slot indices, same send/recv
        # semaphores and capacity credits as the payload banks they scale —
        # the schedule grows no new columns for them
        for _ in range(n_banks):
            kscbufs.append(rest.pop(0))
            vscbufs.append(rest.pop(0))
    kchunk = rest.pop(0)
    vchunk = rest.pop(0)
    if wire is not None:
        ksc_t = rest.pop(0)      # VMEM (1, 1) f32 per-chunk scale tiles
        vsc_t = rest.pop(0)
    (mstat, lstat, accbuf, acc_in, acc_scr, m_sw, l_sw,
     cp_sem, chunk_sem, acc_sem) = rest[:10]
    rest = rest[10:]
    ksend, krecv, vsend, vrecv, free = [], [], [], [], []
    for _ in range(n_banks):
        ksend.append(rest.pop(0))
        krecv.append(rest.pop(0))
        vsend.append(rest.pop(0))
        vrecv.append(rest.pop(0))
        free.append(rest.pop(0))
    if has_seg:
        segbuf = rest.pop(0)     # VMEM (1, s) int32: this round's kv ids
        seg_sem = rest.pop(0)

    r = pl.program_id(0)
    b_ = pl.program_id(1)
    h = pl.program_id(2)
    i = pl.program_id(3)
    bank = sched_ref[r, sched_ir.CONSUME_BANK]
    slot = sched_ref[r, sched_ir.CONSUME_SLOT]
    first_of_round = (b_ == 0) & (h == 0) & (i == 0)
    last_of_round = (b_ == n_b - 1) & (h == n_h - 1) & (i == nqb - 1)

    if collect:
        @pl.when(first_of_round)
        def _slot_tally():
            # devstats slot-reuse counter: zero once at round 0, then one
            # scalar SMEM increment per round for the (bank, slot) consumed
            @pl.when(r == 0)
            def _zero():
                for bb in range(slot_use_ref.shape[0]):
                    for j in range(slot_use_ref.shape[1]):
                        slot_use_ref[bb, j] = 0

            slot_use_ref[bank, slot] = slot_use_ref[bank, slot] + 1

    # ---- round choreography (first grid step of the round only) ----
    @pl.when(first_of_round & (r == 0))
    def _copy_in():
        # local chunk -> its program-designated slot(s): one HBM->HBM copy
        # per bank the schedule launches from, so every later round
        # (compute reads, RDMA sends) addresses the banks uniformly
        cps = []
        per = 2 if wire is None else 4
        for idx, (cb, cslot) in enumerate(prog.copy_in):
            cps.append(pltpu.make_async_copy(k_hbm, kbufs[cb].at[cslot],
                                             cp_sem.at[per * idx]))
            cps.append(pltpu.make_async_copy(v_hbm, vbufs[cb].at[cslot],
                                             cp_sem.at[per * idx + 1]))
            if wire is not None:
                cps.append(pltpu.make_async_copy(
                    ksc_hbm, kscbufs[cb].at[cslot], cp_sem.at[per * idx + 2]))
                cps.append(pltpu.make_async_copy(
                    vsc_hbm, vscbufs[cb].at[cslot], cp_sem.at[per * idx + 3]))
        for c in cps:
            c.start()
        for c in cps:
            c.wait()

    if hw_sync:
        @pl.when(first_of_round & (r == 0))
        def _barrier():
            # every RDMA peer must have entered the kernel (buffers live)
            # before any send targets its slots
            bar = pltpu.get_barrier_semaphore()
            n_sig = 0
            for ch in statics["ch_active"]:
                _, _, _, _, meta_dst = _SENDC[ch]
                pltpu.semaphore_signal(
                    bar, inc=1, device_id=sched_ref[R, meta_dst],
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
                pltpu.semaphore_signal(
                    bar, inc=1, device_id=sched_ref[R, _GRANTC[ch][1]],
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
                n_sig += 2
            pltpu.semaphore_wait(bar, n_sig)

    @pl.when(first_of_round & (sched_ref[r, sched_ir.RECV] == 1))
    def _recv_wait():
        # round r's chunk must have LANDED in its slot before compute or
        # the onward send may read it
        for b in statics["consume_banks"]:
            @pl.when(bank == b)
            def _wait_bank(b=b):
                dma_sem_wait(krecv[b].at[slot], kbufs[b].at[slot])
                dma_sem_wait(vrecv[b].at[slot], vbufs[b].at[slot])
                if wire is not None:
                    # the scale sub-payloads ride the SAME recv semaphores:
                    # retire the payload-sized transfer first, then the
                    # scale-sized one — the sem drains to zero either way
                    dma_sem_wait(krecv[b].at[slot], kscbufs[b].at[slot])
                    dma_sem_wait(vrecv[b].at[slot], vscbufs[b].at[slot])

    for ch in statics["ch_active"]:
        send_c, src_c, dst_c, take_c, meta_dst = _SENDC[ch]

        @pl.when(first_of_round & (sched_ref[r, send_c] == 1))
        def _send_onward(ch=ch, send_c=send_c, src_c=src_c, dst_c=dst_c,
                         take_c=take_c, meta_dst=meta_dst):
            dst_slot = sched_ref[r, dst_c]
            src_slot = sched_ref[r, src_c]
            dst_dev = sched_ref[R, meta_dst]
            if hw_sync and ch in statics["take_chs"]:
                @pl.when(sched_ref[r, take_c] == 1)
                def _capacity():
                    # dst slot is being reused: take ITS credit, granted by
                    # the receiver after the slot's previous last read
                    pltpu.semaphore_wait(free[ch].at[dst_slot], 1)

            def _emit(sb):
                sk = pltpu.make_async_remote_copy(
                    src_ref=kbufs[sb].at[src_slot],
                    dst_ref=kbufs[ch].at[dst_slot],
                    send_sem=ksend[ch].at[dst_slot],
                    recv_sem=krecv[ch].at[dst_slot],
                    device_id=dst_dev,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
                sv = pltpu.make_async_remote_copy(
                    src_ref=vbufs[sb].at[src_slot],
                    dst_ref=vbufs[ch].at[dst_slot],
                    send_sem=vsend[ch].at[dst_slot],
                    recv_sem=vrecv[ch].at[dst_slot],
                    device_id=dst_dev,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
                sk.start()
                sv.start()
                if wire is not None:
                    # quantize-on-send is free here — the payload banks hold
                    # wire-dtype data end to end; each operand's scale rides
                    # as a second remote copy on the SAME sem pair
                    ssk = pltpu.make_async_remote_copy(
                        src_ref=kscbufs[sb].at[src_slot],
                        dst_ref=kscbufs[ch].at[dst_slot],
                        send_sem=ksend[ch].at[dst_slot],
                        recv_sem=krecv[ch].at[dst_slot],
                        device_id=dst_dev,
                        device_id_type=pltpu.DeviceIdType.LOGICAL)
                    ssv = pltpu.make_async_remote_copy(
                        src_ref=vscbufs[sb].at[src_slot],
                        dst_ref=vscbufs[ch].at[dst_slot],
                        send_sem=vsend[ch].at[dst_slot],
                        recv_sem=vrecv[ch].at[dst_slot],
                        device_id=dst_dev,
                        device_id_type=pltpu.DeviceIdType.LOGICAL)
                    ssk.start()
                    ssv.start()
                # no wait here: the transfer overlaps this whole round's
                # sweep; the drain wait sits at the round's LAST grid step

            src_banks = statics["src_banks0"] if ch == 0 else (1,)
            if len(src_banks) == 1:
                _emit(src_banks[0])
            else:
                for sb in src_banks:
                    pl.when(sched_ref[r, sched_ir.SRC_BANK0] == sb)(
                        functools.partial(_emit, sb))

    # ---- per-(round, batch, kv-head) chunk load: HBM slot -> VMEM ----
    @pl.when((i == 0) & (h % group == 0))
    def _chunk_load():
        kvh = h // group
        for b in statics["consume_banks"]:
            @pl.when(bank == b)
            def _load_bank(b=b):
                cps = [pltpu.make_async_copy(kbufs[b].at[slot, b_, kvh],
                                             kchunk, chunk_sem.at[0]),
                       pltpu.make_async_copy(vbufs[b].at[slot, b_, kvh],
                                             vchunk, chunk_sem.at[1])]
                if wire is not None:
                    cps.append(pltpu.make_async_copy(
                        kscbufs[b].at[slot, b_, kvh], ksc_t,
                        chunk_sem.at[2]))
                    cps.append(pltpu.make_async_copy(
                        vscbufs[b].at[slot, b_, kvh], vsc_t,
                        chunk_sem.at[3]))
                for c in cps:
                    c.start()
                for c in cps:
                    c.wait()

    # ---- per-(round, batch) segment-id row: gathered table -> VMEM ----
    if has_seg:
        @pl.when((i == 0) & (h == 0))
        def _seg_load():
            # the rotating side's partition (appended table column) selects
            # which shard's ids this round's kv chunk carries
            part = sched_ref[r, sched_ir.FWD_COLS]
            cp = pltpu.make_async_copy(sega_hbm.at[b_, part], segbuf,
                                       seg_sem.at[0])
            cp.start()
            cp.wait()

    # ---- start the acc carry load early: it overlaps the whole sweep ----
    @pl.when(r > 0)
    def _acc_load_start():
        pltpu.make_async_copy(accbuf.at[b_, h, i], acc_in,
                              acc_sem.at[0]).start()

    # ---- local online-softmax sweep over this round's chunk ----
    spec_r = tuple(sched_ref[r, c] for c in range(5))
    r0 = i * bq
    m_sw[:] = jnp.full_like(m_sw, NEG_INF)
    l_sw[:] = jnp.zeros_like(l_sw)
    acc_scr[:] = jnp.zeros_like(acc_scr)
    q_t = q_ref[0, 0, :, :] * (scale * LOG2E)

    def _fold(c0, mask):
        ks = kchunk[pl.ds(c0, bkv), :]
        if wire is not None:
            # in-tile rescale on consume (ops/ragged_paged.py's int8-pool
            # idiom): the wire-dtype tile is cast up and the per-chunk
            # scalar scale folds into the score AFTER the dot — never a
            # raw int8/fp8 operand into the MXU, never an unscaled value
            # into the fp32 accumulators
            ks = ks.astype(jnp.float32)
        s_t = jax.lax.dot_general(
            q_t, ks, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if wire is not None:
            s_t = s_t * ksc_t[0, 0]
        if mask is not None:
            s_t = jnp.where(mask, s_t, NEG_INF)
        m_prev = m_sw[:]
        m_new = jnp.maximum(m_prev, jnp.max(s_t, axis=1, keepdims=True))
        alpha = jnp.where(m_prev >= m_new, 1.0, jnp.exp2(m_prev - m_new))
        p = jnp.exp2(s_t - m_new)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)  # all-masked-row nan guard
        l_sw[:] = l_sw[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_sw[:] = m_new
        if wire is None:
            pv = jax.lax.dot_general(
                p.astype(vchunk.dtype), vchunk[pl.ds(c0, bkv), :],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        else:
            pv = jax.lax.dot_general(
                p, vchunk[pl.ds(c0, bkv), :].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * vsc_t[0, 0]
        acc_scr[:] = acc_scr[:] * alpha + pv

    segq = segq_ref[0, pl.ds(r0, bq), :] if has_seg else None   # (bq, 1)
    for j in range(nkb):
        c0 = j * bkv
        live = _block_has_work(spec_r, r0, c0, bq, bkv, wnd)
        full = _block_full(spec_r, r0, c0, bq, bkv, wnd)
        if has_seg:
            segk = segbuf[:, pl.ds(c0, bkv)]                    # (1, bkv)
            seg_pair = (segq, segk)
            # the fast path must also be single-segment-uniform: a
            # structurally-full block can still straddle a packing boundary
            fast = full & _seg_uniform_eq(segq, segk)
        else:
            seg_pair = None
            fast = full

        @pl.when(live & fast)
        def _fast(c0=c0):
            _fold(c0, None)

        @pl.when(live & ~fast)
        def _masked(c0=c0, seg_pair=seg_pair):
            _fold(c0, _block_mask(spec_r, r0, c0, bq, bkv, wnd,
                                  seg=seg_pair))

    # ---- merge with the carried state (split-k style combine) ----
    @pl.when(r == 0)
    def _init_state():
        # round 0 is always the self round: no carry, state = local sweep
        _stat_write(mstat, b_, h, i, m_sw[:], bq, lp)
        _stat_write(lstat, b_, h, i, l_sw[:], bq, lp)

    @pl.when(r > 0)
    def _merge():
        m1 = _stat_read(mstat, b_, h, i, bq, lp)
        l1 = _stat_read(lstat, b_, h, i, bq, lp)
        m2, l2 = m_sw[:], l_sw[:]
        m = jnp.maximum(m1, m2)
        a1 = jnp.where(m1 == NEG_INF, 0.0, jnp.exp2(m1 - m))
        a2 = jnp.where(m2 == NEG_INF, 0.0, jnp.exp2(m2 - m))
        pltpu.make_async_copy(accbuf.at[b_, h, i], acc_in,
                              acc_sem.at[0]).wait()
        acc_scr[:] = acc_in[:] * a1 + acc_scr[:] * a2
        _stat_write(mstat, b_, h, i, m, bq, lp)
        _stat_write(lstat, b_, h, i, l1 * a1 + l2 * a2, bq, lp)

    @pl.when(r < R - 1)
    def _acc_store():
        st = pltpu.make_async_copy(acc_scr, accbuf.at[b_, h, i],
                                   acc_sem.at[1])
        st.start()
        st.wait()

    @pl.when(r == R - 1)
    def _finalize():
        # fused finalize: o = acc / l in the caller's dtype; lse back to the
        # natural-log domain, packed rows into the resident lse out block
        m = _stat_read(mstat, b_, h, i, bq, lp)
        l = _stat_read(lstat, b_, h, i, bq, lp)
        o_ref[0, 0, :, :] = jnp.where(
            l > 0, acc_scr[:] / l, 0.0).astype(o_ref.dtype)
        lse = jnp.where(l > 0, m * LN2 + jnp.log(l), NEG_INF)
        rows = bq // lp
        lse_ref[b_, h, pl.ds(i * rows, rows), :] = jnp.reshape(
            lse, (rows, lp))

    # ---- round epilogue (last grid step of the round only) ----
    for ch in statics["ch_active"]:
        send_c, _, dst_c, _, _ = _SENDC[ch]

        @pl.when(last_of_round & (sched_ref[r, send_c] == 1))
        def _send_drain(ch=ch, dst_c=dst_c):
            # our outgoing RDMA read its src slot; it must be out the door
            # before the writer may overwrite that slot (free credit below)
            # and before the kernel may exit with a live DMA
            dst_slot = sched_ref[r, dst_c]
            dma_sem_wait(ksend[ch].at[dst_slot], kbufs[ch].at[dst_slot])
            dma_sem_wait(vsend[ch].at[dst_slot], vbufs[ch].at[dst_slot])
            if wire is not None:
                dma_sem_wait(ksend[ch].at[dst_slot],
                             kscbufs[ch].at[dst_slot])
                dma_sem_wait(vsend[ch].at[dst_slot],
                             vscbufs[ch].at[dst_slot])

    if hw_sync:
        for b in statics["grant_banks"]:
            grant_c, meta_src = _GRANTC[b]

            @pl.when(last_of_round & (sched_ref[r, grant_c] > 0))
            def _grant_free(b=b, grant_c=grant_c, meta_src=meta_src):
                # the named slot has no further readers here — its writer
                # (the bank's upstream neighbor) may target it again
                pltpu.semaphore_signal(
                    free[b].at[sched_ref[r, grant_c] - 1], inc=1,
                    device_id=sched_ref[R, meta_src],
                    device_id_type=pltpu.DeviceIdType.LOGICAL)


# ---------------------------------------------------------------------------
# shard-level entry point


def build_sched_table(cfg, prog, s_q: int, s_kv: int, *, swap_roles=False,
                      with_part=False):
    """The [R + 1, cols] traced prefetch table for a compiled program:
    per-round mask-spec scalars (the partition each round holds comes from
    the program's rotation applied to this device's ring coordinates) next
    to the program's op columns, plus the META neighbor-id row from
    parallel/ring.device_roles.  `swap_roles` builds backward-orientation
    specs (the rotating payload is the q side, the resident chunk the kv
    side).  `with_part` appends one extra column holding the rotating
    side's PARTITION id per round — the packed-segment kernels use it to
    pick that round's segment-id row out of the gathered side table.
    Returns (table, specs) — the per-round MaskSpecs are reused for
    devstats occupancy tallies."""
    inter_rank, intra_rank, _, _ = ring_coords(
        cfg.intra_axis, cfg.inter_axis, cfg.fused_seq_factor)
    me_part = inter_rank * prog.n_intra + intra_rank
    op_table = prog.to_table()
    ncols = op_table.shape[1] + int(with_part)
    rows = []
    specs = []
    for r in range(prog.n_rounds):
        part_r = sched_ir.partition_for_round(prog, r, inter_rank,
                                              intra_rank)
        if swap_roles:
            sp = round_spec(part_r, me_part, s_q, s_kv, cfg.causal,
                            cfg.layout, window=cfg.window)
        else:
            sp = round_spec(me_part, part_r, s_q, s_kv, cfg.causal,
                            cfg.layout, window=cfg.window)
        specs.append(sp)
        row = [_spec_array(sp), jnp.asarray(op_table[r, 5:], jnp.int32)]
        if with_part:
            row.append(jnp.reshape(jnp.asarray(part_r, jnp.int32), (1,)))
        rows.append(jnp.concatenate(row))
    roles = device_roles(cfg.intra_axis, cfg.inter_axis,
                         mesh_axes=cfg.mesh_axes,
                         factor=cfg.fused_seq_factor,
                         home_offsets=prog.home_offsets)
    dirs = prog.channels
    meta = [roles["me"]]
    meta.append(roles[f"{dirs[0]}_dst"])
    meta.append(roles[f"{dirs[0]}_src"])
    if len(dirs) > 1:
        meta.append(roles[f"{dirs[1]}_dst"])
        meta.append(roles[f"{dirs[1]}_src"])
    else:
        meta += [jnp.int32(0), jnp.int32(0)]
    for j in range(2):
        meta.append(roles.get(f"home{j}", jnp.int32(0)))
    meta += [jnp.int32(0)] * (ncols - len(meta))
    rows.append(jnp.stack([jnp.asarray(x, jnp.int32) for x in meta]))
    return jnp.stack(rows), specs


def gather_seg_table(seg, cfg):
    """[B, world, 1, S] int32 side table of EVERY ring shard's segment ids,
    in partition order, from this shard's [B, S] local ids.  One all_gather
    at entry (ids are tiny next to KV) — the ring itself still moves zero
    XLA collectives; burstlint's zero-collective census counts ppermute/
    all_to_all, and the fused-path contract is "no per-round collectives",
    which a single O(S) prologue gather keeps.  Partition id ordering:
    inter-major (inter_rank * n_intra + intra_rank), which for both the
    flat and the factored ring equals the gather order (ring.ring_coords
    maps flat rank f to (f // n_s, f % n_s))."""
    x = jax.lax.all_gather(seg.astype(jnp.int32), cfg.intra_axis)
    if cfg.inter_axis is not None:
        x = jax.lax.all_gather(x, cfg.inter_axis)
        x = x.reshape((-1,) + x.shape[2:])
    x = jnp.moveaxis(x, 0, 1)          # [B, world, S]
    return x[:, :, None, :]


def fused_ring_fwd(q, k, v, cfg, *, seg=None, interpret=None,
                   collect_stats=False):
    """Forward burst attention on per-shard arrays via the fused ring kernel.

    Call inside shard_map on the ring axis (same contract as
    parallel/burst._fwd_impl): q [B, N, S, D], k/v [B, Nk, S, D] in layout
    order, `seg` [B, S] optional packed-segment ids (attention never
    crosses a segment boundary; ids are gathered ring-wide once at entry
    and each round's row rides the prefetch table's partition column).
    Returns (o [B, N, S, D] in q.dtype, lse [B, N, S] f32) — plus a
    per-shard obs.devstats.DevStats when `collect_stats`: mask occupancy and
    liveness are derived in-graph from the SAME sched-table specs the kernel
    masks by, per-(bank, slot) reuse counts come out of the kernel itself as
    an extra scalar (SMEM) output, and lse/o health is computed on the
    results.  The stats-off call emits the identical kernel (no extra
    output), so traces without stats are bit-identical to pre-devstats
    builds.  Callers must have checked `supported` first.
    """
    b, n, s, d = q.shape
    n_kv = k.shape[1]
    assert n % n_kv == 0, f"GQA needs Nq % Nk == 0, got {n} % {n_kv}"
    group = n // n_kv
    if interpret is None:
        interpret = jax.default_backend() != "tpu" and not hw_trace_forced()
    scale = cfg.scale if cfg.scale is not None else d ** -0.5
    n_intra_ax = axis_size(cfg.intra_axis)
    n_inter_ax = (axis_size(cfg.inter_axis)
                  if cfg.inter_axis is not None else 1)
    topology, t_inter, t_intra = resolve_topology(cfg, n_intra_ax,
                                                  n_inter_ax)
    prog = _compile_for(cfg, topology, t_inter, t_intra, "fwd", s=s)
    statics = kernel_statics(prog)
    R = prog.n_rounds
    rf = resolve_fused(cfg.fused_block_q, cfg.fused_block_kv,
                       cfg.fused_kv_slots,
                       ccw_slots=getattr(cfg, "fused_ccw_slots", None),
                       wire_dtype=getattr(cfg, "wire_dtype", None))
    wire = rf.wire_dtype
    bq = _pick_block(s, rf.block_q)
    bkv = _pick_block(s, rf.block_kv)
    lp = _pick_block(bq, 128)
    nqb = s // bq
    nkb = s // bkv

    sched, specs = build_sched_table(cfg, prog, s, s,
                                     with_part=seg is not None)

    if wire is not None:
        # quantize ONCE on the host graph before the kernel: the payload
        # rotates unchanged, so pre-quantizing the local chunk == quantize-
        # on-send at every hop.  Per-(batch, kv-head) scalar scales travel
        # as fp32 sub-banks next to the wire-dtype slot banks.
        k_in, kscale = wire_quantize(k, wire, (2, 3))
        v_in, vscale = wire_quantize(v, wire, (2, 3))
    else:
        k_in, v_in = k, v

    kernel = functools.partial(
        _fused_fwd_kernel, prog=prog, statics=statics, scale=scale, bq=bq,
        bkv=bkv, lp=lp, nqb=nqb, nkb=nkb, group=group, n_b=b, n_h=n,
        hw_sync=not interpret, collect=collect_stats,
        wnd=cfg.window, has_seg=seg is not None, wire=wire,
    )

    def q_map(r, b_, h, i, sp):
        return (b_, h, i, 0)

    out_specs = [
        pl.BlockSpec((1, 1, bq, d), q_map),
        # whole-array resident block: written row-range-wise at the last
        # round, flushed once (block dims == array dims, always legal)
        pl.BlockSpec((b, n, s // lp, lp),
                     lambda r, b_, h, i, sp: (0, 0, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b, n, s, d), q.dtype),
        jax.ShapeDtypeStruct((b, n, s // lp, lp), jnp.float32),
    ]
    max_slots = max(prog.slots)
    if collect_stats:
        # devstats slot-reuse counts: whole-array SMEM output, scalar writes
        # only at round boundaries (see _fused_fwd_kernel); one row per
        # bank/direction (dir=cw|ccw in the published counter)
        out_specs.append(
            pl.BlockSpec(memory_space=pltpu.SMEM))
        out_shape.append(
            jax.ShapeDtypeStruct((prog.n_banks, max_slots), jnp.int32))

    scratch = []
    for bank in range(prog.n_banks):
        scratch.append(pl.ANY((prog.slots[bank], b, n_kv, s, d),
                              k_in.dtype))
        scratch.append(pl.ANY((prog.slots[bank], b, n_kv, s, d),
                              v_in.dtype))
    if wire is not None:
        for bank in range(prog.n_banks):
            # scale sub-banks: same slot layout, fp32, O(1) per chunk
            scratch.append(pl.ANY((prog.slots[bank], b, n_kv, 1, 1),
                                  jnp.float32))
            scratch.append(pl.ANY((prog.slots[bank], b, n_kv, 1, 1),
                                  jnp.float32))
    scratch += [
        pltpu.VMEM((s, d), k_in.dtype),               # kchunk
        pltpu.VMEM((s, d), v_in.dtype),               # vchunk
    ]
    if wire is not None:
        scratch += [
            pltpu.VMEM((1, 1), jnp.float32),          # ksc_t
            pltpu.VMEM((1, 1), jnp.float32),          # vsc_t
        ]
    scratch += [
        pltpu.VMEM((b, n, s // lp, lp), jnp.float32),  # mstat (base-2)
        pltpu.VMEM((b, n, s // lp, lp), jnp.float32),  # lstat (linear)
        pl.ANY((b, n, nqb, bq, d), jnp.float32),      # accbuf (carry)
        pltpu.VMEM((bq, d), jnp.float32),             # acc_in
        pltpu.VMEM((bq, d), jnp.float32),             # acc_scr
        pltpu.VMEM((bq, 1), jnp.float32),             # m_sw
        pltpu.VMEM((bq, 1), jnp.float32),             # l_sw
        pltpu.SemaphoreType.DMA(
            ((2 if wire is None else 4) * len(prog.copy_in),)),  # cp_sem
        pltpu.SemaphoreType.DMA((2 if wire is None else 4,)),  # chunk_sem
        pltpu.SemaphoreType.DMA((2,)),                # acc_sem
    ]
    for bank in range(prog.n_banks):
        scratch += [
            pltpu.SemaphoreType.DMA((prog.slots[bank],)),   # ksend[bank]
            pltpu.SemaphoreType.DMA((prog.slots[bank],)),   # krecv[bank]
            pltpu.SemaphoreType.DMA((prog.slots[bank],)),   # vsend[bank]
            pltpu.SemaphoreType.DMA((prog.slots[bank],)),   # vrecv[bank]
            pltpu.SemaphoreType.REGULAR((prog.slots[bank],)),  # free[bank]
        ]

    in_specs = [
        pl.BlockSpec((1, 1, bq, d), q_map),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    inputs = [sched, q, k_in, v_in]
    if wire is not None:
        # per-block fp32 scales ride along as ANY inputs; the kernel pops
        # them right after k/v and copies them into the scale slot banks
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        inputs.append(kscale)
        inputs.append(vscale)
    if seg is not None:
        # local ids resident per batch; the gathered ring-wide table stays
        # in ANY space and the kernel pulls one partition's row per round
        in_specs.append(pl.BlockSpec((1, s, 1),
                                     lambda r, b_, h, i, sp: (b_, 0, 0)))
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        inputs.append(seg.astype(jnp.int32)[:, :, None])
        inputs.append(gather_seg_table(seg, cfg))
        scratch += [
            pltpu.VMEM((1, s), jnp.int32),       # segbuf
            pltpu.SemaphoreType.DMA((1,)),       # seg_sem
        ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R, b, n, nqb),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        # everything is sequential by construction: the ring choreography,
        # the VMEM-resident stats, and the acc carry all assume one core
        # walks the grid in order — a megacore split would race them
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT,
            dimension_semantics=("arbitrary",) * 4,
            collective_id=_COLLECTIVE_ID,
        ),
        interpret=interpret,
    )(*inputs)
    o, lse_packed = outs[0], outs[1]
    lse = _unpack(lse_packed)
    if not collect_stats:
        return o, lse
    from ..obs import devstats

    # occupancy/liveness from the SAME per-round specs the kernel masks by;
    # the fused ring executes every scheduled round (band-dead blocks are
    # in-kernel masked) and the occupancy compiler has already ELIDED the
    # fully-dead rounds — rounds_elided counts what never launched.
    # Segment occupancy is data-dependent and NOT in these tallies: pair
    # counts stay band-only (documented in docs/observability.md).
    pairs = sum(spec_pair_count(sp, s, s, window=cfg.window) for sp in specs)
    live = sum(spec_live(sp, cfg.window).astype(jnp.int32) for sp in specs)
    slot_use = outs[2]
    qam = 0.0
    if wire is not None:
        f32 = jnp.float32
        qam = jnp.maximum(jnp.max(jnp.abs(k.astype(f32))),
                          jnp.max(jnp.abs(v.astype(f32))))
    stats = devstats.ring_stats(
        rounds=R, rounds_live=live, attn_pairs=pairs,
        total_pairs=float(R) * s * s, head_dim=d,
        m=None,  # the running row max never leaves the kernel
        lse=lse, acc=o, fused_rounds=R, rounds_elided=prog.world - R,
        slot_use=slot_use[0],
        slot_use_ccw=slot_use[1] if prog.n_banks > 1 else None,
        quant_absmax=qam)
    return o, lse, stats
