"""Fused on-device ring attention BACKWARD: all R backward rounds in ONE
Pallas kernel — the comm-optimized BurstAttention backward with both of its
concurrent streams carried by in-kernel inter-chip RDMA instead of
per-round `lax.ppermute` collectives between kernel launches.

Roles flip versus the fused forward (ops/fused_ring.py): K and V stay
RESIDENT on their home device for the whole kernel (fp32 dk/dv accumulate
in VMEM per (batch, kv-head) segment and never move), while the q-side
BUNDLE — (delta|o, do, q, lse) in `optimize_bwd_comm` form — rotates
exactly like the forward's KV, and the dq partial gradients ride
accumulating rings ONE HOP BEHIND their bundles: a block's dq cannot leave
until the local contribution is folded in, so each [bq, D] row-block
streams out the moment its grid step finishes.

Schedule IR.  Like the forward, this kernel interprets a compiled
`RingProgram` (parallel/schedule.compile_bwd) and contains no topology
logic of its own.  The bundle movement reuses the forward program's
channel/bank/credit columns; the dq plan adds per-round columns saying
which dq ring the local contribution folds into, whether a partial
arrives, and the send kind:

  RING      onward hop to the bank's direction neighbor (cw / ccw / intra)
  HOME      the direction's terminal round: the completed partial takes ONE
            direct RDMA to its partition's owner (`home_offsets` away — the
            uni ring's right neighbor; a bidi ring's two directions end
            ceil/floor((W-1)/2) hops out on opposite sides)
  BOUNDARY  double ring, end of a non-final cycle: fold the held inter
            partial, hop the sum one inter step (the scan backward's
            dq_inter add-and-forward), into a 2-slot ping/pong bank
  FINAL     double ring, end of the last cycle: fold and take the composed
            (inter+1, intra+1) home hop — one RDMA where the scan path
            pays two ppermutes

Topologies: uni (exactly the hand-built PR-5 choreography), bidi (two
counter-rotating bundle+dq ring pairs, per-direction banks/semaphores; the
owner receives its gradient as TWO complementary partials — cw carrying
contributions from the self round and the clockwise visitors, ccw from the
counter-clockwise visitors — summed outside the kernel), and double (the
hierarchical ring with the bundle's inter prefetch one full intra-cycle
early).  Every program is simulation-proven by burstlint before trust
(analysis/oracle.verify_ring_program: bundle delivery + slot safety per
bank and the dq streams' exactly-once return-home with all `world`
contributions).

Compute path.  Per grid step (r, b, h, i) the kernel folds bundle q-block i
against the WHOLE resident KV chunk: per kv block j it forms
p = exp2(s·scale·log2e − lse·log2e) from the FINAL lse riding the bundle
(no online softmax — p is the true probability), then dv += pᵀ·do,
ds = p·(dp − delta), dk += dsᵀ·q, dq_local += ds·k, all f32 accumulated
with the trailing *scale of ds deferred exactly like pallas_flash's
backward kernels.  Masks reuse the SAME per-round ops/masks.round_spec
scalars the scan backward computes, with q/kv roles swapped, so the two
paths mask identically by construction.

Semaphore ledger (everything drains to zero; N = B*Nq*nqb grid steps per
round):

  precv[bank][slot]   +4 per arriving bundle (+7 under wire_dtype: three
                      fp32 scale sub-payloads ride the same slot), -same
                      at the consuming round's first grid step
  psend[bank][slot]   +4 (+7 wire) per outgoing bundle send, -same at the
                      same round's last grid step (drain)
  dqrecv[bank][slot]  +N from the writer's streamed previous-serving
                      blocks, -N at the serving round's first grid step
  dqsend[bank][slot]  +N per round's streamed ring sends, -N at that
                      round's last grid step
  dqi send/recv[slot] (double) +N per boundary stream, drained at the
                      boundary's last step / waited at the next boundary's
                      first step
  home{b} send/recv   +N each around a HOME/FINAL round; drained/waited at
                      the terminal epilogue before the output copy
  free_pay[bank][slot], free_dq[bank][slot], free_dqi[slot] (hw only)
                      per-SLOT capacity credits, compiler-assigned
                      (GRANT columns carry slot+1, takes ride the sends)
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.lax import axis_size

from .pallas_flash import (
    BIG_LSE,
    LOG2E,
    NEG_INF,
    VMEM_LIMIT,
    _block_full,
    _block_has_work,
    _block_mask,
    _pack,
    _pick_block,
    _seg_uniform_eq,
)
from .tuning import resolve_fused
from .fused_ring import (build_sched_table, dma_sem_wait, gather_seg_table,
                         kernel_statics, _SENDC, _GRANTC)
from ..parallel import schedule as sched_ir
from ..parallel.ring import WIRE_QMAX, wire_quantize

# barrier-semaphore namespace, distinct from the fused forward's (13) so a
# program tracing both kernels never aliases their startup barriers
_COLLECTIVE_ID = 14

_LOGICAL = None  # filled lazily to keep module import light


def _wire_quant_tile(x, wire):
    """In-kernel symmetric quantization of one fp32 tile: per-block scalar
    scale (the dq ring's refreshed per-hop scale).  Mirrors
    parallel/ring.wire_quantize with keepdims collapsed to a 0-d scalar."""
    amax = jnp.max(jnp.abs(x))
    sc = jnp.maximum(amax, 1e-30) / WIRE_QMAX[wire]
    if wire == "int8":
        q = jnp.clip(jnp.round(x / sc), -127.0, 127.0).astype(jnp.int8)
    else:
        q = (x / sc).astype(jnp.float8_e4m3fn)
    return q, sc


def _col_from_pack(pack, bq, lp):
    """[bq // lp, lp] packed row-stat tile -> (bq, 1) column (element t of
    the flat row vector lives at pack[t // lp, t % lp] — same layout as
    pallas_flash's packed stats, read from a VMEM tile instead of a ref)."""
    if lp == 1:
        return pack
    rep = jnp.repeat(pack, lp, axis=0)  # (bq, lp); row t = pack[t // lp]
    t_lane = jax.lax.broadcasted_iota(jnp.int32, (bq, lp), 0) % lp
    c_idx = jax.lax.broadcasted_iota(jnp.int32, (bq, lp), 1)
    return jnp.sum(jnp.where(t_lane == c_idx, rep, 0.0), axis=1, keepdims=True)


def bwd_statics(prog):
    """Static dq-plan structure of a compiled backward program: which dq
    ring banks exist, where each home send happens, whether the double
    ring's inter (dqi) machinery is present.  Like fused_ring.kernel_
    statics this decides which code the kernel EMITS, so the remote-DMA
    census is a function of the program alone."""
    rows = prog.rows
    R = prog.n_rounds
    ring_banks = tuple(sorted({rows["dq_bank"][r] for r in range(R)
                               if rows["dq_send"][r] == sched_ir.DQ_RING}))
    serve_banks = tuple(sorted({rows["dq_bank"][r] for r in range(R)}))
    home_rounds = {}
    for r in range(R):
        if rows["dq_send"][r] == sched_ir.DQ_HOME:
            home_rounds[rows["dq_bank"][r]] = r
        elif rows["dq_send"][r] == sched_ir.DQ_FINAL:
            home_rounds[0] = r
    has_dqi = any(rows["dqi_recv"][r] or
                  rows["dq_send"][r] == sched_ir.DQ_BOUNDARY
                  for r in range(R))
    take_banks = tuple(b for b in range(2)
                       if any(rows[f"dq_take{b}"][r] for r in range(R)))
    grant_banks = tuple(b for b in range(2)
                        if any(rows[f"dq_grant{b}"][r] for r in range(R)))
    return dict(ring_banks=ring_banks, serve_banks=serve_banks,
                home_rounds=home_rounds, has_dqi=has_dqi,
                take_banks=take_banks, grant_banks=grant_banks)


# ---------------------------------------------------------------------------
# kernel


def _fused_bwd_kernel(
    sched_ref,
    first_hbm, do_hbm, q_hbm, lse_hbm, k_hbm, v_hbm,
    *refs,
    prog, statics, dq_statics, scale, bq, bkv, lp, nqb, nkb, group,
    n_b, n_h, hw_sync, collect, opt_comm, wnd, has_seg, wire,
):
    """One grid step = bundle q-block i of head h, batch b_, bwd ring round r.

    sched_ref is the [R + 1, BWD_COLS] prefetch table (parallel/schedule.py
    column constants): per-round mask scalars (q side = rotating bundle
    partition, kv side = resident chunk), the bundle's bank/slot/send/credit
    columns, and the dq plan; row R holds the traced neighbor/home ids.

    `collect` (static) appends one more OUTPUT after dq/dk/dv: a
    [n_banks, max_slots] int32 SMEM array counting bundle consumes per
    (bank, slot) — the devstats bwd slot-reuse counter with per-direction
    rows, written with pure scalar increments at round boundaries so the
    compute/DMA choreography (and dq/dk/dv) is bit-identical to
    collect=off.
    """
    R = prog.n_rounds
    n_banks = prog.n_banks
    dq_banks = prog.n_dq_banks if prog.topology != "double" else 1
    home_banks = sorted(dq_statics["home_rounds"])
    has_dqi = dq_statics["has_dqi"]
    refs = list(refs)
    # wire-quantized bundles carry three per-(batch, head) fp32 scale
    # inputs right after the six bundle/kv operands (lse never quantizes)
    if wire is not None:
        fsc_hbm = refs.pop(0)    # [B, N, 1, 1] f32 first/delta scales
        dosc_hbm = refs.pop(0)   # [B, N, 1, 1] f32 do scales
        qsc_hbm = refs.pop(0)    # [B, N, 1, 1] f32 q scales
    # optional segment-id inputs ride after the six bundle/kv operands:
    # local KV-side ids resident in VMEM, the gathered ring-wide table in
    # ANY (roles swapped vs the forward — the ROTATING side is q here)
    if has_seg:
        segkv_ref = refs.pop(0)  # [1, 1, s] VMEM block: LOCAL kv ids
        sega_hbm = refs.pop(0)   # [B, world, s, 1] ANY: every shard's ids
    # outputs first: dq per home bank (+ their scale outputs under wire),
    # dk, dv, (slot_use)
    dq_refs = [refs.pop(0) for _ in home_banks]
    dqsc_refs = [refs.pop(0) for _ in home_banks] if wire is not None \
        else []
    dk_ref = refs.pop(0)
    dv_ref = refs.pop(0)
    if collect:
        slot_use_ref = refs.pop(0)
    firstbuf, dobuf, qbuf, lsebuf = [], [], [], []
    for _ in range(n_banks):
        firstbuf.append(refs.pop(0))
        dobuf.append(refs.pop(0))
        qbuf.append(refs.pop(0))
        lsebuf.append(refs.pop(0))
    fscbuf, doscbuf, qscbuf = [], [], []
    if wire is not None:
        # fp32 scale sub-banks: same slot indices, same send/recv
        # semaphores and capacity credits as the bundle banks they scale
        for _ in range(n_banks):
            fscbuf.append(refs.pop(0))
            doscbuf.append(refs.pop(0))
            qscbuf.append(refs.pop(0))
    dqbuf = [refs.pop(0) for _ in range(dq_banks)]
    dqscbuf = [refs.pop(0) for _ in range(dq_banks)] if wire is not None \
        else []
    dqibuf = refs.pop(0) if has_dqi else None
    dqiscbuf = refs.pop(0) if has_dqi and wire is not None else None
    (kchunk, vchunk, dk_acc, dv_acc,
     q_t, do_t, first_t, lse_t, dq_arr, dqi_arr, dq_scr,
     cp_sem, chunk_sem, kvio_sem, tile_sem, dqio_sem) = refs[:16]
    refs = refs[16:]
    if wire is not None:
        # (1, 1) f32 scale tiles + the dq re-quantization staging pair
        (fsc_t, dosc_t, qsc_t, dqsc_arr, dqisc_arr, dq_q, dqsc_w) = refs[:7]
        refs = refs[7:]
    psend, precv, free_pay = [], [], []
    for _ in range(n_banks):
        psend.append(refs.pop(0))
        precv.append(refs.pop(0))
        free_pay.append(refs.pop(0))
    dqsend, dqrecv, free_dq = [], [], []
    for _ in range(dq_banks):
        dqsend.append(refs.pop(0))
        dqrecv.append(refs.pop(0))
        free_dq.append(refs.pop(0))
    if has_dqi:
        dqi_send = refs.pop(0)
        dqi_recv = refs.pop(0)
        free_dqi = refs.pop(0)
    home_sems = {b: refs.pop(0) for b in home_banks}
    if has_seg:
        segbuf = refs.pop(0)     # VMEM (s, 1) int32: this round's q ids
        seg_sem = refs.pop(0)
    assert not refs, f"{len(refs)} scratch refs left over"

    LOGICAL = pltpu.DeviceIdType.LOGICAL
    r = pl.program_id(0)
    b_ = pl.program_id(1)
    h = pl.program_id(2)
    i = pl.program_id(3)
    bank = sched_ref[r, sched_ir.CONSUME_BANK]
    slot = sched_ref[r, sched_ir.CONSUME_SLOT]
    dq_bank_c = sched_ref[r, sched_ir.DQ_BANK]
    dq_slot = sched_ref[r, sched_ir.DQ_SLOT]
    dq_kind = sched_ref[r, sched_ir.DQ_SEND]
    first_of_round = (b_ == 0) & (h == 0) & (i == 0)
    last_of_round = (b_ == n_b - 1) & (h == n_h - 1) & (i == nqb - 1)
    n_steps = n_b * n_h * nqb  # dq blocks streamed per round

    def dq_banked(fn):
        """Run fn(bank) under a pl.when for each dq ring bank."""
        if prog.topology == "double":
            fn(0)
            return
        for b in range(dq_banks):
            pl.when(dq_bank_c == b)(functools.partial(fn, b))

    if collect:
        @pl.when(first_of_round)
        def _slot_tally():
            @pl.when(r == 0)
            def _zero():
                for bb in range(slot_use_ref.shape[0]):
                    for j in range(slot_use_ref.shape[1]):
                        slot_use_ref[bb, j] = 0

            slot_use_ref[bank, slot] = slot_use_ref[bank, slot] + 1

    # ---- round choreography (first grid step of the round only) ----
    # every site that moves "the bundle" walks this list: the four dense
    # operands plus, under wire, the three scale sub-banks riding the same
    # slots/semaphores (sends, recv waits, drains and copy_in stay in sync
    # by construction)
    bundle_hbm = [first_hbm, do_hbm, q_hbm, lse_hbm]
    bundle_bufs = [firstbuf, dobuf, qbuf, lsebuf]
    if wire is not None:
        bundle_hbm += [fsc_hbm, dosc_hbm, qsc_hbm]
        bundle_bufs += [fscbuf, doscbuf, qscbuf]
    per_op = len(bundle_bufs)

    @pl.when(first_of_round & (r == 0))
    def _copy_in():
        # local bundle -> its program-designated slot(s): one HBM->HBM copy
        # per operand per launch bank
        cps = []
        for idx, (cb, cslot) in enumerate(prog.copy_in):
            for j, (src, bufs) in enumerate(zip(bundle_hbm, bundle_bufs)):
                cps.append(pltpu.make_async_copy(
                    src, bufs[cb].at[cslot], cp_sem.at[per_op * idx + j]))
        for c in cps:
            c.start()
        for c in cps:
            c.wait()

    if hw_sync:
        @pl.when(first_of_round & (r == 0))
        def _barrier():
            # every RDMA peer must have entered the kernel (buffers live)
            # before any send targets its slots; home peers are covered by
            # the ring peers' transitive barrier (the home hop happens
            # R - 1 rounds later)
            bar = pltpu.get_barrier_semaphore()
            n_sig = 0
            for ch in statics["ch_active"]:
                pltpu.semaphore_signal(
                    bar, inc=1, device_id=sched_ref[R, _SENDC[ch][4]],
                    device_id_type=LOGICAL)
                pltpu.semaphore_signal(
                    bar, inc=1, device_id=sched_ref[R, _GRANTC[ch][1]],
                    device_id_type=LOGICAL)
                n_sig += 2
            pltpu.semaphore_wait(bar, n_sig)

    @pl.when(first_of_round & (sched_ref[r, sched_ir.RECV] == 1))
    def _recv_wait():
        # round r's bundle (4 operands) must have LANDED in its slot
        for b in statics["consume_banks"]:
            @pl.when(bank == b)
            def _w(b=b):
                # one wait per operand transfer; together they retire the
                # full bundle regardless of landing order
                for bufs in bundle_bufs:
                    dma_sem_wait(precv[b].at[slot], bufs[b].at[slot])

    @pl.when(first_of_round & (sched_ref[r, sched_ir.DQ_RECV] == 1))
    def _dq_recv_wait():
        # every streamed dq block of the writer's previous serving round:
        # the n_steps block transfers sum to exactly one slot entry (2x
        # transfers under wire: each block's payload plus its scale)
        def _w(b):
            dma_sem_wait(dqrecv[b].at[dq_slot], dqbuf[b].at[dq_slot])
            if wire is not None:
                dma_sem_wait(dqrecv[b].at[dq_slot], dqscbuf[b].at[dq_slot])

        dq_banked(_w)

    if has_dqi:
        @pl.when(first_of_round & (sched_ref[r, sched_ir.DQI_RECV] == 1))
        def _dqi_recv_wait():
            dqi_slot = sched_ref[r, sched_ir.DQI_SLOT]
            dma_sem_wait(dqi_recv.at[dqi_slot], dqibuf.at[dqi_slot])
            if wire is not None:
                dma_sem_wait(dqi_recv.at[dqi_slot], dqiscbuf.at[dqi_slot])

    for ch in statics["ch_active"]:
        send_c, src_c, dst_c, take_c, meta_dst = _SENDC[ch]

        @pl.when(first_of_round & (sched_ref[r, send_c] == 1))
        def _send_bundle(ch=ch, src_c=src_c, dst_c=dst_c, take_c=take_c,
                         meta_dst=meta_dst):
            dst_slot = sched_ref[r, dst_c]
            src_slot = sched_ref[r, src_c]
            dst_dev = sched_ref[R, meta_dst]
            if hw_sync and ch in statics["take_chs"]:
                @pl.when(sched_ref[r, take_c] == 1)
                def _capacity():
                    pltpu.semaphore_wait(free_pay[ch].at[dst_slot], 1)

            def _emit(sb):
                for bufs in bundle_bufs:
                    pltpu.make_async_remote_copy(
                        src_ref=bufs[sb].at[src_slot],
                        dst_ref=bufs[ch].at[dst_slot],
                        send_sem=psend[ch].at[dst_slot],
                        recv_sem=precv[ch].at[dst_slot],
                        device_id=dst_dev, device_id_type=LOGICAL).start()
                # no wait here: the transfers overlap this whole round's
                # sweep; the drain wait sits at the round's LAST grid step

            src_banks = statics["src_banks0"] if ch == 0 else (1,)
            if len(src_banks) == 1:
                _emit(src_banks[0])
            else:
                for sb in src_banks:
                    pl.when(sched_ref[r, sched_ir.SRC_BANK0] == sb)(
                        functools.partial(_emit, sb))

    # ---- per-(round, batch, kv-head) chunk load: HBM -> VMEM, plus the
    # fp32 dk/dv accumulator carry (outputs double as the between-round
    # staging, like the forward's acc scratch) ----
    @pl.when((i == 0) & (h % group == 0))
    def _chunk_load():
        kvh = h // group
        lk = pltpu.make_async_copy(k_hbm.at[b_, kvh], kchunk, chunk_sem.at[0])
        lv = pltpu.make_async_copy(v_hbm.at[b_, kvh], vchunk, chunk_sem.at[1])
        lk.start()
        lv.start()

        @pl.when(r > 0)
        def _carry_load():
            ldk = pltpu.make_async_copy(dk_ref.at[b_, kvh], dk_acc,
                                        kvio_sem.at[0])
            ldv = pltpu.make_async_copy(dv_ref.at[b_, kvh], dv_acc,
                                        kvio_sem.at[1])
            ldk.start()
            ldv.start()
            ldk.wait()
            ldv.wait()

        @pl.when(r == 0)
        def _carry_zero():
            dk_acc[:] = jnp.zeros_like(dk_acc)
            dv_acc[:] = jnp.zeros_like(dv_acc)

        lk.wait()
        lv.wait()

    # ---- per-(round, batch) segment-id row: gathered table -> VMEM ----
    if has_seg:
        @pl.when((i == 0) & (h == 0))
        def _seg_load():
            # the rotating bundle's partition (appended table column)
            # selects which shard's ids this round's q blocks carry
            part = sched_ref[r, sched_ir.BWD_COLS]
            cp = pltpu.make_async_copy(sega_hbm.at[b_, part], segbuf,
                                       seg_sem.at[0])
            cp.start()
            cp.wait()

    # ---- per-step bundle tile loads: slot HBM -> VMEM (started in the
    # consume bank's branch, awaited unconditionally so the arriving-dq
    # load below overlaps them) ----
    for b in statics["consume_banks"]:
        @pl.when(bank == b)
        def _tile_start(b=b):
            pltpu.make_async_copy(qbuf[b].at[slot, b_, h, i], q_t,
                                  tile_sem.at[0]).start()
            pltpu.make_async_copy(dobuf[b].at[slot, b_, h, i], do_t,
                                  tile_sem.at[1]).start()
            pltpu.make_async_copy(firstbuf[b].at[slot, b_, h, i], first_t,
                                  tile_sem.at[2]).start()
            pltpu.make_async_copy(lsebuf[b].at[slot, b_, h, i], lse_t,
                                  tile_sem.at[3]).start()
            if wire is not None:
                pltpu.make_async_copy(fscbuf[b].at[slot, b_, h], fsc_t,
                                      tile_sem.at[4]).start()
                pltpu.make_async_copy(doscbuf[b].at[slot, b_, h], dosc_t,
                                      tile_sem.at[5]).start()
                pltpu.make_async_copy(qscbuf[b].at[slot, b_, h], qsc_t,
                                      tile_sem.at[6]).start()

    # start the arriving-dq loads early: they are only needed at the merge,
    # after the whole local sweep
    @pl.when(sched_ref[r, sched_ir.DQ_RECV] == 1)
    def _dq_arr_start():
        def _s(b):
            pltpu.make_async_copy(dqbuf[b].at[dq_slot, b_, h, i], dq_arr,
                                  dqio_sem.at[0]).start()
            if wire is not None:
                pltpu.make_async_copy(dqscbuf[b].at[dq_slot, b_, h, i],
                                      dqsc_arr, dqio_sem.at[3]).start()

        dq_banked(_s)

    if has_dqi:
        @pl.when(sched_ref[r, sched_ir.DQI_RECV] == 1)
        def _dqi_arr_start():
            pltpu.make_async_copy(
                dqibuf.at[sched_ref[r, sched_ir.DQI_SLOT], b_, h, i],
                dqi_arr, dqio_sem.at[2]).start()
            if wire is not None:
                pltpu.make_async_copy(
                    dqiscbuf.at[sched_ref[r, sched_ir.DQI_SLOT], b_, h, i],
                    dqisc_arr, dqio_sem.at[5]).start()

    tiles = [q_t, do_t, first_t, lse_t]
    if wire is not None:
        tiles += [fsc_t, dosc_t, qsc_t]
    for j, tile in enumerate(tiles):
        dma_sem_wait(tile_sem.at[j], tile)

    # ---- local sweep over the resident chunk (no online softmax: p is
    # the true probability from the bundle's final lse) ----
    spec_r = tuple(sched_ref[r, c] for c in range(5))
    r0 = i * bq
    lse_col = _col_from_pack(lse_t[:], bq, lp)
    # fully-masked rows carry lse = -inf; BIG_LSE makes p underflow to 0
    # on the fast path without an elementwise select (pallas_flash idiom)
    lse_col = jnp.where(lse_col == NEG_INF, BIG_LSE, lse_col * LOG2E)
    if wire is None:
        q_raw = q_t[:]
        do_raw = do_t[:]
        first_f = first_t[:]
    else:
        # in-tile column rescale BEFORE any accumulation: the quantized
        # bundle dequantizes against its per-(batch, head) scale tiles
        q_raw = q_t[:].astype(jnp.float32) * qsc_t[0, 0]
        do_raw = do_t[:].astype(jnp.float32) * dosc_t[0, 0]
        first_f = first_t[:].astype(jnp.float32) * fsc_t[0, 0]
    if opt_comm:
        delta_col = _col_from_pack(first_f, bq, lp)
    else:
        delta_col = jnp.sum(
            first_f.astype(jnp.float32) * do_raw.astype(jnp.float32),
            axis=1, keepdims=True)
    q_sc = q_raw * (scale * LOG2E)
    dq_scr[:] = jnp.zeros_like(dq_scr)

    def _fold(c0, mask):
        ks = kchunk[pl.ds(c0, bkv), :]
        vs = vchunk[pl.ds(c0, bkv), :]
        s_t = jax.lax.dot_general(
            q_sc, ks, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dp is independent of the softmax: issue it before the VPU chain
        dp = jax.lax.dot_general(
            do_raw, vs, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        p = jnp.exp2(s_t - lse_col)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        # trailing *scale of ds deferred to the dq merge / final dk store
        ds = p * (dp - delta_col)
        dv_acc[pl.ds(c0, bkv), :] = dv_acc[pl.ds(c0, bkv), :] + \
            jax.lax.dot_general(
                p.astype(do_raw.dtype), do_raw, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        dk_acc[pl.ds(c0, bkv), :] = dk_acc[pl.ds(c0, bkv), :] + \
            jax.lax.dot_general(
                ds.astype(q_raw.dtype), q_raw, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(ks.dtype), ks, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    segq = segbuf[pl.ds(r0, bq), :] if has_seg else None      # (bq, 1)
    for j in range(nkb):
        c0 = j * bkv
        live = _block_has_work(spec_r, r0, c0, bq, bkv, wnd)
        full = _block_full(spec_r, r0, c0, bq, bkv, wnd)
        if has_seg:
            segk = segkv_ref[0, :, pl.ds(c0, bkv)]            # (1, bkv)
            seg_pair = (segq, segk)
            # fast path also needs single-segment uniformity (see fwd)
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

    # ---- dq merge: arriving partial (one hop behind) + local contribution
    # (+ the held inter partial at double-ring boundaries), staged back into
    # the slot and streamed onward immediately ----
    @pl.when(sched_ref[r, sched_ir.DQ_RECV] == 1)
    def _dq_merge():
        dma_sem_wait(dqio_sem.at[0], dq_arr)
        if wire is None:
            arr = dq_arr[:]
        else:
            # rescale the arriving quantized partial with the scale that
            # rode its slot before folding into the fp32 accumulator
            dma_sem_wait(dqio_sem.at[3], dqsc_arr)
            arr = dq_arr[:].astype(jnp.float32) * dqsc_arr[0, 0]
        dq_scr[:] = arr + dq_scr[:] * scale

    @pl.when(sched_ref[r, sched_ir.DQ_RECV] == 0)
    def _dq_seed():
        # this direction's ring starts here: no arrival to merge
        dq_scr[:] = dq_scr[:] * scale

    if has_dqi:
        @pl.when(sched_ref[r, sched_ir.DQI_RECV] == 1)
        def _dqi_merge():
            dma_sem_wait(dqio_sem.at[2], dqi_arr)
            if wire is None:
                arr = dqi_arr[:]
            else:
                dma_sem_wait(dqio_sem.at[5], dqisc_arr)
                arr = dqi_arr[:].astype(jnp.float32) * dqisc_arr[0, 0]
            dq_scr[:] = dq_scr[:] + arr

    def _wb(b):
        if wire is None:
            wb = pltpu.make_async_copy(
                dq_scr, dqbuf[b].at[dq_slot, b_, h, i], dqio_sem.at[1])
            wb.start()
            wb.wait()
        else:
            # re-quantize the folded fp32 partial with its REFRESHED
            # per-block scale; payload and scale land in parallel slots
            qt, sc = _wire_quant_tile(dq_scr[:], wire)
            dq_q[:] = qt
            dqsc_w[:] = sc[None, None]
            wb1 = pltpu.make_async_copy(
                dq_q, dqbuf[b].at[dq_slot, b_, h, i], dqio_sem.at[1])
            wb2 = pltpu.make_async_copy(
                dqsc_w, dqscbuf[b].at[dq_slot, b_, h, i], dqio_sem.at[4])
            wb1.start()
            wb2.start()
            wb1.wait()
            wb2.wait()

    dq_banked(_wb)

    for b in dq_statics["ring_banks"]:
        @pl.when((dq_kind == sched_ir.DQ_RING) & (dq_bank_c == b))
        def _dq_send_ring(b=b):
            # the concurrent dq stream: this block's partial leaves NOW,
            # while later blocks of the same round are still computing —
            # it lands in the bank's direction neighbor before that
            # neighbor's next serving round needs it
            dst_slot = sched_ref[r, sched_ir.DQ_DST_SLOT]
            if hw_sync and b in dq_statics["take_banks"] \
                    and prog.topology != "double":
                @pl.when(first_of_round
                         & (sched_ref[r, sched_ir.DQ_TAKE0 if b == 0 else
                                      sched_ir.DQ_TAKE1] == 1))
                def _cap():
                    pltpu.semaphore_wait(free_dq[b].at[dst_slot], 1)
            pltpu.make_async_remote_copy(
                src_ref=dqbuf[b].at[dq_slot, b_, h, i],
                dst_ref=dqbuf[b].at[dst_slot, b_, h, i],
                send_sem=dqsend[b].at[dst_slot],
                recv_sem=dqrecv[b].at[dst_slot],
                device_id=sched_ref[R, _SENDC[b][4]],
                device_id_type=LOGICAL).start()
            if wire is not None:
                # the block's refreshed scale rides the same slot credits
                pltpu.make_async_remote_copy(
                    src_ref=dqscbuf[b].at[dq_slot, b_, h, i],
                    dst_ref=dqscbuf[b].at[dst_slot, b_, h, i],
                    send_sem=dqsend[b].at[dst_slot],
                    recv_sem=dqrecv[b].at[dst_slot],
                    device_id=sched_ref[R, _SENDC[b][4]],
                    device_id_type=LOGICAL).start()

    if hw_sync and prog.topology == "double" and 0 in \
            dq_statics["take_banks"]:
        # double ring: the intra dq stream's takes (DQ_TAKE0) ride the
        # ring-send rounds; emitted once at the round's first step
        @pl.when(first_of_round & (sched_ref[r, sched_ir.DQ_TAKE0] == 1))
        def _dq_cap_double():
            pltpu.semaphore_wait(
                free_dq[0].at[sched_ref[r, sched_ir.DQ_DST_SLOT]], 1)

    for b in home_banks:
        kinds = (sched_ir.DQ_HOME,) if prog.topology != "double" else \
            (sched_ir.DQ_FINAL,)

        @pl.when((dq_kind == kinds[0]) & (dq_bank_c == (b if prog.topology
                                                        != "double" else 0)))
        def _dq_send_home(b=b):
            # return-home hop: the completed partial lands in its OWNER's
            # dedicated home slot (index dq_slots[b], outside the ring
            # cycle) — one direct RDMA, `home_offsets[b]` positions away
            src_b = b if prog.topology != "double" else 0
            home_idx = prog.dq_slots[src_b]
            home_dev = sched_ref[R, sched_ir.META_HOME0 if b == 0
                                 else sched_ir.META_HOME1]
            pltpu.make_async_remote_copy(
                src_ref=dqbuf[src_b].at[dq_slot, b_, h, i],
                dst_ref=dqbuf[src_b].at[home_idx, b_, h, i],
                send_sem=home_sems[b].at[0], recv_sem=home_sems[b].at[1],
                device_id=home_dev, device_id_type=LOGICAL).start()
            if wire is not None:
                pltpu.make_async_remote_copy(
                    src_ref=dqscbuf[src_b].at[dq_slot, b_, h, i],
                    dst_ref=dqscbuf[src_b].at[home_idx, b_, h, i],
                    send_sem=home_sems[b].at[0],
                    recv_sem=home_sems[b].at[1],
                    device_id=home_dev, device_id_type=LOGICAL).start()

    if has_dqi:
        @pl.when(dq_kind == sched_ir.DQ_BOUNDARY)
        def _dq_send_boundary():
            # cycle boundary: the folded (inter_held + cycle partial) block
            # hops one inter step into the ping/pong accumulator bank
            dst_slot = sched_ref[r, sched_ir.DQI_DST_SLOT]
            if hw_sync and 1 in dq_statics["take_banks"]:
                @pl.when(first_of_round
                         & (sched_ref[r, sched_ir.DQ_TAKE1] == 1))
                def _cap():
                    pltpu.semaphore_wait(free_dqi.at[dst_slot], 1)
            pltpu.make_async_remote_copy(
                src_ref=dqbuf[0].at[dq_slot, b_, h, i],
                dst_ref=dqibuf.at[dst_slot, b_, h, i],
                send_sem=dqi_send.at[dst_slot],
                recv_sem=dqi_recv.at[dst_slot],
                device_id=sched_ref[R, sched_ir.META_CH1_DST],
                device_id_type=LOGICAL).start()
            if wire is not None:
                pltpu.make_async_remote_copy(
                    src_ref=dqscbuf[0].at[dq_slot, b_, h, i],
                    dst_ref=dqiscbuf.at[dst_slot, b_, h, i],
                    send_sem=dqi_send.at[dst_slot],
                    recv_sem=dqi_recv.at[dst_slot],
                    device_id=sched_ref[R, sched_ir.META_CH1_DST],
                    device_id_type=LOGICAL).start()

    # ---- dk/dv segment epilogue: stage the fp32 accumulators back to the
    # output buffers (final at the last round, with ds's deferred scale) ----
    @pl.when((i == nqb - 1) & (h % group == group - 1))
    def _kv_store():
        kvh = h // group

        @pl.when(r == R - 1)
        def _final_scale():
            dk_acc[:] = dk_acc[:] * scale

        sk = pltpu.make_async_copy(dk_acc, dk_ref.at[b_, kvh], kvio_sem.at[2])
        sv = pltpu.make_async_copy(dv_acc, dv_ref.at[b_, kvh], kvio_sem.at[3])
        sk.start()
        sv.start()
        sk.wait()
        sv.wait()

    # ---- round epilogue (last grid step of the round only) ----
    for ch in statics["ch_active"]:
        send_c, _, dst_c, _, _ = _SENDC[ch]

        @pl.when(last_of_round & (sched_ref[r, send_c] == 1))
        def _bundle_drain(ch=ch, dst_c=dst_c):
            dst_slot = sched_ref[r, dst_c]
            for bufs in bundle_bufs:
                dma_sem_wait(psend[ch].at[dst_slot], bufs[ch].at[dst_slot])

    for b in dq_statics["ring_banks"]:
        @pl.when(last_of_round & (dq_kind == sched_ir.DQ_RING)
                 & (dq_bank_c == b))
        def _dq_drain(b=b):
            ds_ = sched_ref[r, sched_ir.DQ_DST_SLOT]
            dma_sem_wait(dqsend[b].at[ds_], dqbuf[b].at[ds_])
            if wire is not None:
                dma_sem_wait(dqsend[b].at[ds_], dqscbuf[b].at[ds_])

    if has_dqi:
        @pl.when(last_of_round & (dq_kind == sched_ir.DQ_BOUNDARY))
        def _dqi_drain():
            ds_ = sched_ref[r, sched_ir.DQI_DST_SLOT]
            dma_sem_wait(dqi_send.at[ds_], dqibuf.at[ds_])
            if wire is not None:
                dma_sem_wait(dqi_send.at[ds_], dqiscbuf.at[ds_])

    for b in home_banks:
        send_round = dq_statics["home_rounds"][b]

        @pl.when(last_of_round & (r == send_round))
        def _home_drain(b=b):
            # our outgoing home blocks must be out the door before exit;
            # the n_steps block sends sum to one home-slot entry
            src_bank = b if prog.topology != "double" else 0
            home_idx = prog.dq_slots[src_bank]
            dma_sem_wait(home_sems[b].at[0], dqbuf[src_bank].at[home_idx])
            if wire is not None:
                dma_sem_wait(home_sems[b].at[0],
                             dqscbuf[src_bank].at[home_idx])

    @pl.when(last_of_round & (r == R - 1))
    def _home_epilogue():
        # wait every home bank's arrivals, then land each home slot in its
        # own dq output (multiple partials are summed OUTSIDE the kernel —
        # one jnp add against one extra output, instead of a block loop in
        # the final grid step; under wire the quantized partial and its
        # scales come out as separate outputs and dequantize in XLA)
        cps = []
        for j, b in enumerate(home_banks):
            src_bank = b if prog.topology != "double" else 0
            home_idx = prog.dq_slots[src_bank]
            dma_sem_wait(home_sems[b].at[1], dqbuf[src_bank].at[home_idx])
            cps.append(pltpu.make_async_copy(
                dqbuf[src_bank].at[home_idx], dq_refs[j],
                cp_sem.at[(2 if wire is not None else 1) * j]))
            if wire is not None:
                dma_sem_wait(home_sems[b].at[1],
                             dqscbuf[src_bank].at[home_idx])
                cps.append(pltpu.make_async_copy(
                    dqscbuf[src_bank].at[home_idx], dqsc_refs[j],
                    cp_sem.at[2 * j + 1]))
        for cp in cps:
            cp.start()
        for cp in cps:
            cp.wait()

    if hw_sync:
        for b in statics["grant_banks"]:
            grant_c, meta_src = _GRANTC[b]

            @pl.when(last_of_round & (sched_ref[r, grant_c] > 0))
            def _grant_pay(b=b, grant_c=grant_c, meta_src=meta_src):
                pltpu.semaphore_signal(
                    free_pay[b].at[sched_ref[r, grant_c] - 1], inc=1,
                    device_id=sched_ref[R, meta_src],
                    device_id_type=LOGICAL)

        for b in dq_statics["grant_banks"]:
            grant_c = sched_ir.DQ_GRANT0 if b == 0 else sched_ir.DQ_GRANT1
            is_dqi = has_dqi and b == 1

            @pl.when(last_of_round & (sched_ref[r, grant_c] > 0))
            def _grant_dq(b=b, grant_c=grant_c, is_dqi=is_dqi):
                # the dq bank's writer is its channel's upstream neighbor
                sem = free_dqi if is_dqi else free_dq[b]
                meta_src = _GRANTC[1 if is_dqi else b][1]
                pltpu.semaphore_signal(
                    sem.at[sched_ref[r, grant_c] - 1], inc=1,
                    device_id=sched_ref[R, meta_src],
                    device_id_type=LOGICAL)


# ---------------------------------------------------------------------------
# shard-level entry point


def fused_ring_bwd(cfg, q, k, v, o, lse, do, *, seg=None, interpret=None,
                   collect_stats=False):
    """Backward burst attention on per-shard arrays via the fused ring
    kernel — the drop-in twin of parallel/burst._bwd_impl's scan ring.

    Call inside shard_map on the ring axis: q/o/do [B, N, S, D], k/v
    [B, Nk, S, D], lse [B, N, S] f32 (the forward residuals in layout
    order), `seg` [B, S] optional packed-segment ids (gathered ring-wide
    once at entry; the ROTATING side here is the q bundle, so each
    round's q ids come off the side table and the kv ids stay local).
    Returns (dq, dk, dv) in float32 — the caller casts back to
    the input dtypes, exactly like the scan backward — plus the kernel's
    [n_banks, slots] int32 bundle slot-consume counters when
    `collect_stats` (the devstats bwd slot-reuse channel, one row per
    direction bank).  Callers must have checked
    `fused_ring.supported(..., pass_="bwd")` first.
    """
    from .fused_ring import hw_trace_forced, resolve_topology, _compile_for

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
    prog = _compile_for(cfg, topology, t_inter, t_intra, "bwd", s=s)
    statics = kernel_statics(prog)
    dq_statics = bwd_statics(prog)
    R = prog.n_rounds
    rf = resolve_fused(cfg.fused_block_q, cfg.fused_block_kv,
                       cfg.fused_kv_slots,
                       block_q_bwd=cfg.fused_block_q_bwd,
                       block_kv_bwd=cfg.fused_block_kv_bwd,
                       bwd_slots=cfg.fused_bwd_slots,
                       ccw_slots=getattr(cfg, "fused_ccw_slots", None),
                       bwd_ccw_slots=getattr(cfg, "fused_bwd_ccw_slots",
                                             None),
                       wire_dtype=getattr(cfg, "wire_dtype", None))
    wire = rf.wire_dtype
    bq = _pick_block(s, rf.block_q_bwd)
    bkv = _pick_block(s, rf.block_kv_bwd)
    lp = _pick_block(bq, 128)
    nqb = s // bq
    rows = bq // lp
    nkb = s // bkv

    # mask scalars with q/kv roles swapped: q side = rotating bundle
    # partition, kv side = resident local chunk
    sched, _specs = build_sched_table(cfg, prog, s, s, swap_roles=True,
                                      with_part=seg is not None)

    # bundle operands, pre-blocked so every slot/tile address is integer
    # indexing ([B, N, nqb, bq, D] is the same memory as [B, N, S, D]);
    # rank-3 stats ride in pallas_flash's packed [.., rows, lp] layout
    # wire mode quantizes the three rotating payloads ONCE at entry (the
    # bundle never changes as it circles, so quantize-at-entry is exactly
    # quantize-on-send); lse stays fp32
    if wire is not None:
        q_q, qsc = wire_quantize(q, wire, (2, 3))      # scales (b, n, 1, 1)
        do_q, dosc = wire_quantize(do, wire, (2, 3))
        q_in = q_q.reshape(b, n, nqb, bq, d)
        do_in = do_q.reshape(b, n, nqb, bq, d)
    else:
        q_in = q.reshape(b, n, nqb, bq, d)
        do_in = do.reshape(b, n, nqb, bq, d)
    lse_in = _pack(lse.astype(jnp.float32), lp).reshape(b, n, nqb, rows, lp)
    if cfg.optimize_bwd_comm:
        delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                        axis=-1)
        if wire is not None:
            delta, fsc = wire_quantize(delta, wire, (2,))  # (b, n, 1)
            fsc = fsc[..., None]
        first_in = _pack(delta, lp).reshape(b, n, nqb, rows, lp)
        first_slot_shape = (b, n, nqb, rows, lp)
        first_tile_shape = (rows, lp)
        first_dtype = delta.dtype
    else:
        # ring payload grows by a factor of head_dim; delta is recomputed
        # per tile from the rotated (o, do) pair (reference parity)
        first_in = o
        if wire is not None:
            first_in, fsc = wire_quantize(o, wire, (2, 3))
        first_in = first_in.reshape(b, n, nqb, bq, d)
        first_slot_shape = (b, n, nqb, bq, d)
        first_tile_shape = (bq, d)
        first_dtype = first_in.dtype

    kernel = functools.partial(
        _fused_bwd_kernel, prog=prog, statics=statics,
        dq_statics=dq_statics, scale=scale, bq=bq, bkv=bkv, lp=lp, nqb=nqb,
        nkb=nkb, group=group, n_b=b, n_h=n, hw_sync=not interpret,
        collect=collect_stats, opt_comm=cfg.optimize_bwd_comm,
        wnd=cfg.window, has_seg=seg is not None, wire=wire,
    )

    home_banks = sorted(dq_statics["home_rounds"])
    dq_ring_banks = prog.n_dq_banks if topology != "double" else 1
    has_dqi = dq_statics["has_dqi"]

    dq_out_dtype = jnp.float32 if wire is None else jnp.dtype(
        jnp.int8 if wire == "int8" else jnp.float8_e4m3fn)
    out_specs = [pl.BlockSpec(memory_space=pl.ANY)
                 for _ in home_banks]                      # dq partial(s)
    out_shape = [jax.ShapeDtypeStruct((b, n, nqb, bq, d), dq_out_dtype)
                 for _ in home_banks]
    if wire is not None:
        # the arriving quantized partials' per-block scales, dequantized
        # against their payload outputs by XLA just below
        out_specs += [pl.BlockSpec(memory_space=pl.ANY)
                      for _ in home_banks]
        out_shape += [jax.ShapeDtypeStruct((b, n, nqb, 1, 1), jnp.float32)
                      for _ in home_banks]
    out_specs += [
        pl.BlockSpec(memory_space=pl.ANY),  # dk
        pl.BlockSpec(memory_space=pl.ANY),  # dv
    ]
    out_shape += [
        jax.ShapeDtypeStruct((b, n_kv, s, d), jnp.float32),
        jax.ShapeDtypeStruct((b, n_kv, s, d), jnp.float32),
    ]
    if collect_stats:
        out_specs.append(
            pl.BlockSpec(memory_space=pltpu.SMEM))
        out_shape.append(jax.ShapeDtypeStruct(
            (prog.n_banks, max(prog.slots)), jnp.int32))

    dq_ring_dtype = jnp.float32 if wire is None else dq_out_dtype
    scratch = []
    for bank in range(prog.n_banks):
        sl = prog.slots[bank]
        scratch += [
            pl.ANY((sl,) + first_slot_shape, first_dtype),      # firstbuf
            pl.ANY((sl, b, n, nqb, bq, d), do_in.dtype),        # dobuf
            pl.ANY((sl, b, n, nqb, bq, d), q_in.dtype),         # qbuf
            pl.ANY((sl, b, n, nqb, rows, lp), jnp.float32),     # lsebuf
        ]
    if wire is not None:
        for bank in range(prog.n_banks):
            sl = prog.slots[bank]
            scratch += [
                pl.ANY((sl, b, n, 1, 1), jnp.float32),      # fscbuf
                pl.ANY((sl, b, n, 1, 1), jnp.float32),      # doscbuf
                pl.ANY((sl, b, n, 1, 1), jnp.float32),      # qscbuf
            ]
    dq_bank_slots = []
    for bank in range(dq_ring_banks):
        # ring slots + (when this bank receives a home stream) the
        # dedicated return-home slot just past them
        extra = 1 if bank in home_banks or topology == "double" else 0
        dq_bank_slots.append(prog.dq_slots[bank] + extra)
        scratch.append(pl.ANY(
            (prog.dq_slots[bank] + extra, b, n, nqb, bq, d), dq_ring_dtype))
    if wire is not None:
        for sl in dq_bank_slots:
            scratch.append(pl.ANY((sl, b, n, nqb, 1, 1),
                                  jnp.float32))          # dqscbuf
    if has_dqi:
        scratch.append(pl.ANY((prog.dq_slots[1], b, n, nqb, bq, d),
                              dq_ring_dtype))            # dqibuf
        if wire is not None:
            scratch.append(pl.ANY((prog.dq_slots[1], b, n, nqb, 1, 1),
                                  jnp.float32))          # dqiscbuf
    scratch += [
        pltpu.VMEM((s, d), k.dtype),                  # kchunk
        pltpu.VMEM((s, d), v.dtype),                  # vchunk
        pltpu.VMEM((s, d), jnp.float32),              # dk_acc
        pltpu.VMEM((s, d), jnp.float32),              # dv_acc
        pltpu.VMEM((bq, d), q_in.dtype),              # q_t
        pltpu.VMEM((bq, d), do_in.dtype),             # do_t
        pltpu.VMEM(first_tile_shape, first_dtype),    # first_t
        pltpu.VMEM((rows, lp), jnp.float32),          # lse_t
        pltpu.VMEM((bq, d), dq_ring_dtype),           # dq_arr
        pltpu.VMEM((bq, d), dq_ring_dtype),           # dqi_arr
        pltpu.VMEM((bq, d), jnp.float32),             # dq_scr
        pltpu.SemaphoreType.DMA((max(
            (7 if wire is not None else 4) * len(prog.copy_in),
            (2 if wire is not None else 1) * len(home_banks)),)),  # cp_sem
        pltpu.SemaphoreType.DMA((2,)),                # chunk_sem
        pltpu.SemaphoreType.DMA((4,)),                # kvio_sem
        pltpu.SemaphoreType.DMA((7 if wire is not None else 4,)),  # tile_sem
        pltpu.SemaphoreType.DMA((6 if wire is not None else 3,)),  # dqio_sem
    ]
    if wire is not None:
        scratch += [
            pltpu.VMEM((1, 1), jnp.float32),          # fsc_t
            pltpu.VMEM((1, 1), jnp.float32),          # dosc_t
            pltpu.VMEM((1, 1), jnp.float32),          # qsc_t
            pltpu.VMEM((1, 1), jnp.float32),          # dqsc_arr
            pltpu.VMEM((1, 1), jnp.float32),          # dqisc_arr
            pltpu.VMEM((bq, d), dq_ring_dtype),       # dq_q
            pltpu.VMEM((1, 1), jnp.float32),          # dqsc_w
        ]
    for bank in range(prog.n_banks):
        sl = prog.slots[bank]
        scratch += [
            pltpu.SemaphoreType.DMA((sl,)),           # psend[bank]
            pltpu.SemaphoreType.DMA((sl,)),           # precv[bank]
            pltpu.SemaphoreType.REGULAR((sl,)),       # free_pay[bank]
        ]
    for bank in range(dq_ring_banks):
        sl = prog.dq_slots[bank]
        scratch += [
            pltpu.SemaphoreType.DMA((sl,)),           # dqsend[bank]
            pltpu.SemaphoreType.DMA((sl,)),           # dqrecv[bank]
            pltpu.SemaphoreType.REGULAR((sl,)),       # free_dq[bank]
        ]
    if has_dqi:
        scratch += [
            pltpu.SemaphoreType.DMA((prog.dq_slots[1],)),   # dqi_send
            pltpu.SemaphoreType.DMA((prog.dq_slots[1],)),   # dqi_recv
            pltpu.SemaphoreType.REGULAR((prog.dq_slots[1],)),  # free_dqi
        ]
    for _ in home_banks:
        scratch.append(pltpu.SemaphoreType.DMA((2,)))  # home_sems[b]

    in_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 6
    inputs = [sched, first_in, do_in, q_in, lse_in, k, v]
    if wire is not None:
        # per-(batch, head) bundle scales: popped by the kernel right
        # after the six dense operands, ahead of any segment inputs
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 3
        inputs += [fsc, dosc, qsc]
    if seg is not None:
        # local KV-side ids resident per batch; the gathered table (q-side
        # orientation: [B, world, S, 1]) stays in ANY space
        in_specs.append(pl.BlockSpec((1, 1, s),
                                     lambda r, b_, h, i, sp: (b_, 0, 0)))
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        inputs.append(seg.astype(jnp.int32)[:, None, :])
        inputs.append(jnp.swapaxes(gather_seg_table(seg, cfg), 2, 3))
        scratch += [
            pltpu.VMEM((s, 1), jnp.int32),       # segbuf
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
        # sequential by construction: the ring choreography, the VMEM
        # dk/dv accumulators and the dq streams all assume one core walks
        # the grid in order — a megacore split would race them
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT,
            dimension_semantics=("arbitrary",) * 4,
            collective_id=_COLLECTIVE_ID,
        ),
        interpret=interpret,
    )(*inputs)
    # a bidi owner receives its gradient as two complementary directional
    # partials; the sum is one fused XLA add — everything else already
    # happened in-kernel.  Wire mode lands the partials quantized with
    # their per-block scales in trailing outputs: dequantize (one rescale
    # per home bank), THEN sum — the accumulators inside the kernel were
    # fp32 throughout, only the return-home hop crossed the wire narrow.
    nh = len(home_banks)
    if wire is None:
        dq = outs[0]
        for j in range(1, nh):
            dq = dq + outs[j]
        n_out = nh
    else:
        dq = outs[0].astype(jnp.float32) * outs[nh]
        for j in range(1, nh):
            dq = dq + outs[j].astype(jnp.float32) * outs[nh + j]
        n_out = 2 * nh
    dq = dq.reshape(b, n, s, d)
    dk, dv = outs[n_out], outs[n_out + 1]
    if not collect_stats:
        return dq, dk, dv
    return dq, dk, dv, outs[n_out + 2]
