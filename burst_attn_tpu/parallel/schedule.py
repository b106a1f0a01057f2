"""Ring-schedule IR and compiler (ROADMAP item 1).

A ring schedule is a small compiled PROGRAM — per-round
consume/send/recv/credit ops per stream — emitted once by `compile_fwd` /
`compile_bwd`.  It was written for two executors, of which one is left:

  * `scan_events(program)` flattens it to the ordered (cls, axis, hops)
    collective stream the scan ring issues (`parallel/ring.ring_round_counts`
    derives its hop accounting from this, and burstlint matches the traced
    scan program against the same stream via analysis/oracle.py);
  * `to_table(program)` packs it into an int32 scalar-prefetch table for a
    ring kernel with in-kernel RDMA.  The kernels that read it never
    compiled for hardware and are gone (git keeps them at PR 31's parent);
    the table, the `bidi` programs and the slot / credit ops have no
    executor until a new such kernel is written (ROADMAP D7c).

Topologies the compiler emits (all simulation-proven by
analysis/oracle.verify_ring_program before any kernel may consume them —
the proof obligation lives with the compiler, not with each new PR):

  "uni"    the classic single ring: every chunk travels world-1 cw hops.
           Reproduces the legacy hand-built schedules bit for bit.
  "bidi"   counter-rotating bidirectional ring (TASP, arXiv 2509.26541):
           the payload stream is split across BOTH ICI directions — chunks
           for offsets 1..ceil((W-1)/2) arrive clockwise, offsets
           1..floor((W-1)/2) counter-clockwise, interleaved round-robin.
           Each direction owns its own slot bank and DMA semaphores, each
           in-flight transfer has TWO rounds of compute to hide under, and
           both link directions carry traffic concurrently — on comm-bound
           configs the effective per-hop latency halves.
  "double" the hierarchical double ring (BurstAttention's signature
           schedule): n_inter cycles of n_intra intra hops; the inter-hop
           payload (the next cycle's base chunk) is issued ONE FULL
           INTRA-CYCLE early into a dedicated prefetch bank, so the slow
           inter link hides behind n_intra rounds of compute.  Works on a
           two-axis ("inter", "intra") mesh or factored onto a flat ring
           axis (`n_inter * n_intra == world`).

Program shape.  Payload movement is expressed through at most two send
CHANNELS, each owning a slot BANK on the receiving side:

  channel 0  "cw" sends (uni/bidi) or intra-ring sends (double) -> bank 0
  channel 1  "ccw" sends (bidi) or inter-prefetch sends (double) -> bank 1

Per round the table row says which (bank, slot) compute consumes, whether
that slot's recv semaphores must be awaited first, which channels send
(src bank/slot, dst slot), and the capacity-credit ops (grant/take per
bank) that make slot reuse safe — the same handshake the hand-built
kernels used, now ASSIGNED BY THE COMPILER from the write/read event
order and checked (grant strictly before take) at compile time.

Backward programs add the dq ring plan: per round, which dq bank the
local contribution folds into, whether a partial arrives (one hop behind
the bundle), and the send kind — onward ring hop, direct return-home hop
(a single RDMA to the partition owner, `home_offsets` away), or the
double ring's cycle-boundary fold into the inter accumulator and the
final composed (inter+1, intra+1) home hop.

Everything here is host-side python/numpy: programs are compiled once per
(topology, world, slots) at trace time and are hashable static metadata
from the kernels' point of view.
"""

from dataclasses import dataclass, field, replace
from typing import Dict, List, Optional, Tuple

import numpy as np

TOPOLOGIES = ("uni", "bidi", "double")

# wire precision of the rotating payloads (ROADMAP item 5): None ships the
# caller's dtypes; "int8"/"fp8" quantize every rotating operand except lse
# (which stays fp32 — it is already tiny and exponent-critical) to 1-byte
# symmetric per-block values with an fp32 scale riding the SAME slot: the
# scale sub-buffer is a parallel bank indexed by the identical slot ids,
# its transfers signal the identical send/recv semaphores, and its reuse is
# licensed by the identical per-slot credits — no new columns exist in the
# op table, which is exactly what oracle.verify_ring_program checks.
WIRE_DTYPES = (None, "int8", "fp8")

# ---------------------------------------------------------------------------
# table column layout (bwd extends fwd).
# Columns 0..4 are reserved for the per-round mask-spec scalars
# (ops/masks.round_spec via pallas_flash._spec_array) which the kernel
# ENTRY fills in — they are traced values (they depend on the device's
# partition), while everything the compiler emits is a host integer.

SPEC0 = 0                 # q_lo, q_hi, kv_hi, causal, offset
CONSUME_BANK = 5
CONSUME_SLOT = 6
RECV = 7                  # 1 = wait payload recv sems on the consume slot
SEND0 = 8                 # channel-0 send issued at this round's first step
SRC_BANK0 = 9
SRC_SLOT0 = 10
DST_SLOT0 = 11
GRANT0 = 12               # bank-0 slot+1 whose credit this round grants
TAKE0 = 13                # 1 = this round's send takes its dst slot's credit
SEND1 = 14
SRC_SLOT1 = 15            # channel-1 sends always source from bank 1
DST_SLOT1 = 16
GRANT1 = 17
TAKE1 = 18
FWD_COLS = 19

DQ_BANK = 19              # which dq ring this round's contribution folds into
DQ_RECV = 20              # 1 = a partial arrives (one hop behind the bundle)
DQ_SLOT = 21
DQ_SEND = 22              # 0 none | 1 ring | 2 home | 3 boundary | 4 final
DQ_DST_SLOT = 23
DQ_GRANT0 = 24
DQ_TAKE0 = 25
DQ_GRANT1 = 26
DQ_TAKE1 = 27
DQI_RECV = 28             # double: consume the held inter partial this round
DQI_SLOT = 29
DQI_DST_SLOT = 30
BWD_COLS = 31

# dq send kinds
DQ_NONE, DQ_RING, DQ_HOME, DQ_BOUNDARY, DQ_FINAL = 0, 1, 2, 3, 4

# meta-row entries (appended by the kernel entry as TRACED device ids —
# the compiler never sees concrete ranks): me, channel-0 dst/src neighbor,
# channel-1 dst/src neighbor, dq home targets per dq bank
META_ME = 0
META_CH0_DST = 1
META_CH0_SRC = 2
META_CH1_DST = 3
META_CH1_SRC = 4
META_HOME0 = 5
META_HOME1 = 6


@dataclass(frozen=True)
class RingProgram:
    """One compiled ring schedule (see module docstring)."""

    kind: str                     # "fwd" | "bwd"
    topology: str                 # "uni" | "bidi" | "double"
    n_inter: int
    n_intra: int
    slots: Tuple[int, ...]        # payload slots per bank (len = n_banks)
    channels: Tuple[str, ...]     # channel dirs: subset of (cw, ccw, inter)
    copy_in: Tuple[Tuple[int, int], ...]  # round-0 local copies (bank, slot)
    rows: Dict[str, Tuple[int, ...]] = field(hash=False)
    # per-round rotation of the consumed payload: partition =
    # ((inter_rank - rot_inter) % I) * N + ((intra_rank - rot_intra) % N)
    rot_inter: Tuple[int, ...] = ()
    rot_intra: Tuple[int, ...] = ()
    # bwd only: dq ring geometry
    dq_slots: Tuple[int, ...] = ()          # ring slots per dq bank (no home)
    home_offsets: Tuple[Tuple[int, int], ...] = ()  # per dq bank:
    #   (inter_off, intra_off) — the final home hop targets the device
    #   `offset` positions forward of the sender
    # wire precision of the rotating payloads (see WIRE_DTYPES): purely a
    # payload-encoding attribute — the op table is IDENTICAL to the dense
    # compile of the same topology (asserted by burstlint), only the slot
    # dtypes, the scale sub-banks and the remote-DMA census change
    wire: Optional[str] = None

    @property
    def world(self) -> int:
        return self.n_inter * self.n_intra

    @property
    def n_rounds(self) -> int:
        return len(self.rot_intra)

    @property
    def n_banks(self) -> int:
        return len(self.slots)

    @property
    def n_dq_banks(self) -> int:
        return len(self.dq_slots)

    def col(self, name_idx: int) -> Tuple[int, ...]:
        return tuple(self.rows[_COL_NAMES[name_idx]])

    def to_table(self) -> np.ndarray:
        """[n_rounds, FWD_COLS|BWD_COLS] int32 op table (spec cols zeroed —
        the kernel entry fills them with traced per-round mask scalars)."""
        ncols = BWD_COLS if self.kind == "bwd" else FWD_COLS
        out = np.zeros((self.n_rounds, ncols), dtype=np.int32)
        for idx in range(5, ncols):
            out[:, idx] = self.rows[_COL_NAMES[idx]]
        return out

    def export(self) -> dict:
        """Plain-dict form for the analysis oracle: everything the
        simulation proof needs, nothing it must trust the compiler for."""
        return {
            "kind": self.kind, "topology": self.topology,
            "n_inter": self.n_inter, "n_intra": self.n_intra,
            "slots": self.slots, "channels": self.channels,
            "copy_in": self.copy_in, "rot_inter": self.rot_inter,
            "rot_intra": self.rot_intra, "dq_slots": self.dq_slots,
            "home_offsets": self.home_offsets, "wire": self.wire,
            "rows": {k: tuple(v) for k, v in self.rows.items()},
        }


_COL_NAMES = {
    CONSUME_BANK: "consume_bank", CONSUME_SLOT: "consume_slot", RECV: "recv",
    SEND0: "send0", SRC_BANK0: "src_bank0", SRC_SLOT0: "src_slot0",
    DST_SLOT0: "dst_slot0", GRANT0: "grant0", TAKE0: "take0",
    SEND1: "send1", SRC_SLOT1: "src_slot1", DST_SLOT1: "dst_slot1",
    GRANT1: "grant1", TAKE1: "take1",
    DQ_BANK: "dq_bank", DQ_RECV: "dq_recv", DQ_SLOT: "dq_slot",
    DQ_SEND: "dq_send", DQ_DST_SLOT: "dq_dst_slot",
    DQ_GRANT0: "dq_grant0", DQ_TAKE0: "dq_take0",
    DQ_GRANT1: "dq_grant1", DQ_TAKE1: "dq_take1",
    DQI_RECV: "dqi_recv", DQI_SLOT: "dqi_slot",
    DQI_DST_SLOT: "dqi_dst_slot",
}


class ScheduleError(ValueError):
    """A requested schedule cannot be compiled (bad topology/shape) or an
    emitted schedule failed a compile-time obligation (credit ordering)."""


# ---------------------------------------------------------------------------
# credit assignment: the one place the capacity handshake is derived


def _assign_credits(n_rounds: int, slots: int, writes, reads):
    """Derive the per-round capacity-credit schedule for one slot bank.

    writes: ordered [(round, slot)] REMOTE writes into the bank (the
    neighbor's sends, in issue order; the local round-0 copy-in is version
    0 of its slot and prepended by the caller when it exists).
    reads:  [(round, slot)] every read of the bank (consume + send-source).

    Credits are PER SLOT (the kernel's free semaphore is an array indexed
    like the bank): a write that reuses a slot takes that slot's credit at
    its round (take flag — the slot is the send's dst slot, already in the
    table), and the reader grants it at the end of the round holding the
    LAST read of the version being overwritten (grant column = slot + 1).
    A single fungible pool would be unsound for multi-bank-cycle
    schedules: a grant meant to free slot A could be consumed early by a
    write into slot B, silently licensing an overwrite-before-read — the
    oracle's maximally-ahead simulation exposes exactly that.  Compile-
    time obligations: at most one grant per round per bank, and every
    grant round strictly precedes its take round (else hardware
    deadlocks on an ungranted credit).
    """
    grants = [0] * n_rounds  # slot + 1; 0 = no grant
    takes = [0] * n_rounds
    per_slot_writes: Dict[int, List[int]] = {}
    write_meta = []  # (round, slot, version_index)
    for rnd, slot in writes:
        per_slot_writes.setdefault(slot, []).append(rnd)
        write_meta.append((rnd, slot, len(per_slot_writes[slot]) - 1))
    last_read: Dict[Tuple[int, int], int] = {}
    for rnd, slot in reads:
        versions = per_slot_writes.get(slot, [])
        vi = 0
        for j, wr in enumerate(versions):
            if wr <= rnd:
                vi = j
        key = (slot, vi)
        last_read[key] = max(last_read.get(key, -1), rnd)
    for rnd, slot, vi in write_meta:
        if vi == 0:
            continue  # first use of the slot: no credit needed
        takes[rnd] += 1
        prev_key = (slot, vi - 1)
        g = last_read.get(prev_key)
        if g is None:
            raise ScheduleError(
                f"slot {slot} version {vi - 1} overwritten without ever "
                "being read — aliased slot assignment")
        if g >= rnd:
            raise ScheduleError(
                f"credit deadlock: grant for slot {slot} at round {g} does "
                f"not precede the take at round {rnd}")
        if grants[g]:
            raise ScheduleError(
                f"round {g} would grant credits for two slots of one bank "
                f"({grants[g] - 1} and {slot})")
        grants[g] = slot + 1
    if sum(1 for g in grants if g) != sum(takes):
        raise ScheduleError(
            f"unbalanced credits: {sum(1 for g in grants if g)} granted, "
            f"{sum(takes)} taken")
    return grants, takes


def _assign_dq_credits(n_rounds: int, servings):
    """Credits for a dq accumulating ring, whose slots are written twice
    per serving (remote arrival, then the owner's local merged writeback).

    servings: ordered [(round, slot, arrival)] — the rounds this dq bank
    is the active ring, the slot serving them, and whether a partial
    ARRIVES (one hop behind) or the round seeds a fresh partial.  An
    arrival's send was issued during the sender's PREVIOUS serving round
    of this bank (one hop behind by construction), so when it reuses a
    slot the take lands on that round and the grant on the slot's previous
    serving round — which must strictly precede it or the ring deadlocks.
    """
    grants = [0] * n_rounds  # slot + 1; 0 = no grant (per-slot credits)
    takes = [0] * n_rounds
    prev_of_slot: Dict[int, int] = {}
    for k, (rnd, slot, arrival) in enumerate(servings):
        if arrival and k > 0:
            sender_round = servings[k - 1][0]
            if slot in prev_of_slot:
                t_prev = prev_of_slot[slot]
                if t_prev >= sender_round:
                    raise ScheduleError(
                        f"dq credit deadlock: slot {slot} last served at "
                        f"round {t_prev}, rewritten by the send at round "
                        f"{sender_round}")
                takes[sender_round] += 1
                if grants[t_prev]:
                    raise ScheduleError(
                        f"round {t_prev} would grant dq credits for two "
                        f"slots ({grants[t_prev] - 1} and {slot})")
                grants[t_prev] = slot + 1
        prev_of_slot[slot] = rnd
    return grants, takes


# ---------------------------------------------------------------------------
# forward compiler


def _blank_rows(n_rounds: int, ncols: int) -> Dict[str, List[int]]:
    return {name: [0] * n_rounds for idx, name in _COL_NAMES.items()
            if idx < ncols}


def _bidi_order(world: int) -> List[Tuple[str, int]]:
    """Global sweep order of the counter-rotating ring: the self round,
    then cw offset c and ccw offset u interleaved (cw first).  cw carries
    offsets 1..ceil((W-1)/2), ccw offsets 1..floor((W-1)/2)."""
    h_cw = (world - 1 + 1) // 2
    h_ccw = (world - 1) // 2
    order: List[Tuple[str, int]] = [("cw", 0)]
    for j in range(1, max(h_cw, h_ccw) + 1):
        if j <= h_cw:
            order.append(("cw", j))
        if j <= h_ccw:
            order.append(("ccw", j))
    return order


def compile_fwd(topology: str, n_intra: int, n_inter: int = 1, *,
                slots: int = 2, slots1: Optional[int] = None,
                r_live: Optional[int] = None,
                wire: Optional[str] = None) -> RingProgram:
    """Compile a forward (KV-rotation) ring schedule.

    n_intra/n_inter: ring factorization (uni/bidi use n_inter == 1; double
    requires both >= 2, world = n_inter * n_intra).  slots: payload slots
    of bank 0 (>= 2); slots1: bank 1 (default = slots for bidi, 2 for the
    double prefetch bank).

    r_live: occupancy truncation (dead-round ELISION).  When the per-round
    occupancy (ops/masks.live_round_prefix, built on spec_pair_count) says
    only ring offsets {0..r_live-1} ever attend a pair, the compiled
    program keeps exactly those rounds and OMITS every op of the dead
    tail: no consume, no send/recv, no credit traffic — the elided rounds
    do not exist in the table, so the kernel issues no RDMA and sweeps no
    KV for them.  uni keeps its first r_live rounds; bidi degrades to the
    cw-only prefix program (serving offsets 0..r_live-1 down one direction
    is strictly cheaper than splitting a short prefix across two streams,
    and the bidi interleave's tail is not a round prefix); double keeps
    the first r_live rounds of its (cycle-major) visit order — whose flat
    offset IS the round index, so prefix truncation applies directly, and
    the inter prefetch for a cycle that would start at or past r_live is
    elided with it.

    wire: wire precision of the rotating payloads (WIRE_DTYPES) — attached
    to the program so `expected_remote_dma` and `wire_round_bytes` account
    the scale sub-payloads; the op table itself is identical to the dense-
    precision compile (scales ride the same slots, sems and credits).
    """
    if topology not in TOPOLOGIES:
        raise ScheduleError(f"unknown topology {topology!r}")
    if wire not in WIRE_DTYPES:
        raise ScheduleError(f"unknown wire dtype {wire!r} "
                            f"(must be one of {WIRE_DTYPES})")
    if slots < 2:
        raise ScheduleError(f"need slots >= 2, got {slots}")
    world = n_inter * n_intra
    if world < 1:
        raise ScheduleError(f"need world >= 1, got {world}")
    if topology != "double" and n_inter != 1:
        raise ScheduleError(f"{topology} rings need n_inter == 1")
    if topology == "double" and (n_inter < 2 or n_intra < 1):
        raise ScheduleError(
            f"double ring needs n_inter >= 2 and n_intra >= 1, got "
            f"{n_inter}x{n_intra}")
    if r_live is not None:
        if not (1 <= r_live <= world):
            raise ScheduleError(
                f"r_live must be in [1, world={world}], got {r_live}")
        if r_live == world:
            r_live = None  # no dead tail: compile the dense program

    if topology == "uni":
        prog = _compile_fwd_uni(world, slots, r_live)
    elif topology == "bidi":
        if r_live is not None:
            # a truncated bidi degrades to the cw-only prefix program: the
            # live offsets {0..r_live-1} all fit one direction, and the
            # bidi interleave's own tail is not a round prefix
            prog = _compile_fwd_uni(world, slots, r_live)
        else:
            prog = _compile_fwd_bidi(world, slots,
                                     slots if slots1 is None else slots1)
    else:
        prog = _compile_fwd_double(n_inter, n_intra, slots,
                                   2 if slots1 is None else slots1, r_live)
    return prog if wire is None else replace(prog, wire=wire)


def _compile_fwd_uni(world: int, slots: int, r_live=None) -> RingProgram:
    n_rounds = world if r_live is None else r_live
    c0 = min(slots, world)
    rows = _blank_rows(n_rounds, FWD_COLS)
    writes = [(0, 0)]  # copy-in = version 0 of slot 0
    reads = []
    for r in range(n_rounds):
        slot = r % c0
        rows["consume_slot"][r] = slot
        rows["recv"][r] = int(r > 0)
        reads.append((r, slot))
        if r < n_rounds - 1:
            rows["send0"][r] = 1
            rows["src_slot0"][r] = slot
            rows["dst_slot0"][r] = (r + 1) % c0
            writes.append((r, (r + 1) % c0))
            reads.append((r, slot))
    grants, takes = _assign_credits(n_rounds, c0, writes, reads)
    rows["grant0"], rows["take0"] = grants, takes
    return RingProgram(
        kind="fwd", topology="uni", n_inter=1, n_intra=world,
        slots=(c0,), channels=("cw",), copy_in=((0, 0),),
        rows={k: tuple(v) for k, v in rows.items()},
        rot_inter=(0,) * n_rounds, rot_intra=tuple(range(n_rounds)))


def _compile_fwd_bidi(world: int, slots: int, slots1: int) -> RingProgram:
    order = _bidi_order(world)
    n_rounds = len(order)
    assert n_rounds == world
    h_cw = sum(1 for d, _ in order if d == "cw") - 1
    h_ccw = sum(1 for d, _ in order if d == "ccw")
    c0 = min(slots, h_cw + 1) if h_cw else 1
    c0 = max(c0, 1)
    c1 = max(min(slots1, h_ccw + 1), 1) if h_ccw else 1
    rows = _blank_rows(n_rounds, FWD_COLS)
    rot = []
    writes0, reads0 = [(0, 0)], []
    writes1, reads1 = ([(0, 0)], []) if h_ccw else ([], [])
    copy_in = ((0, 0), (1, 0)) if h_ccw else ((0, 0),)
    for r, (d, j) in enumerate(order):
        bank = 0 if d == "cw" else 1
        c = c0 if bank == 0 else c1
        slot = j % c
        rot.append(j if d == "cw" else -j)
        rows["consume_bank"][r] = bank
        rows["consume_slot"][r] = slot
        rows["recv"][r] = int(j > 0)
        (reads0 if bank == 0 else reads1).append((r, slot))
        # onward send of the just-consumed chunk, same direction
        last = (j == h_cw) if d == "cw" else (j == h_ccw)
        if not last:
            dst = (j + 1) % c
            if bank == 0:
                rows["send0"][r] = 1
                rows["src_slot0"][r] = slot
                rows["dst_slot0"][r] = dst
                writes0.append((r, dst))
                reads0.append((r, slot))
            else:
                rows["send1"][r] = 1
                rows["src_slot1"][r] = slot
                rows["dst_slot1"][r] = dst
                writes1.append((r, dst))
                reads1.append((r, slot))
        # round 0 additionally launches the ccw stream from the bank-1 copy
        if r == 0 and h_ccw:
            rows["send1"][r] = 1
            rows["src_slot1"][r] = 0
            rows["dst_slot1"][r] = 1 % c1
            writes1.append((r, 1 % c1))
            reads1.append((r, 0))
    grants, takes = _assign_credits(n_rounds, c0, writes0, reads0)
    rows["grant0"], rows["take0"] = grants, takes
    if h_ccw:
        grants, takes = _assign_credits(n_rounds, c1, writes1, reads1)
        rows["grant1"], rows["take1"] = grants, takes
    channels = ("cw", "ccw") if h_ccw else ("cw",)
    slots_t = (c0, c1) if h_ccw else (c0,)
    return RingProgram(
        kind="fwd", topology="bidi", n_inter=1, n_intra=world,
        slots=slots_t, channels=channels, copy_in=copy_in,
        rows={k: tuple(v) for k, v in rows.items()},
        rot_inter=(0,) * n_rounds, rot_intra=tuple(rot))


def _compile_fwd_double(n_inter: int, n_intra: int, slots: int,
                        slots1: int, r_live=None) -> RingProgram:
    if slots1 < 2:
        raise ScheduleError(f"double ring needs >= 2 prefetch slots, "
                            f"got {slots1}")
    # dead-round elision: the double ring's visit order is cycle-major, so
    # a round's flat ring offset IS its index — an occupancy prefix of
    # r_live live offsets keeps exactly the first r_live rounds.  Every op
    # whose PURPOSE lies past the horizon goes with them: the intra send
    # feeding round r+1 >= r_live, and the whole inter prefetch of a cycle
    # whose first round (c+1)*n_intra >= r_live.
    n_rounds = n_inter * n_intra if r_live is None else r_live
    c0 = min(slots, n_intra)  # intra bank cycles within one cycle
    c1 = min(slots1, n_inter)
    rows = _blank_rows(n_rounds, FWD_COLS)
    rot_i, rot_s = [], []
    writes0, reads0 = [], []
    writes1, reads1 = [(0, 0)], []  # copy-in: cycle-0 base in prefetch slot 0
    for c in range(n_inter):
        base_slot = c % c1
        for s in range(n_intra):
            r = c * n_intra + s
            if r >= n_rounds:
                break
            rot_i.append(c)
            rot_s.append(s)
            if s == 0:
                # consume the cycle base from the prefetch bank
                rows["consume_bank"][r] = 1
                rows["consume_slot"][r] = base_slot
                rows["recv"][r] = int(c > 0)
                reads1.append((r, base_slot))
                if c < n_inter - 1 and (c + 1) * n_intra < n_rounds:
                    # the signature move: next cycle's base leaves NOW, one
                    # full intra-cycle before its first-step consume
                    rows["send1"][r] = 1
                    rows["src_slot1"][r] = base_slot
                    rows["dst_slot1"][r] = (c + 1) % c1
                    writes1.append((r, (c + 1) % c1))
                    reads1.append((r, base_slot))
                if n_intra > 1 and r + 1 < n_rounds:
                    # intra ring launch: base -> intra-right's bank-0 slot
                    rows["send0"][r] = 1
                    rows["src_bank0"][r] = 1
                    rows["src_slot0"][r] = base_slot
                    rows["dst_slot0"][r] = 1 % c0
                    writes0.append((r, 1 % c0))
                    reads1.append((r, base_slot))
            else:
                slot = s % c0
                rows["consume_slot"][r] = slot
                rows["recv"][r] = 1
                reads0.append((r, slot))
                if s < n_intra - 1 and r + 1 < n_rounds:
                    rows["send0"][r] = 1
                    rows["src_slot0"][r] = slot
                    rows["dst_slot0"][r] = (s + 1) % c0
                    writes0.append((r, (s + 1) % c0))
                    reads0.append((r, slot))
    grants, takes = _assign_credits(n_rounds, c0, writes0, reads0)
    rows["grant0"], rows["take0"] = grants, takes
    grants, takes = _assign_credits(n_rounds, c1, writes1, reads1)
    rows["grant1"], rows["take1"] = grants, takes
    return RingProgram(
        kind="fwd", topology="double", n_inter=n_inter, n_intra=n_intra,
        slots=(c0, c1), channels=("cw", "inter"), copy_in=((1, 0),),
        rows={k: tuple(v) for k, v in rows.items()},
        rot_inter=tuple(rot_i), rot_intra=tuple(rot_s))


# ---------------------------------------------------------------------------
# backward compiler: the q-side bundle replays the forward movement; the
# dq plan is layered on top


def compile_bwd(topology: str, n_intra: int, n_inter: int = 1, *,
                slots: int = 2, slots1: Optional[int] = None,
                dq_slots: Optional[int] = None,
                r_live: Optional[int] = None,
                wire: Optional[str] = None) -> RingProgram:
    """Compile a backward schedule: the bundle rotates exactly like the
    forward KV (same banks/channels/credits), and a dq plan rides along —
    one accumulating ring per direction, each one hop behind its bundle,
    with a direct return-home RDMA at the end (see module docstring).

    r_live: occupancy truncation (see compile_fwd).  The backward's roles
    flip — the q bundle rotates past resident KV — so a live-offset
    PREFIX {0..K} means the bundle must visit offsets 0..K of the OTHER
    direction: the truncated program rotates the bundle counter-clockwise
    for K hops (each device serves q-parts me, me+1, .., me+K in order)
    and the dq partial rides one hop behind on the same ccw stream, with
    a single +K return-home RDMA.  That is strictly fewer rounds, sends
    and credits than the dense program's round-0-plus-tail live set.
    uni/bidi only (a truncated bidi bwd uses the same single-direction
    program); the double bwd keeps its dense dq plan — its cycle-boundary
    folds are not prefix-truncatable — and relies on the in-kernel mask
    predication for dead rounds.  r_live == 1 is refused: the program
    would need a zero-offset self-home hop (callers route the self-only
    case to the scan ring).
    """
    world = n_inter * n_intra
    if wire not in WIRE_DTYPES:
        raise ScheduleError(f"unknown wire dtype {wire!r} "
                            f"(must be one of {WIRE_DTYPES})")
    if r_live is not None:
        if not (1 <= r_live <= world):
            raise ScheduleError(
                f"r_live must be in [1, world={world}], got {r_live}")
        if r_live < world and topology in ("uni", "bidi"):
            if r_live == 1:
                raise ScheduleError(
                    "bwd r_live truncation needs r_live >= 2 (a self-only "
                    "ring has no dq return hop)")
            prog = _compile_bwd_truncated(world, r_live, slots,
                                          slots if dq_slots is None
                                          else dq_slots)
            return prog if wire is None else replace(prog, wire=wire)
        r_live = None  # dense (r_live == world, or double: see docstring)
    fwd = compile_fwd(topology, n_intra, n_inter, slots=slots, slots1=slots1)
    n_rounds = fwd.n_rounds
    rows = {k: list(v) for k, v in fwd.rows.items()}
    for idx in range(FWD_COLS, BWD_COLS):
        rows[_COL_NAMES[idx]] = [0] * n_rounds
    dq_c = min(max(2, slots if dq_slots is None else dq_slots), n_rounds)
    world = fwd.world

    if topology in ("uni", "bidi"):
        order = ([("cw", j) for j in range(world)] if topology == "uni"
                 else _bidi_order(world))
        h = {"cw": 0, "ccw": 0}
        for d, j in order:
            h[d] = max(h[d], j)
        c_by = {"cw": min(dq_c, h["cw"] + 1) if h["cw"] else 1,
                "ccw": min(dq_c, h["ccw"] + 1) if h["ccw"] else 1}
        servings = {"cw": [], "ccw": []}
        for r, (d, j) in enumerate(order):
            bank = 0 if d == "cw" else 1
            c = c_by[d]
            slot = j % c
            rows["dq_bank"][r] = bank
            rows["dq_slot"][r] = slot
            # each direction's ring SEEDS at its first serving round (cw:
            # the self round j=0; ccw: j=1, the first ccw bundle) — no
            # partial is in flight yet there
            seed = j == (0 if d == "cw" else 1)
            rows["dq_recv"][r] = int(not seed)
            servings[d].append((r, slot, not seed))
            if j < h[d]:
                rows["dq_send"][r] = DQ_RING
                rows["dq_dst_slot"][r] = (j + 1) % c
            else:
                rows["dq_send"][r] = DQ_HOME
        for d, bank in (("cw", 0), ("ccw", 1)):
            if not servings[d]:
                continue
            grants, takes = _assign_dq_credits(n_rounds, servings[d])
            rows[f"dq_grant{bank}"] = grants
            rows[f"dq_take{bank}"] = takes
        if topology == "uni":
            dq_slots_t = (c_by["cw"],)
            homes = ((0, -h["cw"] % world),)
        else:
            dq_slots_t = ((c_by["cw"], c_by["ccw"]) if h["ccw"]
                          else (c_by["cw"],))
            homes = (((0, -h["cw"] % world), (0, h["ccw"]))
                     if h["ccw"] else ((0, -h["cw"] % world),))
    else:  # double
        n_i, n_s = fwd.n_inter, fwd.n_intra
        c0 = min(dq_c, n_s)
        c1 = min(2, n_i)
        servings0 = []  # intra dq ring
        servings1 = []  # inter (boundary) ping/pong accumulator
        for c in range(n_i):
            for s in range(n_s):
                r = c * n_s + s
                slot = s % c0
                rows["dq_slot"][r] = slot
                rows["dq_recv"][r] = int(s > 0)
                servings0.append((r, slot, s > 0))
                boundary = s == n_s - 1
                if not boundary:
                    rows["dq_send"][r] = DQ_RING
                    rows["dq_dst_slot"][r] = (s + 1) % c0
                else:
                    if c > 0:
                        rows["dqi_recv"][r] = 1
                        rows["dqi_slot"][r] = (c - 1) % c1
                        servings1.append((r, (c - 1) % c1, True))
                    if c < n_i - 1:
                        rows["dq_send"][r] = DQ_BOUNDARY
                        rows["dqi_dst_slot"][r] = c % c1
                    else:
                        rows["dq_send"][r] = DQ_FINAL
        grants, takes = _assign_dq_credits(n_rounds, servings0)
        rows["dq_grant0"], rows["dq_take0"] = grants, takes
        grants, takes = _assign_dq_credits(n_rounds, servings1)
        rows["dq_grant1"], rows["dq_take1"] = grants, takes
        dq_slots_t = (c0, c1)
        homes = ((1, 1),)  # composed inter+1, intra+1 final hop
    return RingProgram(
        kind="bwd", topology=topology, n_inter=fwd.n_inter,
        n_intra=fwd.n_intra, slots=fwd.slots, channels=fwd.channels,
        copy_in=fwd.copy_in, rows={k: tuple(v) for k, v in rows.items()},
        rot_inter=fwd.rot_inter, rot_intra=fwd.rot_intra,
        dq_slots=dq_slots_t, home_offsets=homes, wire=wire)


def _compile_bwd_truncated(world: int, r_live: int, slots: int,
                           dq_slots: int) -> RingProgram:
    """Occupancy-truncated backward: one ccw bundle stream, one ccw dq ring.

    Round j consumes the bundle of q-part me+j (rot_intra[j] = -j mod
    world): the bundle seeds locally (copy_in), travels ccw one hop per
    round, and stops after K = r_live - 1 hops — beyond that every q-part
    is outside the live band on every device, so the rounds are simply
    absent.  The dq partial for the held bundle accumulates one hop behind
    on the same stream (seeded at round 0, ring-forwarded ccw, merged on
    arrival), and at round K the finished partial — by then K devices
    ccw-forward of its owner — returns home with one +K cw RDMA
    (home_offsets (0, K)).  Credits come from the same assigners as every
    other program; the oracle proves delivery/credits/home on the export
    like any dense schedule."""
    n_rounds = r_live
    k_last = r_live - 1
    c0 = max(min(slots, r_live), 1)
    rows = _blank_rows(n_rounds, BWD_COLS)
    writes = [(0, 0)]  # copy-in = version 0 of slot 0
    reads = []
    for j in range(n_rounds):
        slot = j % c0
        rows["consume_slot"][j] = slot
        rows["recv"][j] = int(j > 0)
        reads.append((j, slot))
        if j < k_last:
            rows["send0"][j] = 1
            rows["src_slot0"][j] = slot
            rows["dst_slot0"][j] = (j + 1) % c0
            writes.append((j, (j + 1) % c0))
            reads.append((j, slot))
    grants, takes = _assign_credits(n_rounds, c0, writes, reads)
    rows["grant0"], rows["take0"] = grants, takes
    dq_c = min(max(2, dq_slots), r_live) if k_last else 1
    servings = []
    for j in range(n_rounds):
        slot = j % dq_c
        rows["dq_slot"][j] = slot
        rows["dq_recv"][j] = int(j > 0)
        servings.append((j, slot, j > 0))
        if j < k_last:
            rows["dq_send"][j] = DQ_RING
            rows["dq_dst_slot"][j] = (j + 1) % dq_c
        else:
            rows["dq_send"][j] = DQ_HOME
    grants, takes = _assign_dq_credits(n_rounds, servings)
    rows["dq_grant0"], rows["dq_take0"] = grants, takes
    return RingProgram(
        kind="bwd", topology="uni", n_inter=1, n_intra=world,
        slots=(c0,), channels=("ccw",), copy_in=((0, 0),),
        rows={k: tuple(v) for k, v in rows.items()},
        rot_inter=(0,) * n_rounds,
        rot_intra=tuple((world - j) % world for j in range(n_rounds)),
        dq_slots=(dq_c,), home_offsets=((0, k_last),))


# ---------------------------------------------------------------------------
# lowerings


def scan_events(program: RingProgram):
    """Lower to the scan ring's ordered collective stream — the oracle's
    (cls, axis, hops) vocabulary (analysis/oracle.py).  This is the stream
    `parallel/burst._fwd_impl` realizes with lax.ppermute for the uni and
    double topologies; bidi is a fused-only topology (the scan ring's
    ppermute is already asynchronous — there is nothing to counter-rotate
    around) but still lowers here so the verifier can account its hops."""
    ev = []
    if program.topology == "double":
        # row-driven so r_live-truncated programs account only the sends
        # they kept; identical to the legacy cycle-major enumeration for
        # dense programs (send1 precedes send0 within a round)
        for r in range(program.n_rounds):
            if program.rows["send1"][r]:
                ev.append(("pay", "inter", 1))
            if program.rows["send0"][r]:
                ev.append(("pay", "intra", 1))
        return ev
    if program.topology == "uni":
        # the truncated bwd program rotates its single stream ccw
        sign = -1 if program.channels == ("ccw",) else 1
        return [("pay", "intra", sign)] * (program.n_rounds - 1)
    # bidi: one event per send, signed direction via hops +-1
    for r in range(program.n_rounds):
        if program.rows["send0"][r]:
            ev.append(("pay", "intra", 1))
        if program.rows["send1"][r]:
            ev.append(("pay", "intra", -1))
    return ev


def hop_totals(program: RingProgram):
    """Per-axis payload hop totals of the compiled schedule — what
    `parallel/ring.ring_round_counts` reports per dispatch."""
    totals = {"intra": 0, "inter": 0}
    for cls, axis, hops in scan_events(program):
        totals[axis] += abs(hops)
    return totals


def quantized_operands(program: RingProgram) -> int:
    """Payload-bundle operands that carry a quantized wire encoding (and
    therefore an extra scale transfer per send site) under this program's
    wire dtype: fwd rotates k+v (both quantized); the bwd bundle rotates
    (delta|o, do, q, lse) of which lse is exempt — it stays fp32.  Zero
    when the program ships dense payloads."""
    if program.wire is None:
        return 0
    return 2 if program.kind == "fwd" else 3


def expected_remote_dma(program: RingProgram, operands_ch: int = 2) -> int:
    """Remote dma_start CALL SITES a ring kernel lowered from this
    program must contain.

    operands_ch: arrays per payload send (fwd: k+v = 2; bwd bundle: 4).
    Channel 0 contributes one site per (operand, src bank) it ever sources
    from; channel 1 one per operand; each dq bank one ring site (if it has
    ring sends) and one home/boundary/final site.

    A quantized program (program.wire) adds the scale sub-payload sites:
    one extra transfer per QUANTIZED operand per payload send site
    (quantized_operands — lse never quantizes), and every dq site doubles
    (the streamed dq partial is int8|fp8 + its refreshed per-block scale).
    The scale transfers ride the same semaphores and credits, so they add
    call sites but no schedule rows."""
    per_send = operands_ch + quantized_operands(program)
    n = 0
    src_banks0 = {program.rows["src_bank0"][r]
                  for r in range(program.n_rounds)
                  if program.rows["send0"][r]}
    n += per_send * len(src_banks0)
    if any(program.rows["send1"][r] for r in range(program.n_rounds)):
        n += per_send
    if program.kind == "bwd":
        dq_mult = 2 if program.wire is not None else 1
        kinds = {program.rows["dq_send"][r] for r in range(program.n_rounds)}
        for bank in range(program.n_dq_banks):
            ring = any(program.rows["dq_send"][r] == DQ_RING
                       and program.rows["dq_bank"][r] == bank
                       for r in range(program.n_rounds))
            n += int(ring) * dq_mult
        n += int(DQ_HOME in kinds and 0 in
                 {program.rows["dq_bank"][r] for r in range(program.n_rounds)
                  if program.rows["dq_send"][r] == DQ_HOME}) * dq_mult
        n += int(any(program.rows["dq_send"][r] == DQ_HOME
                     and program.rows["dq_bank"][r] == 1
                     for r in range(program.n_rounds))) * dq_mult
        n += int(DQ_BOUNDARY in kinds) * dq_mult
        n += int(DQ_FINAL in kinds) * dq_mult
    return n


# ---------------------------------------------------------------------------
# wire byte accounting — the ONE derivation of per-round ring bytes.  The
# obs dispatch counters (parallel/burst._note_dispatch) and the
# schedule-replay test (tests/test_wire_quant.py) both call this helper, so
# they cannot drift from each other by construction.


def wire_itemsize(wire: Optional[str], dense_itemsize: int = 4) -> int:
    """Bytes per element a rotating operand ships under a wire dtype."""
    if wire is None:
        return dense_itemsize
    if wire not in WIRE_DTYPES:
        raise ScheduleError(f"unknown wire dtype {wire!r}")
    return 1


def wire_round_bytes(pass_: str, wire: Optional[str], *, b: int, n: int,
                     n_kv: int, s: int, d: int, opt_comm: bool = True,
                     itemsize: int = 4, d_v: Optional[int] = None
                     ) -> Dict[str, int]:
    """Per-ROUND per-DEVICE payload bytes each rotating stream ships over
    one ring hop, by stream name:

      fwd  {"kv": ...}                    the k+v chunk (+ scales)
      bwd  {"bundle": ..., "dq": ...}     the q-side bundle (+ scales) and
                                          the streamed dq partial

    `itemsize` is the dense per-element width of the caller's tensors
    (4 for the fp32 comm-floor rows — the acceptance baseline).  Quantized
    streams ship 1 byte/element plus one fp32 scale per quantized block at
    the scan ring's granularity (fwd: per (batch, kv head); bundle: per
    (batch, head) per operand; dq: per (batch, head)); lse always ships
    b*n*s fp32.  Shapes are PER-SHARD.  `d` is the width of q and k (and
    dq), `d_v` of v, o and do (None: d).

    This is THE byte derivation: the permutes of the compiled program ship
    it (tests/test_wire_quant.py reads them by scope), and the burstcost
    roofline re-derives it
    independently (analysis/costmodel.stream_bytes) with the
    cost-model-consistent lint rule pinning the two equal — a change
    here that the model doesn't mirror fails the gate."""
    wi = wire_itemsize(wire, itemsize)
    scale_b = 0 if wire is None else 4
    d_v = d if d_v is None else d_v
    if pass_ == "fwd":
        kv = b * n_kv * s * (d + d_v) * wi + 2 * b * n_kv * scale_b
        return {"kv": kv}
    if pass_ != "bwd":
        raise ValueError(f"pass_ must be 'fwd' or 'bwd', got {pass_!r}")
    # bundle: (delta | o), do, q quantize; lse stays fp32
    first = b * n * s * (4 if wire is None else 1) if opt_comm \
        else b * n * s * d_v * wi
    bundle = (first + b * n * s * (d + d_v) * wi  # do + q
              + b * n * s * 4                      # lse (fp32, exempt)
              + 3 * b * n * scale_b)               # delta|o, do, q scales
    dq = b * n * s * d * (4 if wire is None else 1) + b * n * scale_b
    return {"bundle": bundle, "dq": dq}


def partition_for_round(program: RingProgram, r: int, inter_rank, intra_rank):
    """Traced (or host) partition id of the payload consumed at round r:
    the IR's rotation pair applied to this device's ring coordinates.
    Matches parallel/ring.partition_at_round for the uni/double visit
    order (asserted in tests/test_schedule_ir.py)."""
    n_i, n_s = program.n_inter, program.n_intra
    ci = (inter_rank - program.rot_inter[r]) % n_i
    si = (intra_rank - program.rot_intra[r]) % n_s
    return ci * n_s + si


def bank_dirs(program: RingProgram) -> Tuple[str, ...]:
    """Human labels of the slot banks, in bank order."""
    return program.channels
