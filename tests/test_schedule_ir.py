"""Schedule IR + compiler unit tests (parallel/schedule.py).

Host-side only — no mesh, no kernels: the compiled programs' structure,
the oracle's simulation proofs across the topology matrix, and the
lowering helpers the scan ring consumes.  The proof-has-teeth mutations
ride tests/test_analysis.py.
"""

import numpy as np
import pytest

from burst_attn_tpu.analysis import oracle, ringcheck
from burst_attn_tpu.parallel import ring, schedule


# ---------------------------------------------------------------------------
# compiler output structure


def test_uni_consume_slots_cycle_round_mod_slots():
    """The "uni" programs consume their slots in the closed form of the
    hand-built schedules the IR replaced: round r reads slot r mod slots,
    forward and backward."""
    for world, slots in ((2, 2), (4, 2), (8, 2), (8, 3), (8, 8)):
        want = (np.arange(world) % min(slots, world)).tolist()
        fwd = schedule.compile_fwd("uni", world, slots=slots)
        assert list(fwd.col(schedule.CONSUME_SLOT)) == want, (world, slots)
        bwd = schedule.compile_bwd("uni", world, slots=slots, dq_slots=slots)
        assert list(bwd.col(schedule.CONSUME_SLOT)) == want, (world, slots)


def test_table_shape_and_spec_columns():
    fwd = schedule.compile_fwd("bidi", 8)
    t = fwd.to_table()
    assert t.shape == (8, schedule.FWD_COLS) and t.dtype == np.int32
    assert (t[:, :5] == 0).all()  # spec cols are the kernel entry's
    bwd = schedule.compile_bwd("double", 4, 2)
    t = bwd.to_table()
    assert t.shape == (8, schedule.BWD_COLS) and t.dtype == np.int32


def test_bidi_consumes_every_partition_once_with_split_directions():
    prog = schedule.compile_fwd("bidi", 5)
    # offsets 0, +1, -1, +2, -2: cw carries ceil(4/2)=2, ccw 2
    assert prog.rot_intra == (0, 1, -1, 2, -2)
    assert prog.channels == ("cw", "ccw")
    banks = prog.rows["consume_bank"]
    assert banks == (0, 0, 1, 0, 1)


def test_bidi_small_worlds_degrade():
    # world=2 has a single neighbor: only the cw channel exists
    prog = schedule.compile_fwd("bidi", 2)
    assert prog.channels == ("cw",)
    assert prog.n_banks == 1


def test_double_prefetch_leaves_cycle_start():
    prog = schedule.compile_fwd("double", 4, 2)
    send1 = prog.rows["send1"]
    # the inter hop leaves at round 0 (cycle 0's first round), one full
    # intra cycle before its round-4 consume — the signature move
    assert send1[0] == 1 and not any(send1[1:])
    assert prog.rows["consume_bank"][4] == 1
    assert prog.rows["recv"][4] == 1


def test_hop_totals_match_ring_round_counts():
    for n_inter, n_intra in ((1, 8), (2, 4), (4, 2)):
        topo = "uni" if n_inter == 1 else "double"
        prog = schedule.compile_fwd(topo, n_intra, n_inter)
        totals = schedule.hop_totals(prog)
        rounds, intra, inter = ring.ring_round_counts(n_inter, n_intra)
        assert rounds == prog.n_rounds
        assert totals["intra"] == intra
        assert totals["inter"] == inter


def test_scan_events_uni_stream():
    prog = schedule.compile_fwd("uni", 6)
    assert schedule.scan_events(prog) == [("pay", "intra", 1)] * 5


def test_partition_for_round_matches_schedule_oracle():
    """The IR's rotation pair replays oracle.ring_schedule (the host-side
    expectation parallel/ring.partition_at_round is tested against) for
    the uni and double visit orders."""
    for n_inter, n_intra in ((1, 8), (2, 4)):
        topo = "uni" if n_inter == 1 else "double"
        prog = schedule.compile_fwd(topo, n_intra, n_inter)
        want = oracle.ring_schedule(n_intra, n_inter)
        for d in range(prog.world):
            ci, si = divmod(d, n_intra)
            got = [schedule.partition_for_round(prog, r, ci, si)
                   for r in range(prog.n_rounds)]
            assert got == list(want[d]), (topo, d)


def test_expected_remote_dma_census():
    """The per-program remote-DMA call-site census burstlint's traced
    checks pin against the real kernels (values asserted here so a silent
    census regression cannot hide inside the verifier)."""
    cases = (
        ("uni", 1, 4, 2, 6),
        ("bidi", 1, 4, 4, 11),
        ("bidi", 1, 8, 4, 12),
        ("double", 2, 2, 4, 11),
        ("double", 2, 4, 6, 15),
    )
    for topo, n_inter, n_intra, want_fwd, want_bwd in cases:
        pf = schedule.compile_fwd(topo, n_intra, n_inter)
        pb = schedule.compile_bwd(topo, n_intra, n_inter)
        assert schedule.expected_remote_dma(pf, 2) == want_fwd, (topo, n_intra)
        assert schedule.expected_remote_dma(pb, 4) == want_bwd, (topo, n_intra)


def test_bwd_bidi_ccw_ring_seeds_at_first_ccw_round():
    prog = schedule.compile_bwd("bidi", 5)
    rows = prog.rows
    ccw_rounds = [r for r in range(prog.n_rounds)
                  if rows["dq_bank"][r] == 1]
    assert rows["dq_recv"][ccw_rounds[0]] == 0  # seed, nothing in flight
    assert all(rows["dq_recv"][r] == 1 for r in ccw_rounds[1:])


def test_bwd_home_offsets():
    uni = schedule.compile_bwd("uni", 8)
    assert uni.home_offsets == ((0, 1),)  # w-1 hops forward = 1 back
    bidi = schedule.compile_bwd("bidi", 8)
    # cw partial ends h_cw hops out, ccw partial h_ccw hops the other way
    assert bidi.home_offsets == ((0, (-4) % 8), (0, 3))
    dbl = schedule.compile_bwd("double", 4, 2)
    assert dbl.home_offsets == ((1, 1),)  # composed inter+1, intra+1


# ---------------------------------------------------------------------------
# compile-time obligations / error paths


def test_compiler_rejects_bad_shapes():
    with pytest.raises(schedule.ScheduleError):
        schedule.compile_fwd("spiral", 4)
    with pytest.raises(schedule.ScheduleError):
        schedule.compile_fwd("uni", 4, slots=1)
    with pytest.raises(schedule.ScheduleError):
        schedule.compile_fwd("bidi", 4, 2)  # bidi is single-axis
    with pytest.raises(schedule.ScheduleError):
        schedule.compile_fwd("double", 4, 1)  # nothing to nest
    with pytest.raises(schedule.ScheduleError):
        schedule.compile_fwd("double", 4, 2, slots1=1)
    # truncation is no longer rejected on bidi: a truncated bidi degrades
    # to the cw-only uni prefix program (the live offsets fit one
    # direction; the bidi interleave's tail is not a round prefix)
    bidi_cut = schedule.compile_fwd("bidi", 4, r_live=2)
    assert bidi_cut.export() == schedule.compile_fwd(
        "uni", 4, r_live=2).export()
    with pytest.raises(schedule.ScheduleError):
        schedule.compile_fwd("bidi", 4, r_live=0)  # still bounds-checked


def test_credit_assignment_catches_unread_overwrite():
    with pytest.raises(schedule.ScheduleError, match="aliased"):
        schedule._assign_credits(
            3, 2, writes=[(0, 0), (1, 0), (2, 0)], reads=[(2, 0)])


def test_credit_assignment_catches_ambiguous_grant_round():
    # both slots' last pre-overwrite read land on round 1: one grant round
    # cannot free credits for two slots of the same bank
    with pytest.raises(schedule.ScheduleError, match="two slots"):
        schedule._assign_credits(
            4, 2, writes=[(0, 0), (0, 1), (2, 0), (2, 1)],
            reads=[(1, 0), (1, 1), (3, 0), (3, 1)])


# ---------------------------------------------------------------------------
# simulation proofs over the whole emitted matrix (the same configs
# burstlint re-proves on every run)


@pytest.mark.parametrize("topology,n_inter,n_intra,kw",
                         ringcheck.IR_PROOF_CONFIGS)
def test_every_emitted_program_is_simulation_proven(topology, n_inter,
                                                    n_intra, kw):
    for compiler in (schedule.compile_fwd, schedule.compile_bwd):
        prog = compiler(topology, n_intra, n_inter, **kw)
        oracle.verify_ring_program(prog.export())  # raises on violation


def test_windowed_uni_truncation_program():
    prog = schedule.compile_fwd("uni", 8, r_live=3)
    assert prog.n_rounds == 3
    oracle.verify_ring_program(prog.export())
    assert schedule.hop_totals(prog) == {"intra": 2, "inter": 0}
