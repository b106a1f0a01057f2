"""burstcost (analysis/costmodel.py): the ragged plan against the real
gate, the closed-form algebra against brute force, and the roofline's
inputs against the production counters.

The lint family (analysis/costcheck.py) runs the full-matrix versions of
these identities in the gate; here the model is additionally proven
against ground truth the gate can't afford: dense-mask pair counts and a
sweep of the ragged dispatch predicate.
"""

import json

import numpy as np
import pytest

from burst_attn_tpu.analysis import costmodel as cm
from burst_attn_tpu.ops import tuning
from burst_attn_tpu.ops.pallas_flash import VMEM_LIMIT
from burst_attn_tpu.parallel import schedule as sched

WORLD = 8


# ---------------------------------------------------------------------------
# FLOPs: closed forms vs brute force and vs the devstats per-round sum


def _dense_mask_pairs(S, causal, window):
    rows = np.arange(S)[:, None]
    cols = np.arange(S)[None, :]
    live = np.ones((S, S), dtype=bool)
    if causal:
        live &= cols <= rows
    if window is not None:
        live &= cols > rows - window
    return int(live.sum())


@pytest.mark.parametrize("causal,window", [(False, None), (True, None),
                                           (True, 7), (True, 40),
                                           (True, 200)])
def test_pass_pairs_matches_dense_mask(causal, window):
    s, world = 16, 4
    got = cm.pass_pairs("contig", s, world, causal=causal, window=window)
    assert got == _dense_mask_pairs(s * world, causal, window)


@pytest.mark.parametrize("layout", ["zigzag", "striped", "contig"])
@pytest.mark.parametrize("topology", sched.TOPOLOGIES)
def test_devstats_sum_equals_closed_form(layout, topology):
    """The ring visits every (q chunk, kv chunk) pair exactly once across
    devices x rounds, so the per-round devstats algebra summed over the
    compiled program equals the global closed form — for every layout and
    topology."""
    s = 256
    program = cm.compile_program("fwd", topology, WORLD)
    closed = cm.pass_pairs(layout, s, WORLD, causal=True)
    assert cm.devstats_pass_pairs(program, layout, s, causal=True) == closed


def test_devstats_sum_exact_on_elided_program():
    """Dead rounds attend zero pairs, so elision changes the schedule but
    not the pair total — the identity the 'including elided rounds'
    acceptance clause pins."""
    from burst_attn_tpu.ops.masks import live_round_prefix

    s, window = 256, 384
    r_live = live_round_prefix("contig", s, WORLD, causal=True,
                               window=window)
    assert r_live < WORLD  # the window genuinely elides rounds
    program = cm.compile_program("fwd", "uni", WORLD, r_live=r_live)
    assert program.n_rounds < WORLD
    closed = cm.pass_pairs("contig", s, WORLD, causal=True, window=window)
    summed = cm.devstats_pass_pairs(program, "contig", s, causal=True,
                                    window=window)
    assert closed == summed == _dense_mask_pairs(s * WORLD, True, window)


def test_pass_flops_matches_bench_convention():
    """4*d per pair fwd (devstats algebra), x2.5 bwd — benchmark.flops'
    convention at the causal headline shape."""
    from benchmarks.benchmark import flops

    b, n, d, world, s = 1, 32, 128, 8, 8192
    seq = world * s
    fwd = cm.pass_flops("fwd", "zigzag", b=b, n=n, s=s, d=d, world=world,
                        causal=True)
    bench_fwd = flops(b, seq, n, d, mode="fwd", causal=True)
    # bench uses S^2/2; the closed form is exact S(S+1)/2
    assert abs(fwd - bench_fwd) / bench_fwd < 1e-4
    bwd = cm.pass_flops("bwd", "zigzag", b=b, n=n, s=s, d=d, world=world,
                        causal=True)
    assert bwd == pytest.approx(2.5 * fwd)


# ---------------------------------------------------------------------------
# ICI bytes: the model's independent derivation vs the production formula


@pytest.mark.parametrize("pass_", cm.PASSES)
@pytest.mark.parametrize("wire", sched.WIRE_DTYPES)
@pytest.mark.parametrize("opt_comm", [True, False])
@pytest.mark.parametrize("itemsize", [4, 2])
def test_stream_bytes_matches_wire_round_bytes(pass_, wire, opt_comm,
                                               itemsize):
    kw = dict(b=2, n=16, n_kv=4, s=1024, d=128, opt_comm=opt_comm,
              itemsize=itemsize)
    assert cm.stream_bytes(pass_, wire, **kw) == \
        sched.wire_round_bytes(pass_, wire, **kw)


def test_send_census_matches_hop_totals_fwd():
    """Payload sends read off the op table agree with scan_events'
    hop census for every topology."""
    for topo in sched.TOPOLOGIES:
        program = cm.compile_program("fwd", topo, WORLD)
        census = cm.send_census(program)
        totals = sched.hop_totals(program)
        assert census["send0"] + census["send1"] == sum(totals.values())


def test_uni_bwd_dq_hops_are_world():
    """The dense uni bwd dq stream add-and-forwards W-1 ring hops plus
    the final home hop — the chain the comm floor times."""
    program = cm.compile_program("bwd", "uni", WORLD)
    assert cm.send_census(program)["dq"] == WORLD


# ---------------------------------------------------------------------------
# the ragged VMEM plan vs its dispatch gate


def test_ragged_plan_matches_ragged_supported():
    """The model's ragged plan reproduces ragged_supported's admission
    across fitting and oversized pages (structural constraints held
    satisfiable so the VMEM clause decides)."""
    from burst_attn_tpu.ops.ragged_paged import ragged_supported

    cases = [dict(d_head=128, page=128, group=1, quantized=False),
             dict(d_head=128, page=256, group=8, quantized=True),
             dict(d_head=256, page=512, group=8, quantized=False),
             dict(d_head=128, page=131072, group=1, quantized=False),
             dict(d_head=256, page=131072, group=8, quantized=False)]
    for c in cases:
        plan = cm.ragged_plan_bytes(**c)
        reason = ragged_supported(
            n_kv_heads=1, n_q_heads=c["group"], q_tokens=8,
            d_head=c["d_head"], page=c["page"], quantized=c["quantized"],
            interpret=True)
        if plan <= VMEM_LIMIT:
            assert reason is None, (c, reason)
        else:
            assert reason is not None and "VMEM plan" in reason, c


# ---------------------------------------------------------------------------
# roofline


def test_hw_peaks_match_train_smoke_table():
    """costmodel.HW's bf16 peaks are the SAME numbers train_smoke's MFU
    denominator uses — two tables, one truth, pinned here instead of a
    cross-import in product code."""
    from benchmarks.train_smoke import PEAK_BF16

    for gen, peak in PEAK_BF16.items():
        assert cm.HW[gen].peak_flops == peak, gen
    assert cm.HW["default"] == cm.HW["v5e"]


def test_predict_floors_sane_and_ordered():
    kw = dict(b=1, n=32, n_kv=32, s=8192, d=128, world=WORLD,
              generation="v5e")
    t_comm, t_compute = cm.predict_floors("fwd", **kw)
    assert t_comm > 0 and t_compute > 0
    # quantized wire moves ~4x fewer bytes down the same hops
    t_comm_q, _ = cm.predict_floors("fwd", wire="int8", **kw)
    assert t_comm_q < t_comm / 2
    # the bidi ring splits the chain across two concurrent directions
    t_comm_bidi, _ = cm.predict_floors("fwd", topology="bidi", **kw)
    assert t_comm_bidi < t_comm
    # bwd moves the (delta, do, q, lse) bundle + dq: strictly more comm
    t_comm_bwd, _ = cm.predict_floors("bwd", **kw)
    assert t_comm_bwd > t_comm


def test_predict_metric_prices_headlines_only():
    v = cm.predict_metric(
        "flash-attn fwd+bwd TFLOPs/s/chip @ seq=65536 causal bf16")
    assert v is not None and 0 < v <= cm.HW["v5e"].peak_flops / 1e12
    assert cm.predict_metric("serve.ttft_p99 s @ ragged chunk=16") is None
    assert cm.predict_metric("TFLOPs/s/chip but no seq") is None


def test_check_regression_predicted_field(tmp_path):
    """The --summary-json verdicts carry the model's analytic expectation
    for priceable metrics and null otherwise."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "check_regression", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts", "check_regression.py"))
    cr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cr)
    assert cr.predicted_value(
        "flash-attn fwd+bwd TFLOPs/s/chip @ seq=65536 causal bf16") > 0
    assert cr.predicted_value("serve.tokens_per_s @ ragged") is None

    (tmp_path / "headline.json").write_text(json.dumps(
        {"metric": "x fwd TFLOPs/s/chip @ seq=16384 causal bf16",
         "value": 100.0}))
    spath = tmp_path / "summary.json"
    rc = cr.main(["--headline", str(tmp_path / "headline.json"),
                  "--history", str(tmp_path / "none_*.json"),
                  "--summary-json", str(spath)])
    assert rc == 0
    rep = json.loads(spath.read_text())
    assert all("predicted" in v for v in rep["verdicts"])
    assert rep["verdicts"][0]["predicted"] > 0


# ---------------------------------------------------------------------------
# cost table export


def test_cost_table_covers_matrix_and_fits():
    t = cm.cost_table()
    assert t["schema"] == "burstcost-v3"
    combos = {(r["generation"], r["topology"], r["wire"], r["pass"])
              for r in t["rows"]}
    expected = {(g, topo, w, p) for g in tuning.generations()
                for topo in sched.TOPOLOGIES for w in sched.WIRE_DTYPES
                for p in cm.PASSES}
    assert combos == expected
    assert all(r["fits"] for r in t["ragged"])
    # roofline fields are populated and internally consistent
    for r in t["rows"]:
        assert r["flops"] > 0 and r["ici_bytes"] > 0 and r["hbm_bytes"] > 0
        assert r["t_compute_s"] > 0 and r["t_comm_s"] > 0
