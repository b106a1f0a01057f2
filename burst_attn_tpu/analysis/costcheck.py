"""cost-*: the static resource & roofline verifier (burstlint family 4).

Three rules back onto burstcost (analysis/costmodel.py), which prices
with no device in hand:

  kernel-vmem-budget     every ragged-paged serving shape fits its VMEM
                         plan under the Mosaic VMEM_LIMIT
  cost-model-consistent  the roofline's inputs agree with production
                         counters: closed-form pass pairs == the devstats
                         per-round pair algebra summed over the compiled
                         program (exactly, including elided rounds), and
                         the model's independent stream-bytes derivation
                         == schedule.wire_round_bytes (the single source
                         of the ring's payload bytes) over
                         pass x wire x opt_comm x itemsize
  tuning-table-sound     the raw tables obey the invariants dispatch
                         assumes: bwd blocks never resolve larger than
                         fwd, cliff clamps are monotone and in-budget,
                         blocks lane-aligned, aliases resolve, and later
                         generations never shrink the v5e-measured cliff
                         areas

The checks compute through the SAME resolution algebra production
dispatch runs (tuning.resolve_blocks(table=row), sched.compile_fwd/bwd)
so they watch real code, not a spec that can drift.  Pure host
arithmetic, well under a second.  Mutation coverage
(tests/test_analysis.py): a window-blind pair function and a fwd<bwd
table inversion each fire exactly one rule.
"""

from typing import List

from .core import Finding, rule
from . import costmodel as cm
from ..ops import tuning
from ..parallel import schedule as sched

rule("kernel-vmem-budget", "cost",
     "every ragged-paged serving shape's VMEM plan fits the Mosaic "
     "limit")(None)
rule("cost-model-consistent", "cost",
     "roofline FLOPs == devstats pair algebra over compiled programs "
     "(incl. elided rounds); model stream bytes == wire_round_bytes (the "
     "ring's byte formula)")(None)
rule("tuning-table-sound", "cost",
     "tuning tables obey dispatch's invariants: bwd blocks <= fwd, "
     "cliff clamps monotone + in-budget, blocks lane-aligned, aliases "
     "resolve")(None)

# the small consistency mesh: exact identities are shape-independent, so
# the gate proves them at a cheap shard size on the canonical 8-ring
_CONSIST_S, _CONSIST_WORLD = 512, 8


def _anchor(which: str):
    """Anchor findings at the production code whose numbers the model
    mirrors — where a fix (or a model update) goes."""
    import inspect

    try:
        if which == "ragged":
            from ..ops import ragged_paged
            fn = ragged_paged.ragged_supported
        elif which == "wire":
            fn = sched.wire_round_bytes
        elif which == "pairs":
            from ..ops import masks
            fn = masks.spec_pair_count
        else:  # "table"
            fn = tuning.block_defaults
        return inspect.getsourcefile(fn), inspect.getsourcelines(fn)[1]
    except (OSError, TypeError, ImportError):
        return "<trace>", 0


# ---------------------------------------------------------------------------
# kernel-vmem-budget


def check_vmem_budget() -> List[Finding]:
    """Every serving shape of the ragged matrix within the Mosaic limit."""
    findings: List[Finding] = []
    rag_f, rag_ln = _anchor("ragged")
    for cfgr in cm.RAGGED_MATRIX:
        pb = cm.ragged_plan_bytes(**cfgr)
        if pb > cm.VMEM_LIMIT:
            findings.append(Finding(
                rule="kernel-vmem-budget",
                message=(f"ragged {cfgr}: plan {pb} B exceeds "
                         f"VMEM_LIMIT {cm.VMEM_LIMIT} B"),
                file=rag_f, line=rag_ln))
    return findings


# ---------------------------------------------------------------------------
# cost-model-consistent


def check_cost_consistency(pair_fn=None) -> List[Finding]:
    """Pin the roofline's inputs to production counters.  `pair_fn`
    substitutes the devstats per-round pair twin (the mutation seam — a
    window-blind variant must fire); default is the production twin."""
    findings: List[Finding] = []
    s, world = _CONSIST_S, _CONSIST_WORLD
    pairs_f, pairs_ln = _anchor("pairs")
    cases = [(layout, topo, True, None)
             for layout in ("zigzag", "striped", "contig")
             for topo in sched.TOPOLOGIES]
    cases += [("zigzag", "uni", False, None),    # non-causal
              ("contig", "uni", True, 3 * s // 2)]  # windowed -> elision
    for layout, topo, causal, window in cases:
        r_live = None
        if window is not None:
            from ..ops.masks import live_round_prefix
            rl = live_round_prefix(layout, s, world, causal=causal,
                                   window=window)
            r_live = rl if rl < world else None
        program = cm.compile_program("fwd", topo, world, r_live=r_live)
        closed = cm.pass_pairs(layout, s, world, causal=causal,
                               window=window)
        summed = cm.devstats_pass_pairs(program, layout, s, causal=causal,
                                        window=window, pair_fn=pair_fn)
        if closed != summed:
            findings.append(Finding(
                rule="cost-model-consistent",
                message=(f"pair algebra split: closed form says {closed} "
                         f"attending pairs for {layout}/{topo} "
                         f"(causal={causal}, window={window}, s={s}, "
                         f"world={world}) but the devstats per-round sum "
                         f"over the compiled program says {summed} — the "
                         "roofline's FLOPs no longer match what the "
                         "devstats counters will integrate"),
                file=pairs_f, line=pairs_ln))
    wire_f, wire_ln = _anchor("wire")
    for pass_ in cm.PASSES:
        for wire in sched.WIRE_DTYPES:
            for opt_comm in (True, False):
                for itemsize in (4, 2):
                    kw = dict(b=2, n=16, n_kv=4, s=s, d=128,
                              opt_comm=opt_comm, itemsize=itemsize)
                    ours = cm.stream_bytes(pass_, wire, **kw)
                    theirs = sched.wire_round_bytes(pass_, wire, **kw)
                    if ours != theirs:
                        findings.append(Finding(
                            rule="cost-model-consistent",
                            message=(f"stream-bytes split for {pass_}/"
                                     f"{wire or 'fp32'}/opt_comm={opt_comm}"
                                     f"/itemsize={itemsize}: model says "
                                     f"{ours}, wire_round_bytes (the "
                                     f"ring's byte formula) says "
                                     f"{theirs}"),
                            file=wire_f, line=wire_ln))
    return findings


# ---------------------------------------------------------------------------
# tuning-table-sound


def check_tuning_sound(table=None) -> List[Finding]:
    """The invariants dispatch assumes of every table row.  `table`
    narrows to one injected row (the mutation seam — a fwd<bwd inversion
    must fire); default sweeps the real tables."""
    findings: List[Finding] = []
    tab_f, tab_ln = _anchor("table")

    def bad(msg):
        findings.append(Finding(rule="tuning-table-sound", message=msg,
                                file=tab_f, line=tab_ln))

    gens = ((("<injected>", table),) if table is not None
            else tuple((g, tuning.generation_row(g))
                       for g in tuning.generations()))
    for gen, row in gens:
        # the raw bwd cliff area never exceeds the fwd one: the bwd step
        # keeps more live per block pair, so its cliff cannot sit higher
        if row.bwd_cliff_area > row.fwd_cliff_area:
            bad(f"{gen}: bwd_cliff_area={row.bwd_cliff_area} > "
                f"fwd_cliff_area={row.fwd_cliff_area} — the bwd pass "
                "keeps more of a block pair live than the fwd")
        # resolved view (the SAME algebra dispatch runs): bwd blocks never
        # resolve larger than fwd
        rb = tuning.resolve_blocks(table=row)
        if rb.block_q_bwd > rb.block_q or rb.block_kv_bwd > rb.block_kv:
            bad(f"{gen}: resolved bwd blocks "
                f"({rb.block_q_bwd},{rb.block_kv_bwd}) exceed fwd "
                f"({rb.block_q},{rb.block_kv})")
        for field in ("fwd_block_q", "fwd_block_kv", "fwd_block_kv_compute",
                      "bwd_block_q", "bwd_block_kv", "band_block"):
            v = getattr(row, field)
            if v is not None and (v <= 0 or v % 128):
                bad(f"{gen}: {field}={v} is not a positive multiple of "
                    "the 128-lane tile")
        # cliff clamp monotone: never grows kv, never exceeds the area
        # (above the 128-lane floor), and larger areas never shrink the
        # result.  Probed at areas below AND above the blocks' product so
        # the clamping branch itself is exercised; the tuning logger is
        # muted around the probes (the warning is for real dispatches).
        bq, bkv = row.bwd_block_q, row.bwd_block_kv
        prev = 0
        if tuning._cliff_ok():
            continue  # BURST_ALLOW_CLIFF=1: the clamp is deliberately off
        was_disabled = tuning.logger.disabled
        tuning.logger.disabled = True
        try:
            for area in sorted({bq * 128, bq * bkv // 2, bq * bkv,
                                row.bwd_cliff_area}):
                _, kv = tuning._clamp_cliff(bq, bkv, area, "cost-lint")
                if kv > bkv:
                    bad(f"{gen}: _clamp_cliff grew block_kv {kv} past the "
                        f"table's {bkv} at area {area}")
                if bq * kv > max(area, 128 * bq):
                    bad(f"{gen}: _clamp_cliff result {kv} violates area "
                        f"{area} (and is not the 128-lane floor)")
                if kv < prev:
                    bad(f"{gen}: _clamp_cliff not monotone in area: {kv} "
                        f"at area {area} < {prev} at the smaller area")
                prev = kv
        finally:
            tuning.logger.disabled = was_disabled
    if table is None:
        for alias, target in tuning._KIND_ALIASES:
            if target not in tuning._TABLE:
                bad(f"alias {alias!r} -> {target!r} resolves outside the "
                    "tuning table")
        v5e = tuning.generation_row("v5e")
        for gen in ("v5p", "v4"):
            row = tuning.generation_row(gen)
            if (row.fwd_cliff_area < v5e.fwd_cliff_area
                    or row.bwd_cliff_area < v5e.bwd_cliff_area):
                bad(f"{gen}: cliff areas shrink below the v5e-measured "
                    "floor — bigger-VMEM generations never clamp harder")
    return findings


def check_all() -> List[Finding]:
    findings = check_vmem_budget()
    findings += check_cost_consistency()
    findings += check_tuning_sound()
    return findings
