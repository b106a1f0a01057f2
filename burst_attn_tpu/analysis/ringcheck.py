"""Jaxpr-level ring verifiers (burstlint family 1).

Abstractly traces the burst forward/backward shard programs
(parallel/burst._fwd_impl / _bwd_impl) and the ulysses shard program under
a matrix of simulated mesh topologies, extracts every collective from the
jaxpr, and checks the structural ring invariants against the host-side
schedule oracle (analysis/oracle.py):

  ring-rotation     every ppermute is a bijective uniform rotation of its
                    axis (single Hamiltonian cycle for the unit hops the
                    schedule pins; multi-hop jumps only where the oracle
                    stream places them), and never sits under a data-
                    dependent cond or a while loop.
  ring-hops         per-axis per-leaf payload hop totals equal the
                    schedule-oracle transition counts.
  ring-order        the full ordered event stream matches the oracle
                    stream — this pins the double-ring prefetch exactly
                    one intra-cycle early and the add-and-forward fold
                    points.
  dq-return-home    the backward's dq event substream matches the oracle
                    stream that verify_dq_returns_home PROVES returns
                    every contribution to its owner.
  window-truncation the windowed contig ring's live-round prefix matches
                    the independent dense-band derivation, so truncation
                    never references a dead round and never drops a live
                    one.

Tracing is abstract (jax.make_jaxpr on ShapeDtypeStructs): nothing
executes, no TPU is needed, and the whole matrix runs in seconds on CPU.
"""

import inspect
from dataclasses import dataclass
from typing import Dict, List, Optional

from .core import Finding, rule
from . import oracle
from .jaxpr_tools import collect_collectives

# registered rule docs (checkers live in verify_* below; the names must
# exist in the registry for --disable and the report)
rule("ring-rotation", "jaxpr",
     "every ppermute is a bijective uniform rotation, not under cond/while")(None)
rule("ring-hops", "jaxpr",
     "per-axis payload hop totals match the schedule oracle")(None)
rule("ring-order", "jaxpr",
     "ordered collective stream matches the oracle (prefetch distance)")(None)
rule("dq-return-home", "jaxpr",
     "bwd dq ring stream matches the proven return-home schedule")(None)
rule("window-truncation", "jaxpr",
     "occupancy truncation (window band / max_segment_len reach) matches "
     "the independent dense live-set derivation")(None)
rule("fused-ring-schedule", "jaxpr",
     "every schedule the compiler emits (uni, bidi, double; fwd AND bwd) "
     "is simulation-proven: delivery of the declared rotation, hop "
     "counts, per-slot overwrite-before-read safety per direction, "
     "prefetch distance >= one intra cycle, dq exactly-once return-home; "
     "the legacy uni slot views still match the independent derivation")(None)
rule("fused-ring-fused", "jaxpr",
     "fused fwd/bwd issue zero XLA collectives and exactly the compiled "
     "program's remote-copy census (schedule.expected_remote_dma: per-"
     "direction payload channels, dq rings, return-home hops), with "
     "fp32-accum numerics — for uni, bidi, double and multi-axis meshes")(None)


@dataclass
class RingEntry:
    name: str
    axes: Dict[str, int]          # mesh axes, e.g. {"sp": 4} / {"inter":2,...}
    layout: str
    causal: bool
    window: Optional[int] = None
    max_segment_len: Optional[int] = None
    case_split: bool = True
    s_local: int = 16

    @property
    def world(self):
        import numpy as np

        return int(np.prod(list(self.axes.values())))


ENTRIES = [
    RingEntry("flat-zigzag-causal", {"sp": 4}, "zigzag", True),
    RingEntry("flat-striped-causal", {"sp": 4}, "striped", True),
    RingEntry("flat-contig-noncausal", {"sp": 4}, "contig", False),
    RingEntry("flat-zigzag-nosplit", {"sp": 4}, "zigzag", True,
              case_split=False),
    RingEntry("double-2x4-zigzag", {"inter": 2, "intra": 4}, "zigzag", True),
    RingEntry("window-contig", {"sp": 4}, "contig", True, window=20),
    RingEntry("segments-contig", {"sp": 4}, "contig", True,
              max_segment_len=16),
]


def _anchor(fn):
    """file:line of a traced entry point, for clickable findings."""
    try:
        path = inspect.getsourcefile(fn)
        line = inspect.getsourcelines(fn)[1]
        return path, line
    except (OSError, TypeError):
        return "<trace>", 0


def _leaf_encoded(events, classify, leaves_of, findings, where, anchor,
                  axis_map):
    """Run-length encode extracted events into the oracle's per-leaf form.

    classify(event) -> "pay" | "dq"; leaves_of(cls) -> leaf fan-out the
    pytree ppermute expands each logical hop into; axis_map translates
    mesh axis names to the oracle's {"intra", "inter"} vocabulary."""
    path, line = anchor
    runs = []
    for ev in events:
        if ev.prim != "ppermute":
            continue
        if ev.in_cond or ev.in_while:
            findings.append(Finding(
                rule="ring-rotation", file=path, line=line,
                message=f"{where}: ppermute under "
                        f"{'cond' if ev.in_cond else 'while'} — ring "
                        "collectives must be unconditional (deadlock/"
                        "divergence hazard across ranks)"))
        if ev.hops is None:
            findings.append(Finding(
                rule="ring-rotation", file=path, line=line,
                message=f"{where}: ppermute on axis {ev.axis!r} is not a "
                        f"bijective uniform rotation: perm={ev.perm}"))
            continue
        key = (classify(ev), axis_map.get(ev.axis, ev.axis), ev.hops)
        if runs and runs[-1][0] == key:
            runs[-1][1] += 1
        else:
            runs.append([key, 1])
    out = []
    for (cls, axis, hops), count in runs:
        leaves = leaves_of(cls)
        if count % leaves:
            findings.append(Finding(
                rule="ring-hops", file=path, line=line,
                message=f"{where}: {count} consecutive {cls} ppermutes on "
                        f"axis {axis!r} is not a multiple of the {leaves} "
                        "payload leaves — a leaf is missing a rotation"))
            continue
        out.append((cls, axis, hops, count // leaves))
    return out


def _match_streams(got, want, rule_name, where, findings, anchor,
                   only_cls=None):
    if only_cls is not None:
        got = [r for r in got if r[0] == only_cls]
        want = [r for r in want if r[0] == only_cls]
    if got != want:
        path, line = anchor
        findings.append(Finding(
            rule=rule_name, file=path, line=line,
            message=f"{where}: collective stream mismatch — expected "
                    f"{want}, traced {got}"))


def _check_totals(got_runs, expected, where, findings, anchor):
    path, line = anchor
    totals = {"intra": 0, "inter": 0}
    for cls, axis, hops, count in got_runs:
        if cls != "pay":
            continue
        totals[axis] += hops * count
    for ax in ("intra", "inter"):
        want = expected.get(ax, 0)
        if totals[ax] != want:
            findings.append(Finding(
                rule="ring-hops", file=path, line=line,
                message=f"{where}: payload rotated {totals[ax]} {ax} hops, "
                        f"schedule oracle expects {want}"))


def verify_traced_ring(closed_jaxpr, *, kind: str, n_inter: int, n_intra: int,
                       r_live=None, leaves_pay: int, axis_map,
                       where: str, anchor, window: bool = False
                       ) -> List[Finding]:
    """Run the ring rules on one already-traced shard program.

    kind: "fwd" | "bwd".  Shared by verify_ring_entry (tracing the real
    implementation) and the mutation tests (tracing seeded-bad rings);
    the oracle streams are recomputed — and the bwd one re-proven — here,
    so a caller cannot accidentally verify against a stale schedule."""
    findings: List[Finding] = []
    classify = (lambda ev: "dq" if (ev.dtype == "float32" and ev.rank == 4)
                else "pay")
    ev = collect_collectives(closed_jaxpr)
    got = _leaf_encoded(ev, classify,
                        lambda cls: 1 if cls == "dq" else leaves_pay,
                        findings, where, anchor, axis_map)
    if kind == "fwd":
        want = oracle.encode_runs(oracle.fwd_stream(n_inter, n_intra, r_live))
        _match_streams(got, want, "ring-order", where, findings, anchor)
        _check_totals(got, oracle.expected_hop_totals(n_inter, n_intra,
                                                      r_live),
                      where, findings, anchor)
        if window and r_live is not None:
            got_intra = sum(hops * cnt for cls, ax, hops, cnt in got
                            if cls == "pay" and ax == "intra")
            if got_intra != r_live - 1:
                findings.append(Finding(
                    rule="window-truncation", file=anchor[0], line=anchor[1],
                    message=f"{where}: fwd issues {got_intra} intra hops "
                            f"but the band mask proves {r_live} live rounds "
                            f"({r_live - 1} hops) — truncation references a "
                            "dead round or drops a live one"))
    else:
        oracle.verify_dq_returns_home(n_inter, n_intra, r_live)
        want = oracle.encode_runs(oracle.bwd_stream(n_inter, n_intra, r_live))
        _match_streams(got, want, "ring-order", where, findings, anchor)
        _match_streams(got, want, "dq-return-home", where, findings, anchor,
                       only_cls="dq")
        if window and r_live is not None:
            jump = [r for r in got if r[0] == "pay" and r[2] > 1]
            want_jump = n_intra - (r_live - 1)
            if r_live > 1 and want_jump > 1 and (
                    len(jump) != 1 or jump[0][2] != want_jump):
                findings.append(Finding(
                    rule="window-truncation", file=anchor[0], line=anchor[1],
                    message=f"{where}: bwd dead-middle jump should be one "
                            f"{want_jump}-hop permute, traced {jump}"))
    return findings


def verify_ring_entry(entry: RingEntry) -> List[Finding]:
    """Trace one topology config and run every ring rule on it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from ..parallel import burst
    from jax import shard_map

    findings: List[Finding] = []
    axes = entry.axes
    names = tuple(axes)
    if len(names) == 2:
        inter_axis, intra_axis = names
        n_inter, n_intra = axes[inter_axis], axes[intra_axis]
    else:
        inter_axis, intra_axis = None, names[0]
        n_inter, n_intra = 1, axes[intra_axis]
    axis_map = {intra_axis: "intra"}
    if inter_axis is not None:
        axis_map[inter_axis] = "inter"

    devs = jax.devices()
    if len(devs) < entry.world:
        raise RuntimeError(
            f"analysis needs {entry.world} simulated devices "
            f"(XLA_FLAGS=--xla_force_host_platform_device_count=8); "
            f"have {len(devs)}")
    mesh = Mesh(np.asarray(devs[:entry.world]).reshape(
        tuple(axes.values())), names)

    cfg = burst.BurstConfig(
        causal=entry.causal, layout=entry.layout, intra_axis=intra_axis,
        inter_axis=inter_axis, backend="jnp", window=entry.window,
        max_segment_len=entry.max_segment_len,
        case_split=entry.case_split)

    b, n, d = 1, 2, 8
    seq = entry.world * entry.s_local
    S = jax.ShapeDtypeStruct
    q = S((b, n, seq, d), jnp.bfloat16)
    lse = S((b, n, seq), jnp.float32)
    spec4 = P(None, None, names if len(names) > 1 else names[0], None)
    spec3 = P(None, None, names if len(names) > 1 else names[0])

    # expected streams — the bwd one is only trusted after its proof.
    # The truncated live set comes from the INDEPENDENT dense derivations
    # (live_rounds_contig / live_rounds_contig_seg), not from the
    # implementation's masks.live_round_prefix — agreement between the two
    # is exactly what window-truncation proves.
    r_live = None
    truncating = (entry.window is not None
                  or entry.max_segment_len is not None)
    if truncating and n_inter == 1:
        if entry.window is not None:
            live = oracle.live_rounds_contig(seq, entry.world, entry.window)
        else:
            live = oracle.live_rounds_contig_seg(seq, entry.world,
                                                 entry.max_segment_len)
        if live != set(range(len(live))):
            findings.append(Finding(
                rule="window-truncation", file=_anchor(burst._fwd_impl)[0],
                line=_anchor(burst._fwd_impl)[1],
                message=f"{entry.name}: live round set {sorted(live)} is not "
                        "a prefix — static truncation cannot express it"))
            return findings
        r_live = len(live)

    # ---- forward ----
    fwd = shard_map(lambda q, k, v: burst._fwd_impl(q, k, v, cfg),
                    mesh=mesh, in_specs=(spec4,) * 3,
                    out_specs=(spec4, spec3), check_vma=False)
    findings += verify_traced_ring(
        jax.make_jaxpr(fwd)(q, q, q), kind="fwd", n_inter=n_inter,
        n_intra=n_intra, r_live=r_live, leaves_pay=2, axis_map=axis_map,
        where=f"{entry.name} fwd", anchor=_anchor(burst._fwd_impl),
        window=truncating)

    # ---- backward ----
    bwd = shard_map(
        lambda q, k, v, o, lse, do: burst._bwd_impl(cfg, q, k, v, o, lse, do),
        mesh=mesh, in_specs=(spec4,) * 4 + (spec3, spec4),
        out_specs=(spec4,) * 3, check_vma=False)
    findings += verify_traced_ring(
        jax.make_jaxpr(bwd)(q, q, q, q, lse, q), kind="bwd", n_inter=n_inter,
        n_intra=n_intra, r_live=r_live, leaves_pay=4, axis_map=axis_map,
        where=f"{entry.name} bwd", anchor=_anchor(burst._bwd_impl),
        window=truncating)
    return findings


def _remote_dma_starts(closed_jaxpr):
    from .jaxpr_tools import iter_eqns

    return [e for e in iter_eqns(closed_jaxpr)
            if e.primitive.name == "dma_start"
            and e.params.get("device_id_type") is not None
            and "LOGICAL" in str(e.params["device_id_type"]).upper()]


def verify_fused_fwd_trace(closed_jaxpr, *, where: str, anchor,
                           expected_dma: int = 2) -> List[Finding]:
    """fused-ring-fused checks on one traced fused FORWARD shard program.

    The trace must contain ZERO XLA collectives (the ring lives entirely
    inside the kernel) and exactly `expected_dma` remote dma_start call
    sites — schedule.expected_remote_dma of the compiled program (the
    classic uni ring's k+v pair is 2; a bidi ring doubles it, the double
    ring adds the inter-prefetch channel); more would double-send, fewer
    would starve a stream — the kernel's dots must pass the
    fp32-accum/lse-fp32 contract, and any quantized wire payloads must
    pass the scale-handling proof (numerics.check_wire_trace: every
    int8/fp8 dequant meets its per-block scale multiply before
    accumulation; vacuous on dense traces)."""
    from . import numerics

    findings: List[Finding] = []
    path, line = anchor
    colls = [e for e in collect_collectives(closed_jaxpr)
             if e.prim in ("ppermute", "all_to_all")]
    if colls:
        findings.append(Finding(
            rule="fused-ring-fused", file=path, line=line,
            message=f"{where}: fused forward issues XLA collectives "
                    f"{[(e.prim, e.axis) for e in colls]} — the ring "
                    "must live entirely inside the kernel"))
    remote = _remote_dma_starts(closed_jaxpr)
    if len(remote) != expected_dma:
        findings.append(Finding(
            rule="fused-ring-fused", file=path, line=line,
            message=f"{where}: expected exactly {expected_dma} remote "
                    f"dma_starts (the compiled program's census), traced "
                    f"{len(remote)}"))
    findings += numerics.check_trace(closed_jaxpr, where=where, anchor=anchor)
    findings += numerics.check_wire_trace(closed_jaxpr, where=where,
                                          anchor=anchor)
    return findings


def verify_fused_bwd_trace(closed_jaxpr, *, where: str, anchor,
                           expected_dma: int = 6) -> List[Finding]:
    """fused-ring-fused checks on one traced fused BACKWARD shard program.

    Shared by verify_fused_ring (tracing the real dispatch) and the
    mutation tests (tracing seeded-bad programs): the trace must contain
    ZERO XLA collectives (the two rotating streams live entirely inside
    the kernel) and exactly `expected_dma` remote dma_starts — for the
    classic uni ring 6: 4 for the q-side bundle (delta|o, do, q, lse),
    1 for the streamed dq ring hop, 1 for the dq return-home hop; other
    topologies derive theirs from schedule.expected_remote_dma of the
    compiled program.  More would double-send, fewer would starve a
    stream — the kernel's dots must pass the fp32-accum/lse-fp32
    contract, and quantized wire payloads the scale-handling proof
    (numerics.check_wire_trace; vacuous on dense traces)."""
    from . import numerics

    findings: List[Finding] = []
    path, line = anchor
    colls = [e for e in collect_collectives(closed_jaxpr)
             if e.prim in ("ppermute", "all_to_all")]
    if colls:
        findings.append(Finding(
            rule="fused-ring-fused", file=path, line=line,
            message=f"{where}: fused backward issues XLA collectives "
                    f"{[(e.prim, e.axis) for e in colls]} — both the "
                    "bundle and the dq ring must live inside the kernel"))
    remote = _remote_dma_starts(closed_jaxpr)
    if len(remote) != expected_dma:
        findings.append(Finding(
            rule="fused-ring-fused", file=path, line=line,
            message=f"{where}: expected exactly {expected_dma} remote "
                    f"dma_starts (bundle operands + dq ring/boundary + "
                    f"return-home), traced {len(remote)}"))
    findings += numerics.check_trace(closed_jaxpr, where=where, anchor=anchor)
    findings += numerics.check_wire_trace(closed_jaxpr, where=where,
                                          anchor=anchor)
    return findings


# (topology, n_inter, n_intra, compile kwargs) matrix of compiler-emitted
# programs burstlint simulation-proves on every run — fwd AND bwd for each.
# The proof obligation rides the compiler: any new topology must land here.
IR_PROOF_CONFIGS = (
    ("uni", 1, 2, {}),
    ("uni", 1, 4, {}),
    ("uni", 1, 8, {}),
    ("uni", 1, 8, {"slots": 3}),
    ("uni", 1, 8, {"slots": 8}),
    ("bidi", 1, 3, {}),
    ("bidi", 1, 4, {}),
    ("bidi", 1, 5, {}),
    ("bidi", 1, 8, {}),
    ("bidi", 1, 8, {"slots": 3, "slots1": 2}),
    ("double", 2, 2, {}),
    ("double", 2, 4, {}),
    ("double", 4, 2, {}),
    ("double", 2, 4, {"slots": 3, "slots1": 3}),
    ("double", 3, 3, {}),
    # occupancy-elided programs (r_live < world): the schedules a windowed
    # or length-bounded packed-segment contig ring compiles to after dead-
    # round elision.  verify_ring_programs proves these with the matching
    # live-offset set: the program must serve EXACTLY offsets {0..r_live-1}
    # — keeping a dead offset or dropping a live one both fire.  (The
    # double-ring BWD ignores r_live by design — its interleaved visit
    # order makes the live set a non-prefix, so dead rounds stay in the
    # program and the kernel's mask predication zeroes them.)
    ("uni", 1, 8, {"r_live": 3}),
    ("uni", 1, 8, {"r_live": 2}),
    ("uni", 1, 4, {"r_live": 3}),
    ("bidi", 1, 8, {"r_live": 3}),
    ("bidi", 1, 5, {"r_live": 2}),
    ("bidi", 1, 8, {"r_live": 4, "slots": 3}),
    ("double", 2, 4, {"r_live": 3}),
    ("double", 4, 2, {"r_live": 5}),
)


def verify_elided_program(prog_export: dict, r_live: int, *, where: str,
                          anchor=None) -> List[Finding]:
    """fused-ring-schedule, elision obligation: an occupancy-compiled
    program claiming live prefix {0..r_live-1} must serve EXACTLY those
    ring offsets — a compiler that fails to elide a dead round (wasted
    RDMA, possible garbage reads) or elides a live one (dropped attention
    mass) both fire.  Shared by verify_ring_programs (proving the real
    compiler's matrix) and the mutation tests (proving seeded-bad programs
    are caught)."""
    if anchor is None:
        from ..parallel import schedule as sched

        anchor = _anchor(sched.compile_fwd)
    findings: List[Finding] = []
    try:
        oracle.verify_ring_program(prog_export,
                                   live_deltas=tuple(range(r_live)))
    except AssertionError as e:
        findings.append(Finding(
            rule="fused-ring-schedule", file=anchor[0], line=anchor[1],
            message=f"{where}: elision proof failed: {e}"))
    return findings


def verify_ring_programs() -> List[Finding]:
    """fused-ring-schedule, IR family: every program the schedule compiler
    emits across the topology matrix is proven by direct simulation
    (analysis/oracle.verify_ring_program) — payload delivery of the
    declared rotation, per-slot overwrite-before-read safety per direction
    under a maximally-ahead sender, the double ring's >= one-intra-cycle
    prefetch distance, and (bwd) the dq streams' exactly-once return-home
    with all `world` contributions.  r_live configs additionally prove the
    served-offset set equals the live prefix (dead rounds elided, live
    rounds kept) and that elision strictly shrinks the remote-DMA census
    vs the dense compile of the same topology.

    Every row is ALSO recompiled with wire="int8" and proven again, plus
    the credit-neutrality obligation of the wire-precision layer: scale
    sub-payloads ride the SAME slot credits as their payloads (second DMA
    on the same semaphore pair), so the op table, slot banks, and copy-in
    list must be bit-identical to the dense-wire compile while the
    remote-DMA census strictly grows (the extra scale call sites)."""
    import numpy as np

    from ..parallel import schedule as sched

    findings: List[Finding] = []
    anchor_ir = _anchor(sched.compile_fwd)
    for topology, n_inter, n_intra, kw in IR_PROOF_CONFIGS:
        r_live = kw.get("r_live")
        for kind, compiler in (("fwd", sched.compile_fwd),
                               ("bwd", sched.compile_bwd)):
            tag = (f"{kind} {topology} {n_inter}x{n_intra}"
                   f"{' ' + str(kw) if kw else ''}")
            try:
                prog = compiler(topology, n_intra, n_inter, **kw)
            except sched.ScheduleError as e:
                findings.append(Finding(
                    rule="fused-ring-schedule", file=anchor_ir[0],
                    line=anchor_ir[1],
                    message=f"{tag}: compiler refused a supported "
                            f"topology: {e}"))
                continue
            # double-ring bwd keeps the dense program under r_live by
            # design (non-prefix visit order; in-kernel mask predication
            # covers the dead rounds) — prove it as dense
            elide = (r_live is not None
                     and not (kind == "bwd" and topology == "double"))
            if elide:
                findings += verify_elided_program(
                    prog.export(), r_live, where=tag, anchor=anchor_ir)
                dense_kw = {k: w for k, w in kw.items() if k != "r_live"}
                dense = compiler(topology, n_intra, n_inter, **dense_kw)
                payload = 2 if kind == "fwd" else 4
                got = sched.expected_remote_dma(prog, payload)
                ref = sched.expected_remote_dma(dense, payload)
                if prog.n_rounds >= dense.n_rounds:
                    findings.append(Finding(
                        rule="fused-ring-schedule", file=anchor_ir[0],
                        line=anchor_ir[1],
                        message=f"{tag}: elided program keeps "
                                f"{prog.n_rounds} rounds, dense has "
                                f"{dense.n_rounds} — nothing was elided"))
                if got > ref:
                    findings.append(Finding(
                        rule="fused-ring-schedule", file=anchor_ir[0],
                        line=anchor_ir[1],
                        message=f"{tag}: elided remote-DMA census {got} "
                                f"exceeds the dense census {ref}"))
            else:
                try:
                    oracle.verify_ring_program(prog.export())
                except AssertionError as e:
                    findings.append(Finding(
                        rule="fused-ring-schedule", file=anchor_ir[0],
                        line=anchor_ir[1],
                        message=f"{tag}: simulation proof failed: {e}"))

            # ---- wire-precision recompile: credit neutrality ----
            prog_w = compiler(topology, n_intra, n_inter, wire="int8", **kw)
            try:
                oracle.verify_ring_program(
                    prog_w.export(),
                    live_deltas=tuple(range(r_live)) if elide else None)
            except AssertionError as e:
                findings.append(Finding(
                    rule="fused-ring-schedule", file=anchor_ir[0],
                    line=anchor_ir[1],
                    message=f"{tag} wire=int8: simulation proof "
                            f"failed: {e}"))
            if not (np.array_equal(np.asarray(prog_w.to_table()),
                                   np.asarray(prog.to_table()))
                    and tuple(prog_w.slots) == tuple(prog.slots)
                    and list(prog_w.copy_in) == list(prog.copy_in)):
                findings.append(Finding(
                    rule="fused-ring-schedule", file=anchor_ir[0],
                    line=anchor_ir[1],
                    message=f"{tag} wire=int8: op table / slot banks / "
                            "copy-in differ from the dense compile — "
                            "scale sub-payloads must ride the SAME slot "
                            "credits, never new schedule columns"))
            payload = 2 if kind == "fwd" else 4
            got_w = sched.expected_remote_dma(prog_w, payload)
            ref_d = sched.expected_remote_dma(prog, payload)
            if got_w <= ref_d:
                findings.append(Finding(
                    rule="fused-ring-schedule", file=anchor_ir[0],
                    line=anchor_ir[1],
                    message=f"{tag} wire=int8: remote-DMA census {got_w} "
                            f"does not exceed the dense census {ref_d} — "
                            "the scale streams' extra call sites are "
                            "missing from the expectation"))
    return findings


def verify_fused_ring() -> List[Finding]:
    """Fused ring (ops/fused_ring.py + ops/fused_ring_bwd.py) rules.

    Schedule family: the slot schedule the kernel consumes (exported by
    parallel/ring.fused_slot_schedule and delivered via scalar prefetch) is
    matched against the oracle's independent derivation, and the oracle
    PROVES — by simulating a maximally-ahead sender against the capacity
    handshake — neighbor-only delivery of ring_schedule, exactly world-1
    hops per chunk, and that no slot is overwritten before its last read.

    Jaxpr family: the fused forward shard program is traced abstractly on a
    simulated mesh and must contain ZERO XLA collectives (ppermute /
    all_to_all / psum on the ring payload — the whole point of the fused
    path) and exactly 2 remote dma_starts inside the kernel (one per
    operand per hop; more would double-send, fewer would starve the ring);
    the kernel's dots are also run through the fp32-accum/lse-fp32
    numerics contract."""
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from ..ops import fused_ring as fr
    from ..parallel import burst, ring
    from jax import shard_map

    findings: List[Finding] = []
    anchor_plan = _anchor(ring.fused_slot_schedule)
    for world, slots in ((2, 2), (4, 2), (8, 2), (8, 3), (8, 8)):
        got = [int(x) for x in ring.fused_slot_schedule(world, slots)]
        want = oracle.fused_slot_schedule(world, slots)
        if got != want:
            findings.append(Finding(
                rule="fused-ring-schedule", file=anchor_plan[0],
                line=anchor_plan[1],
                message=f"world={world} slots={slots}: exported slot "
                        f"schedule {got} != oracle derivation {want}"))
            continue
        try:
            oracle.verify_fused_ring(world, slots, got)
        except AssertionError as e:
            findings.append(Finding(
                rule="fused-ring-schedule", file=anchor_plan[0],
                line=anchor_plan[1],
                message=f"world={world} slots={slots}: schedule proof "
                        f"failed: {e}"))

    # ---- bwd schedule family: the bundle + dq twin streams ----
    anchor_bwd_plan = _anchor(ring.fused_bwd_slot_schedule)
    for world, slots in ((2, 2), (4, 2), (8, 2), (8, 3), (8, 8)):
        got = [int(x) for x in ring.fused_bwd_slot_schedule(world, slots)]
        want = oracle.fused_bwd_slot_schedule(world, slots)
        if got != want:
            findings.append(Finding(
                rule="fused-ring-schedule", file=anchor_bwd_plan[0],
                line=anchor_bwd_plan[1],
                message=f"world={world} slots={slots}: exported bwd slot "
                        f"schedule {got} != oracle derivation {want}"))
            continue
        try:
            oracle.verify_fused_ring_bwd(world, slots, got)
        except AssertionError as e:
            findings.append(Finding(
                rule="fused-ring-schedule", file=anchor_bwd_plan[0],
                line=anchor_bwd_plan[1],
                message=f"world={world} slots={slots}: bwd schedule proof "
                        f"failed: {e}"))

    # ---- traced structure of the fused forward ----
    anchor = _anchor(fr.fused_ring_fwd)
    devs = jax.devices()
    world = 4
    if len(devs) < world:
        raise RuntimeError(
            f"analysis needs {world} simulated devices "
            f"(XLA_FLAGS=--xla_force_host_platform_device_count=8); "
            f"have {len(devs)}")
    mesh = Mesh(np.asarray(devs[:world]), ("sp",))
    b, n, d, s_local = 1, 2, 8, 16
    S = jax.ShapeDtypeStruct
    q = S((b, n, s_local * world, d), jnp.bfloat16)
    spec4 = P(None, None, "sp", None)
    spec3 = P(None, None, "sp")
    # make_jaxpr never executes, but the dispatch's supported() gate reads
    # the interpret opt-in off-TPU — enable it for the trace only
    prev = os.environ.get("BURST_FUSED_INTERPRET")
    os.environ["BURST_FUSED_INTERPRET"] = "1"
    try:
        for layout, causal in (("zigzag", True), ("striped", True),
                               ("contig", False)):
            cfg = burst.BurstConfig(causal=causal, layout=layout,
                                    intra_axis="sp", backend="fused_ring")
            fwd = shard_map(lambda q, k, v: burst._fwd_impl(q, k, v, cfg),
                            mesh=mesh, in_specs=(spec4,) * 3,
                            out_specs=(spec4, spec3), check_vma=False)
            jx = jax.make_jaxpr(fwd)(q, q, q)
            where = f"fused-{layout}{'-causal' if causal else ''}"
            findings += verify_fused_fwd_trace(jx, where=where,
                                               anchor=anchor)

        # ---- traced structure of the fused backward ----
        from ..ops import fused_ring_bwd as frb

        anchor_bwd = _anchor(frb.fused_ring_bwd)
        lse = S((b, n, s_local * world), jnp.float32)
        for layout, causal, opt in (("zigzag", True, True),
                                    ("striped", True, False),
                                    ("contig", False, True)):
            cfg = burst.BurstConfig(causal=causal, layout=layout,
                                    intra_axis="sp", backend="fused_ring",
                                    optimize_bwd_comm=opt)
            bwd = shard_map(
                lambda q, k, v, o, l, do: burst._bwd_impl(
                    cfg, q, k, v, o, l, do),
                mesh=mesh, in_specs=(spec4,) * 4 + (spec3, spec4),
                out_specs=(spec4,) * 3, check_vma=False)
            jx = jax.make_jaxpr(bwd)(q, q, q, q, lse, q)
            where = (f"fused-bwd-{layout}{'-causal' if causal else ''}"
                     f"{'' if opt else '-rotate-o'}")
            findings += verify_fused_bwd_trace(jx, where=where,
                                               anchor=anchor_bwd)

        # ---- end-to-end: value_and_grad through the fused backend keeps
        # BOTH passes collective-free (the acceptance-criterion trace) ----
        cfg = burst.BurstConfig(causal=True, layout="zigzag",
                                intra_axis="sp", backend="fused_ring")

        def loss(q, k, v):
            o = burst._burst_attn_shard_plain(q, k, v, cfg)
            return jnp.sum(o.astype(jnp.float32))

        vg = shard_map(
            lambda q, k, v: jax.value_and_grad(loss, (0, 1, 2))(q, k, v),
            mesh=mesh, in_specs=(spec4,) * 3,
            out_specs=(P(), (spec4,) * 3), check_vma=False)
        jx = jax.make_jaxpr(vg)(q, q, q)
        colls = [e for e in collect_collectives(jx)
                 if e.prim in ("ppermute", "all_to_all")]
        if colls:
            findings.append(Finding(
                rule="fused-ring-fused", file=anchor_bwd[0],
                line=anchor_bwd[1],
                message="value_and_grad(fused_ring) issues XLA collectives "
                        f"{[(e.prim, e.axis) for e in colls]} — both passes "
                        "must live inside their kernels"))
    finally:
        if prev is None:
            os.environ.pop("BURST_FUSED_INTERPRET", None)
        else:
            os.environ["BURST_FUSED_INTERPRET"] = prev
    return findings


def verify_fused_topologies() -> List[Finding]:
    """fused-ring-fused, schedule-IR topologies: the configs the hand-built
    schedules could never express trace fused with ZERO XLA collectives and
    exactly the compiled program's remote-DMA census
    (schedule.expected_remote_dma) — fwd AND bwd each:

      bidi         counter-rotating flat ring (both ICI directions)
      double-flat  hierarchical double ring factored onto one ring axis
      double-2ax   the real two-axis ("inter", "intra") double ring
      multi-axis   pp x tp x sp training mesh, ring on "sp" with
                   cfg.mesh_axes proving the extra axes never alias
                   ring traffic

    bidi and double-flat are single-named-axis programs, so they trace
    under the interpret opt-in like the uni checks; the two-axis double
    ring and the multi-axis mesh cannot be discharged by the interpreter
    at all — BURST_FUSED_ASSUME_TPU forces the HARDWARE trace (full
    semaphore choreography, never executed), which is exactly the program
    a TPU would run, so the acceptance-criterion traces are checked
    off-TPU on every burstlint run."""
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from ..ops import fused_ring as fr
    from ..parallel import burst, schedule as sched
    from jax import shard_map

    findings: List[Finding] = []
    anchor_fwd = _anchor(fr.fused_ring_fwd)
    devs = jax.devices()
    if len(devs) < 8:
        raise RuntimeError(
            "analysis needs 8 simulated devices "
            "(XLA_FLAGS=--xla_force_host_platform_device_count=8); "
            f"have {len(devs)}")
    b, n, d, s_local = 1, 2, 8, 16
    S = jax.ShapeDtypeStruct

    # (name, env flag, mesh axes+sizes, ring axes, cfg extras, q specs).
    # The windowed-* / segments-* rows are OCCUPANCY-ELIDED programs: the
    # compiler truncates them to the live prefix, and the census assertion
    # below proves the elided program's remote-DMA call-site count never
    # exceeds — and for bidi strictly undercuts — the dense compile's.
    CASES = (
        ("bidi-4", "BURST_FUSED_INTERPRET", (("sp", 4),), ("sp", None),
         {"fused_topology": "bidi"}),
        ("double-flat-2x2", "BURST_FUSED_INTERPRET", (("sp", 4),),
         ("sp", None), {"fused_seq_factor": (2, 2)}),
        ("double-2ax-2x4", "BURST_FUSED_ASSUME_TPU",
         (("inter", 2), ("intra", 4)), ("intra", "inter"), {}),
        ("multiaxis-pp2-tp2-sp2", "BURST_FUSED_ASSUME_TPU",
         (("pp", 2), ("tp", 2), ("sp", 2)), ("sp", None),
         {"mesh_axes": (("pp", 2), ("tp", 2), ("sp", 2))}),
        ("windowed-uni-8", "BURST_FUSED_INTERPRET", (("sp", 8),),
         ("sp", None), {"layout": "contig", "window": 20}),
        ("windowed-bidi-8", "BURST_FUSED_INTERPRET", (("sp", 8),),
         ("sp", None), {"layout": "contig", "window": 20,
                        "fused_topology": "bidi"}),
        ("segments-uni-8", "BURST_FUSED_INTERPRET", (("sp", 8),),
         ("sp", None), {"layout": "contig", "max_segment_len": 16}),
        # wire-precision rows: the quantized traces must keep ZERO XLA
        # collectives, hit the wire-aware census (expected_remote_dma
        # counts the scale sub-payload call sites: fwd 2 -> 4 per channel,
        # bwd bundle 4 -> 7 and dq sites x2), and discharge the
        # scale-handling proof inside verify_fused_*_trace
        ("wire-int8-uni-4", "BURST_FUSED_INTERPRET", (("sp", 4),),
         ("sp", None), {"wire_dtype": "int8"}),
        ("wire-fp8-bidi-4", "BURST_FUSED_INTERPRET", (("sp", 4),),
         ("sp", None), {"wire_dtype": "fp8", "fused_topology": "bidi"}),
        ("wire-int8-double-2ax", "BURST_FUSED_ASSUME_TPU",
         (("inter", 2), ("intra", 4)), ("intra", "inter"),
         {"wire_dtype": "int8"}),
    )
    for name, env, axes, (intra_axis, inter_axis), extras in CASES:
        names = tuple(a for a, _ in axes)
        sizes = tuple(sz for _, sz in axes)
        mesh = Mesh(np.asarray(devs[:int(np.prod(sizes))]).reshape(sizes),
                    names)
        extras = dict(extras)
        layout = extras.pop("layout", "zigzag")
        cfg = burst.BurstConfig(
            causal=True, layout=layout, intra_axis=intra_axis,
            inter_axis=inter_axis, backend="fused_ring", **extras)
        ring_names = tuple(a for a in (inter_axis, intra_axis) if a)
        world = int(np.prod([dict(axes)[a] for a in ring_names]))
        seq = world * s_local
        q = S((b, n, seq, d), jnp.bfloat16)
        lse = S((b, n, seq), jnp.float32)
        seq_spec = ring_names if len(ring_names) > 1 else ring_names[0]
        spec4 = P(None, None, seq_spec, None)
        spec3 = P(None, None, seq_spec)
        n_inter = dict(axes).get(inter_axis, 1) if inter_axis else 1
        topo, t_i, t_s = fr.resolve_topology(cfg, world // n_inter, n_inter)
        elided = fr.occupancy_r_live(cfg, world, s_local) is not None
        prev = os.environ.get(env)
        os.environ[env] = "1"
        try:
            prog_f = fr._compile_for(cfg, topo, t_i, t_s, "fwd", s=s_local)
            fwd = shard_map(lambda q, k, v: burst._fwd_impl(q, k, v, cfg),
                            mesh=mesh, in_specs=(spec4,) * 3,
                            out_specs=(spec4, spec3), check_vma=False)
            findings += verify_fused_fwd_trace(
                jax.make_jaxpr(fwd)(q, q, q), where=f"fused-{name}-fwd",
                anchor=anchor_fwd,
                expected_dma=sched.expected_remote_dma(prog_f, 2))

            from ..ops import fused_ring_bwd as frb

            prog_b = fr._compile_for(cfg, topo, t_i, t_s, "bwd", s=s_local)
            bwd = shard_map(
                lambda q, k, v, o, l, do: burst._bwd_impl(
                    cfg, q, k, v, o, l, do),
                mesh=mesh, in_specs=(spec4,) * 4 + (spec3, spec4),
                out_specs=(spec4,) * 3, check_vma=False)
            findings += verify_fused_bwd_trace(
                jax.make_jaxpr(bwd)(q, q, q, q, lse, q),
                where=f"fused-{name}-bwd", anchor=_anchor(frb.fused_ring_bwd),
                expected_dma=sched.expected_remote_dma(prog_b, 4))
            if elided:
                # elision census: the dense compile of the SAME topology
                # must never undercut the elided program, and the bidi
                # ring must strictly shrink (its dead ccw bank vanishes)
                dense_f = fr._compile_for(cfg, topo, t_i, t_s, "fwd")
                dense_b = fr._compile_for(cfg, topo, t_i, t_s, "bwd")
                for pss, prog, dense, payload in (
                        ("fwd", prog_f, dense_f, 2),
                        ("bwd", prog_b, dense_b, 4)):
                    got = sched.expected_remote_dma(prog, payload)
                    ref = sched.expected_remote_dma(dense, payload)
                    strict = topo == "bidi"
                    if got > ref or (strict and got >= ref):
                        findings.append(Finding(
                            rule="fused-ring-fused", file=anchor_fwd[0],
                            line=anchor_fwd[1],
                            message=f"fused-{name}-{pss}: elided remote-"
                                    f"DMA census {got} does not undercut "
                                    f"the dense census {ref}"))
                    if prog.n_rounds >= dense.n_rounds:
                        findings.append(Finding(
                            rule="fused-ring-fused", file=anchor_fwd[0],
                            line=anchor_fwd[1],
                            message=f"fused-{name}-{pss}: elided program "
                                    f"keeps {prog.n_rounds} rounds, dense "
                                    f"has {dense.n_rounds}"))
        finally:
            if prev is None:
                os.environ.pop(env, None)
            else:
                os.environ[env] = prev
    return findings


def verify_ulysses() -> List[Finding]:
    """Ulysses a2a contract: exactly 4 all_to_alls (q, k, v in; o out) on
    the sequence axis, no ppermutes, none conditional."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from ..parallel import ulysses
    from jax import shard_map

    findings: List[Finding] = []
    anchor = _anchor(ulysses._ulysses_shard)
    devs = jax.devices()
    mesh = Mesh(np.asarray(devs[:4]), ("sp",))
    b, n, seq, d = 1, 4, 64, 8
    S = jax.ShapeDtypeStruct
    q = S((b, n, seq, d), jnp.bfloat16)
    spec = P(None, None, "sp", None)
    fn = shard_map(
        lambda q, k, v: ulysses._ulysses_shard(
            q, k, v, axis="sp", scale=1.0, causal=True, backend="jnp",
            block_q=None, block_kv=None),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False)
    ev = collect_collectives(jax.make_jaxpr(fn)(q, q, q))
    a2a = [e for e in ev if e.prim == "all_to_all"]
    pperm = [e for e in ev if e.prim == "ppermute"]
    if len(a2a) != 4 or any(e.axis != "sp" for e in a2a):
        findings.append(Finding(
            rule="ring-order", file=anchor[0], line=anchor[1],
            message=f"ulysses: expected exactly 4 all_to_alls on 'sp' "
                    f"(q,k,v scatter-heads + o gather), traced "
                    f"{[(e.prim, e.axis) for e in a2a]}"))
    if pperm:
        findings.append(Finding(
            rule="ring-order", file=anchor[0], line=anchor[1],
            message=f"ulysses: unexpected ppermute(s) in an all-to-all "
                    f"program: {[(e.axis, e.hops) for e in pperm]}"))
    if any(e.in_cond or e.in_while for e in a2a):
        findings.append(Finding(
            rule="ring-rotation", file=anchor[0], line=anchor[1],
            message="ulysses: all_to_all under cond/while — collectives "
                    "must be unconditional"))
    return findings


def check_all() -> List[Finding]:
    findings: List[Finding] = []
    for entry in ENTRIES:
        findings += verify_ring_entry(entry)
    findings += verify_ring_programs()
    findings += verify_fused_ring()
    findings += verify_fused_topologies()
    findings += verify_ulysses()
    return findings
