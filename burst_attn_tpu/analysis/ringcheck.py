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

import dataclasses
import inspect
from dataclasses import dataclass
from typing import Dict, List, Optional

from .core import Finding, rule
from . import numerics, oracle
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
     "prefetch distance >= one intra cycle, dq exactly-once return-home")(None)


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

    def trace(cfg):
        fwd = shard_map(lambda q, k, v: burst._fwd_impl(q, k, v, cfg),
                        mesh=mesh, in_specs=(spec4,) * 3,
                        out_specs=(spec4, spec3), check_vma=False)
        bwd = shard_map(
            lambda q, k, v, o, lse, do: burst._bwd_impl(cfg, q, k, v, o, lse,
                                                        do),
            mesh=mesh, in_specs=(spec4,) * 4 + (spec3, spec4),
            out_specs=(spec4,) * 3, check_vma=False)
        return (jax.make_jaxpr(fwd)(q, q, q),
                jax.make_jaxpr(bwd)(q, q, q, q, lse, q))

    jx_fwd, jx_bwd = trace(cfg)
    findings += verify_traced_ring(
        jx_fwd, kind="fwd", n_inter=n_inter,
        n_intra=n_intra, r_live=r_live, leaves_pay=2, axis_map=axis_map,
        where=f"{entry.name} fwd", anchor=_anchor(burst._fwd_impl),
        window=truncating)
    findings += verify_traced_ring(
        jx_bwd, kind="bwd", n_inter=n_inter,
        n_intra=n_intra, r_live=r_live, leaves_pay=4, axis_map=axis_map,
        where=f"{entry.name} bwd", anchor=_anchor(burst._bwd_impl),
        window=truncating)

    # ---- the same shard programs on a quantized wire: every K/V, bundle
    # and dq payload meets its scale before it is accumulated ----
    for wire in ("int8", "fp8"):
        for kind, jx, impl in zip(
                ("fwd", "bwd"),
                trace(dataclasses.replace(cfg, wire_dtype=wire)),
                (burst._fwd_impl, burst._bwd_impl)):
            findings += numerics.check_wire_trace(
                jx, where=f"{entry.name} {kind} wire={wire}",
                anchor=_anchor(impl))
    return findings


def _remote_dma_starts(closed_jaxpr):
    """Cross-chip dma_start equations of a trace (servecheck's census: the
    serving kernels must hold none)."""
    from .jaxpr_tools import iter_eqns

    return [e for e in iter_eqns(closed_jaxpr)
            if e.primitive.name == "dma_start"
            and e.params.get("device_id_type") is not None
            and "LOGICAL" in str(e.params["device_id_type"]).upper()]


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
    findings += verify_ulysses()
    return findings
