"""burstcost: analytic roofline for the ring, static plans for the ragged
serving kernel.

Following the IO-aware analyses of FlashAttention (arXiv 2205.14135) and the
CUTLASS case study (arXiv 2312.11918), the traffic of a ring pass is
statically derivable, so this module computes — with no device in hand —

  * an analytic ROOFLINE cost model: FLOPs from the masks.spec_pair_count
    closed forms (elided rounds contribute exactly zero — the identity the
    cost-model-consistent rule pins against the devstats pair algebra),
    ICI bytes from schedule.wire_round_bytes times the compiled program's
    send census, HBM bytes from the block plans — exported as a machine-
    readable table (python -m burst_attn_tpu.analysis --cost-json) that
    fleet/sim.py consumes as its replica cost function;

  * the VMEM plan of the ragged-paged serving kernel, mirroring its
    dispatch gate (ops/ragged_paged.ragged_supported), and the per-pool-
    dtype decode HBM pricing.

Cross-validation story (analysis/costcheck.py runs both at lint time):
against the devstats pair/flop counters (closed form == per-round sum over
the compiled program) and against the ring's byte formula
(stream_bytes == schedule.wire_round_bytes, the single derivation).

Everything here is host-side integer/float arithmetic over compiled
RingPrograms — no tracing, no devices; safe in the burstlint gate.
"""

from typing import Dict, List, NamedTuple, Optional, Tuple

from ..ops import tuning
from ..ops.masks import _host_round_pairs, live_round_prefix
from ..ops.pallas_flash import VMEM_LIMIT
from ..ops.ragged_paged import _block_rows
from ..parallel import schedule as sched

# ---------------------------------------------------------------------------
# hardware roofline constants

# Per-generation spec-sheet rates.  peak_flops is dense bf16 (MUST match
# benchmarks/train_smoke.PEAK_BF16 — pinned by tests/test_costmodel.py and
# the cost-model-consistent rule); hbm_bw is the published HBM bandwidth;
# ici_bw is the usable ONE-DIRECTION bandwidth of a single ring link —
# spec-sheet derived, not calibrated against a measured ring.
class HwSpec(NamedTuple):
    peak_flops: float  # dense bf16 FLOPs/s per chip
    hbm_bw: float      # HBM bytes/s per chip
    ici_bw: float      # one-direction ring-link bytes/s


HW: Dict[str, HwSpec] = {
    "v5e": HwSpec(197e12, 819e9, 45e9),
    "v5p": HwSpec(459e12, 2765e9, 90e9),
    "v4": HwSpec(275e12, 1228e9, 45e9),
    "v6": HwSpec(918e12, 1638e9, 90e9),
    # the default row tunes like a v5e (tuning._DEFAULT mirrors the v5e
    # measurements), so it prices like one
    "default": HwSpec(197e12, 819e9, 45e9),
}

# canonical 8-device benchmark shape class (bench.py headline: seq=65536 on
# an 8-ring, 32 heads, d=128 -> per-shard s=8192); the cost table prices
# every config at this shape
DEFAULT_SHAPE = dict(b=1, n=32, n_kv=32, s=8192, d=128)
DEFAULT_WORLD = 8
PASSES = ("fwd", "bwd")


def _hw(generation: str) -> HwSpec:
    if generation not in HW:
        raise KeyError(f"no HwSpec for generation {generation!r}")
    return HW[generation]


def _factor(world: int) -> Tuple[int, int]:
    """(n_inter, n_intra) the double ring factors a flat world into
    (smallest n_inter >= 2)."""
    n_i = 2
    while world % n_i or (world // n_i) < 2:
        n_i += 1
        if n_i > world // 2:
            raise ValueError(f"world {world} has no double-ring factoring")
    return n_i, world // n_i


def compile_program(pass_: str, topology: str, world: int,
                    wire: Optional[str] = None,
                    r_live: Optional[int] = None) -> sched.RingProgram:
    """The RingProgram of this config, at the schedule compiler's own slot
    defaults."""
    n_inter, n_intra = (1, world) if topology != "double" else _factor(world)
    compiler = sched.compile_fwd if pass_ == "fwd" else sched.compile_bwd
    return compiler(topology, n_intra, n_inter, r_live=r_live, wire=wire)


# ---------------------------------------------------------------------------
# the ragged serving kernel's VMEM plan and decode HBM traffic


def ragged_plan_bytes(*, d_head: int, page: int, group: int,
                      quantized: bool, block_q: int = 8) -> int:
    """ops/ragged_paged.ragged_supported's VMEM plan, re-derived: q/o/acc
    tiles, score + m/l columns, double-buffered k/v pages."""
    rows = _block_rows(max(1, block_q), group)
    kv_bytes = 1 if quantized else 2
    return (rows * d_head * 4 * 3
            + rows * (page + 2) * 4
            + 4 * page * d_head * kv_bytes)


# the serving shapes the ragged plan is proven for: every combination the
# engine's defaults can dispatch (pages are 128-lane multiples; group 8 is
# the GQA headline, 1 the MHA floor; quantized covers the int8 KV cache)
RAGGED_MATRIX = tuple(
    dict(d_head=d_head, page=page, group=group, quantized=quantized)
    for d_head in (128, 256)
    for page in (128, 256, 512)
    for group in (1, 8)
    for quantized in (False, True))


# pool storage dtypes the paged KV cache can hold natively (ISSUE 17),
# with their per-element byte cost; the 1 B/elem dtypes additionally
# stream one fp32 scale per (token, kv head) for each of K and V
POOL_DTYPES = {"fp32": 4, "int8": 1, "fp8": 1}


def ragged_hbm_bytes(*, d_head: int, n_kv: int, kv_len: int,
                     pool_dtype: str) -> int:
    """Analytic HBM bytes ONE decode step's attention must stream per
    sequence: the full resident K+V at the pool's storage width, plus —
    on natively quantized pools — the fp32 per-token scale columns.
    Decode is bandwidth-bound (the q tile is one token), so this ratio
    IS the analytic decode-throughput win of a quantized pool."""
    eb = POOL_DTYPES[pool_dtype]
    total = 2 * n_kv * kv_len * d_head * eb         # K + V pages
    if eb == 1:
        total += 2 * n_kv * kv_len * 4              # fp32 scale sidecars
    return total


# ---------------------------------------------------------------------------
# FLOPs: closed forms over the global mask, and the devstats per-round sum


def pass_pairs(layout: str, s: int, world: int, *, causal: bool,
               window: Optional[int] = None) -> int:
    """Closed-form attending (row, col) pair count of ONE full ring pass,
    per (batch, head): the ring visits every (q chunk, kv chunk) pair
    exactly once across all devices and rounds, so the total is the GLOBAL
    S x S mask's pair count (S = world * s) — independent of layout and of
    dead-round elision (elided rounds attend zero pairs by construction).
    This is the identity the cost-model-consistent rule pins against the
    devstats algebra (devstats_pass_pairs)."""
    del layout  # layouts permute token placement, not the global mask
    S = world * s
    if not causal:
        return S * S
    if window is None:
        return S * (S + 1) // 2
    w = min(window, S)
    # rows 0..w-1 attend i+1 cols (triangular head); the rest attend w
    return w * (w + 1) // 2 + (S - w) * w


def devstats_pass_pairs(program: sched.RingProgram, layout: str, s: int, *,
                        causal: bool, window: Optional[int] = None,
                        pair_fn=None) -> int:
    """The devstats pair algebra: sum of the per-round occupancy closed
    form (masks.spec_pair_count's host twin) over every device and every
    EXECUTED round of the compiled program — exactly what the
    devstats.flops counter integrates at 4*d FLOPs/pair.  `pair_fn` is a
    mutation seam for the lint tests (defaults to the production twin)."""
    fn = _host_round_pairs if pair_fn is None else pair_fn
    total = 0
    for dev in range(program.world):
        inter, intra = divmod(dev, program.n_intra)
        for r in range(program.n_rounds):
            kv_part = sched.partition_for_round(program, r, inter, intra)
            total += fn(layout, dev, kv_part, s, causal, window)
    return total


def pass_flops(pass_: str, layout: str, *, b: int, n: int, s: int, d: int,
               world: int, causal: bool,
               window: Optional[int] = None) -> float:
    """Analytic MXU FLOPs of one pass across the WHOLE ring: 4*d per
    attending pair forward (qk^T + pv, matching obs/devstats.py's
    flops = attn_pairs * 4 * head_dim), 2.5x that backward (the 5-matmul
    recompute factor benchmarks/benchmark.flops uses)."""
    pairs = pass_pairs(layout, s, world, causal=causal, window=window)
    fwd = 4.0 * d * pairs * b * n
    return fwd if pass_ == "fwd" else 2.5 * fwd


# ---------------------------------------------------------------------------
# ICI bytes: an independent re-derivation of the wire formula, plus the
# compiled program's send census


def stream_bytes(pass_: str, wire: Optional[str], *, b: int, n: int,
                 n_kv: int, s: int, d: int, opt_comm: bool = True,
                 itemsize: int = 4) -> Dict[str, int]:
    """Per-round per-device ring bytes by stream, re-derived here from the
    payload shapes and quantization rules — deliberately NOT a call into
    schedule.wire_round_bytes, so the cost-model-consistent rule can pin
    the two derivations equal and catch either one drifting.

    fwd "kv": the k and v chunks (1 B/elem quantized, else the dense
    itemsize) plus one fp32 scale per (batch, kv head) per operand.
    bwd "bundle": delta (opt_comm) | o, do, q at wire width; lse exempt
    (fp32); three per-(batch, head) scales when quantized.
    bwd "dq": the streamed partial (fp32 dense / 1 B quantized) plus its
    refreshed per-(batch, head) scale."""
    wi = itemsize if wire is None else 1
    scale = 0 if wire is None else 4
    if pass_ == "fwd":
        return {"kv": 2 * (b * n_kv * s * d * wi + b * n_kv * scale)}
    if pass_ != "bwd":
        raise ValueError(f"pass_ must be 'fwd' or 'bwd', got {pass_!r}")
    first = b * n * s * (4 if wire is None else 1) if opt_comm \
        else b * n * s * d * wi
    bundle = first + 2 * b * n * s * d * wi + b * n * s * 4 \
        + 3 * b * n * scale
    dq = b * n * s * d * (4 if wire is None else 1) + b * n * scale
    return {"bundle": bundle, "dq": dq}


def send_census(program: sched.RingProgram) -> Dict[str, int]:
    """Per-device send counts of one compiled pass, straight off the op
    table: payload sends per channel, dq hops (ring + boundary + home /
    final — the add-and-forward stream the comm floor times)."""
    rows = program.rows
    n0 = sum(rows["send0"][r] for r in range(program.n_rounds))
    n1 = sum(rows["send1"][r] for r in range(program.n_rounds))
    out = {"send0": int(n0), "send1": int(n1), "dq": 0}
    if program.kind == "bwd":
        out["dq"] = sum(1 for r in range(program.n_rounds)
                        if rows["dq_send"][r] != sched.DQ_NONE)
    return out


def pass_ici_bytes(pass_: str, program: sched.RingProgram, *, b: int,
                   n: int, n_kv: int, s: int, d: int, opt_comm: bool = True,
                   itemsize: int = 4) -> int:
    """Total per-device ICI bytes of one pass: the per-round stream bytes
    times the compiled program's send census."""
    per = stream_bytes(pass_, program.wire, b=b, n=n, n_kv=n_kv, s=s, d=d,
                       opt_comm=opt_comm, itemsize=itemsize)
    c = send_census(program)
    if pass_ == "fwd":
        return (c["send0"] + c["send1"]) * per["kv"]
    return (c["send0"] + c["send1"]) * per["bundle"] + c["dq"] * per["dq"]


def pass_hbm_bytes(pass_: str, program: sched.RingProgram, *, b: int,
                   n: int, n_kv: int, s: int, d: int, opt_comm: bool = True,
                   itemsize: int = 4) -> int:
    """Per-device HBM traffic of one pass — the block-plan derivation.

    fwd: per executed round, the q sweep re-reads q and round-trips the
    fp32 accbuf carry, and the consumed chunk copies slot -> VMEM; one
    final o + lse writeback.  bwd: per round, the bundle copies slot ->
    tiles and the active dq partial round-trips; the resident k/v reads
    once and dk/dv write once."""
    R = program.n_rounds
    wi = itemsize if program.wire is None else 1
    if pass_ == "fwd":
        per_round = (b * n * s * d * itemsize        # q re-read
                     + 2 * b * n * s * d * 4         # accbuf round trip
                     + 2 * s * d * wi)               # chunk slot -> VMEM
        final = b * n * s * d * itemsize + b * n * s * 4   # o + lse
        return R * per_round + final + 2 * s * d * itemsize  # kv copy-in
    first = b * n * s * (4 if program.wire is None else 1) if opt_comm \
        else b * n * s * d * wi
    per_round = (first + 2 * b * n * s * d * wi + b * n * s * 4  # bundle
                 + 2 * b * n * s * d * 4)            # dq round trip
    return R * per_round + 2 * s * d * itemsize + 2 * s * d * 4


# ---------------------------------------------------------------------------
# roofline floors


class CostEstimate(NamedTuple):
    flops: float        # whole-ring pass FLOPs (all devices)
    hbm_bytes: int      # per-device HBM traffic
    ici_bytes: int      # per-device ICI traffic
    t_compute_s: float  # per-device compute floor: max(MXU, HBM) time
    t_comm_s: float     # per-device serialized-hop comm floor


def _comm_floor_s(pass_: str, program: sched.RingProgram, hw: HwSpec, *,
                  b: int, n: int, n_kv: int, s: int, d: int,
                  opt_comm: bool = True, itemsize: int = 4) -> float:
    """Serialized-hop comm floor: the critical chain of sends a device
    must wait out, per topology.  uni serializes every send down one link;
    bidi runs its two directions concurrently (the longer chain bounds);
    the double ring's inter hop is prefetched a full intra cycle early, so
    only the intra chain bounds.  The bwd dq stream shares the bundle's
    links one hop behind, so its hops add to the same chain (halved across
    the two directions of a bidi ring)."""
    per = stream_bytes(pass_, program.wire, b=b, n=n, n_kv=n_kv, s=s, d=d,
                       opt_comm=opt_comm, itemsize=itemsize)
    c = send_census(program)
    if program.topology == "bidi":
        chain = max(c["send0"], c["send1"])
        dq_hops = -(-c["dq"] // 2)
    elif program.topology == "double":
        chain = c["send0"]      # inter sends (send1) hide behind the cycle
        dq_hops = c["dq"]
    else:
        chain = c["send0"] + c["send1"]
        dq_hops = c["dq"]
    if pass_ == "fwd":
        return chain * per["kv"] / hw.ici_bw
    return (chain * per["bundle"] + dq_hops * per["dq"]) / hw.ici_bw


def roofline(pass_: str, generation: str, program: sched.RingProgram, *,
             layout: str, b: int, n: int, n_kv: int, s: int, d: int,
             causal: bool, window: Optional[int] = None,
             opt_comm: bool = True, itemsize: int = 4) -> CostEstimate:
    hw = _hw(generation)
    fl = pass_flops(pass_, layout, b=b, n=n, s=s, d=d,
                    world=program.world, causal=causal, window=window)
    hbm = pass_hbm_bytes(pass_, program, b=b, n=n, n_kv=n_kv, s=s, d=d,
                         opt_comm=opt_comm, itemsize=itemsize)
    ici = pass_ici_bytes(pass_, program, b=b, n=n, n_kv=n_kv, s=s, d=d,
                         opt_comm=opt_comm, itemsize=itemsize)
    t_mxu = fl / program.world / hw.peak_flops
    t_hbm = hbm / hw.hbm_bw
    t_comm = _comm_floor_s(pass_, program, hw, b=b, n=n, n_kv=n_kv, s=s,
                           d=d, opt_comm=opt_comm, itemsize=itemsize)
    return CostEstimate(fl, hbm, ici, max(t_mxu, t_hbm), t_comm)


def predict_floors(pass_: str, *, b: int, n: int, n_kv: int, s: int, d: int,
                   world: int, topology: str = "uni",
                   generation: Optional[str] = None,
                   wire: Optional[str] = None, layout: str = "zigzag",
                   causal: bool = True, window: Optional[int] = None,
                   opt_comm: bool = True,
                   itemsize: int = 4) -> Tuple[float, float]:
    """(t_comm_pred_s, t_compute_pred_s) — the static model's floors for
    one ring config.  generation=None resolves the running device's
    generation and falls back to "v5e" off-TPU (the repo's measured
    hardware)."""
    if generation is None:
        generation = tuning.canonical_kind() or "v5e"
    r_live = None
    if window is not None and layout == "contig" and causal:
        rl = live_round_prefix(layout, s, world, causal=True, window=window)
        r_live = rl if rl < world else None
    program = compile_program(pass_, topology, world, wire, r_live=r_live)
    est = roofline(pass_, generation if generation in HW else "default",
                   program, layout=layout, b=b, n=n, n_kv=n_kv, s=s, d=d,
                   causal=causal, window=window, opt_comm=opt_comm,
                   itemsize=itemsize)
    return est.t_comm_s, est.t_compute_s


def predict_metric(metric: str) -> Optional[float]:
    """Analytic roofline expectation for a bench.py headline metric string
    ("... TFLOPs/s/chip @ seq=65536 causal bf16"), or None when the metric
    is not a TFLOPs-style headline.  Assumes the canonical bench shape
    (world=8, 32 heads, d=128 — bench.py's defaults) and prices on v5e;
    scripts/check_regression.py surfaces it as the `predicted` verdict
    field so a stale cached number sits beside its analytic ceiling."""
    import re

    if "TFLOPs/s" not in metric:
        return None
    m = re.search(r"seq=(\d+)", metric)
    if not m:
        return None
    seq = int(m.group(1))
    world = DEFAULT_WORLD
    n = d = None
    n = DEFAULT_SHAPE["n"]
    d = DEFAULT_SHAPE["d"]
    s = max(1, seq // world)
    causal = "causal" in metric
    itemsize = 2 if "bf16" in metric else 4
    passes = PASSES if "fwd+bwd" in metric else ("fwd",)
    t = 0.0
    total_flops = 0.0
    for p in passes:
        tc, tx = predict_floors(p, b=1, n=n, n_kv=n, s=s, d=d, world=world,
                                generation="v5e", causal=causal,
                                itemsize=itemsize)
        t += max(tc, tx)
        total_flops += pass_flops(p, "zigzag", b=1, n=n, s=s, d=d,
                                  world=world, causal=causal)
    if t <= 0:
        return None
    return round(total_flops / world / t / 1e12, 2)


# ---------------------------------------------------------------------------
# the exported cost table (--cost-json): autotuner pruning + fleet/sim.py


def cost_table(world: int = DEFAULT_WORLD,
               shape: Optional[dict] = None) -> dict:
    """The generation x topology x wire-dtype x pass matrix, one machine-
    readable row per config with its roofline estimates.  Plus the
    ragged-paged serving plans and the per-pool-dtype decode HBM pricing
    (`ragged_hbm`).  Schema "burstcost-v3" (v2 carried the VMEM plans of
    ring kernels that are gone) is pinned by tests/test_analysis.py."""
    shp = dict(DEFAULT_SHAPE if shape is None else shape)
    b, n, n_kv, s, d = (shp[k] for k in ("b", "n", "n_kv", "s", "d"))
    rows: List[dict] = []
    for gen in tuning.generations():
        for wire in sched.WIRE_DTYPES:
            for topo in sched.TOPOLOGIES:
                for pass_ in PASSES:
                    program = compile_program(pass_, topo, world, wire)
                    est = roofline(pass_, gen, program, layout="zigzag",
                                   b=b, n=n, n_kv=n_kv, s=s, d=d,
                                   causal=True)
                    rows.append({
                        "generation": gen, "topology": topo,
                        "wire": wire, "pass": pass_,
                        "n_rounds": program.n_rounds,
                        "flops": est.flops,
                        "hbm_bytes": est.hbm_bytes,
                        "ici_bytes": est.ici_bytes,
                        "t_compute_s": est.t_compute_s,
                        "t_comm_s": est.t_comm_s,
                    })
    ragged = []
    for cfgr in RAGGED_MATRIX:
        pb = ragged_plan_bytes(**cfgr)
        ragged.append({**cfgr, "plan_bytes": pb, "vmem_limit": VMEM_LIMIT,
                       "fits": bool(pb <= VMEM_LIMIT)})
    # per-pool-dtype decode bandwidth: what one decode step streams at
    # the canonical shape's resident length, and the analytic win a
    # 1 B/elem pool buys over fp32 (scale sidecars included)
    ragged_hbm = []
    for d_head in (128, 256):
        base = ragged_hbm_bytes(d_head=d_head, n_kv=n_kv, kv_len=s,
                                pool_dtype="fp32")
        for pool_dtype, eb in sorted(POOL_DTYPES.items()):
            hb = ragged_hbm_bytes(d_head=d_head, n_kv=n_kv, kv_len=s,
                                  pool_dtype=pool_dtype)
            ragged_hbm.append({
                "d_head": d_head, "n_kv": n_kv, "kv_len": s,
                "pool_dtype": pool_dtype, "kv_elem_bytes": eb,
                "hbm_bytes": hb,
                "win_vs_fp32": base / hb,
            })
    return {
        "schema": "burstcost-v3",
        "world": world,
        "shape": shp,
        "hw": {g: {"peak_flops": h.peak_flops, "hbm_bw": h.hbm_bw,
                   "ici_bw": h.ici_bw} for g, h in sorted(HW.items())},
        "n_rows": len(rows),
        "rows": rows,
        "ragged": ragged,
        "ragged_hbm": ragged_hbm,
    }
