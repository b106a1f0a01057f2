"""Ring-overlap microbenchmark: scan+ppermute ring vs the fused RDMA kernel.

Measures, per (seq, layout, pass) config on the real ring mesh:

  t_scan     — the scan-based ring (`backend="pallas"` per-round
               pallas_call + lax.ppermute; overlap is whatever XLA's async
               collective scheduling achieves)
  t_fused    — the fused single-kernel ring (`backend="fused_ring"`:
               in-kernel RDMA rotation — KV for the forward,
               ops/fused_ring.py; q-side bundle + concurrent dq ring for
               the backward, ops/fused_ring_bwd.py)
  t_compute  — compute-only floor: the same W rounds of tile compute with
               the ring rotation REMOVED (every round re-reads the resident
               local operands; identical kernel launches, masks and state
               carry, zero inter-chip traffic)
  t_comm     — comm-only floor: just the rotations (fwd: W-1 k/v permutes;
               bwd: W-1 bundle permutes + the W dq add-and-forward hops),
               no attention compute

and derives the achieved overlap fraction

  overlap = (t_compute + t_comm - t_ring) / min(t_compute, t_comm)

(1.0 = the smaller phase is fully hidden behind the larger; 0.0 = fully
serialized), plus the ideal-floor ratio t_ring / max(t_compute, t_comm).
One JSON line per (config, pass) appends to results/ring_overlap.jsonl,
each tagged with its `pass` ("fwd" | "bwd" | "fwd+bwd"; the combined pass
times one value_and_grad program and reports no floors — its floors are
the sum of the per-pass ones).

On a CPU host this still runs a tiny smoke config through the interpreted
fused kernels (BURST_FUSED_INTERPRET=1 is set for the fused legs) so the
harness itself is testable anywhere; the numbers are only meaningful on a
TPU ring.

Usage:  python -m benchmarks.ring_overlap [--seqs 16384,65536]
        [--mesh 8] [--layout zigzag] [--heads 32] [--dim 128]
        [--pass fwd|bwd|fwd+bwd|all] [--topology uni|bidi|double|all]
        [--window W] [--wire-dtype fp32|int8|fp8]
        [--out results/ring_overlap.jsonl]

--window W dispatches the occupancy-elided contig schedule
(docs/schedule_ir.md "Occupancy compilation"): both ring legs run the
r_live-round program, the floors are measured at r_live rounds/hops, and
the row additionally records the DENSE full-ring floors
(t_comm_dense_s / t_compute_dense_s) — the comm and compute the
dead-round elision removed.

--topology selects the compiled fused-ring schedule (parallel/schedule.py):
"bidi" runs the counter-rotating ring and also records the per-direction
comm floors (t_comm_uni_s vs the split t_comm_only_s — the reclaimable
hop latency), "double" factors the flat mesh inter-major and times the
prefetched inter hop in its floor.

--wire-dtype int8|fp8 runs both ring legs with the wire-precision layer
(cfg.wire_dtype: rotating payloads quantized to 1 byte/element with fp32
per-block scales riding the same slots; docs/fused_ring.md) and times an
additional QUANTIZED comm-only floor per fwd/bwd row (`t_comm_q_s`:
1-byte carriers + the scale sub-payloads, same hop structure).  Every
fwd/bwd row also records `wire_bytes_per_round` — the per-round
per-device ring bytes from schedule.wire_round_bytes, the single
derivation the obs counters and the schedule-replay test share — so the
fp32 vs int8 byte ratio is read straight off the jsonl.

Every row additionally records the STATIC cost model's predicted floors
(analysis/costmodel.py roofline: `t_comm_pred_s`, `t_compute_pred_s`)
and `pred_ratio` (measured fused time over the model's binding floor),
so each TPU window calibrates the model's spec-sheet HW table for free —
the cost-model-consistent lint rule reads TPU rows back and fails when a
measured comm floor drifts outside the model's calibration band.
"""

import argparse
import json
import os
import time

# off-TPU smoke runs need a simulated ring; must be set before jax inits
# (harmless when a real TPU backend is selected)
if os.environ.get("JAX_PLATFORMS", "") == "cpu" or not os.environ.get(
        "JAX_PLATFORMS"):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from benchmarks.benchmark import bench_fn, flops
from burst_attn_tpu.parallel import burst, layouts
from burst_attn_tpu.parallel.ring import ppermute_next


def _mesh(world):
    devs = jax.devices()
    if len(devs) < world:
        raise SystemExit(f"need {world} devices, have {len(devs)}")
    return Mesh(np.array(devs[:world]), ("sp",))


def _shard_fwd(mesh, cfg, no_rotate=False, n_rounds=None):
    """Shard-level forward launcher; no_rotate=True swaps every ring
    rotation for a no-op (the compute-only floor: same rounds, same tile
    kernels, the resident chunk stands in for every arriving chunk).
    n_rounds overrides the floor's round count — the occupancy-elided
    schedule's compute floor is r_live rounds, not the full ring."""
    spec4 = P(None, None, "sp", None)
    spec3 = P(None, None, "sp")

    def f(q, k, v):
        if not no_rotate:
            o, lse = burst._fwd_impl(q, k, v, cfg)
            return jnp.sum(o.astype(jnp.float32)) + jnp.sum(lse)
        # compute-only: W self-spec rounds against the resident chunk
        from burst_attn_tpu.ops.masks import round_spec
        from burst_attn_tpu.parallel.ring import my_partition
        from jax.lax import axis_size

        world = n_rounds or axis_size(cfg.intra_axis)
        me = my_partition(cfg.intra_axis, None)
        s = q.shape[2]
        spec = round_spec(me, me, s, s, cfg.causal, cfg.layout)
        st = burst._tile_fwd(cfg, q, k, v, None, None, None,
                             q.shape[3] ** -0.5, spec, triangular=cfg.causal)
        for _ in range(world - 1):
            st = burst._tile_fwd(cfg, q, k, v, *st, q.shape[3] ** -0.5, spec,
                                 triangular=cfg.causal)
        m, lse, acc = st
        return jnp.sum(acc.astype(jnp.float32)) + jnp.sum(lse)

    fn = shard_map(f, mesh=mesh, in_specs=(spec4,) * 3, out_specs=P(),
                   check_vma=False)
    return jax.jit(lambda q, k, v: fn(q, k, v))


def _comm_only(mesh, world, topology="uni", factor=None, n_rounds=None,
               wire=None):
    """Comm-only floor of one forward topology, no compute.

    n_rounds truncates the uni rotation count to an occupancy-elided
    schedule's r_live (r_live - 1 hops: the elided program never sends the
    dead rounds' chunks at all).

    wire ("int8" | "fp8") is the QUANTIZED floor (t_comm_q_s): the k/v
    payload rotates as 1-byte carriers (int8 and fp8 both ship 1 B/elem)
    plus the two per-(batch, kv head) fp32 scale sub-payloads the fused
    kernels send down the same slots — schedule.wire_round_bytes' fwd
    accounting.  The quantize cast happens once inside the program, like
    the real entry's quantize-once-at-entry.

    uni     W-1 full-payload rotations of the (k, v) pair.
    bidi    the counter-rotating split: each round moves HALF the payload
            clockwise and half counter-clockwise concurrently, for
            max(ceil, floor)((W-1)/2) rounds — both ICI directions carry
            traffic at once, so on a comm-bound ring this floor is the
            headroom the bidirectional schedule can claim.  The (tiny)
            scale stream rides clockwise.
    double  factored (n_inter, n_intra): per cycle, n_intra-1 intra unit
            hops plus (except the last cycle) one inter hop of n_intra
            positions along the flat axis.
    """
    spec4 = P(None, None, "sp", None)

    def rot(t, hops):
        from jax.lax import axis_size
        import jax.lax as lax

        n = axis_size("sp")
        perm = [(i, (i + hops) % n) for i in range(n)]
        return jax.tree_util.tree_map(
            lambda x: lax.ppermute(x, "sp", perm), t)

    def f(k, v):
        kv = (k, v)
        scales = ()
        if wire is not None:
            kv = tuple(t.astype(jnp.int8) for t in kv)
            scales = (jnp.zeros((k.shape[0], k.shape[1], 1, 1),
                                jnp.float32),) * 2
        if topology == "bidi":
            h_cw = (world - 1 + 1) // 2
            h_ccw = (world - 1) // 2
            half = k.shape[2] // 2
            cw = tuple(t[:, :, :half] for t in kv)
            ccw = tuple(t[:, :, half:] for t in kv)
            for j in range(max(h_cw, h_ccw)):
                if j < h_cw:
                    cw = rot(cw, 1)
                    scales = rot(scales, 1)
                if j < h_ccw:
                    ccw = rot(ccw, -1)
            return sum(jnp.sum(t.astype(jnp.float32))
                       for t in cw + ccw + scales)
        if topology == "double":
            n_i, n_s = factor
            acc = jnp.float32(0.0)
            for c in range(n_i):
                for _ in range(n_s - 1):
                    kv = rot(kv, 1)
                    scales = rot(scales, 1)
                if c < n_i - 1:
                    kv = rot(kv, n_s)  # the prefetched inter hop
                    scales = rot(scales, n_s)
                acc = acc + jnp.sum(kv[0].astype(jnp.float32))
            return acc + sum(jnp.sum(t.astype(jnp.float32))
                             for t in kv[1:] + scales)
        for _ in range((n_rounds or world) - 1):
            kv = ppermute_next(kv, "sp")
            scales = ppermute_next(scales, "sp")
        return sum(jnp.sum(t.astype(jnp.float32)) for t in kv + scales)

    fn = shard_map(f, mesh=mesh, in_specs=(spec4,) * 2, out_specs=P(),
                   check_vma=False)
    return jax.jit(lambda k, v: fn(k, v))


def _shard_fwd_residuals(mesh, cfg):
    """(o, lse) of the scan forward — the residuals both backward legs
    consume, computed once per config outside the timed region."""
    spec4 = P(None, None, "sp", None)
    spec3 = P(None, None, "sp")
    fn = shard_map(lambda q, k, v: burst._fwd_impl(q, k, v, cfg),
                   mesh=mesh, in_specs=(spec4,) * 3,
                   out_specs=(spec4, spec3), check_vma=False)
    return jax.jit(fn)


def _shard_bwd(mesh, cfg, no_rotate=False, n_rounds=None):
    """Shard-level backward launcher; no_rotate=True swaps both rotating
    streams for no-ops (the compute-only floor: same W rounds of tile_bwd
    against the resident bundle, zero inter-chip traffic)."""
    spec4 = P(None, None, "sp", None)
    spec3 = P(None, None, "sp")

    def f(q, k, v, o, lse, do):
        if not no_rotate:
            dq, dk, dv = burst._bwd_impl(cfg, q, k, v, o, lse, do)
            return (jnp.sum(dq) + jnp.sum(dk) + jnp.sum(dv)).astype(
                jnp.float32)
        from burst_attn_tpu.ops.masks import round_spec
        from burst_attn_tpu.parallel.ring import my_partition
        from jax.lax import axis_size

        world = n_rounds or axis_size(cfg.intra_axis)
        me = my_partition(cfg.intra_axis, None)
        s = q.shape[2]
        scale = q.shape[3] ** -0.5
        spec = round_spec(me, me, s, s, cfg.causal, cfg.layout)
        delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                        axis=-1)
        acc = jnp.float32(0.0)
        for _ in range(world):
            dq, dk, dv = burst._tile_bwd(cfg, do, q, k, v, delta, lse,
                                         scale, spec)
            acc = acc + jnp.sum(dq) + jnp.sum(dk) + jnp.sum(dv)
        return acc

    fn = shard_map(f, mesh=mesh, in_specs=(spec4,) * 4 + (spec3, spec4),
                   out_specs=P(), check_vma=False)
    return jax.jit(lambda *a: fn(*a))


def _comm_only_bwd(mesh, world, opt_comm, n_rounds=None, wire=None):
    """Comm-only backward floor: W-1 rotations of the 4-operand q-side
    bundle (delta|o, do, q, lse) plus the dq ring's W add-and-forward hops
    (W-1 in-ring + the return-home hop), no compute.  n_rounds truncates
    both streams to an elided schedule's r_live (the dq return-home hop
    always remains).

    wire ("int8" | "fp8") is the QUANTIZED floor (t_comm_q_s): the
    bundle's (delta|o, do, q) rotate as 1-byte carriers with three
    per-(batch, head) fp32 scale scalars riding along (lse stays fp32,
    exempt from quantization), and the dq stream moves 1 byte/element
    plus its per-hop refreshed scale — schedule.wire_round_bytes' bwd
    accounting."""
    spec4 = P(None, None, "sp", None)
    spec3 = P(None, None, "sp")
    first_spec = spec3 if opt_comm else spec4

    def f(first, do, q, lse):
        pay = (first, do, q, lse)
        if wire is not None:
            sc = jnp.zeros((q.shape[0], q.shape[1], 1, 1), jnp.float32)
            pay = tuple(t.astype(jnp.int8) for t in (first, do, q)) \
                + (lse, sc, sc, sc)
            dqs = (jnp.zeros(q.shape, jnp.int8), sc)
        else:
            dqs = (jnp.zeros(q.shape, jnp.float32),)
        for _ in range((n_rounds or world) - 1):
            pay = ppermute_next(pay, "sp")
            dqs = ppermute_next(dqs, "sp")
        dqs = ppermute_next(dqs, "sp")  # return-home hop
        return sum(jnp.sum(t.astype(jnp.float32)) for t in pay + dqs)

    fn = shard_map(f, mesh=mesh,
                   in_specs=(first_spec, spec4, spec4, spec3),
                   out_specs=P(), check_vma=False)
    return jax.jit(lambda *a: fn(*a))


def _shard_fwdbwd(mesh, cfg):
    """value_and_grad through the shard-level custom_vjp — both passes of
    one training-step attention in one timed program."""
    spec4 = P(None, None, "sp", None)

    def f(q, k, v, do):
        def loss(q, k, v):
            o = burst.burst_attn_shard(q, k, v, cfg)
            return jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32))

        l, grads = jax.value_and_grad(loss, (0, 1, 2))(q, k, v)
        return l + sum(jnp.sum(g.astype(jnp.float32)) for g in grads)

    fn = shard_map(f, mesh=mesh, in_specs=(spec4,) * 4, out_specs=P(),
                   check_vma=False)
    return jax.jit(lambda *a: fn(*a))


def run_config(seq, world, layout, n, d, causal, out_path, pass_="fwd",
               topology="uni", window=None, wire_dtype="fp32"):
    on_tpu = jax.default_backend() == "tpu"
    wire = None if wire_dtype in (None, "fp32") else wire_dtype
    mesh = _mesh(world)
    # --window W: occupancy-elided schedule (contig causal band).  Both ring
    # legs dispatch the elided program; the floors are measured twice —
    # r_live rounds/hops (what the elided schedule actually moves and
    # computes) AND the dense full-ring floors, so the jsonl row shows the
    # comm+compute the elision removed, not just the end-to-end time.
    r_live = None
    if window is not None:
        from burst_attn_tpu.ops.masks import live_round_prefix

        if layout != "contig" or not causal:
            raise SystemExit("--window needs --layout contig and causal")
        r_live = live_round_prefix("contig", seq // world, world,
                                   causal=True, window=window)
    # topology -> fused-dispatch config + the factored double-ring shape
    factor = None
    topo_kw = {}
    if topology == "bidi":
        topo_kw = {"fused_topology": "bidi"}
    elif topology == "double":
        n_i = 2
        while world % n_i or (world // n_i) < 2:
            n_i += 1
            if n_i > world // 2:
                raise SystemExit(f"--topology double needs a composite "
                                 f"mesh, got {world}")
        factor = (n_i, world // n_i)
        topo_kw = {"fused_seq_factor": factor}
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    key = jax.random.PRNGKey(0)
    kq, kk, kv, kg = jax.random.split(key, 4)
    q = jax.random.normal(kq, (1, n, seq, d), dtype)
    k = jax.random.normal(kk, (1, n, seq, d), dtype)
    v = jax.random.normal(kv, (1, n, seq, d), dtype)
    do = jax.random.normal(kg, (1, n, seq, d), dtype)
    q, k, v, do = (layouts.to_layout(t, layout, world, 2)
                   for t in (q, k, v, do))

    tile_backend = "pallas" if on_tpu else "jnp"
    win_kw = {} if window is None else {"window": window}
    scan_cfg = burst.BurstConfig(causal=causal, layout=layout,
                                 intra_axis="sp", backend=tile_backend,
                                 wire_dtype=wire, **win_kw)
    fused_cfg = burst.BurstConfig(causal=causal, layout=layout,
                                  intra_axis="sp", backend="fused_ring",
                                  wire_dtype=wire, **topo_kw, **win_kw)

    bench_kw = dict(warmup=2, iters=3, reps=2) if not on_tpu else {}
    os.environ["BURST_FUSED_INTERPRET"] = "1"  # fused legs off-TPU
    dir_floors = {}
    if pass_ == "fwd":
        t_scan = bench_fn(_shard_fwd(mesh, scan_cfg), q, k, v, **bench_kw)
        t_fused = bench_fn(_shard_fwd(mesh, fused_cfg), q, k, v, **bench_kw)
        t_compute = bench_fn(
            _shard_fwd(mesh, scan_cfg, no_rotate=True, n_rounds=r_live),
            q, k, v, **bench_kw)
        t_comm = bench_fn(
            _comm_only(mesh, world, topology, factor, n_rounds=r_live),
            k, v, **bench_kw)
        if r_live is not None:
            # the dense floors: what a non-elided schedule would move
            dir_floors["t_compute_dense_s"] = round(bench_fn(
                _shard_fwd(mesh, scan_cfg, no_rotate=True),
                q, k, v, **bench_kw), 6)
            dir_floors["t_comm_dense_s"] = round(bench_fn(
                _comm_only(mesh, world, topology, factor),
                k, v, **bench_kw), 6)
        if wire is not None:
            dir_floors["t_comm_q_s"] = round(bench_fn(
                _comm_only(mesh, world, topology, factor, n_rounds=r_live,
                           wire=wire),
                k, v, **bench_kw), 6)
        if topology == "bidi":
            # per-direction floors: what each ICI direction costs alone —
            # the gap between t_comm_uni and t_comm is the latency the
            # counter-rotating split reclaims on comm-bound configs
            dir_floors["t_comm_uni_s"] = round(
                bench_fn(_comm_only(mesh, world), k, v, **bench_kw), 6)
            dir_floors["dir_hops"] = {"cw": (world - 1 + 1) // 2,
                                      "ccw": (world - 1) // 2}
        elif topology == "double":
            dir_floors["dir_hops"] = {"intra": factor[0] * (factor[1] - 1),
                                      "inter": factor[0] - 1}
    elif pass_ == "bwd":
        # residuals once, outside the timed region — both legs consume the
        # identical (o, lse)
        o, lse = jax.block_until_ready(
            _shard_fwd_residuals(mesh, scan_cfg)(q, k, v))
        t_scan = bench_fn(_shard_bwd(mesh, scan_cfg), q, k, v, o, lse, do,
                          **bench_kw)
        t_fused = bench_fn(_shard_bwd(mesh, fused_cfg), q, k, v, o, lse, do,
                           **bench_kw)
        t_compute = bench_fn(
            _shard_bwd(mesh, scan_cfg, no_rotate=True, n_rounds=r_live),
            q, k, v, o, lse, do, **bench_kw)
        delta_or_o = (jnp.sum(o.astype(jnp.float32)
                              * do.astype(jnp.float32), axis=-1)
                      if scan_cfg.optimize_bwd_comm else o)
        t_comm = bench_fn(
            _comm_only_bwd(mesh, world, scan_cfg.optimize_bwd_comm,
                           n_rounds=r_live),
            delta_or_o, do, q, lse.astype(jnp.float32), **bench_kw)
        if wire is not None:
            dir_floors["t_comm_q_s"] = round(bench_fn(
                _comm_only_bwd(mesh, world, scan_cfg.optimize_bwd_comm,
                               n_rounds=r_live, wire=wire),
                delta_or_o, do, q, lse.astype(jnp.float32), **bench_kw), 6)
        if r_live is not None:
            dir_floors["t_compute_dense_s"] = round(bench_fn(
                _shard_bwd(mesh, scan_cfg, no_rotate=True),
                q, k, v, o, lse, do, **bench_kw), 6)
            dir_floors["t_comm_dense_s"] = round(bench_fn(
                _comm_only_bwd(mesh, world, scan_cfg.optimize_bwd_comm),
                delta_or_o, do, q, lse.astype(jnp.float32), **bench_kw), 6)
    elif pass_ == "fwd+bwd":
        # one value_and_grad program per backend; floors are the sum of the
        # per-pass floors, so none are (re)measured here
        t_scan = bench_fn(_shard_fwdbwd(mesh, scan_cfg), q, k, v, do,
                          **bench_kw)
        t_fused = bench_fn(_shard_fwdbwd(mesh, fused_cfg), q, k, v, do,
                           **bench_kw)
        t_compute = t_comm = None
    else:
        raise SystemExit(f"unknown --pass {pass_!r}")

    def overlap(t_ring):
        lo = min(t_compute, t_comm)
        if lo <= 0:
            return 0.0
        return max(0.0, min(1.0, (t_compute + t_comm - t_ring) / lo))

    mode = {"fwd": "fwd", "bwd": "bwd", "fwd+bwd": "fwd_bwd"}[pass_]
    pass_f = flops(1, seq, n, d, mode=mode, causal=causal)
    rec = {
        "bench": "ring_overlap",
        "backend": jax.default_backend(),
        "pass": pass_,
        "topology": topology,
        "seq": seq, "world": world, "layout": layout, "heads": n, "dim": d,
        "causal": causal,
        "wire_dtype": wire_dtype,
        **({} if window is None else {"window": window, "r_live": r_live}),
        **dir_floors,
        "t_scan_s": round(t_scan, 6),
        "t_fused_s": round(t_fused, 6),
        "fused_speedup": round(t_scan / t_fused, 4),
        "tflops_scan": round(pass_f / t_scan / 1e12 / world, 2),
        "tflops_fused": round(pass_f / t_fused / 1e12 / world, 2),
        "ts_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    if pass_ in ("fwd", "bwd"):
        # per-round per-device ring bytes from the shared derivation
        # (schedule.wire_round_bytes) — what the fp32-vs-int8 acceptance
        # ratio is read from; streams broken out beside the total
        from burst_attn_tpu.parallel import schedule as sched

        wb = sched.wire_round_bytes(
            pass_, wire, b=1, n=n, n_kv=n, s=seq // world, d=d,
            opt_comm=scan_cfg.optimize_bwd_comm,
            itemsize=jnp.dtype(dtype).itemsize)
        rec["wire_bytes_per_round"] = int(sum(wb.values()))
        rec["wire_round_bytes"] = {kk_: int(vv_) for kk_, vv_ in wb.items()}
    if t_compute is not None:
        rec.update({
            "t_compute_only_s": round(t_compute, 6),
            "t_comm_only_s": round(t_comm, 6),
            "overlap_scan": round(overlap(t_scan), 4),
            "overlap_fused": round(overlap(t_fused), 4),
            "ring_vs_floor_scan": round(t_scan / max(t_compute, t_comm), 4),
            "ring_vs_floor_fused": round(t_fused / max(t_compute, t_comm), 4),
        })
    # the static cost model's predicted floors (analysis/costmodel.py)
    # beside the measured ones: every TPU row calibrates the roofline's
    # spec-sheet HW table for free (the cost-model-consistent lint rule
    # reads these rows back), and pred_ratio is the measured-over-model
    # correction factor.  Best-effort: the benchmark never fails on the
    # model — a row without pred fields is a model bug to chase, not a
    # lost measurement.
    try:
        from burst_attn_tpu.analysis import costmodel

        pred_passes = ("fwd", "bwd") if pass_ == "fwd+bwd" else (pass_,)
        t_comm_pred = t_compute_pred = 0.0
        for p_ in pred_passes:
            tc_, tx_ = costmodel.predict_floors(
                p_, b=1, n=n, n_kv=n, s=seq // world, d=d, world=world,
                topology=topology, wire=wire, layout=layout,
                causal=causal, window=window,
                opt_comm=scan_cfg.optimize_bwd_comm,
                itemsize=jnp.dtype(dtype).itemsize)
            t_comm_pred += tc_
            t_compute_pred += tx_
        # ns precision: CPU smoke shapes have sub-microsecond model floors
        rec.update({
            "t_comm_pred_s": round(t_comm_pred, 9),
            "t_compute_pred_s": round(t_compute_pred, 9),
            "pred_ratio": round(
                t_fused / max(t_comm_pred, t_compute_pred), 4),
        })
    except Exception as e:  # noqa: BLE001 — keep the measurement
        rec["pred_error"] = f"{type(e).__name__}: {e}"
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "a") as f:
        f.write(json.dumps(rec) + "\n")
        f.flush()
        os.fsync(f.fileno())
    print(json.dumps(rec))
    # mirror the headline quantities into the obs registry so the overlap
    # numbers show up in `python -m burst_attn_tpu.obs` next to the ring
    # dispatch counters the measured programs just advanced
    from burst_attn_tpu import obs

    labels = {"seq": seq, "world": world, "layout": layout, "pass": pass_,
              "topology": topology, "wire": wire_dtype}
    for key in ("overlap_scan", "overlap_fused", "fused_speedup",
                "tflops_scan", "tflops_fused"):
        if key in rec:
            obs.gauge(f"bench.ring_overlap.{key}").set(rec[key], **labels)
    obs.counter("bench.ring_overlap_runs").inc(**{"pass": pass_})
    return rec


def main():
    ap = argparse.ArgumentParser()
    on_tpu = jax.default_backend() == "tpu"
    ap.add_argument("--seqs", default="16384,65536" if on_tpu else "128")
    ap.add_argument("--mesh", type=int, default=8 if on_tpu else 4)
    ap.add_argument("--layout", default="zigzag")
    ap.add_argument("--heads", type=int, default=32 if on_tpu else 2)
    ap.add_argument("--dim", type=int, default=128 if on_tpu else 16)
    ap.add_argument("--noncausal", action="store_true")
    ap.add_argument("--window", type=int, default=None,
                    help="sliding-window width: dispatch the occupancy-"
                         "elided contig schedule and record its r_live "
                         "floors next to the dense ones (needs --layout "
                         "contig)")
    ap.add_argument("--pass", dest="pass_", default="fwd",
                    choices=["fwd", "bwd", "fwd+bwd", "all"],
                    help="which pass(es) to measure; 'all' runs the three "
                         "modes back to back per seq")
    ap.add_argument("--topology", default="uni",
                    choices=["uni", "bidi", "double", "all"],
                    help="fused-ring schedule topology (parallel/schedule."
                         "py); bidi records per-direction comm floors, "
                         "double factors the flat mesh inter-major; 'all' "
                         "sweeps the three")
    ap.add_argument("--wire-dtype", default="fp32",
                    choices=["fp32", "int8", "fp8"],
                    help="wire precision for the rotating payloads "
                         "(cfg.wire_dtype): int8/fp8 run both ring legs "
                         "quantized and add the t_comm_q_s quantized comm "
                         "floor; every fwd/bwd row records "
                         "wire_bytes_per_round either way")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "results", "ring_overlap.jsonl"))
    args = ap.parse_args()
    passes = (["fwd", "bwd", "fwd+bwd"] if args.pass_ == "all"
              else [args.pass_])
    topologies = (["uni", "bidi", "double"] if args.topology == "all"
                  else [args.topology])
    if args.window is not None and args.layout != "contig":
        # the band structure only exists in natural token order
        print("note: --window implies --layout contig")
        args.layout = "contig"
    for seq in [int(s) for s in args.seqs.split(",")]:
        for topo in topologies:
            for p in passes:
                run_config(seq, args.mesh, args.layout, args.heads,
                           args.dim, not args.noncausal, args.out,
                           pass_=p, topology=topo, window=args.window,
                           wire_dtype=args.wire_dtype)
    # one obs export per invocation, beside the jsonl results
    from burst_attn_tpu import obs

    obs.export_jsonl(os.path.join(os.path.dirname(args.out), "obs.jsonl"))


if __name__ == "__main__":
    main()
