"""Block sweep of single tile CALLS at a cell's geometry, on the chip: what
`ops/tuning.py`'s rule in rows and band width was measured with.

`sweep_blocks.py` times whole 64K ops by the host clock; a call of a short
row or of a narrow band takes well under a millisecond, so here each
configuration is run under the profiler and its time is the device time of
its `burst_flash_*` events (the XLA ops around the kernel, which do not
depend on the tiles, are reported beside it).  Every configuration's outputs
are compared with the first one's of its call, so a tile size at which the
fused backward's in-place `dq` races shows as a difference, not as a time.

    python -m benchmarks.sweep_tile_calls --out chiprun_out/sweep_tile_calls.jsonl

results/sweep_tile_calls.jsonl is PR 29's sweep on one v5e, then PR 33's of
the forward's diagonal sub-square edge (`--edges`: the rows with an `edge`,
and beside each the host seconds its call site took to trace and to lower,
`trace_s` / `lower_s`: a Python-unrolled body's other cost, which lands in a
cell's `setup_s`), then PR 34's of the 16,384-row call at 192 / 128 (`--only
mla16k,causal16k`: the rows with `d_qk`), then PR 35's of the backward's
cut-block edge (`--edges --bwd`: the backward rows with an `edge`, and
`compile_s` beside the other two).  Those rows carry `body` and `order`: what
the tree holds is `body: "one traced position"`, `order: "cols"`; the rows
of the forms that lost (`order: "rows"`: the square in row chunks; `body: "a
position each"`: a Python-unrolled body for every position of a cut block
under its kv block, beside a whole-tile masked fallback, which ran into the
VMEM cliff at some geometries: 27 and 95 ms) were taken with trees that did
not stay, and say so (`reproducible` false).  Its four rows with `qk_feed` were
taken with a trace-time hook that did not stay, so this script cannot take
them again (each row says so: `reproducible` false): the 192-deep score
product as it is (`whole`), or as `dot(q[:, :128], k[:, :128]) + dot(q[:,
128:], k[:, 128:])` (`128+64`: 6.6 % slower forward, the same backward).
"""

import argparse
import json
import shutil
import sys
import tempfile
import time

ITERS = 5

# (rows a call covers, batch, query heads, kv heads, mask unit, token window)
# of the cells' calls: train_sdar_bd_1x8k's halves, train_mistral_1x8k,
# train_mistral_8x1k; and two token windows, which no cell has, to hold the
# band rule at more than the one band width
GEOMETRIES = {
    "bd8k": dict(s=8192, b=1, n=32, n_kv=4, unit=4),
    "causal8k": dict(s=8192, b=1, n=32, n_kv=8, unit=1),
    "causal1k": dict(s=1024, b=8, n=32, n_kv=8, unit=1),
    "win256_8k": dict(s=8192, b=1, n=32, n_kv=8, unit=1, win=256),
    "win1024_8k": dict(s=8192, b=1, n=32, n_kv=8, unit=1, win=1024),
    # PR 34: train_kanana2_mla_1x16k's call (latent attention: q, k 192 wide,
    # v, o 128), and the same rows at 128 / 128 beside it
    "mla16k": dict(s=16384, b=1, n=32, n_kv=32, unit=1, d_qk=192, d_v=128),
    "causal16k": dict(s=16384, b=1, n=32, n_kv=32, unit=1),
    # PR 35: the triangular backward at 8,192 rows and at op_causal_64k's
    "mha8k": dict(s=8192, b=1, n=32, n_kv=32, unit=1),
    "causal64k": dict(s=65536, b=1, n=32, n_kv=32, unit=1),
    # ring4_causal_128k's self round: a shard of 32,768 rows
    "mha32k": dict(s=32768, b=1, n=32, n_kv=32, unit=1),
}

SQUARES = [(128, 128), (256, 256), (512, 512), (1024, 1024)]
ROW_FWD, ROW_BWD = (2048, 2048, True), (1024, 2048)
# the sub-square edge of the forward's diagonal sweep (PR 33) in the row's
# tiles: 0 (the whole tile on the masked path, what every forward entry
# without an edge runs: the tile sizes were swept on it), then the edges
EDGES = [ROW_FWD + (e,) for e in (0, 1024, 512, 256, 128, 64)]
# the sub-square edge of the backward's cut blocks (PR 35) in the row's
# blocks: 0 (the whole block on the masked path, what every backward entry
# without an edge ran), then the edges
BWD_EDGES = [ROW_BWD + (e,) for e in (0, 256)]
BWD_EDGES_8K = BWD_EDGES + [ROW_BWD + (e,) for e in (512, 128)]
# {(geometry, call, pass): [(block_q, block_kv[, ask for the all-live
# grid[, diagonal sub-square edge]]), ...]}; the first entry of each list is
# the reference the others are compared with.  Calls: the three quadrants
# (`diagonal` folds into the `below` call's state, as burst._bd_fwd chains
# them; `diagonal_empty` and `below_carried` are the other order) and
# `banded`, a causal call under the geometry's token window.  The `edge`
# sweeps are `--edges`' (the forward rows of the cells' causal calls).
SWEEPS = {
    # the block-diagonal call: the v5e row on the rectangular grid (what the
    # cell ran before), then the band grid over tile sizes
    ("bd8k", "diagonal", "fwd"): [(2048, 2048, False)] + [
        (bq, bkv, True) for bq, bkv in
        SQUARES + [(2048, 2048), (256, 512), (512, 256), (512, 1024)]],
    ("bd8k", "diagonal", "bwd"): [ROW_BWD] + SQUARES + [
        (256, 512), (512, 1024), (128, 256)],
    ("bd8k", "diagonal_empty", "fwd"): [(512, 512, True)],
    ("bd8k", "below_carried", "fwd"): [ROW_FWD],
    ("bd8k", "clean", "fwd"): [ROW_FWD, (1024, 1024, True),
                               (2048, 1024, True), (1024, 512, True),
                               (4096, 1024, True), (512, 512, True)],
    ("bd8k", "clean", "bwd"): [ROW_BWD, (1024, 1024), (512, 2048),
                               (512, 1024), (2048, 1024), (512, 512)],
    ("bd8k", "below", "fwd"): [ROW_FWD, (1024, 1024, True)],
    ("bd8k", "below", "bwd"): [ROW_BWD, (1024, 1024), (512, 1024)],
    ("causal8k", "clean", "fwd"): [ROW_FWD, (1024, 1024, True),
                                   (2048, 1024, True), (4096, 1024, True),
                                   (512, 512, True)],
    ("causal8k", "clean", "bwd"): [ROW_BWD, (1024, 1024), (512, 2048),
                                   (512, 1024), (2048, 1024), (512, 512)],
    ("causal1k", "clean", "fwd"): [ROW_FWD, (512, 512, True),
                                   (256, 256, True), (512, 256, True),
                                   (1024, 512, True), (128, 128, True)],
    ("causal1k", "clean", "bwd"): [ROW_BWD, (512, 1024), (512, 512),
                                   (256, 512), (256, 256), (256, 1024)],
    ("win256_8k", "banded", "fwd"): [ROW_FWD, (256, 256, True),
                                     (512, 512, True), (1024, 1024, True)],
    ("win256_8k", "banded", "bwd"): [ROW_BWD, (256, 256), (512, 512),
                                     (1024, 1024)],
    ("win1024_8k", "banded", "fwd"): [ROW_FWD, (512, 512, True),
                                      (1024, 1024, True)],
    ("win1024_8k", "banded", "bwd"): [ROW_BWD, (512, 512), (1024, 1024)],
    ("mla16k", "clean", "fwd"): [ROW_FWD, (1024, 1024, True),
                                 (2048, 1024, True), (1024, 2048, True)],
    ("mla16k", "clean", "bwd"): [ROW_BWD, (1024, 1024), (512, 2048),
                                 (2048, 1024)],
    ("causal16k", "clean", "fwd"): [ROW_FWD],
    ("causal16k", "clean", "bwd"): [ROW_BWD, (1024, 1024)],
    ("bd8k", "clean", "edge"): EDGES,
    ("bd8k", "below", "edge"): EDGES,
    ("bd8k", "below_carried", "edge"): EDGES,
    ("causal8k", "clean", "edge"): EDGES,
    # one 1024 x 1024 tile a head on the rectangular grid: edge 1024 is 0
    ("causal1k", "clean", "edge"): [EDGES[0]] + EDGES[2:],
    # the backward by edge (`--edges --bwd`): the rectangular kernel at 32 / 8
    # and 32 / 4 heads (the latter in block units, with and without a carry),
    # the triangular one at 32 / 32 and at 192 / 128
    ("causal8k", "clean", "bwd_edge"): BWD_EDGES_8K,
    ("mha8k", "clean", "bwd_edge"): BWD_EDGES_8K,
    ("bd8k", "clean", "bwd_edge"): BWD_EDGES,
    ("bd8k", "below", "bwd_edge"): BWD_EDGES,
    ("causal1k", "clean", "bwd_edge"): BWD_EDGES,
    ("causal16k", "clean", "bwd_edge"): BWD_EDGES,
    ("mla16k", "clean", "bwd_edge"): BWD_EDGES,
    ("mha32k", "clean", "bwd_edge"): BWD_EDGES,
    ("causal64k", "clean", "bwd_edge"): BWD_EDGES,
}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--out", default="chiprun_out/sweep_tile_calls.jsonl")
    p.add_argument("--only", default="",
                   help="comma list of geometry names (default: all)")
    p.add_argument("--first", type=int, default=0,
                   help="only the first N configurations of each sweep")
    p.add_argument("--edges", action="store_true",
                   help="only the sweeps of the diagonal sub-square edge")
    p.add_argument("--bwd", action="store_true",
                   help="with --edges: the backward's sweeps, not the "
                        "forward's")
    args = p.parse_args()

    import os

    os.environ["BURST_ALLOW_CLIFF"] = "1"

    import jax
    import jax.numpy as jnp

    from burst_attn_tpu.ops import pallas_flash as pf
    from burst_attn_tpu.ops.masks import BlockUnits, MaskSpec
    from burst_attn_tpu.ops.tile import finalize
    from chipbench import trace

    if jax.default_backend() != "tpu":
        print("sweep_tile_calls: not on TPU; refusing to record numbers",
              file=sys.stderr)
        sys.exit(1)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)

    def record(row):
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")
        print(json.dumps(row), flush=True)

    def device_ms(fn, *xs):
        """(ms of the flash kernels, ms of every other device op, {op: ms}
        of the kernels and the four longest others) of one call, mean over
        ITERS traced runs, and its outputs."""
        out = jax.block_until_ready(fn(*xs))
        tmp = tempfile.mkdtemp(prefix="sweep_tile_")
        try:
            jax.profiler.start_trace(tmp)
            for _ in range(ITERS):
                jax.block_until_ready(fn(*xs))
            jax.profiler.stop_trace()
            raw = trace.read_xplane(tmp)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        by_name = {}
        for lines in raw["devices"].values():
            for line in lines:
                for name, start, end in trace.self_segments(line):
                    by_name[name] = by_name.get(name, 0) + end - start
        ms = {name: ns / ITERS / 1e6 for name, ns in by_name.items()}
        flash = {name: t for name, t in ms.items()
                 if trace.FLASH_KERNEL in name}
        other = sorted(((t, name) for name, t in ms.items()
                        if name not in flash), reverse=True)
        return (sum(flash.values()), sum(t for t, _ in other),
                {**flash, **{name: t for t, name in other[:4]}}, out)

    def worst(a, b):
        f32 = lambda x: x.astype(jnp.float32)
        return max(float(jnp.max(jnp.abs(f32(x) - f32(y))))
                   for x, y in zip(a, b))

    only = [g for g in args.only.split(",") if g]
    for (gname, call, pass_), configs in SWEEPS.items():
        wanted = ({"bwd_edge" if args.bwd else "edge"} if args.edges
                  else {"fwd", "bwd"})
        if (only and gname not in only) or pass_ not in wanted:
            continue
        pass_ = {"edge": "fwd", "bwd_edge": "bwd"}.get(pass_, pass_)
        g = GEOMETRIES[gname]
        s, b, n, n_kv, unit = g["s"], g["b"], g["n"], g["n_kv"], g["unit"]
        d, d_v = g.get("d_qk", 128), g.get("d_v", 128)
        scale = d ** -0.5
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(ks[0], (b, n, s, d), jnp.bfloat16)
        do = jax.random.normal(ks[1], (b, n, s, d_v), jnp.bfloat16)
        k = jax.random.normal(ks[2], (b, n_kv, s, d), jnp.bfloat16)
        v = jax.random.normal(ks[3], (b, n_kv, s, d_v), jnp.bfloat16)
        nb = s // unit
        i32 = lambda x: jnp.asarray(x, jnp.int32)
        kind = call.split("_")[0]
        offset, win = {"clean": (0, None), "below": (-1, None),
                       "diagonal": (0, 1), "banded": (0, g.get("win"))}[kind]

        def causal(off):
            return MaskSpec(i32(0), i32(nb), i32(nb), i32(1), i32(off))

        def units(w):
            return BlockUnits(unit, w) if unit != 1 else w

        spec, window = causal(offset), units(win)
        # the state a call is handed: the forward folds into the state of
        # the noised rows' other call where it is the second of the two, the
        # `below` backward adds into the `clean` call's dk / dv; lse / delta
        # are of a whole forward of this quadrant
        other = {"diagonal": (causal(-1), units(None)),
                 "below_carried": (causal(0), units(1))}.get(call)

        @jax.jit
        def prep(q, k, v, do):
            state = (None, None, None)
            if other is not None:
                state = pf.flash_fwd(q, k, v, None, None, None, scale,
                                     other[0], block_q=2048, block_kv=2048,
                                     window=other[1])
            m, lse, acc = pf.flash_fwd(q, k, v, *state, scale, spec,
                                       block_q=2048, block_kv=2048,
                                       window=window)
            o = finalize(m, lse, acc, jnp.float32)
            delta = jnp.sum(o * do.astype(jnp.float32), -1)
            return state, lse, delta

        state, lse, delta = jax.block_until_ready(prep(q, k, v, do))
        carry = None
        if call == "below":
            carry = (jnp.ones((b, n_kv, s, d), jnp.float32),) * 2
        first = None
        for cfg in configs[:args.first or None]:
            bq, bkv = cfg[0], cfg[1]
            row = dict(geometry=gname, call=call, **{"pass": pass_}, bq=bq,
                       bkv=bkv, rows=s, batch=b, heads=n, kv_heads=n_kv,
                       d_qk=d, d_v=d_v)
            try:
                if pass_ == "fwd":
                    row["band_or_tri"] = cfg[2]
                    row["edge"] = edge = cfg[3] if len(cfg) > 3 else 0
                    fn = jax.jit(lambda q, k, v, *st, bq=bq, bkv=bkv,
                                 tri=cfg[2], edge=edge: pf.flash_fwd(
                        q, k, v, *(st or (None,) * 3), scale, spec,
                        block_q=bq, block_kv=bkv, triangular=tri,
                        window=window, diag_block=edge))
                    xs = (q, k, v) + (state if other is not None else ())
                    if len(cfg) > 3:
                        # what the call site costs before the compiler: a
                        # first trace of this edge's body, and its lowering
                        t0 = time.perf_counter()
                        traced = fn.trace(*xs)
                        t1 = time.perf_counter()
                        traced.lower()
                        row.update(trace_s=round(t1 - t0, 3), lower_s=round(
                            time.perf_counter() - t1, 3))
                else:
                    more = {}
                    if len(cfg) > 2:
                        row["edge"] = cfg[2]
                        more = dict(diag_block=cfg[2])
                    fn = jax.jit(lambda do, q, k, v, delta, lse, *c, bq=bq,
                                 bkv=bkv, more=more: pf.flash_bwd(
                        do, q, k, v, delta, lse, scale, spec, block_q=bq,
                        block_kv=bkv, triangular=True,
                        window=window, carry=c or None, **more))
                    xs = (do, q, k, v, delta, lse) + (carry or ())
                    if more:
                        t0 = time.perf_counter()
                        traced = fn.trace(*xs)
                        t1 = time.perf_counter()
                        lowered = traced.lower()
                        t2 = time.perf_counter()
                        fn = lowered.compile()
                        row.update(trace_s=round(t1 - t0, 3),
                                   lower_s=round(t2 - t1, 3),
                                   compile_s=round(
                                       time.perf_counter() - t2, 3))
                flash, other, names, out = device_ms(fn, *xs)
                row.update(flash_ms=round(flash, 4), other_ms=round(other, 4),
                           ops={k_: round(t, 4) for k_, t in names.items()})
                if pass_ == "fwd":
                    # m and acc depend on the tile order; lse and o do not
                    m, lse_, acc = out
                    out = (jnp.where(jnp.isneginf(lse_), 0.0, lse_),
                           finalize(m, lse_, acc, jnp.float32))
                if first is None:
                    first = out
                row["max_abs_diff_vs_first"] = worst(out, first)
            except Exception as e:  # noqa: BLE001 - record, go on sweeping
                row["error"] = f"{type(e).__name__}: {e}"[:300]
            record(row)


if __name__ == "__main__":
    main()
