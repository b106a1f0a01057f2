"""Kernel block-size sweep on real TPU — finds the fwd/bwd block optimum
that bench.py's defaults should use.

Each fresh kernel shape compiles anew; results append to a jsonl file
immediately so an interrupted sweep keeps what it measured:

    python -m benchmarks.sweep_blocks --out /tmp/sweep.jsonl
"""

import argparse
import json
import sys


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--seq", type=int, default=65536)
    p.add_argument("--heads", type=int, default=32)
    p.add_argument("--kv-heads", type=int, default=None)
    p.add_argument("--dim", type=int, default=128)
    p.add_argument("--out", default="results/sweep_blocks.jsonl")
    p.add_argument("--fwd", default="2048x2048,2048x4096,1024x4096",
                   help="comma list of BQxBKV (fwd), empty to skip")
    p.add_argument("--bwd", default="1024x2048,1024x4096,2048x2048,512x4096",
                   help="comma list of BQxBKV (bwd-only, fused kernel), "
                        "BQxBKVxsplit (split dq / dkdv kernels), or "
                        "BQxBKVxtri (wrapped-diagonal causal grid; optional "
                        "xBKC sub-block and xloop for the fori_loop sweep, "
                        "e.g. 1024x4096xtrix1024xloop); empty to skip")
    p.add_argument("--fwd-compute", default="",
                   help="comma list of BQxBKVxBKC (fwd with compute sub-block)")
    p.add_argument("--ablate-fwd", default="",
                   help="comma list of BQxBKV timed with the softmax chain "
                        "stripped (wrong numerics; measures the MXU/pipeline "
                        "ceiling to localize the fwd kernel's VPU cost)")
    p.add_argument("--fwd-loop", default="",
                   help="comma list of BQxBKVxBKC timed with the fori_loop "
                        "sub-block sweep (loop_sweep=True): buffers reuse "
                        "per iteration, probing whether the VMEM area cliff "
                        "is unrolled-stage liveness")
    p.add_argument("--fwd-raw-empty", default="",
                   help="comma list of BQxBKV[xBKC] timed through the RAW "
                        "flash_fwd scaffold with the None-carry fast path "
                        "(empty_carry=True) — isolates the carry-state DMA "
                        "cost vs the carried rows the same scaffold times "
                        "by default (--fwd already times the None-carry "
                        "path end-to-end through flash_attention)")
    args = p.parse_args()

    import os

    # sweeps measure whatever config they're told to, including past-cliff
    # ones (how the cliff law in ops/tuning.py was found in the first place)
    os.environ["BURST_ALLOW_CLIFF"] = "1"

    import jax
    import jax.numpy as jnp

    from benchmarks.benchmark import bench_fn, flops
    from burst_attn_tpu.ops.pallas_flash import flash_attention, tri_bwd_supported

    if jax.default_backend() != "tpu":
        print("sweep_blocks: not on TPU; refusing to record numbers", file=sys.stderr)
        sys.exit(1)

    b, n, d, seq = 1, args.heads, args.dim, args.seq
    nkv = args.kv_heads or n
    key = jax.random.PRNGKey(0)
    kq, kk, kv, kg = jax.random.split(key, 4)
    q = jax.random.normal(kq, (b, n, seq, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, nkv, seq, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, nkv, seq, d), jnp.bfloat16)
    do = jax.random.normal(kg, (b, n, seq, d), jnp.bfloat16)

    def record(row):
        row.update(seq=seq, heads=n, kv_heads=nkv, dim=d)
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")
        print(json.dumps(row), flush=True)

    def parse(spec):
        return [tuple(int(x) for x in c.split("x")) for c in spec.split(",") if c]

    for cfg in parse(args.fwd) + parse(args.fwd_compute):
        bq, bkv = cfg[0], cfg[1]
        bkc = cfg[2] if len(cfg) > 2 else None
        try:
            f = jax.jit(lambda q, k, v, bq=bq, bkv=bkv, bkc=bkc: jnp.sum(
                flash_attention(q, k, v, None, True, bq, bkv,
                                block_kv_compute=bkc).astype(jnp.float32)))
            t = bench_fn(f, q, k, v)
            record({"pass": "fwd", "bq": bq, "bkv": bkv, "bkc": bkc,
                    "ms": round(t * 1e3, 2),
                    "tflops": round(flops(b, seq, n, d, "fwd", True) / t / 1e12, 1)})
        except Exception as e:  # noqa: BLE001 - record and continue the sweep
            record({"pass": "fwd", "bq": bq, "bkv": bkv, "bkc": bkc,
                    "error": f"{type(e).__name__}: {e}"[:200]})

    def bench_flash_fwd(pass_name, cfgs, **fwd_kw):
        """Raw-flash_fwd timing modes (loop / ablation variants): one row
        per BQxBKV[xBKC] config via the scaffold shared with batch_probe
        (benchmarks.benchmark.time_flash_fwd)."""
        from benchmarks.benchmark import time_flash_fwd

        for cfg in cfgs:
            bq, bkv = cfg[0], cfg[1]
            bkc = cfg[2] if len(cfg) > 2 else None
            row = {"pass": pass_name, "bq": bq, "bkv": bkv, "bkc": bkc}
            try:
                t, tf = time_flash_fwd(b, n, seq, d, n_kv=nkv, block_q=bq,
                                       block_kv=bkv, block_kv_compute=bkc,
                                       **fwd_kw)
                row.update(ms=round(t * 1e3, 2), tflops=round(tf, 1))
            except Exception as e:  # noqa: BLE001
                row.update(error=f"{type(e).__name__}: {e}"[:200])
            record(row)

    bench_flash_fwd("fwd-loop", parse(args.fwd_loop), loop_sweep=True)
    bench_flash_fwd("fwd-ablate-nosoftmax", parse(args.ablate_fwd),
                    _ablate="nosoftmax")
    bench_flash_fwd("fwd-raw-empty", parse(args.fwd_raw_empty),
                    empty_carry=True)

    bwd_cfgs = [c for c in args.bwd.split(",") if c]
    if bwd_cfgs:
        # bwd-only timing isolates the kernel being tuned: one fwd run
        # provides the (lse, delta) inputs every bwd config reuses
        from burst_attn_tpu.ops.masks import round_spec
        from burst_attn_tpu.ops.pallas_flash import (
            _flash_attention_fwd_impl, flash_bwd,
        )

        scale = d**-0.5
        spec = round_spec(jnp.int32(0), jnp.int32(0), seq, seq, True, "contig")

        @jax.jit
        def prep(q, k, v, do):
            o, lse = _flash_attention_fwd_impl(q, k, v, None, True, 2048, 2048)
            delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), -1)
            return delta, lse

        try:
            delta, lse = jax.block_until_ready(prep(q, k, v, do))
        except Exception as e:  # noqa: BLE001 - record so the sweep's silence
            record({"pass": "bwd", "error": f"prep: {type(e).__name__}: {e}"[:200]})
            return

        for c in bwd_cfgs:
            parts = c.split("x")
            bqb, bkvb = int(parts[0]), int(parts[1])
            if len(parts) > 2 and parts[2] not in ("split", "tri"):
                record({"pass": "bwd", "error": f"bad config {c!r}: third "
                        "token must be 'split' or 'tri'"})
                continue
            fused = len(parts) <= 2 or parts[2] == "tri"
            tri = len(parts) > 2 and parts[2] == "tri"
            # optional trailing tokens (tri only, any order-tolerant mix):
            # a numeric compute sub-block and/or the literal 'loop' for the
            # fori_loop sweep, e.g. 1024x4096xtrix1024xloop.  Anything else
            # is an error ROW, not a sweep abort (a malformed token must
            # not cost the remaining multi-hour configs), and a misspelled
            # 'loop' must not silently time the unrolled kernel.
            bkc, loop, bad = None, False, None
            for tok in parts[3:]:
                if tok == "loop":
                    loop = True
                elif tok.isdigit():
                    bkc = int(tok)
                else:
                    bad = tok
            if bad is not None:
                record({"pass": "bwd", "error": f"bad config {c!r}: "
                        f"unknown token {bad!r} (want a number or 'loop')"})
                continue
            # record which kernel actually runs: flash_bwd silently falls
            # back to the rectangular fused kernel when the tri gate fails
            # (which also ignores loop_sweep — record the EFFECTIVE flags)
            tri_eff = tri and tri_bwd_supported(
                seq, seq, n, nkv, d, block_q=bqb, block_kv=bkvb,
                block_kv_compute=bkc)
            row = {"pass": "bwd", "bq_bwd": bqb, "bkv_bwd": bkvb,
                   "fused": fused, "tri": tri_eff, "bkc_bwd": bkc,
                   "loop": loop and tri_eff}
            if tri and not tri_eff:
                row["tri_requested_fell_back"] = True
            try:
                f = jax.jit(lambda q, k, v, do, delta, lse, bqb=bqb, bkvb=bkvb,
                            fused=fused, tri=tri, bkc=bkc, loop=loop: sum(
                    jnp.sum(g.astype(jnp.float32)) for g in flash_bwd(
                        do, q, k, v, delta, lse, scale, spec,
                        block_q=bqb, block_kv=bkvb, fused=fused, triangular=tri,
                        block_kv_compute=bkc, loop_sweep=loop)))
                t = bench_fn(f, q, k, v, do, delta, lse)
                row.update(ms=round(t * 1e3, 2),
                           tflops=round(flops(b, seq, n, d, "bwd", True) / t / 1e12, 1))
            except Exception as e:  # noqa: BLE001
                row.update(ms=None, error=f"{type(e).__name__}: {e}"[:200])
            record(row)


if __name__ == "__main__":
    main()
