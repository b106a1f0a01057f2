"""Batch-scaling probe (round-2 verdict item 3): same-FLOPs configs lose
~25% per-chip throughput as batch count rises (results/results_scaling.jsonl:
fwd 158.4 @ b=1/64K -> 117.0 @ b=4/32K; the reference instead RISES with
batch, reference README.md:100-103).

Per-step arithmetic from round 2: 13.1us (b=1, 64K) -> 14.1 (b=2, 32K) ->
17.3 (b=4, 32K) with IDENTICAL 2048x2048 blocks — per-step cost grows with
batch count / shrinking per-entry rows.  Candidate causes this probe
separates:

  * batch-count term: b=1 vs b=2 vs b=4 at FIXED seq=32K (same per-entry
    grid, same per-step work; flat TFLOPs/s here acquits the batch dim)
  * row-length term: the tri grid's init/finalize steps (_read_rows /
    _write_rows state repacking) are a 4/(nqb+1) fraction of all steps —
    nqb=16 at 32K pays 23.5%, nqb=32 at 64K pays 12%
  * grid-geometry term: tri vs rect (BURST_NO_TRI) at the same configs
    (the rect grid has uniform init/fin density by construction)
  * block-size term: bq=1024 at 32K restores nqb=32 (the 64K init/fin
    density) at 4x the step count

Writes one jsonl row per config to --out; run on a real chip:

    python -m benchmarks.batch_probe --out results/batch_probe.jsonl
"""

import argparse
import json
import os
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--out", default="results/batch_probe.jsonl")
    ap.add_argument("--trace-dir", default="",
                    help="capture an XLA trace of the worst config")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from benchmarks.benchmark import bench_fn, flops

    if jax.default_backend() != "tpu":
        print("batch_probe: not on TPU; refusing to record numbers",
              file=sys.stderr)
        sys.exit(1)

    from burst_attn_tpu.ops.pallas_flash import flash_attention

    n, d = args.heads, args.dim
    if os.environ.get("BURST_NO_TRI", "").strip().lower() not in ("", "0", "false"):
        # _tri_disabled() is read at trace time: with the env var exported
        # the "tri" rows would silently compile rect grids and the per-step
        # arithmetic would be ~2x off.  The probe owns this knob.
        sys.exit("batch_probe: unset BURST_NO_TRI first (the probe toggles "
                 "it per case and needs both grids)")
    if os.path.dirname(args.out):
        os.makedirs(os.path.dirname(args.out), exist_ok=True)

    def record(row):
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")
        print(json.dumps(row), flush=True)

    # (batch, seq, block_q or (block_q, block_kv), no_tri)
    cases = [
        (1, 65536, None, False),   # round-2 anchor: 158.4
        (1, 32768, None, False),   # NEW: batch-free seq term
        (2, 32768, None, False),   # round-2: 143.1
        (4, 32768, None, False),   # round-2: 117.0
        (4, 32768, None, True),    # rect grid: uniform init/fin density
        (1, 32768, 1024, False),   # nqb=32 at 32K: 64K's init/fin fraction
        (4, 32768, 1024, False),
        (8, 16384, None, False),   # extreme: nqb=8, 4/9 steps init/fin
        # tall-q tri grid (round 4): same area/step count, init/fin events
        # drop to 4/((nqb+1)r) of steps and K/V bytes to 1/r — the fix
        # candidate for the regression if the init/fin term is convicted
        (4, 32768, (4096, 1024), False),
        (1, 65536, (4096, 1024), False),
        (8, 16384, (4096, 1024), False),
    ]

    def run_ablate(b, s):
        """nosoftmax ablation at batch b (discriminator: if the batch
        regression SURVIVES with the whole VPU softmax chain stripped,
        it is grid/DMA-side — per-step overhead, megacore, state blocks —
        not VPU scheduling).  Timing scaffold shared with sweep_blocks
        (benchmarks.benchmark.time_flash_fwd)."""
        from benchmarks.benchmark import time_flash_fwd

        try:
            t, tf = time_flash_fwd(b, n, s, d, block_q=2048, block_kv=2048,
                                   block_kv_compute=1024,
                                   _ablate="nosoftmax")
            record({"batch": b, "seq": s, "block_q": 2048, "grid": "tri",
                    "ablate": "nosoftmax", "ms": round(t * 1e3, 2),
                    "tflops": round(tf, 1)})
        except Exception as e:  # noqa: BLE001
            record({"batch": b, "seq": s, "ablate": "nosoftmax",
                    "error": f"{type(e).__name__}: {e}"[:200]})


    for b, s, bq, no_tri in cases:
        key = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (b, n, s, d), jnp.bfloat16)
        k = jax.random.normal(kk, (b, n, s, d), jnp.bfloat16)
        v = jax.random.normal(kv, (b, n, s, d), jnp.bfloat16)
        if no_tri:
            os.environ["BURST_NO_TRI"] = "1"
        bq_eff, bkv_eff = (bq if isinstance(bq, tuple) else (bq or 2048,
                                                             bq or 2048))
        try:
            f = jax.jit(lambda q, k, v, bq=bq_eff, bkv=bkv_eff: jnp.sum(
                flash_attention(q, k, v, None, True, bq, bkv)
                .astype(jnp.float32)))
            t = bench_fn(f, q, k, v)
            fl = flops(b, s, n, d, "fwd", True)
            # tri-grid step count: b*n * (nqb/2) * (nqb+1)*r, r = bq/bkv
            nqb = s // bq_eff
            r = bq_eff // bkv_eff
            steps = b * n * (nqb // 2) * (nqb + 1) * r if not no_tri else (
                b * n * nqb * nqb * r)
            record({"batch": b, "seq": s, "block_q": bq_eff,
                    "block_kv": bkv_eff,
                    "grid": "rect" if no_tri else "tri",
                    "ms": round(t * 1e3, 2),
                    "tflops": round(fl / t / 1e12, 1),
                    "us_per_step": round(t * 1e6 / steps, 2),
                    "initfin_frac": round(4 / ((nqb + 1) * r), 3)})
        except Exception as e:  # noqa: BLE001 — record and continue
            record({"batch": b, "seq": s, "block_q": bq_eff,
                    "block_kv": bkv_eff,
                    "grid": "rect" if no_tri else "tri",
                    "error": f"{type(e).__name__}: {e}"[:200]})
        finally:
            if no_tri:
                os.environ.pop("BURST_NO_TRI", None)

    # ablation discriminator AFTER the anchors (a run cut short should
    # cost the extras, not the baseline rows)
    run_ablate(1, 32768)
    run_ablate(4, 32768)

    if args.trace_dir:
        b, s = 4, 32768
        key = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (b, n, s, d), jnp.bfloat16)
        k = jax.random.normal(kk, (b, n, s, d), jnp.bfloat16)
        v = jax.random.normal(kv, (b, n, s, d), jnp.bfloat16)
        f = jax.jit(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, None, True).astype(jnp.float32)))
        float(f(q, k, v))  # compile + warm
        with jax.profiler.trace(args.trace_dir):
            float(f(q, k, v))
        print(f"trace written to {args.trace_dir}", flush=True)


if __name__ == "__main__":
    main()
