"""Grid-step overhead decomposition probe (round 4).

The 64K fwd measures ~13.1 us/grid-step against ~5.5 us of MXU work and
~1.2 us of K/V DMA at 819 GB/s — leaving ~5-6 us/step unexplained even
with the whole softmax chain ablated (nosoftmax floor 12.2 us/step).
This probe times a MINIMAL pallas kernel — per step: fetch one kv-sized
block and run one matmul into scratch, nothing else — across step counts
and block sizes, to split the per-step cost into

    t_step = t_fixed + bytes/bw + flops/mxu

If t_fixed dominates (per-step cost barely moves with block bytes), the
production kernel's ceiling really is Mosaic per-step sequencing and only
a step-count reduction (the VMEM-cliff break) can move the headline; if
the bytes term dominates, tall-q-style DMA shaping matters too.

    python -m benchmarks.step_probe --out results/step_probe.jsonl
"""

import argparse
import functools
import json
import os
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--bq", type=int, default=2048,
                    help="rows of the resident block the matmul feeds")
    ap.add_argument("--kv-blocks", default="256,1024,2048,4096",
                    help="comma list of kv block heights (bytes scale)")
    ap.add_argument("--steps", default="512,2048,8192",
                    help="comma list of grid lengths (fixed-cost scale)")
    ap.add_argument("--no-matmul", action="store_true",
                    help="DMA-only variant (drop the MXU term entirely)")
    ap.add_argument("--out", default="results/step_probe.jsonl")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from benchmarks.benchmark import bench_fn

    if jax.default_backend() != "tpu":
        print("step_probe: not on TPU; refusing to record numbers",
              file=sys.stderr)
        sys.exit(1)
    if os.path.dirname(args.out):
        os.makedirs(os.path.dirname(args.out), exist_ok=True)

    def record(row):
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")
        print(json.dumps(row), flush=True)

    d, bq = args.dim, args.bq

    def kernel(q_ref, k_ref, o_ref, acc, *, do_mm):
        j = pl.program_id(0)

        @pl.when(j == 0)
        def _init():
            acc[:] = jnp.zeros_like(acc)

        if do_mm:
            w = min(acc.shape[1], k_ref.shape[1])  # static
            acc[:, :w] = acc[:, :w] + jax.lax.dot_general(
                q_ref[0, :, :], k_ref[0, :, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )[:, :w]

        @pl.when(j == pl.num_programs(0) - 1)
        def _fin():
            o_ref[0, :, :] = acc[:]

    for bkv in (int(x) for x in args.kv_blocks.split(",") if x):
        for n_steps in (int(x) for x in args.steps.split(",") if x):
            # one kv block per step, streamed from a CAPPED pool addressed
            # j % n_pool — the index changes every step so the DMA always
            # re-issues, but HBM stays bounded for any step count (an
            # uncapped [n_steps, bkv, d] pool is 8.6 GB at 4096x8192);
            # q stays resident (constant index map)
            n_pool = min(n_steps, 512)
            do_mm = not args.no_matmul
            try:
                q = jax.random.normal(jax.random.PRNGKey(0), (1, bq, d),
                                      jnp.bfloat16)
                kpool = jax.random.normal(jax.random.PRNGKey(1),
                                          (n_pool, bkv, d), jnp.bfloat16)
                fn = pl.pallas_call(
                    functools.partial(kernel, do_mm=do_mm),
                    grid=(n_steps,),
                    in_specs=[
                        pl.BlockSpec((1, bq, d), lambda j: (0, 0, 0)),
                        pl.BlockSpec((1, bkv, d),
                                     lambda j, n_pool=n_pool: (j % n_pool, 0, 0)),
                    ],
                    out_specs=pl.BlockSpec((1, bq, 128), lambda j: (0, 0, 0)),
                    out_shape=jax.ShapeDtypeStruct((1, bq, 128), jnp.float32),
                    scratch_shapes=[pltpu.VMEM((bq, 128), jnp.float32)],
                    compiler_params=pltpu.CompilerParams(
                        dimension_semantics=("arbitrary",),
                    ),
                )
                f = jax.jit(lambda q, kp: jnp.sum(fn(q, kp)))
                t = bench_fn(f, q, kpool)
                step_us = t * 1e6 / n_steps
                mb = bkv * d * 2 / 1e6
                record({"bq": bq, "bkv": bkv, "steps": n_steps,
                        "matmul": do_mm, "ms": round(t * 1e3, 3),
                        "us_per_step": round(step_us, 3),
                        "kv_mb_per_step": round(mb, 3),
                        # residual after the 819 GB/s bytes term
                        "us_minus_dma": round(step_us - mb / 819 * 1e3, 3)})
            except Exception as e:  # noqa: BLE001 — record and continue
                record({"bq": bq, "bkv": bkv, "steps": n_steps,
                        "matmul": do_mm,
                        "error": f"{type(e).__name__}: {e}"[:200]})


if __name__ == "__main__":
    main()
