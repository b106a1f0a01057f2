"""Benchmark harness — the reference's benchmarks/benchmark.py rebuilt.

Same FLOPs convention (reference benchmark.py:17-24): fwd FLOPs =
4*b*s^2*n*d / (2 if causal), bwd = 2.5x, fwd+bwd = 3.5x; TFLOPs/s divided by
ring width for distributed methods -> per-chip numbers comparable with the
reference README tables (SURVEY.md §6).  Results append to a jsonl file
(reference utils.py:73-86).

Methods (reference benchmark.py:146-153, get_burst_func :242):
  flash         — single-chip Pallas flash attention over the full sequence
  burst         — burst_attn, zigzag layout
  burst_striped — burst_attn, striped layout
  ring          — score-materializing ring baseline (benchmarks/ring_baseline)

Usage:  python -m benchmarks.benchmark [--methods burst,flash] [--seqs 4096]
        [--mesh 8 | --mesh 2x4] [--causal] [--double-ring] [--out results.jsonl]
"""

import argparse
import json
import time
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def flops(b, s, n, d, mode="fwd", causal=False):
    f = 4 * b * s * s * n * d / (2 if causal else 1)
    return {"fwd": f, "bwd": 2.5 * f, "fwd_bwd": 3.5 * f}[mode]


def efficiency(flop, t):
    return flop / t / 1e12


def bench_fn(fn, *args, warmup=3, iters=10, reps=3, on_event=None):
    """fn must return a SCALAR.  All `iters` dispatches are queued
    asynchronously and synchronized by ONE host fetch of their sum — a
    per-iteration fetch would add a host<->device round trip to every
    measurement.

    `on_event(phase, **fields)`: optional progress hook (bench.py's
    incremental JSONL log) fired at compile start/end, after each warmup
    call, and after each rep — a run killed at its time limit then still leaves
    per-phase timestamps behind."""
    ev = on_event if on_event is not None else (lambda phase, **kw: None)
    ev("compile_start")
    float(fn(*args))  # first call compiles (or replays the compile cache)
    ev("compile_end")
    for i in range(1, warmup):
        float(fn(*args))
        ev("warmup", i=i)
    ts = []
    for r in range(reps):
        t0 = time.perf_counter()
        acc = None
        for _ in range(iters):
            res = fn(*args)
            acc = res if acc is None else acc + res
        float(acc)
        ts.append((time.perf_counter() - t0) / iters)
        ev("rep", i=r, s_per_iter=round(ts[-1], 6))
    return float(np.min(ts))


def time_flash_fwd(b, n, s, d, *, block_q, block_kv, block_kv_compute=None,
                   n_kv=None, triangular=True, empty_carry=False, **fwd_kw):
    """Time ONE raw flash_fwd config on fresh bf16 inputs — the
    kernel-sweep scaffold shared by sweep_blocks (--fwd-loop/--ablate-fwd)
    and batch_probe (nosoftmax rows), so the two probes cannot silently
    drift apart.  Returns (seconds, fwd TFLOPs/s).  fwd_kw passes through
    to flash_fwd (loop_sweep=True, _ablate="nosoftmax", ...).

    empty_carry=True times the None-carry fast path (what the single-device
    flash_attention forward runs); the default times a carried state, which
    is what every ring round after the first pays."""
    from burst_attn_tpu.ops.masks import round_spec
    from burst_attn_tpu.ops.pallas_flash import flash_fwd
    from burst_attn_tpu.ops.tile import init_state

    n_kv = n_kv or n
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (b, n, s, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, n_kv, s, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, n_kv, s, d), jnp.bfloat16)
    spec = round_spec(jnp.int32(0), jnp.int32(0), s, s, True, "contig")
    st = (None, None, None) if empty_carry else init_state(b, n, s, d)
    f = jax.jit(lambda q, k, v: jnp.sum(flash_fwd(
        q, k, v, *st, d**-0.5, spec,
        block_q=block_q, block_kv=block_kv,
        block_kv_compute=block_kv_compute, triangular=triangular,
        **fwd_kw)[2]))
    t = bench_fn(f, q, k, v)
    return t, flops(b, s, n, d, "fwd", True) / t / 1e12


def _scalar_grads(grads):
    return sum(jnp.sum(g.astype(jnp.float32)) for g in grads)


def make_mesh(spec: str):
    devs = jax.devices()
    if "x" in spec:
        inter, intra = (int(x) for x in spec.split("x"))
        if inter * intra > len(devs):
            raise SystemExit(f"mesh {spec} needs {inter*intra} devices, have {len(devs)}")
        mesh = Mesh(np.array(devs[: inter * intra]).reshape(inter, intra), ("inter", "intra"))
        return mesh, ("inter", "intra")
    w = int(spec)
    return Mesh(np.array(devs[:w]), ("sp",)), ("sp",)


def run_method(method, mesh, seq_axes, b, s, n, d, n_kv, causal, dtype, backend,
               fwd_only=False):
    from burst_attn_tpu import burst_attn
    from burst_attn_tpu.parallel import layouts

    w = int(np.prod([mesh.shape[a] for a in seq_axes]))
    key = jax.random.PRNGKey(0)
    kq, kk, kv, kg = jax.random.split(key, 4)

    if method == "flash":
        # full sequence on ONE chip (reference benchmark.py:146-153)
        from burst_attn_tpu.ops.pallas_flash import flash_attention

        q = jax.random.normal(kq, (b, n, s, d), dtype)
        k = jax.random.normal(kk, (b, n_kv, s, d), dtype)
        v = jax.random.normal(kv, (b, n_kv, s, d), dtype)
        fwd = jax.jit(
            lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, None, causal).astype(jnp.float32)))
        if fwd_only:
            # at the longest sequences the bwd residuals don't fit one chip;
            # fwd-only still anchors the TFLOPs scaling curve (BASELINE.md)
            return bench_fn(fwd, q, k, v), None, 1
        do = jax.random.normal(kg, (b, n, s, d), dtype)

        # NB: big arrays (do) must be jit ARGUMENTS, not closures — a closed-
        # over array is embedded in the program as a constant
        @jax.jit
        def fb(q, k, v, do):
            def loss(q, k, v):
                return jnp.sum(
                    flash_attention(q, k, v, None, causal).astype(jnp.float32)
                    * do.astype(jnp.float32))
            return _scalar_grads(jax.grad(loss, argnums=(0, 1, 2))(q, k, v))

        return bench_fn(fwd, q, k, v), bench_fn(fb, q, k, v, do), 1

    layout = {"burst": "zigzag", "burst_striped": "striped", "ring": "contig"}[method]
    seq_spec = seq_axes if len(seq_axes) > 1 else seq_axes[0]
    shard = NamedSharding(mesh, P(None, None, seq_spec, None))
    q = jax.device_put(jax.random.normal(kq, (b, n, s, d), dtype), shard)
    k = jax.device_put(jax.random.normal(kk, (b, n_kv, s, d), dtype), shard)
    v = jax.device_put(jax.random.normal(kv, (b, n_kv, s, d), dtype), shard)
    # the gradient seed is only materialized when the bwd actually runs —
    # fwd-only exists for configs where one more q-sized buffer OOMs
    do = (None if fwd_only
          else jax.device_put(jax.random.normal(kg, (b, n, s, d), dtype), shard))

    if method == "ring":
        from benchmarks.ring_baseline import ring_attention

        if len(seq_axes) != 1:
            raise SystemExit("ring baseline supports a single 'sp' axis only")
        fwd = jax.jit(
            lambda q, k, v: jnp.sum(
                ring_attention(q, k, v, mesh=mesh, causal=causal).astype(jnp.float32)))
        if fwd_only:
            return bench_fn(fwd, q, k, v), None, w

        @jax.jit
        def fb(q, k, v, do):
            def loss(q, k, v):
                o = ring_attention(q, k, v, mesh=mesh, causal=causal)
                return jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32))
            return _scalar_grads(jax.grad(loss, argnums=(0, 1, 2))(q, k, v))

        return bench_fn(fwd, q, k, v), bench_fn(fb, q, k, v, do), w

    attn = partial(
        burst_attn, mesh=mesh, seq_axes=seq_axes, causal=causal, layout=layout,
        backend=backend,
    )
    fwd = jax.jit(lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32)))
    if fwd_only:
        return bench_fn(fwd, q, k, v), None, w

    @jax.jit
    def fb(q, k, v, do):
        def loss(q, k, v):
            return jnp.sum(attn(q, k, v).astype(jnp.float32) * do.astype(jnp.float32))
        return _scalar_grads(jax.grad(loss, argnums=(0, 1, 2))(q, k, v))

    return bench_fn(fwd, q, k, v), bench_fn(fb, q, k, v, do), w


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--methods", default="burst,flash")
    ap.add_argument("--seqs", default="4096")
    ap.add_argument("--mesh", default=str(len(jax.devices())))
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=None)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--causal", action="store_true")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--backend", default="auto")
    ap.add_argument("--fwd-only", action="store_true",
                    help="skip the fwd+bwd timing (longest seqs OOM the bwd)")
    ap.add_argument("--out", default="results/results.jsonl")
    args = ap.parse_args()

    import os

    if os.path.dirname(args.out):
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
    mesh, seq_axes = make_mesh(args.mesh)
    dtype = jnp.dtype(args.dtype)
    n_kv = args.kv_heads or args.heads
    for s in (int(x) for x in args.seqs.split(",")):
        for method in args.methods.split(","):
            t_f, t_fb, w = run_method(
                method, mesh, seq_axes, args.batch, s, args.heads, args.dim,
                n_kv, args.causal, dtype, args.backend,
                fwd_only=args.fwd_only,
            )
            rec = {
                "method": method, "seq": s, "batch": args.batch,
                "heads": args.heads, "kv_heads": n_kv, "dim": args.dim,
                "causal": args.causal, "dtype": str(dtype), "world": w,
                "fwd_ms": round(t_f * 1e3, 3),
                "fwd_tflops_per_chip": round(
                    efficiency(flops(args.batch, s, args.heads, args.dim, "fwd", args.causal), t_f) / w, 2),
            }
            if t_fb is not None:
                rec["fwd_bwd_ms"] = round(t_fb * 1e3, 3)
                rec["fwd_bwd_tflops_per_chip"] = round(
                    efficiency(flops(args.batch, s, args.heads, args.dim, "fwd_bwd", args.causal), t_fb) / w, 2)
            print(json.dumps(rec))
            # append per record: an interrupted multi-config run keeps
            # what it measured
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
