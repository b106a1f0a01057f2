"""What a program's forward call sites cost BEFORE the compiler sees them:
seconds of `jax.jit(fn).trace(...)` and of `.lower()` for a described (not
attached) v5e, off the chip.  A kernel body is traced by Pallas anew at every
`pallas_call` and a model makes one a layer, so a body that grows lands in a
cell's `setup_s` once a call site unless the sites share one trace (PR 33,
`pallas_flash._fwd_launch_traced`; PR 32 was refused on `setup_s` for lack of
this reading).

    python -m benchmarks.trace_cost [--tree DIR] [--repeats 3] [--edges 0,512,256,128]

`--tree` puts another checkout of this repo first on the path (the parent
commit's `git archive`), so that both sides are read by the same script.
Prints one JSON line a program: the single causal forward call at 1 x 8,192
rows 32 / 8 heads (with `--edges`, once an edge where the tree's flash_fwd
takes `diag_block`), and `jax.grad` over four chained
`jax.checkpoint(burst_attn)` blocks at 1 x 8,192 and at 8 x 1,024 rows with
the `tpu_custom_call`s in the lowered text counted.  Host seconds of this
machine's CPU: they compare two trees on one host and are no device metric.
"""

import argparse
import inspect
import json
import sys
import time


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--tree", default=None)
    p.add_argument("--repeats", type=int, default=3)
    p.add_argument("--edges", default="",
                   help="comma list of diagonal edges for the single call")
    args = p.parse_args()
    if args.tree:
        sys.path.insert(0, args.tree)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import burst_attn_tpu as bat
    from burst_attn_tpu.ops import pallas_flash as pf, tuning
    from burst_attn_tpu.ops.masks import MaskSpec

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    jax.config.update("jax_enable_compilation_cache", False)
    # answer as the chip would (tests/test_tpu_compile.py's `on_chip`)
    jax.default_backend = lambda: "tpu"
    row = tuning.generation_row("v5e")
    tuning.block_defaults = lambda device=None: row
    mesh = Mesh(np.array(topo.devices[:1]), ("sp",))
    sharding = NamedSharding(mesh, P())
    heads, kv_heads, d = 32, 8, 128

    def shapes(batch, rows):
        q = jax.ShapeDtypeStruct((batch, heads, rows, d), jnp.bfloat16,
                                 sharding=sharding)
        kv = jax.ShapeDtypeStruct((batch, kv_heads, rows, d), jnp.bfloat16,
                                  sharding=sharding)
        return q, kv, kv

    def read(name, fn, xs, **more):
        trace_s, lower_s, calls = [], [], None
        for _ in range(args.repeats):
            jax.clear_caches()
            t0 = time.perf_counter()
            traced = jax.jit(fn).trace(*xs)
            t1 = time.perf_counter()
            text = traced.lower().as_text()
            t2 = time.perf_counter()
            trace_s.append(round(t1 - t0, 3))
            lower_s.append(round(t2 - t1, 3))
            calls = text.count("tpu_custom_call")
        print(json.dumps(dict(
            program=name, tree=args.tree or ".", trace_s=trace_s,
            lower_s=lower_s, sum_min_s=round(min(trace_s) + min(lower_s), 3),
            mosaic_calls=calls, **more)), flush=True)

    def single(edge):
        i32 = lambda x: jnp.asarray(x, jnp.int32)
        spec = MaskSpec(i32(0), i32(8192), i32(8192), i32(1), i32(0))
        kw = {} if edge is None else dict(diag_block=edge)
        return lambda q, k, v: pf.flash_fwd(
            q, k, v, None, None, None, d ** -0.5, spec, block_q=2048,
            block_kv=2048, triangular=True, **kw)

    takes_edge = "diag_block" in inspect.signature(pf.flash_fwd).parameters
    edges = [int(e) for e in args.edges.split(",") if e] if takes_edge else []
    for edge in [None] + edges:
        read("fwd_call_1x8192", single(edge), shapes(1, 8192), edge=edge)

    def model(q, k, v):
        block = jax.checkpoint(lambda x: bat.burst_attn(
            x, k, v, mesh=mesh, causal=True, backend="auto"))
        for _ in range(4):
            q = block(q)
        return jnp.sum(q.astype(jnp.float32))

    for batch, rows in ((1, 8192), (8, 1024)):
        read(f"grad_4_checkpoint_blocks_{batch}x{rows}", jax.grad(model),
             shapes(batch, rows))


if __name__ == "__main__":
    main()
