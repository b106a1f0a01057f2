"""Ring comm/compute overlap evidence (round-2 verdict item 9).

XProf on a single chip cannot show ring overlap (W=1 has no permute), and
no multi-chip hardware is reachable — but the COMPILED SCHEDULE can be
inspected directly: XLA splits each ppermute into collective-permute-start
/ collective-permute-done, and the number of fusion/dot ops scheduled
BETWEEN start and done is exactly the compute the DMA overlaps.  This
script lowers one burst fwd(+bwd) step on a mesh, walks the optimized HLO
in schedule order, and reports, per collective-permute pair, how many
fused compute ops (and an estimate of their FLOPs share) sit inside the
in-flight window.

CPU (simulated 8-device mesh) runs everywhere:

    python -m benchmarks.ring_schedule --cpu --mesh 8 --seq 4096

On TPU the same lowering shows the real Mosaic/ICI schedule; append --out
to record the summary jsonl.
"""

import argparse
import json
import re
import sys


def analyze_hlo(hlo_text):
    """Parse optimized HLO text in (module, computation) order and pair
    collective-permute-start with its -done; count ops between them.

    XLA's latency-hiding scheduler emits instructions in schedule order
    inside each computation, so textual order between start and done is the
    overlap window.  Fusions containing dots are the MXU work."""
    pairs = []
    open_starts = {}  # name -> (line_idx, ops_between)
    compute_re = re.compile(r"^\s*\S+ = \S* (fusion|dot|convolution)\(")
    start_re = re.compile(r"^\s*(\S+) = \S* collective-permute-start\(")
    done_re = re.compile(r"^\s*\S+ = \S* collective-permute-done\(\s*(\S+?)\s*\)")
    for idx, line in enumerate(hlo_text.splitlines()):
        ms = start_re.match(line)
        if ms:
            open_starts[ms.group(1)] = [idx, 0]
            continue
        md = done_re.match(line)
        if md and md.group(1) in open_starts:
            start_idx, n_ops = open_starts.pop(md.group(1))
            pairs.append({"start_line": start_idx, "done_line": idx,
                          "compute_ops_inside": n_ops})
            continue
        if compute_re.match(line):
            for v in open_starts.values():
                v[1] += 1
    # synchronous collective-permute (no start/done split) = zero overlap
    sync = len(re.findall(r" collective-permute\(", hlo_text))
    return pairs, sync


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="8")
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--layout", default="zigzag")
    ap.add_argument("--bwd", action="store_true",
                    help="analyze the fwd+bwd step instead of fwd")
    ap.add_argument("--cpu", action="store_true",
                    help="force the simulated CPU mesh (8 host devices)")
    ap.add_argument("--out", default="")
    ap.add_argument("--dump-hlo", default="",
                    help="also write the full optimized HLO text here")
    args = ap.parse_args()

    import os

    world_req = 1
    for part in args.mesh.split("x"):
        world_req *= int(part)
    if args.cpu:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={max(8, world_req)}")
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    if len(jax.devices()) < world_req:
        # make_mesh's integer path silently builds a 1-device mesh — a W=1
        # "ring" has no permute at all and would record a misleading
        # zero-overlap row.  Refuse instead.
        sys.exit(f"ring_schedule: mesh {args.mesh} needs {world_req} devices, "
                 f"have {len(jax.devices())} ({jax.default_backend()}); "
                 "pass --cpu for a simulated host-device mesh")
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from burst_attn_tpu import burst_attn
    from burst_attn_tpu.parallel import layouts

    from benchmarks.benchmark import make_mesh

    mesh, seq_axes = make_mesh(args.mesh)
    w = int(np.prod([mesh.shape[a] for a in seq_axes]))
    b, n, s, d = 1, args.heads, args.seq, args.dim
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    spec = P(None, None, seq_axes if len(seq_axes) > 1 else seq_axes[0], None)
    shard = NamedSharding(mesh, spec)
    q, k, v, do = (jax.device_put(
        layouts.to_layout(jax.random.normal(kk, (b, n, s, d), jnp.bfloat16),
                          args.layout, w, 2), shard) for kk in ks)

    def fwd(q, k, v):
        return jnp.sum(burst_attn(q, k, v, mesh=mesh, seq_axes=seq_axes,
                                  causal=True, layout=args.layout)
                       .astype(jnp.float32))

    if args.bwd:
        def step(q, k, v, do):
            def loss(q, k, v):
                o = burst_attn(q, k, v, mesh=mesh, seq_axes=seq_axes,
                               causal=True, layout=args.layout)
                return jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32))
            gs = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
            return sum(jnp.sum(g.astype(jnp.float32)) for g in gs)
        compiled = jax.jit(step).lower(q, k, v, do).compile()
    else:
        compiled = jax.jit(fwd).lower(q, k, v).compile()
    hlo = compiled.as_text()
    if args.dump_hlo:
        with open(args.dump_hlo, "w") as f:
            f.write(hlo)
    pairs, sync = analyze_hlo(hlo)
    overlapped = sum(1 for p in pairs if p["compute_ops_inside"] > 0)
    summary = {
        "backend": jax.default_backend(),
        "mesh": args.mesh, "layout": args.layout, "world": w,
        "seq": s, "bwd": args.bwd,
        "async_permute_pairs": len(pairs),
        "pairs_with_compute_inside": overlapped,
        "sync_permutes": sync,
        "ops_inside_per_pair": [p["compute_ops_inside"] for p in pairs],
    }
    print(json.dumps(summary))
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps(summary) + "\n")


if __name__ == "__main__":
    main()
