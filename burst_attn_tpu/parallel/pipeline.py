"""Pipeline parallelism: GPipe-style microbatching over a mesh axis.

Absent from the reference (SURVEY.md §2.4: DP/TP/PP delegated to host
frameworks); first-class here because a complete framework offers every
standard parallelism axis next to the sequence ring.

TPU-first formulation — no schedulers, no per-stage processes, no streams:
the whole pipeline is ONE `lax.scan` inside `shard_map`.  Stage p holds its
slice of the parameters (leading stage axis sharded over the `pp` mesh
axis).  At schedule tick t, every stage applies its stage function to the
activation it holds and `ppermute`s the result one hop forward; stage 0
injects microbatch t while t < M, stage P-1 banks finished microbatches.
After M + P - 1 ticks all M microbatches are through.  The classic GPipe
"bubble" is the (P-1)/(M+P-1) fraction of ticks a stage computes garbage
(masked out) — exactly as in the paper, amortized by more microbatches.

Gradients need no pipeline-aware code at all: `jax.grad` of scan+ppermute IS
the reverse pipeline schedule (AD transposes ppermute to the reverse
permutation and walks the scan backward), with activation rematerialization
handled by `jax.checkpoint` on the stage function if requested.

    out = pipeline(stage_fn, stage_params, x, mesh=mesh, axis="pp",
                   microbatches=8)

stage_fn   : (params_slice, activation [mb, ...]) -> activation [mb, ...]
stage_params: pytree whose leaves have a leading [P, ...] stage axis
x          : [B, ...] global batch (B divisible by microbatches)
"""

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from jax import shard_map
from jax.lax import axis_size


def pipeline_shard(stage_fn, stage_params, x_mb, axis: str):
    """Per-shard pipeline body — call inside shard_map.

    stage_params: this stage's params (leading stage axis already sliced to
    size 1 by shard_map; squeezed here).  x_mb: [M, mb, ...] microbatched
    input (replicated across stages; only stage 0 reads it).  Returns
    [M, mb, ...] outputs (valid on every stage after the final psum).
    """
    n_stages = axis_size(axis)
    stage = lax.axis_index(axis)
    params = jax.tree.map(lambda a: jnp.squeeze(a, axis=0), stage_params)
    m = x_mb.shape[0]
    ticks = m + n_stages - 1

    buf = jnp.zeros_like(x_mb[0])          # activation arriving from the left
    out = jnp.zeros_like(x_mb)             # banked results (stage P-1 only)

    def body(carry, t):
        buf, out = carry
        # stage 0 injects microbatch t (clamped read; masked after m)
        inject = lax.dynamic_index_in_dim(
            x_mb, jnp.minimum(t, m - 1), axis=0, keepdims=False
        )
        cur = jnp.where(stage == 0, inject, buf)
        y = stage_fn(params, cur)
        # microbatch id leaving the LAST stage at tick t is t - (P-1);
        # bank it with a select (uniform SPMD program, no per-device branch)
        mb_id = t - (n_stages - 1)
        bank = (stage == n_stages - 1) & (mb_id >= 0)
        banked = lax.dynamic_update_index_in_dim(
            out, y, jnp.clip(mb_id, 0, m - 1), axis=0
        )
        out = jnp.where(bank, banked, out)
        nxt = lax.ppermute(
            y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
        )
        return (nxt, out), None

    (_, out), _ = lax.scan(body, (buf, out), jnp.arange(ticks))
    # results live on the last stage only; zeros elsewhere -> psum replicates
    return lax.psum(out, axis)


def pipeline(stage_fn, stage_params, x, *, mesh, axis: str = "pp",
             microbatches: int, remat: bool = False):
    """Run `x` through P pipeline stages (P = mesh.shape[axis]).

    stage_params leaves carry a leading [P, ...] stage axis (see
    `stack_stages`); each stage applies `stage_fn(params_p, act)`.
    `remat=True` wraps the stage in jax.checkpoint — the standard GPipe
    memory/recompute trade for long pipelines.
    Returns stage_fn applied P times: [B, ...] with B preserved.
    """
    b = x.shape[0]
    if b % microbatches:
        raise ValueError(f"batch {b} not divisible by microbatches {microbatches}")
    fn = jax.checkpoint(stage_fn) if remat else stage_fn
    x_mb = x.reshape(microbatches, b // microbatches, *x.shape[1:])

    spec_params = jax.tree.map(lambda _: P(axis), stage_params)
    out = shard_map(
        partial(pipeline_shard, fn, axis=axis),
        mesh=mesh,
        in_specs=(spec_params, P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x_mb)
    return out.reshape(b, *x.shape[1:])


def stack_stages(per_stage_params):
    """[pytree_stage0, pytree_stage1, ...] -> one pytree with leading [P,...]
    stage axis (the layout `pipeline` expects, sharded over the pp axis)."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *per_stage_params)
