"""Ulysses (DeepSpeed-style) all-to-all sequence parallelism.

Absent from the reference (SURVEY.md §2.4: "no all-to-all anywhere") but a
natural complement to the burst ring: instead of rotating KV around a ring,
each device exchanges its sequence shard for a head shard (one all-to-all),
runs FULL-sequence attention on its subset of heads, and exchanges back.

Trade-offs vs the ring (why both belong in the framework):
  * comm volume: 2 all-to-alls of the activations vs W-1 KV rotations —
    Ulysses moves less data when W is large and heads are plentiful;
  * no causal load-balance problem: every device sees the full sequence, so
    plain causal masking is already balanced (no zigzag/striped layouts);
  * hard cap: parallelism cannot exceed the KV head count (GQA limits it),
    where the ring scales with sequence length alone.

TPU mapping: `lax.all_to_all` along the mesh axis inside shard_map (XLA
lowers it onto ICI), local attention = the Pallas flash kernel (or the jnp
tile off-TPU); differentiable end to end, so no hand-written VJP is needed —
the transpose of all-to-all is all-to-all and XLA inserts it.
"""

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from jax import shard_map


def _local_attention(q, k, v, scale, causal, backend, block_q, block_kv,
                     window=None, segment_ids=None):
    if backend == "pallas":
        from ..ops.pallas_flash import flash_attention

        return flash_attention(q, k, v, scale, causal, block_q, block_kv,
                               window=window, segment_ids=segment_ids)
    from ..ops.tile import single_device_attention

    return single_device_attention(q, k, v, scale, causal, window=window,
                                   segment_ids=segment_ids)


def _ulysses_shard(q, k, v, seg=None, *, axis, scale, causal, backend,
                   block_q, block_kv, window=None):
    """Per-shard [B, N, S/W, D] -> [B, N, S/W, D] with full-seq attention on
    N/W heads in between.  `seg` [B, S] (the FULL sequence's packed ids,
    replicated — after the all-to-all every device holds the whole
    sequence, so the ids need no exchange)."""
    # scatter heads (axis 1), gather sequence (axis 2)
    qh = lax.all_to_all(q, axis, split_axis=1, concat_axis=2, tiled=True)
    kh = lax.all_to_all(k, axis, split_axis=1, concat_axis=2, tiled=True)
    vh = lax.all_to_all(v, axis, split_axis=1, concat_axis=2, tiled=True)
    o = _local_attention(qh, kh, vh, scale, causal, backend, block_q, block_kv,
                         window, segment_ids=seg)
    # scatter sequence back, gather heads
    return lax.all_to_all(o, axis, split_axis=2, concat_axis=1, tiled=True)


def ulysses_attn(
    q,
    k,
    v,
    *,
    mesh,
    seq_axis: str = "sp",
    causal: bool = False,
    scale: Optional[float] = None,
    backend: str = "auto",
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
    batch_axes=None,
    head_axes=None,
    window: Optional[int] = None,
    segment_ids=None,
) -> jax.Array:
    """All-to-all sequence-parallel attention on global [B, N, S, D] arrays.

    S is sharded over `seq_axis` in NATURAL token order (no ring layouts);
    `head_axes` optionally shards heads over a tensor-parallel axis riding
    alongside (the all-to-all then exchanges the LOCAL heads of each tp
    group).  Requires per-tp-group head counts divisible by the seq axis
    size W for both q and kv heads.  `segment_ids` [B, S] int32 packs
    multiple documents (attention stays in-segment); the ids enter the
    shard replicated over the sequence axis.
    """
    from .burst import _resolve_backend

    w = mesh.shape.get(seq_axis, 1)
    tp = 1
    if head_axes is not None:
        for a in ((head_axes,) if isinstance(head_axes, str) else head_axes):
            tp *= mesh.shape.get(a, 1)
    if (q.shape[1] // tp) % w or (k.shape[1] // tp) % w:
        raise ValueError(
            f"ulysses needs per-group q heads {q.shape[1]}/{tp} and kv heads "
            f"{k.shape[1]}/{tp} divisible by the '{seq_axis}' axis size {w}"
        )
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if window is not None and not causal:
        raise ValueError("window attention requires causal=True")
    from ..ops.tuning import resolve_blocks

    block_q, block_kv = resolve_blocks(block_q, block_kv)[:2]
    shard = partial(
        _ulysses_shard,
        axis=seq_axis,
        scale=scale,
        causal=causal,
        backend=_resolve_backend(backend),
        block_q=block_q,
        block_kv=block_kv,
        window=window,
    )
    qkv_spec = P(batch_axes, head_axes, seq_axis, None)
    if segment_ids is not None:
        fn = shard_map(
            shard,
            mesh=mesh,
            in_specs=(qkv_spec,) * 3 + (P(batch_axes, None),),
            out_specs=qkv_spec,
            check_vma=False,
        )
        return fn(q, k, v, jnp.asarray(segment_ids, jnp.int32))
    fn = shard_map(
        shard,
        mesh=mesh,
        in_specs=(qkv_spec,) * 3,
        out_specs=qkv_spec,
        check_vma=False,
    )
    return fn(q, k, v)
