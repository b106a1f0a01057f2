"""Score-materializing ring attention — the memory-inefficient baseline that
burst attention beats (fixed TPU port of the reference's ColossalAI-style
RingQK/RingAV, benchmarks/ring_attn.py:16-130; the reference copy is broken
at this snapshot — comm._ring passes 3 args to the 2-param ring_send_recv,
SURVEY.md §2.2).

Each device materializes its full [B*N, S/W, S] score block by rotating K
around the ring (RingQK), softmaxes it, then rotates V to form the output
(RingAV).  O(S^2/W) memory per device vs burst's O(S/W) — kept as the
benchmark baseline only.
"""

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from jax import shard_map
from jax.lax import axis_size

from burst_attn_tpu.parallel.ring import ppermute_next


def _ring_scores(q, k, axis_name):
    """s[global] = q_local @ k_global^T via W ppermute rounds.
    q, k: [B, N, S_local, D] -> scores [B, N, S_local, S_global]."""
    w = axis_size(axis_name)
    my = lax.axis_index(axis_name)

    def body(carry, r):
        k_cur, _ = carry
        k_next = ppermute_next(k_cur, axis_name)
        blk = jnp.einsum("bnid,bnjd->bnij", q, k_cur, preferred_element_type=jnp.float32)
        src = (my - r) % w  # whose K block we hold at round r
        return (k_next, None), (src, blk)

    (_, _), (srcs, blks) = lax.scan(body, (k, None), jnp.arange(w))
    # blks: [W, B, N, S_l, S_l]; scatter block r at global columns src*s_l
    s_l = q.shape[2]
    out = jnp.zeros(q.shape[:2] + (s_l, s_l * w), jnp.float32)

    def place(r, o):
        return lax.dynamic_update_slice_in_dim(o, blks[r], srcs[r] * s_l, axis=3)

    return lax.fori_loop(0, w, place, out)


def _ring_av(p, v, axis_name):
    """o = p @ v_global via W ppermute rounds.  p [B,N,S_l,S_g], v [B,N,S_l,D]."""
    w = axis_size(axis_name)
    my = lax.axis_index(axis_name)
    s_l = v.shape[2]

    def body(carry, r):
        v_cur, acc = carry
        v_next = ppermute_next(v_cur, axis_name)
        src = (my - r) % w
        p_blk = lax.dynamic_slice_in_dim(p, src * s_l, s_l, axis=3)
        acc = acc + jnp.einsum(
            "bnij,bnjd->bnid", p_blk, v_cur, preferred_element_type=jnp.float32
        )
        return (v_next, acc), None

    acc0 = jnp.zeros(v.shape, jnp.float32)
    (_, acc), _ = lax.scan(body, (v, acc0), jnp.arange(w))
    return acc


def ring_attention_shard(q, k, v, axis_name: str, scale=None, causal=False):
    """Baseline ring attention on per-shard [B,N,S_l,D] arrays (contig layout).
    Materializes the [S_l, S_global] score matrix."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    w = axis_size(axis_name)
    my = lax.axis_index(axis_name)
    s_l = q.shape[2]
    s = _ring_scores(q, k, axis_name) * scale
    if causal:
        rows = my * s_l + jnp.arange(s_l, dtype=jnp.int32)[:, None]
        cols = jnp.arange(s_l * w, dtype=jnp.int32)[None, :]
        s = jnp.where(cols <= rows, s, float("-inf"))
    p = jax.nn.softmax(s, axis=-1)
    return _ring_av(p, v, axis_name).astype(q.dtype)


def ring_attention(q, k, v, *, mesh, axis_name="sp", scale=None, causal=False):
    """Global-array entry point: q,k,v [B,N,S,D] sharded over axis_name on S."""
    spec = P(None, None, axis_name, None)
    fn = shard_map(
        partial(ring_attention_shard, axis_name=axis_name, scale=scale, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
