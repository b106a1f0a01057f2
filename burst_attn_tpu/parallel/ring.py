"""Ring transport over mesh axes — the TPU-native equivalent of the
reference's NCCL P2P layer (burst_attn/comm.py).

Every reference primitive maps to an XLA collective on a named mesh axis:

  Ring._make_ring_ops / batch_isend_irecv  -> lax.ppermute (async
      collective-permute; XLA overlaps it with compute, replacing the
      reference's CUDA stream/event choreography, comm.py:267-282)
  double ring intra/inter streams          -> two mesh axes ("inter","intra")
  even/odd deadlock ordering (comm.py:166) -> not needed (ppermute is one op)
  all_reduce / broadcast (comm.py:16,67)   -> lax.psum / device_put+pjit

The partition-id schedule (reference get_partition_id,
burst_attn_interface.py:20-37) tracks which global sequence partition a
device's rotating buffer holds at ring round r.
"""

import jax
import jax.numpy as jnp
from jax import lax
from jax.lax import axis_size


def ppermute_next(x, axis_name: str):
    """Rotate a pytree one hop forward (rank i -> i+1) along a mesh axis."""
    return ppermute_by(x, axis_name, 1)


def ppermute_by(x, axis_name: str, hops: int):
    """Rotate a pytree `hops` positions forward in ONE collective.

    A ppermute is an arbitrary permutation — jumping h hops costs one
    collective, not h.  The windowed ring uses this to skip its dead
    middle rounds (parallel/burst.py round truncation) without paying
    their payload traffic.  hops is static; hops % world == 0 is a no-op."""
    n = axis_size(axis_name)
    h = hops % n
    if h == 0:
        return x
    perm = [(i, (i + h) % n) for i in range(n)]
    # named_scope: pure metadata so each hop is identifiable on the xprof
    # timeline under the obs span naming convention (docs/observability.md);
    # adds no equations, so burstlint's jaxpr rules see the same program
    with jax.named_scope(f"obs.ring.hop{h}.{axis_name}"):
        return jax.tree.map(lambda t: lax.ppermute(t, axis_name, perm), x)


# Symmetric wire quantization of rotating ring payloads (ROADMAP item 5).
# One representable-range constant per wire dtype: int8 maps amax -> +-127;
# fp8 (e4m3fn, no inf) maps amax -> +-448, its finite max.  Scales are
# per-BLOCK SCALARS (amax over the reduced axes, keepdims) so they ride the
# ring as O(1) fp32 sub-payloads next to the 1 B/elem tensors, in the same
# rotating pytree.  Accumulation is NEVER quantized: dequantize() is applied
# before any dot/add fold, exactly like ops/ragged_paged.py's int8 pool
# rescale.
WIRE_QMAX = {"int8": 127.0, "fp8": 448.0}


def wire_quantize(x, wire, axes):
    """(payload, scale) for one ring hop.  `axes` are the amax-reduction
    axes (everything inside one scale block); scale keeps dims so
    dequantization is a broadcast multiply.  wire=None passes `x` through
    with scale=None — callers on the dense path never see a new op."""
    if wire is None:
        return x, None
    if wire not in WIRE_QMAX:
        raise ValueError(f"wire must be None, 'int8' or 'fp8', got {wire!r}")
    f = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(f), axis=axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / WIRE_QMAX[wire]
    if wire == "int8":
        q = jnp.clip(jnp.round(f / scale), -127.0, 127.0).astype(jnp.int8)
    else:
        q = (f / scale).astype(jnp.float8_e4m3fn)
    return q, scale


def wire_dequantize(q, scale, dtype):
    """Inverse of wire_quantize: rescale in fp32, then cast to the compute
    dtype the dense ring would have shipped.  scale=None is the dense
    pass-through."""
    if scale is None:
        return q
    return (q.astype(jnp.float32) * scale).astype(dtype)


def ring_round_counts(n_inter: int, n_intra: int, r_live=None):
    """Host-side accounting of ONE forward ring schedule: (rounds,
    intra_hops, inter_hops).  The obs dispatch instrumentation
    (parallel/burst._note_dispatch) takes its round count from here, so the
    dispatch counters agree with the schedule the verifier proves
    (burstlint ring-order) instead of being counted by hand at the call
    site; the compiled program's `obs.ring.*` scopes show the same rounds
    and hops (tests/test_obs.py).

    Single ring (n_inter == 1): a windowed contig ring truncates to
    `r_live` live rounds (parallel/burst._r_live) — r_live-1 KV hops.
    Double ring: every cycle runs n_intra rounds with n_intra-1 intra hops
    (the last round of a cycle consumes without sending), plus one
    prefetched inter hop per cycle boundary.

    Derived from the schedule IR's scan lowering (parallel/schedule):
    the counts reported here are the hop totals of the same compiled
    program burstlint simulation-proves, not a hand-kept formula."""
    from . import schedule

    if n_inter == 1:
        live = n_intra if r_live is None else r_live
        prog = schedule.compile_fwd("uni", n_intra, r_live=live)
    else:
        prog = schedule.compile_fwd("double", n_intra, n_inter)
    totals = schedule.hop_totals(prog)
    return prog.n_rounds, totals["intra"], totals["inter"]


def axis_ranks(intra_axis: str, inter_axis):
    """(inter_rank, intra_rank, inter_size, intra_size) for this device."""
    intra_rank = lax.axis_index(intra_axis)
    intra_size = axis_size(intra_axis)
    if inter_axis is None:
        return jnp.int32(0), intra_rank, 1, intra_size
    return lax.axis_index(inter_axis), intra_rank, axis_size(inter_axis), intra_size


def my_partition(intra_axis: str, inter_axis) -> jnp.ndarray:
    inter_rank, intra_rank, _, intra_size = axis_ranks(intra_axis, inter_axis)
    return inter_rank * intra_size + intra_rank


def ring_schedule(intra_size: int, inter_size: int = 1):
    """Host-side expected schedule: [world, rounds] array where entry
    (device, r) is the partition id device holds at ring round r.

    The debug/verification analogue of the reference's per-rank `record` list
    logged each run (burst_attn_interface.py:213-217,249,290-293,392): the
    distributed schedule (partition_at_round inside shard_map) must replay
    these rows exactly — asserted in tests/test_schedule.py.
    """
    import numpy as np

    world = inter_size * intra_size
    rounds = world
    out = np.empty((world, rounds), dtype=np.int64)
    for dev in range(world):
        inter_rank, intra_rank = divmod(dev, intra_size)
        for r in range(rounds):
            c, s = divmod(r, intra_size)
            out[dev, r] = ((inter_rank - c) % inter_size) * intra_size + (
                (intra_rank - s) % intra_size
            )
    return out


def partition_at_round(r, intra_axis: str, inter_axis):
    """Global partition id of the KV (fwd) / query-side (bwd) payload held at
    0-indexed ring round r under the (double-)ring schedule.

    With the forward rotation i -> i+1, after c inter hops and s intra hops a
    device holds the payload of (inter_rank - c, intra_rank - s); flattened
    partition id = inter*I + intra.  Matches the reference's formula
    (burst_attn_interface.py:27-36) and, for a single ring, is equivalent to
    its `round_r = r` shortcut (the <=-rank comparisons agree).
    """
    inter_rank, intra_rank, inter_size, intra_size = axis_ranks(intra_axis, inter_axis)
    c = r // intra_size
    s = r % intra_size
    return ((inter_rank - c) % inter_size) * intra_size + (intra_rank - s) % intra_size
