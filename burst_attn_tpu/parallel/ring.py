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
# ring as O(1) fp32 sub-payloads next to the 1 B/elem tensors — the scan
# ring rotates them in the same pytree, the fused kernels in parallel scale
# slot banks on the same semaphores (ops/fused_ring.py).  Accumulation is
# NEVER quantized: dequantize() is applied before any dot/add fold, exactly
# like ops/ragged_paged.py's int8 pool rescale.
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
    (parallel/burst._note_dispatch) records these per traced program, so
    `burst.ring_rounds` / `burst.ring_hops` always agree with the schedule
    the verifier proves (burstlint ring-order) instead of being counted by
    hand at the call site.

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


def neighbor_ids(axis_name: str):
    """(me, right, left) traced int32 rank ids on `axis_name`.

    `right` (me + 1) is the ring SEND target — the same direction every
    ppermute_next rotation and the reference's NCCL ring use — and `left`
    is the rank whose sends land in our buffers.  Exported for the fused
    ring kernel (ops/fused_ring.py), whose in-kernel RDMA must target the
    identical neighbor the XLA ring would, so the two paths hold the same
    partition at every round (asserted by burstlint's fused-ring rules).
    """
    me = lax.axis_index(axis_name)
    n = axis_size(axis_name)
    return me, (me + 1) % n, (me - 1) % n


def device_roles(intra_axis: str, inter_axis=None, mesh_axes=None,
                 factor=None, home_offsets=()):
    """Traced LOGICAL device ids for the fused kernels' RDMA targets.

    Mosaic linearizes LOGICAL ids over the mesh's axis order (row-major
    strides over `mesh.axis_names`), so on a multi-axis mesh a neighbor id
    must be computed from EVERY axis index, varying only the ring
    coordinate — that is the structural proof that extra (batch/head/pp)
    axes never alias ring traffic, and what lets the fused kernels run on
    pp×tp×sp meshes.  `mesh_axes` is the host-provided ordered
    ((name, size), ...) of all mesh axes (burst_attn passes
    mesh.shape.items()); None = the ring axes are the only axes in scope
    (the legacy single-axis contract).  `factor` = (n_inter, n_intra)
    grids a DOUBLE-ring schedule onto a flat ring axis (inter-major) when
    no separate inter axis exists.  Returns a dict of traced int32 ids:
    me, cw_dst/cw_src (intra ring right/left), ccw_dst/ccw_src, and —
    when an inter dimension exists — inter_dst/inter_src; `home{i}` ids
    for each requested (inter_off, intra_off) in `home_offsets`.
    """
    if mesh_axes is None:
        mesh_axes = ((intra_axis, axis_size(intra_axis)),)
        if inter_axis is not None:
            mesh_axes = ((inter_axis, axis_size(inter_axis)),) + mesh_axes
    sizes = [int(sz) for _, sz in mesh_axes]
    strides = [1] * len(sizes)
    for a in range(len(sizes) - 2, -1, -1):
        strides[a] = strides[a + 1] * sizes[a + 1]
    idx = {name: lax.axis_index(name) for name, _ in mesh_axes}
    me = jnp.int32(0)
    for (name, _), st in zip(mesh_axes, strides):
        me = me + idx[name] * jnp.int32(st)
    names = [name for name, _ in mesh_axes]
    ai = names.index(intra_axis)
    st_intra, n_intra_ax = strides[ai], sizes[ai]

    def _with_intra(new_idx):
        return me + (new_idx - idx[intra_axis]) * jnp.int32(st_intra)

    if factor is not None:
        n_i, n_s = factor
        if n_i * n_s != n_intra_ax:
            raise ValueError(
                f"factor {factor} does not tile the ring axis "
                f"({n_intra_ax} devices)")
        flat = idx[intra_axis]
        ii, si = flat // n_s, flat % n_s

        def ring_id(di, ds):
            return _with_intra(((ii + di) % n_i) * n_s + (si + ds) % n_s)
    elif inter_axis is not None:
        bi = names.index(inter_axis)
        st_inter, n_i = strides[bi], sizes[bi]
        n_s = n_intra_ax

        def ring_id(di, ds):
            out = _with_intra((idx[intra_axis] + ds) % n_s)
            return out + (((idx[inter_axis] + di) % n_i)
                          - idx[inter_axis]) * jnp.int32(st_inter)
    else:
        n_i, n_s = 1, n_intra_ax

        def ring_id(di, ds):
            return _with_intra((idx[intra_axis] + ds) % n_s)

    roles = {
        "me": me,
        "cw_dst": ring_id(0, 1), "cw_src": ring_id(0, -1),
        "ccw_dst": ring_id(0, -1), "ccw_src": ring_id(0, 1),
        "inter_dst": ring_id(1, 0), "inter_src": ring_id(-1, 0),
    }
    for j, (h_i, h_s) in enumerate(home_offsets):
        roles[f"home{j}"] = ring_id(h_i, h_s)
    return {k: jnp.asarray(v, jnp.int32) for k, v in roles.items()}


def ring_coords(intra_axis: str, inter_axis=None, factor=None):
    """Traced (inter_rank, intra_rank, n_inter, n_intra) of this device's
    position in the (possibly factored) ring — the coordinates
    schedule.partition_for_round consumes."""
    if factor is not None:
        n_i, n_s = factor
        flat = lax.axis_index(intra_axis)
        return flat // n_s, flat % n_s, n_i, n_s
    if inter_axis is None:
        return jnp.int32(0), lax.axis_index(intra_axis), 1, \
            axis_size(intra_axis)
    return (lax.axis_index(inter_axis), lax.axis_index(intra_axis),
            axis_size(inter_axis), axis_size(intra_axis))


def fused_slot_schedule(world: int, slots: int):
    """Host-side KV-slot schedule of the fused ring kernel: [world] int array
    where entry r is the communication-buffer slot holding the chunk a
    device consumes at ring round r.

    The kernel (ops/fused_ring.py) reads THIS array (via scalar prefetch)
    for every slot choice — the send at round r goes from slot[r] into the
    right neighbor's slot[r+1] — so the schedule here is the single source
    of truth, and burstlint verifies it against an independent derivation
    plus a delivery proof (analysis/oracle.verify_fused_ring): neighbor-only
    sends, exactly world-1 hops per chunk, and no slot overwritten before
    its last read under the kernel's capacity handshake.

    With `slots` = 2 this is plain double buffering (slot parity r % 2);
    more slots deepen the pipeline so a send may run `slots - 1` rounds
    ahead of compute before the handshake blocks it.

    Since the schedule-IR refactor this is a VIEW of the compiled "uni"
    program (parallel/schedule.compile_fwd) — the same IR the kernels
    scalar-prefetch — kept for its callers and as the legacy surface
    burstlint's independent-derivation check pins.
    """
    import numpy as np

    from . import schedule

    if world < 1 or slots < 2:
        raise ValueError(f"need world >= 1 and slots >= 2, got "
                         f"world={world}, slots={slots}")
    prog = schedule.compile_fwd("uni", world, slots=slots)
    return np.asarray(prog.col(schedule.CONSUME_SLOT), dtype=np.int64)


def fused_bwd_slot_schedule(world: int, slots: int):
    """Host-side slot schedule of the fused ring BACKWARD kernel
    (ops/fused_ring_bwd.py): [world] int array where entry r is the
    communication-buffer slot holding (a) the q-side bundle (delta|o, do,
    q, lse) and (b) the arriving dq partial a device consumes at backward
    ring round r.

    The two concurrent streams share one slot cycle but live in DISJOINT
    buffers with disjoint semaphores, and their sends are phase-shifted:
    the bundle for round r+1 leaves at round r's FIRST grid step (like the
    forward's KV rotation), while the dq partial for round r+1 streams out
    block-by-block DURING round r, each block sent as soon as its local
    contribution is folded in — "one hop behind the bundle".  Round world-1
    does not send the bundle onward; its dq blocks take the final
    return-home hop into the right neighbor's dedicated home slot (index
    `min(slots, world)`, outside this cycle) instead.

    burstlint re-derives this schedule independently and proves both
    streams by simulation (analysis/oracle.verify_fused_ring_bwd):
    neighbor-only sends, world-1 ring hops per bundle, every dq partial
    arriving home exactly once with all `world` contributions, and no slot
    overwritten before its last read under the capacity handshake.

    Like fused_slot_schedule, now a view of the compiled "uni" backward
    program (parallel/schedule.compile_bwd).
    """
    import numpy as np

    from . import schedule

    if world < 1 or slots < 2:
        raise ValueError(f"need world >= 1 and slots >= 2, got "
                         f"world={world}, slots={slots}")
    prog = schedule.compile_bwd("uni", world, slots=slots, dq_slots=slots)
    return np.asarray(prog.col(schedule.CONSUME_SLOT), dtype=np.int64)


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
