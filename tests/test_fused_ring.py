"""Fused-ring parity: `backend="fused_ring"` — the single-kernel RDMA ring
(ops/fused_ring.py) — against the scan-based ring (`_fwd_impl` /
`_burst_attn_shard_plain`) and the dense oracle (ops/reference.py) on a
simulated 8-device mesh, in interpret mode.

jax's DMA discharge rule emulates `make_async_remote_copy` over a single
named axis on the host backend, so these tests exercise the REAL kernel —
same slot schedule, same masks, same merge — not a stand-in; only the
hardware-only semaphore choreography (startup barrier, capacity handshake)
is statically gated off (see ops/fused_ring.py "Interpret mode").

BURST_FUSED_INTERPRET opts the dispatch into the interpreted fused path
(default off-TPU behavior is the scan fallback); it is read at trace time,
so setting it at module import covers every test here.
"""

import os

os.environ["BURST_FUSED_INTERPRET"] = "1"

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P
from jax import shard_map

from burst_attn_tpu import burst_attn
from burst_attn_tpu.ops.reference import dense_attention
from burst_attn_tpu.parallel import burst, layouts
from burst_attn_tpu.utils.testing import check_close, random_qkv

pytestmark = pytest.mark.fused_ring

KEY = jax.random.PRNGKey(23)
SPEC4 = P(None, None, "sp", None)
SPEC3 = P(None, None, "sp")


def _mesh(world=8):
    return Mesh(np.array(jax.devices()[:world]), ("sp",))


def _fwd_pair(mesh, cfg, ql, kl, vl):
    """(o, lse) of the shard-level forward under `cfg` on the ring mesh."""
    fn = shard_map(lambda q, k, v: burst._fwd_impl(q, k, v, cfg),
                   mesh=mesh, in_specs=(SPEC4,) * 3,
                   out_specs=(SPEC4, SPEC3), check_vma=False)
    return fn(ql, kl, vl)


def run_parity(layout, causal, kv_heads=2, world=8, n=2, d=16,
               seq_per_dev=16, dtype=jnp.float32, tol=1e-5, **cfg_kw):
    """backend="fused_ring" (o, lse) vs the scan ring, the custom_vjp
    wrapper, and the dense oracle."""
    b = 1
    S = seq_per_dev * world
    mesh = _mesh(world)
    q, k, v, _ = random_qkv(KEY, b, n, S, d, kv_heads=kv_heads, dtype=dtype)
    ql, kl, vl = (layouts.to_layout(t, layout, world, 2) for t in (q, k, v))

    fused_cfg = burst.BurstConfig(causal=causal, layout=layout,
                                  intra_axis="sp", backend="fused_ring",
                                  **cfg_kw)
    scan_cfg = burst.BurstConfig(causal=causal, layout=layout,
                                 intra_axis="sp", backend="jnp")
    o_f, lse_f = _fwd_pair(mesh, fused_cfg, ql, kl, vl)
    o_s, lse_s = _fwd_pair(mesh, scan_cfg, ql, kl, vl)

    tag = f"layout={layout} causal={causal} kvh={kv_heads} dtype={dtype}"
    check_close(o_f, o_s, rtol=tol, atol=tol, msg=f"fused o vs scan {tag}")
    # lse is f32 end to end, but with bf16 inputs the fused path's
    # merge-at-end combine rounds differently than the scan's sequential
    # fold — the per-dtype case tolerance applies to both stats
    check_close(lse_f, lse_s, rtol=tol, atol=tol,
                msg=f"fused lse vs scan {tag}")

    o_ref = dense_attention(q, k, v, causal=causal)
    o_nat = layouts.from_layout(o_f, layout, world, 2)
    check_close(o_nat, o_ref, rtol=tol, atol=tol,
                msg=f"fused o vs dense oracle {tag}")


@pytest.mark.parametrize("layout", ["zigzag", "striped", "contig"])
def test_causal_parity(layout):
    run_parity(layout, causal=True)


def test_noncausal_parity():
    run_parity("contig", causal=False, world=4)


def test_custom_vjp_wrapper_dispatches_fused():
    """_burst_attn_shard_plain (the path burst_attn drives) must produce the
    identical fused forward — bitwise, same kernel underneath."""
    world, n, d = 4, 2, 16
    S = 16 * world
    mesh = _mesh(world)
    q, k, v, _ = random_qkv(KEY, 1, n, S, d, kv_heads=2, dtype=jnp.float32)
    ql, kl, vl = (layouts.to_layout(t, "zigzag", world, 2) for t in (q, k, v))
    cfg = burst.BurstConfig(causal=True, layout="zigzag", intra_axis="sp",
                            backend="fused_ring")
    o_f, _ = _fwd_pair(mesh, cfg, ql, kl, vl)
    wrapped = shard_map(
        lambda q, k, v: burst._burst_attn_shard_plain(q, k, v, cfg),
        mesh=mesh, in_specs=(SPEC4,) * 3, out_specs=SPEC4, check_vma=False)
    check_close(wrapped(ql, kl, vl), o_f, rtol=0, atol=0,
                msg="fused via _burst_attn_shard_plain")


def test_gqa_bf16_parity():
    # GQA (group = 2) in bf16 at the acceptance tolerance: 2e-2
    # (accumulation stays f32 in-kernel; only the inputs narrow)
    run_parity("zigzag", causal=True, kv_heads=1, dtype=jnp.bfloat16,
               tol=2e-2)


def test_three_slots_and_custom_blocks():
    # deeper comm pipeline + non-default fused blocks take the same schedule
    run_parity("striped", causal=True, world=4, n=1, kv_heads=1,
               fused_kv_slots=3, fused_block_q=8, fused_block_kv=8)


def test_world_two():
    run_parity("zigzag", causal=True, world=2)


def test_grad_through_fused_backend():
    """jax.grad through backend="fused_ring": fused forward (o + lse
    residuals) feeding the scan-ring backward must reproduce the dense
    oracle's gradients."""
    world, b, n, d = 8, 1, 2, 16
    S = 16 * world
    layout = "zigzag"
    mesh = _mesh(world)
    q, k, v, do = random_qkv(KEY, b, n, S, d, kv_heads=2, dtype=jnp.float32)
    ql, kl, vl, dol = (layouts.to_layout(t, layout, world, 2)
                       for t in (q, k, v, do))

    def loss(ql, kl, vl):
        o = burst_attn(ql, kl, vl, mesh=mesh, seq_axes=("sp",), causal=True,
                       layout=layout, backend="fused_ring")
        return jnp.sum(o.astype(jnp.float32) * dol)

    def ref_loss(q, k, v):
        return jnp.sum(
            dense_attention(q, k, v, causal=True).astype(jnp.float32) * do)

    g = jax.grad(loss, argnums=(0, 1, 2))(ql, kl, vl)
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for got, want, nm in zip(g, g_ref, "qkv"):
        got = layouts.from_layout(got, layout, world, 2)
        check_close(got, want, rtol=2e-4, atol=2e-4, msg=f"fused d{nm}")


def test_no_xla_collectives_in_fused_forward():
    """The fused forward must contain zero ppermute/all_to_all — the ring
    lives entirely inside the kernel (burstlint's fused-ring-fused rule
    checks the same invariant as a standing gate)."""
    from burst_attn_tpu.analysis.jaxpr_tools import collect_collectives

    mesh = _mesh(4)
    cfg = burst.BurstConfig(causal=True, layout="zigzag", intra_axis="sp",
                            backend="fused_ring")
    S = jax.ShapeDtypeStruct((1, 2, 64, 8), jnp.float32)
    fn = shard_map(lambda q, k, v: burst._fwd_impl(q, k, v, cfg),
                   mesh=mesh, in_specs=(SPEC4,) * 3,
                   out_specs=(SPEC4, SPEC3), check_vma=False)
    ev = [e for e in collect_collectives(jax.make_jaxpr(fn)(S, S, S))
          if e.prim in ("ppermute", "all_to_all")]
    assert ev == [], ev


# ---------------------------------------------------------------------------
# fallback matrix: configs the fused kernel declines must silently take the
# scan ring and stay correct end to end


def test_fallback_double_ring():
    world, b, n, d = 8, 1, 2, 16
    S = 16 * world
    mesh = Mesh(np.array(jax.devices()[:world]).reshape(2, 4),
                ("inter", "intra"))
    q, k, v, _ = random_qkv(KEY, b, n, S, d, dtype=jnp.float32)
    ql, kl, vl = (layouts.to_layout(t, "zigzag", world, 2) for t in (q, k, v))
    o = burst_attn(ql, kl, vl, mesh=mesh, seq_axes=("inter", "intra"),
                   causal=True, layout="zigzag", backend="fused_ring")
    check_close(layouts.from_layout(o, "zigzag", world, 2),
                dense_attention(q, k, v, causal=True),
                rtol=2e-4, atol=2e-4, msg="double-ring fallback")


def test_window_and_segments_dispatch_fused():
    """Since the occupancy compiler, windowed and packed-segment contig
    rings RUN FUSED (the historical scan fallback rows are gone): the
    dispatch counter must record path=fused and no window/segments
    fallback reason exists to count, while staying correct vs the dense
    oracle."""
    from burst_attn_tpu import obs

    world, b, n, d = 8, 1, 2, 16
    S = 16 * world
    mesh = _mesh(world)
    q, k, v, _ = random_qkv(KEY, b, n, S, d, dtype=jnp.float32)
    lab = dict(path="fused", backend="fused_ring", tile="jnp")
    before = obs.counter("burst.dispatch").get(**lab)
    o = burst_attn(q, k, v, mesh=mesh, seq_axes=("sp",), causal=True,
                   layout="contig", backend="fused_ring", window=24)
    check_close(o, dense_attention(q, k, v, causal=True, window=24),
                rtol=2e-4, atol=2e-4, msg="window fused")
    assert obs.counter("burst.dispatch").get(**lab) == before + 1

    seg = jnp.concatenate(
        [jnp.zeros((b, S // 2), jnp.int32), jnp.ones((b, S - S // 2), jnp.int32)],
        axis=1)
    o = burst_attn(q, k, v, mesh=mesh, seq_axes=("sp",), causal=True,
                   layout="contig", backend="fused_ring", segment_ids=seg)
    check_close(o, dense_attention(q, k, v, causal=True, segment_ids=seg),
                rtol=2e-4, atol=2e-4, msg="segments fused")
    assert obs.counter("burst.dispatch").get(**lab) == before + 2
    # the stale decline reasons must be gone from the bounded label map
    assert not any(lbl in ("window", "segments")
                   for _, lbl in burst._FALLBACK_LABELS)


def test_supported_reasons():
    """The dispatch gate's reason strings: every fallback row of the doc's
    matrix (docs/fused_ring.md) declines for the documented reason, and the
    supported configs return None — checked inside the trace context the
    gate runs in.  Windowed and packed-segment contig rings are ADMITTED
    since the occupancy compiler (the gate compiles an elided schedule for
    them instead of declining)."""
    from burst_attn_tpu.ops import fused_ring

    mesh = _mesh(4)
    reasons = {}

    def probe(q, k, v):
        base = burst.BurstConfig(causal=True, layout="zigzag",
                                 intra_axis="sp", backend="fused_ring")
        import dataclasses

        reasons["ok"] = fused_ring.supported(base, q.shape, k.shape, False)
        reasons["window"] = fused_ring.supported(
            dataclasses.replace(base, layout="contig", window=8),
            q.shape, k.shape, False)
        reasons["segments"] = fused_ring.supported(base, q.shape, k.shape,
                                                   True)
        reasons["double"] = fused_ring.supported(
            dataclasses.replace(base, inter_axis="inter"),
            q.shape, k.shape, False)
        reasons["cross"] = fused_ring.supported(
            base, q.shape, (k.shape[0], k.shape[1], 2 * k.shape[2],
                            k.shape[3]), False)
        return q

    fn = shard_map(probe, mesh=mesh, in_specs=(SPEC4,) * 3,
                   out_specs=SPEC4, check_vma=False)
    x = jnp.zeros((1, 2, 64, 8), jnp.float32)
    jax.eval_shape(fn, x, x, x)
    assert reasons["ok"] is None
    # window/segments are no longer decline reasons: the occupancy
    # compiler admits both (dead-round elision handles the sparsity)
    assert reasons["window"] is None
    assert reasons["segments"] is None
    assert "double ring" in reasons["double"]
    assert "cross" in reasons["cross"]


# ---------------------------------------------------------------------------
# occupancy-elided schedules (ISSUE 11): windowed / packed-segment contig
# rings run the fused kernel on a truncated program.  Fast canaries above
# (test_window_and_segments_dispatch_fused); the sweeps ride the slow lane.


@pytest.mark.slow
@pytest.mark.parametrize("topo", ["uni", "bidi"])
@pytest.mark.parametrize("window", [1, 20, 40])
def test_windowed_fused_parity_sweep(topo, window):
    """Elided windowed schedules across truncation depths (r_live 1, 3 and
    4 of 8 rounds) on both single-ring topologies vs the dense banded
    oracle."""
    world, b, n, d = 8, 1, 2, 16
    S = 16 * world
    mesh = _mesh(world)
    q, k, v, _ = random_qkv(KEY, b, n, S, d, dtype=jnp.float32)
    o = burst_attn(q, k, v, mesh=mesh, seq_axes=("sp",), causal=True,
                   layout="contig", backend="fused_ring", window=window,
                   fused_topology=topo)
    check_close(o, dense_attention(q, k, v, causal=True, window=window),
                rtol=2e-4, atol=2e-4, msg=f"win{window} {topo}")


@pytest.mark.slow
@pytest.mark.parametrize("parts,msl", [(8, 16), (4, 32), (2, 64)])
def test_segment_elided_fused_parity_sweep(parts, msl):
    """Packed segments under the max_segment_len contract at several
    truncation depths (r_live 2, 3, 5 of 8) vs the dense segment-masked
    oracle."""
    world, b, n, d = 8, 1, 2, 16
    S = 16 * world
    mesh = _mesh(world)
    q, k, v, _ = random_qkv(KEY, b, n, S, d, dtype=jnp.float32)
    seg = jnp.asarray(np.repeat(np.arange(parts), S // parts)[None, :],
                      jnp.int32)
    o = burst_attn(q, k, v, mesh=mesh, seq_axes=("sp",), causal=True,
                   layout="contig", backend="fused_ring", segment_ids=seg,
                   max_segment_len=msl)
    check_close(o, dense_attention(q, k, v, causal=True, segment_ids=seg),
                rtol=2e-4, atol=2e-4, msg=f"seg parts={parts} msl={msl}")
