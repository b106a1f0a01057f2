"""Fused-ring BACKWARD parity: the single-kernel bundle + dq ring
(ops/fused_ring_bwd.py, dispatched from `_bwd_impl` under
`backend="fused_ring"`) against the scan-ring backward and the dense
oracle's gradients on a simulated 8-device mesh, in interpret mode.

Same machinery as tests/test_fused_ring.py: jax's DMA discharge rule
emulates `make_async_remote_copy` over a single named axis, so these tests
exercise the REAL kernel — same slot schedule, same phase-shifted dq
stream, same masks — with only the hardware-only semaphore choreography
(startup barrier, capacity handshake) statically gated off.

The scan backward is the parity reference at the SAME tolerance the fwd
parity suite uses (f32 1e-5, bf16 2e-2); the dense oracle pins end-to-end
`jax.grad` correctness through the custom_vjp at the grad suite's 2e-4.
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
from burst_attn_tpu.parallel import burst, layouts, ring
from burst_attn_tpu.utils.testing import check_close, random_qkv

pytestmark = pytest.mark.fused_ring

KEY = jax.random.PRNGKey(29)
SPEC4 = P(None, None, "sp", None)
SPEC3 = P(None, None, "sp")


def _mesh(world=8):
    return Mesh(np.array(jax.devices()[:world]), ("sp",))


def _bwd_triple(mesh, cfg, ql, kl, vl, o, lse, dol):
    """(dq, dk, dv) of the shard-level backward under `cfg`."""
    fn = shard_map(
        lambda q, k, v, o, l, do: burst._bwd_impl(cfg, q, k, v, o, l, do),
        mesh=mesh, in_specs=(SPEC4,) * 4 + (SPEC3, SPEC4),
        out_specs=(SPEC4,) * 3, check_vma=False)
    return fn(ql, kl, vl, o, lse, dol)


def run_bwd_parity(layout, causal, kv_heads=2, world=8, n=2, d=16,
                   seq_per_dev=16, dtype=jnp.float32, tol=1e-5,
                   optimize_bwd_comm=True, **cfg_kw):
    """fused bwd (dq, dk, dv) vs the scan-ring bwd, identical residuals."""
    b = 1
    S = seq_per_dev * world
    mesh = _mesh(world)
    q, k, v, do = random_qkv(KEY, b, n, S, d, kv_heads=kv_heads, dtype=dtype)
    ql, kl, vl, dol = (layouts.to_layout(t, layout, world, 2)
                       for t in (q, k, v, do))

    fused_cfg = burst.BurstConfig(causal=causal, layout=layout,
                                  intra_axis="sp", backend="fused_ring",
                                  optimize_bwd_comm=optimize_bwd_comm,
                                  **cfg_kw)
    scan_cfg = burst.BurstConfig(causal=causal, layout=layout,
                                 intra_axis="sp", backend="jnp",
                                 optimize_bwd_comm=optimize_bwd_comm)
    # residuals once, from the scan forward: BOTH backward paths consume
    # the identical (o, lse), so any difference is the backward's own
    fwd = shard_map(lambda q, k, v: burst._fwd_impl(q, k, v, scan_cfg),
                    mesh=mesh, in_specs=(SPEC4,) * 3,
                    out_specs=(SPEC4, SPEC3), check_vma=False)
    o, lse = fwd(ql, kl, vl)

    g_scan = _bwd_triple(mesh, scan_cfg, ql, kl, vl, o, lse, dol)
    g_fused = _bwd_triple(mesh, fused_cfg, ql, kl, vl, o, lse, dol)
    tag = (f"layout={layout} causal={causal} kvh={kv_heads} "
           f"opt={optimize_bwd_comm} dtype={dtype}")
    for nm, a, b_ in zip(("dq", "dk", "dv"), g_scan, g_fused):
        check_close(b_, a, rtol=tol, atol=tol,
                    msg=f"fused {nm} vs scan {tag}")


def test_causal_bwd_parity_zigzag():
    # the canonical config, kept in the tier-1 fast lane; the sibling
    # layouts below ride the full/--fused lanes (conftest _SLOW)
    run_bwd_parity("zigzag", causal=True)


@pytest.mark.parametrize("layout", ["striped", "contig"])
def test_causal_bwd_parity(layout):
    run_bwd_parity(layout, causal=True)


def test_noncausal_bwd_parity():
    run_bwd_parity("contig", causal=False, world=4)


@pytest.mark.parametrize("layout", ["zigzag", "contig"])
def test_rotate_o_bwd_parity(layout):
    # optimize_bwd_comm=False: o rides the bundle, delta recomputed in-kernel
    run_bwd_parity(layout, causal=True, optimize_bwd_comm=False)


def test_gqa_bf16_bwd_parity():
    # GQA (group = 2) in bf16 at the acceptance tolerance: accumulation
    # stays f32 in-kernel, only the inputs narrow
    run_bwd_parity("zigzag", causal=True, kv_heads=1, dtype=jnp.bfloat16,
                   tol=2e-2)


def test_three_slots_and_rect_blocks():
    # deeper comm pipeline + rectangular (bq != bkv) bwd blocks take the
    # same schedule
    run_bwd_parity("striped", causal=True, world=4, n=1, kv_heads=1,
                   fused_bwd_slots=3, fused_block_q_bwd=8,
                   fused_block_kv_bwd=16)


def test_world_two():
    run_bwd_parity("zigzag", causal=True, world=2)


@pytest.mark.parametrize("layout,opt", [("zigzag", True), ("striped", False),
                                        ("contig", True)])
def test_grad_matches_dense_oracle(layout, opt):
    """jax.grad end to end through backend="fused_ring": fused forward AND
    fused backward must reproduce the dense oracle's gradients."""
    world, b, n, d = 8, 1, 2, 16
    S = 16 * world
    mesh = _mesh(world)
    q, k, v, do = random_qkv(KEY, b, n, S, d, kv_heads=2, dtype=jnp.float32)
    ql, kl, vl, dol = (layouts.to_layout(t, layout, world, 2)
                       for t in (q, k, v, do))

    def loss(ql, kl, vl):
        o = burst_attn(ql, kl, vl, mesh=mesh, seq_axes=("sp",), causal=True,
                       layout=layout, backend="fused_ring",
                       optimize_bwd_comm=opt)
        return jnp.sum(o.astype(jnp.float32) * dol)

    def ref_loss(q, k, v):
        return jnp.sum(
            dense_attention(q, k, v, causal=True).astype(jnp.float32) * do)

    g = jax.grad(loss, argnums=(0, 1, 2))(ql, kl, vl)
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for got, want, nm in zip(g, g_ref, "qkv"):
        got = layouts.from_layout(got, layout, world, 2)
        check_close(got, want, rtol=2e-4, atol=2e-4,
                    msg=f"fused bwd d{nm} ({layout}, opt={opt})")


def test_no_xla_collectives_in_fused_bwd():
    """The fused backward must contain zero ppermute/all_to_all — both
    rotating streams live inside the kernel (burstlint's fused-ring-fused
    bwd family checks the same invariant as a standing gate), and the
    remote-copy census is exactly 4 bundle + 1 dq ring + 1 dq home."""
    from burst_attn_tpu.analysis.jaxpr_tools import collect_collectives
    from burst_attn_tpu.analysis.ringcheck import _remote_dma_starts

    mesh = _mesh(4)
    cfg = burst.BurstConfig(causal=True, layout="zigzag", intra_axis="sp",
                            backend="fused_ring")
    S = jax.ShapeDtypeStruct((1, 2, 64, 8), jnp.float32)
    L = jax.ShapeDtypeStruct((1, 2, 64), jnp.float32)
    fn = shard_map(
        lambda q, k, v, o, l, do: burst._bwd_impl(cfg, q, k, v, o, l, do),
        mesh=mesh, in_specs=(SPEC4,) * 4 + (SPEC3, SPEC4),
        out_specs=(SPEC4,) * 3, check_vma=False)
    jx = jax.make_jaxpr(fn)(S, S, S, S, L, S)
    ev = [e for e in collect_collectives(jx)
          if e.prim in ("ppermute", "all_to_all")]
    assert ev == [], ev
    assert len(_remote_dma_starts(jx)) == 6


def test_value_and_grad_zero_collectives_both_passes():
    """Acceptance criterion: the whole value_and_grad trace under
    backend="fused_ring" carries zero XLA collectives."""
    from burst_attn_tpu.analysis.jaxpr_tools import collect_collectives

    mesh = _mesh(4)
    cfg = burst.BurstConfig(causal=True, layout="zigzag", intra_axis="sp",
                            backend="fused_ring")
    S = jax.ShapeDtypeStruct((1, 2, 64, 8), jnp.float32)

    def loss(q, k, v):
        o = burst._burst_attn_shard_plain(q, k, v, cfg)
        return jnp.sum(o.astype(jnp.float32))

    fn = shard_map(
        lambda q, k, v: jax.value_and_grad(loss, (0, 1, 2))(q, k, v),
        mesh=mesh, in_specs=(SPEC4,) * 3, out_specs=(P(), (SPEC4,) * 3),
        check_vma=False)
    ev = [e for e in collect_collectives(jax.make_jaxpr(fn)(S, S, S))
          if e.prim in ("ppermute", "all_to_all")]
    assert ev == [], ev


def test_bwd_slot_counters_replay_schedule():
    """collect_stats=True: the kernel's in-kernel bundle slot counters
    replay the exported fused_bwd_slot_schedule exactly, and the grads are
    bit-identical to the stats-off kernel (same SMEM scalar-output channel
    as the forward; see obs/devstats.py `slot_use_bwd`)."""
    from burst_attn_tpu.ops import fused_ring_bwd
    from burst_attn_tpu.ops.tuning import resolve_fused

    world, n, d = 4, 2, 16
    S = 16 * world
    mesh = _mesh(world)
    q, k, v, do = random_qkv(KEY, 1, n, S, d, kv_heads=2, dtype=jnp.float32)
    ql, kl, vl, dol = (layouts.to_layout(t, "zigzag", world, 2)
                       for t in (q, k, v, do))
    cfg = burst.BurstConfig(causal=True, layout="zigzag", intra_axis="sp",
                            backend="fused_ring")
    fwd = shard_map(lambda q, k, v: burst._fwd_impl(q, k, v, cfg),
                    mesh=mesh, in_specs=(SPEC4,) * 3,
                    out_specs=(SPEC4, SPEC3), check_vma=False)
    o, lse = fwd(ql, kl, vl)

    def with_stats(q, k, v, o, l, do):
        dq, dk, dv, slot_use = fused_ring_bwd.fused_ring_bwd(
            cfg, q, k, v, o, l, do, collect_stats=True)
        return dq, dk, dv, slot_use

    slots = min(resolve_fused(None, None, None).bwd_slots, world)
    fn = shard_map(
        with_stats, mesh=mesh, in_specs=(SPEC4,) * 4 + (SPEC3, SPEC4),
        out_specs=(SPEC4,) * 3 + (P("sp"),), check_vma=False)
    dq1, dk1, dv1, slot_use = fn(ql, kl, vl, o, lse, dol)
    dq0, dk0, dv0 = _bwd_triple(mesh, cfg, ql, kl, vl, o, lse, dol)
    assert bool(jnp.all(dq0 == dq1)), "fused bwd dq diverged under collect"
    assert bool(jnp.all(dk0 == dk1)), "fused bwd dk diverged under collect"
    assert bool(jnp.all(dv0 == dv1)), "fused bwd dv diverged under collect"

    sched = ring.fused_bwd_slot_schedule(world, slots)
    want = np.bincount(sched, minlength=slots)
    got = np.asarray(slot_use)  # [world, slots]: one row per device
    assert got.shape == (world, slots), got.shape
    assert (got == want[None, :]).all(), (got, want)


def test_devstats_carries_bwd_slot_use():
    """ring_stats threads the bwd counters into DevStats.slot_use_bwd and
    publish() lands them under devstats.slot_use{pass=bwd}."""
    from burst_attn_tpu.obs import devstats
    from burst_attn_tpu.obs.registry import Registry

    st = devstats.ring_stats(
        4, 4, 10.0, 20.0, 8, jnp.ones(2), jnp.ones(2), jnp.ones((2, 4)),
        fused_rounds=4, slot_use=jnp.asarray([2, 2], jnp.int32),
        slot_use_bwd=jnp.asarray([3, 1], jnp.int32))
    assert np.asarray(st.slot_use_bwd)[:2].tolist() == [3, 1]
    assert np.asarray(st.slot_use_bwd)[2:].sum() == 0
    reg = Registry()
    st.publish(reg)
    # primary-bank counters publish under dir="cw" since the schedule-IR
    # bidi refactor split slot_use by ring direction
    assert reg.counter("devstats.slot_use").get(
        slot=0, dir="cw", **{"pass": "bwd"}) == 3
    assert reg.counter("devstats.slot_use").get(
        slot=1, dir="cw", **{"pass": "bwd"}) == 1
    assert reg.counter("devstats.slot_use").get(
        slot=0, dir="cw", **{"pass": "fwd"}) == 2


# ---------------------------------------------------------------------------
# fallback matrix: configs the fused backward declines must silently take
# the scan-ring backward and stay correct end to end through jax.grad


def _grad_check(mesh, seq_axes, layout, kw, q, k, v, do, world, tag,
                **burst_kw):
    ql, kl, vl, dol = (layouts.to_layout(t, layout, world, 2)
                       for t in (q, k, v, do))

    def loss(ql, kl, vl):
        o = burst_attn(ql, kl, vl, mesh=mesh, seq_axes=seq_axes, causal=True,
                       layout=layout, backend="fused_ring", **burst_kw)
        return jnp.sum(o.astype(jnp.float32) * dol)

    def ref_loss(q, k, v):
        return jnp.sum(
            dense_attention(q, k, v, causal=True, **kw).astype(jnp.float32)
            * do)

    g = jax.grad(loss, argnums=(0, 1, 2))(ql, kl, vl)
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for got, want, nm in zip(g, g_ref, "qkv"):
        got = layouts.from_layout(got, layout, world, 2)
        check_close(got, want, rtol=2e-4, atol=2e-4, msg=f"{tag} d{nm}")


def test_window_grad_dispatch_fused():
    """window=24 on a contig causal ring is ADMITTED by the occupancy
    compiler (r_live=3 of 8 rounds), so this now exercises the FUSED
    truncated backward — not the scan fallback — end to end through
    jax.grad."""
    world, b, n, d = 8, 1, 2, 16
    S = 16 * world
    mesh = _mesh(world)
    q, k, v, do = random_qkv(KEY, b, n, S, d, dtype=jnp.float32)
    _grad_check(mesh, ("sp",), "contig", dict(window=24), q, k, v, do, world,
                "window fused grad", window=24)


def test_fallback_double_ring_grad():
    world, b, n, d = 8, 1, 2, 16
    S = 16 * world
    mesh = Mesh(np.array(jax.devices()[:world]).reshape(2, 4),
                ("inter", "intra"))
    q, k, v, do = random_qkv(KEY, b, n, S, d, dtype=jnp.float32)
    _grad_check(mesh, ("inter", "intra"), "zigzag", {}, q, k, v, do, world,
                "double-ring fallback")


def test_supported_bwd_reasons():
    """The extended gate: pass_="bwd" declines for the same documented
    structural reasons as the forward, admits the supported configs
    (including windowed/segmented rings since the occupancy compiler), and
    rejects an unknown pass loudly."""
    from burst_attn_tpu.ops import fused_ring

    mesh = _mesh(4)
    reasons = {}

    def probe(q, k, v):
        import dataclasses

        base = burst.BurstConfig(causal=True, layout="zigzag",
                                 intra_axis="sp", backend="fused_ring")
        reasons["ok"] = fused_ring.supported(base, q.shape, k.shape, False,
                                             pass_="bwd")
        reasons["window"] = fused_ring.supported(
            dataclasses.replace(base, layout="contig", window=8),
            q.shape, k.shape, False, pass_="bwd")
        # degenerate truncation: window=1 leaves only the self round
        # (r_live == 1) and a single-round ring has no return hop for dq,
        # so the schedule compiler declines the backward
        reasons["window1"] = fused_ring.supported(
            dataclasses.replace(base, layout="contig", window=1),
            q.shape, k.shape, False, pass_="bwd")
        reasons["segments"] = fused_ring.supported(base, q.shape, k.shape,
                                                   True, pass_="bwd")
        reasons["double"] = fused_ring.supported(
            dataclasses.replace(base, inter_axis="inter"),
            q.shape, k.shape, False, pass_="bwd")
        reasons["cross"] = fused_ring.supported(
            base, q.shape, (k.shape[0], k.shape[1], 2 * k.shape[2],
                            k.shape[3]), False, pass_="bwd")
        return q

    fn = shard_map(probe, mesh=mesh, in_specs=(SPEC4,) * 3,
                   out_specs=SPEC4, check_vma=False)
    x = jnp.zeros((1, 2, 64, 8), jnp.float32)
    jax.eval_shape(fn, x, x, x)
    assert reasons["ok"] is None
    # window/segments are ADMITTED since the occupancy compiler: the gate
    # compiles an elided (or dense, for zigzag segments) schedule instead
    # of declining
    assert reasons["window"] is None
    assert reasons["segments"] is None
    # ... except the degenerate r_live == 1 truncation, which the schedule
    # compiler itself declines for the backward
    assert "declined" in reasons["window1"]
    assert "double ring" in reasons["double"]
    assert "cross" in reasons["cross"]
    with pytest.raises(ValueError):
        from burst_attn_tpu.ops import fused_ring

        fused_ring.supported(
            burst.BurstConfig(intra_axis="sp"), (1, 2, 64, 8), (1, 2, 64, 8),
            False, pass_="sideways")


# ---------------------------------------------------------------------------
# occupancy-elided backward (ISSUE 11): fast canaries here, sweeps slow


def test_segments_elided_grad_dispatch_fused():
    """Packed segments + the max_segment_len contract: the truncated fused
    backward (r_live=2 of 8) reproduces the dense segment-masked grads."""
    world, b, n, d = 8, 1, 2, 16
    S = 16 * world
    mesh = _mesh(world)
    q, k, v, do = random_qkv(KEY, b, n, S, d, dtype=jnp.float32)
    seg = jnp.asarray(np.repeat(np.arange(world), 16)[None, :], jnp.int32)
    _grad_check(mesh, ("sp",), "contig", dict(segment_ids=seg), q, k, v, do,
                world, "seg elided grad", segment_ids=seg,
                max_segment_len=16)


@pytest.mark.slow
@pytest.mark.parametrize("topo", ["uni", "bidi"])
@pytest.mark.parametrize("window", [20, 40])
def test_windowed_grad_parity_sweep(topo, window):
    """Truncated fused backward across window depths and both single-ring
    topologies vs the dense banded oracle's grads."""
    world, b, n, d = 8, 1, 2, 16
    S = 16 * world
    mesh = _mesh(world)
    q, k, v, do = random_qkv(KEY, b, n, S, d, dtype=jnp.float32)
    _grad_check(mesh, ("sp",), "contig", dict(window=window), q, k, v, do,
                world, f"win{window} {topo} grad", window=window,
                fused_topology=topo)
