"""Schedule-IR topology parity: the fused kernels interpreting compiler-
emitted bidi (counter-rotating bidirectional) and double-ring programs
against the scan ring and the dense oracle, in interpret mode on the
simulated CPU mesh.

The double ring runs FACTORED onto the flat ring axis here
(`fused_seq_factor`) because jax's interpret-mode DMA discharge emulates a
single named axis; the two-axis program is structurally identical (same
compiled rows, different neighbor ids) and its trace is census-checked by
burstlint's BURST_FUSED_ASSUME_TPU pass (analysis/ringcheck.py
verify_fused_topologies).
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
from burst_attn_tpu.parallel import burst, layouts, schedule
from burst_attn_tpu.utils.testing import check_close, random_qkv

pytestmark = pytest.mark.fused_ring

KEY = jax.random.PRNGKey(29)
SPEC4 = P(None, None, "sp", None)
SPEC3 = P(None, None, "sp")


def _mesh(world):
    return Mesh(np.array(jax.devices()[:world]), ("sp",))


def _fwd_pair(mesh, cfg, ql, kl, vl):
    fn = shard_map(lambda q, k, v: burst._fwd_impl(q, k, v, cfg),
                   mesh=mesh, in_specs=(SPEC4,) * 3,
                   out_specs=(SPEC4, SPEC3), check_vma=False)
    return fn(ql, kl, vl)


def run_fwd_parity(layout, causal, world, *, tol=1e-5, n=2, d=16,
                   seq_per_dev=16, **cfg_kw):
    """Topology-config fused (o, lse) vs the scan ring and the dense
    oracle."""
    b = 1
    S = seq_per_dev * world
    mesh = _mesh(world)
    q, k, v, _ = random_qkv(KEY, b, n, S, d, kv_heads=n, dtype=jnp.float32)
    ql, kl, vl = (layouts.to_layout(t, layout, world, 2) for t in (q, k, v))
    fused_cfg = burst.BurstConfig(causal=causal, layout=layout,
                                  intra_axis="sp", backend="fused_ring",
                                  **cfg_kw)
    scan_cfg = burst.BurstConfig(causal=causal, layout=layout,
                                 intra_axis="sp", backend="jnp")
    o_f, lse_f = _fwd_pair(mesh, fused_cfg, ql, kl, vl)
    o_s, lse_s = _fwd_pair(mesh, scan_cfg, ql, kl, vl)
    tag = f"{cfg_kw} layout={layout} causal={causal} world={world}"
    check_close(o_f, o_s, rtol=tol, atol=tol, msg=f"o vs scan {tag}")
    check_close(lse_f, lse_s, rtol=tol, atol=tol, msg=f"lse vs scan {tag}")
    o_nat = layouts.from_layout(o_f, layout, world, 2)
    check_close(o_nat, dense_attention(q, k, v, causal=causal),
                rtol=tol, atol=tol, msg=f"o vs dense {tag}")


def run_grad_parity(world, *, layout="zigzag", tol=2e-4, **topo_kw):
    """value_and_grad through the topology-config fused backend (fused fwd
    AND fused bwd) vs the dense oracle's gradients."""
    b, n, d = 1, 2, 16
    S = 16 * world
    mesh = _mesh(world)
    q, k, v, do = random_qkv(KEY, b, n, S, d, kv_heads=n, dtype=jnp.float32)
    ql, kl, vl, dol = (layouts.to_layout(t, layout, world, 2)
                       for t in (q, k, v, do))

    def loss(ql, kl, vl):
        o = burst_attn(ql, kl, vl, mesh=mesh, seq_axes=("sp",), causal=True,
                       layout=layout, backend="fused_ring", **topo_kw)
        return jnp.sum(o.astype(jnp.float32) * dol)

    def ref_loss(q, k, v):
        return jnp.sum(
            dense_attention(q, k, v, causal=True).astype(jnp.float32) * do)

    g = jax.grad(loss, argnums=(0, 1, 2))(ql, kl, vl)
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for got, want, nm in zip(g, g_ref, "qkv"):
        got = layouts.from_layout(got, layout, world, 2)
        check_close(got, want, rtol=tol, atol=tol,
                    msg=f"{topo_kw} d{nm}")


# ---------------------------------------------------------------------------
# counter-rotating bidirectional ring


@pytest.mark.parametrize("world", [4, 5])
def test_bidi_fwd_parity(world):
    # odd world = asymmetric directional split (cw carries one more hop)
    run_fwd_parity("zigzag", True, world, fused_topology="bidi")


def test_bidi_fwd_noncausal_contig():
    run_fwd_parity("contig", False, 4, fused_topology="bidi")


def test_bidi_grad_parity():
    run_grad_parity(4, fused_topology="bidi")


def test_bidi_deeper_cw_bank():
    run_fwd_parity("striped", True, 5, fused_topology="bidi",
                   fused_kv_slots=3, fused_ccw_slots=2)


def test_bidi_world_two_degrades_to_uni():
    """No second direction to use below world 3: the dispatch must resolve
    to the uni schedule and still run fused."""
    from burst_attn_tpu.ops import fused_ring

    cfg = burst.BurstConfig(causal=True, layout="zigzag", intra_axis="sp",
                            backend="fused_ring", fused_topology="bidi")
    assert fused_ring.resolve_topology(cfg, 2)[0] == "uni"
    run_fwd_parity("zigzag", True, 2, fused_topology="bidi")


# ---------------------------------------------------------------------------
# fused hierarchical double ring (factored onto the flat ring axis)


@pytest.mark.parametrize("factor", [(2, 2), (2, 4), (4, 2)])
def test_double_fwd_parity(factor):
    world = factor[0] * factor[1]
    run_fwd_parity("zigzag", True, world, fused_seq_factor=factor)


def test_double_grad_parity():
    run_grad_parity(4, fused_seq_factor=(2, 2))


def test_double_fwd_noncausal():
    run_fwd_parity("contig", False, 4, fused_seq_factor=(2, 2))


# ---------------------------------------------------------------------------
# supported(): the distinct axis-env probe failure reason


def test_axis_env_unavailable_reason_is_distinct(monkeypatch):
    """When the axis-env probe itself fails (private API unavailable
    off-trace), supported() must report its own reason — not the
    multi-axis decline — so burst.fused_fallback counters attribute the
    fallback correctly."""
    from burst_attn_tpu.ops import fused_ring

    monkeypatch.setattr(fused_ring, "_extra_named_axes",
                        lambda *a, **k: None)
    cfg = burst.BurstConfig(causal=True, layout="zigzag", intra_axis="sp",
                            backend="fused_ring")
    reason = fused_ring.supported(cfg, (1, 2, 64, 16), (1, 2, 64, 16),
                                  False, world=4)
    assert reason is not None and "axis env unavailable" in reason
    assert "multi-axis" not in reason
    # and the bounded fallback label maps it to its own bucket
    label = next(lbl for prefix, lbl in burst._FALLBACK_LABELS
                 if reason.startswith(prefix))
    assert label == "axis-env-unavailable"


# ---------------------------------------------------------------------------
# devstats: the per-direction slot counters (dir=cw|ccw labels)


def test_bidi_slot_counters_split_by_direction():
    """collect_stats through a bidi schedule: bank-0 (cw) and bank-1 (ccw)
    rows of the kernel's SMEM counter replay the compiled program's
    consume columns, and publish() lands them under
    devstats.slot_use{dir=cw|ccw} (the satellite's on-device verification
    of the bidirectional split)."""
    from burst_attn_tpu.obs.registry import Registry

    world, b, n, d = 4, 1, 2, 16
    S = 16 * world
    mesh = _mesh(world)
    q, k, v, _ = random_qkv(KEY, b, n, S, d, kv_heads=n, dtype=jnp.float32)
    ql, kl, vl = (layouts.to_layout(t, "zigzag", world, 2)
                  for t in (q, k, v))
    _, stats = burst_attn(ql, kl, vl, mesh=mesh, seq_axes=("sp",),
                          causal=True, layout="zigzag",
                          backend="fused_ring", fused_topology="bidi",
                          collect_stats=True)

    prog = schedule.compile_fwd("bidi", world)
    want = {0: [0] * prog.slots[0], 1: [0] * prog.slots[1]}
    for r in range(prog.n_rounds):
        bank = prog.rows["consume_bank"][r]
        want[bank][prog.rows["consume_slot"][r]] += 1
    cw = np.asarray(stats.slot_use).sum(axis=0)
    ccw = np.asarray(stats.slot_use_ccw).sum(axis=0)
    assert cw[:len(want[0])].tolist() == [world * c for c in want[0]]
    assert ccw[:len(want[1])].tolist() == [world * c for c in want[1]]
    assert cw[len(want[0]):].sum() == 0 and ccw[len(want[1]):].sum() == 0

    reg = Registry()
    stats.publish(reg)
    got_cw = sum(reg.counter("devstats.slot_use").get(
        slot=j, dir="cw", **{"pass": "fwd"}) for j in range(len(want[0])))
    got_ccw = sum(reg.counter("devstats.slot_use").get(
        slot=j, dir="ccw", **{"pass": "fwd"}) for j in range(len(want[1])))
    assert got_cw == float(world * sum(want[0]))
    assert got_ccw == float(world * sum(want[1]))
