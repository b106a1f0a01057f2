"""Device-side ring telemetry (obs/devstats.py + collect_stats threading).

The load-bearing contract is BIT-IDENTITY: `collect_stats=True` must change
nothing about the computation — forward outputs AND gradients equal the
plain path bit for bit (the stats custom_vjp twins reuse the plain
backward; burstlint's `devstats-pure` rule proves the jaxpr side of the
same story).  On top of that, the stats themselves must be RIGHT: mask
occupancy equals the dense mask algebra, the causal layouts show their
signature load balance, and publish() lands the documented catalog in a
registry.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

from burst_attn_tpu import burst_attn
from burst_attn_tpu.obs import devstats
from burst_attn_tpu.obs.registry import Registry
from burst_attn_tpu.ops import masks
from burst_attn_tpu.parallel import burst, layouts

KEY = jax.random.PRNGKey(7)


def _mesh(world=8):
    return Mesh(np.array(jax.devices()[:world]), ("sp",))


def _qkv(world=8, n=2, d=16, seq_per_dev=16, layout="zigzag",
         dtype=jnp.float32):
    q = jax.random.normal(KEY, (1, n, seq_per_dev * world, d), dtype)
    return layouts.to_layout(q, layout, world, axis=2)


# ---------------------------------------------------------------------------
# spec_pair_count == dense mask algebra


@pytest.mark.parametrize("layout", ["zigzag", "striped", "contig"])
@pytest.mark.parametrize("causal", [True, False])
def test_spec_pair_count_matches_dense_mask(layout, causal):
    s = 16
    for q_part in range(4):
        for kv_part in range(4):
            sp = masks.round_spec(jnp.int32(q_part), jnp.int32(kv_part),
                                  s, s, causal, layout)
            got = float(masks.spec_pair_count(sp, s, s))
            want = float(masks.dense_mask(sp, s, s).sum())
            assert got == want, (layout, causal, q_part, kv_part, got, want)
            # liveness agrees with the pair count being nonzero
            assert bool(masks.spec_live(sp)) == (want > 0)


def test_spec_pair_count_windowed_matches_dense_mask():
    s, w = 16, 5
    for q_part in range(4):
        for kv_part in range(4):
            sp = masks.round_spec(jnp.int32(q_part), jnp.int32(kv_part),
                                  s, s, True, "contig", window=w)
            got = float(masks.spec_pair_count(sp, s, s, window=w))
            want = float(masks.dense_mask(sp, s, s, window=w).sum())
            assert got == want, (q_part, kv_part, got, want)


# ---------------------------------------------------------------------------
# scan-ring parity + stats correctness (8-dev CPU mesh)


def _fwd_and_grads(ql, mesh, **kw):
    out = burst_attn(ql, ql, ql, mesh=mesh, **kw)
    o, st = out if isinstance(out, tuple) else (out, None)

    def loss(x):
        out = burst_attn(x, x, x, mesh=mesh, **kw)
        oo = out[0] if isinstance(out, tuple) else out
        return jnp.sum(oo.astype(jnp.float32) ** 2)

    return o, st, jax.grad(loss)(ql)


def test_scan_ring_fwd_only_bit_identity_fast():
    """Fast-lane canary (the grad parity matrix below is marked slow):
    collect_stats=True forward output bit-identical to plain, zigzag."""
    world = 8
    mesh = _mesh(world)
    ql = _qkv(world)
    kw = dict(causal=True, layout="zigzag", backend="jnp")
    o0 = burst_attn(ql, ql, ql, mesh=mesh, **kw)
    o1, st = burst_attn(ql, ql, ql, mesh=mesh, collect_stats=True, **kw)
    assert bool(jnp.all(o0 == o1))
    assert np.ptp(np.asarray(st.attn_pairs)) == 0  # zigzag balance
    S = ql.shape[2]
    assert np.asarray(st.attn_pairs).sum() == S * (S + 1) // 2


@pytest.mark.parametrize("layout", ["zigzag", "striped", "contig"])
def test_scan_ring_bit_identity_fwd_and_grads(layout):
    world = 8
    mesh = _mesh(world)
    ql = _qkv(world, layout=layout)
    kw = dict(causal=True, layout=layout, backend="jnp")
    o0, _, g0 = _fwd_and_grads(ql, mesh, **kw)
    o1, st, g1 = _fwd_and_grads(ql, mesh, collect_stats=True, **kw)
    assert bool(jnp.all(o0 == o1)), f"fwd diverged under collect ({layout})"
    assert bool(jnp.all(g0 == g1)), f"grads diverged under collect ({layout})"
    assert st is not None and isinstance(st, devstats.DevStats)

    r = np.asarray(st.rounds)
    assert r.shape == (world,) and (r == world).all()
    occ = np.asarray(st.attn_pairs) / np.asarray(st.total_pairs)
    assert ((0 < occ) & (occ <= 1)).all()
    s_local = ql.shape[2] // world
    if layout == "zigzag":
        # the whole point of the layout: every device does EQUAL work
        assert np.ptp(np.asarray(st.attn_pairs)) == 0
        assert (np.asarray(st.rounds_live) == world).all()
    elif layout == "striped":
        # striped balances up to the diagonal: rank a carries s_local*(a+1)
        # pairs from its own tokens' self-visibility, so the spread across
        # ranks is exactly s_local per step — (world-1)*s_local end to end
        pairs = np.asarray(st.attn_pairs)
        assert (np.diff(pairs) == s_local).all(), pairs
        assert np.ptp(pairs) == (world - 1) * s_local
        assert (np.asarray(st.rounds_live) == world).all()
    else:
        # contig keeps the raw causal triangle: device i sees i+1 live
        # rounds and work grows with rank
        assert (np.asarray(st.rounds_live) == np.arange(world) + 1).all()
        pairs = np.asarray(st.attn_pairs)
        assert (np.diff(pairs) > 0).all()
    # total attended pairs across devices == the global causal triangle
    S = ql.shape[2]
    assert np.asarray(st.attn_pairs).sum() == S * (S + 1) // 2
    assert (np.asarray(st.nonfinite_lse) == 0).all()
    assert (np.asarray(st.nonfinite_acc) == 0).all()
    assert (np.asarray(st.fused_rounds) == 0).all()
    # scan path reports a real running max
    assert np.isfinite(np.asarray(st.m_max)).all()
    lse_min, lse_max = np.asarray(st.lse_min), np.asarray(st.lse_max)
    assert (lse_min <= lse_max).all() and np.isfinite(lse_min).all()


def test_double_ring_collect_matches_plain():
    devs = jax.devices()
    mesh = Mesh(np.asarray(devs[:8]).reshape(2, 4), ("inter", "intra"))
    ql = _qkv(8, layout="zigzag")
    kw = dict(causal=True, layout="zigzag", backend="jnp",
              seq_axes=("inter", "intra"))
    o0, _, g0 = _fwd_and_grads(ql, mesh, **kw)
    o1, st, g1 = _fwd_and_grads(ql, mesh, collect_stats=True, **kw)
    assert bool(jnp.all(o0 == o1)) and bool(jnp.all(g0 == g1))
    assert np.asarray(st.rounds).shape == (8,)
    assert (np.asarray(st.rounds) == 8).all()
    assert np.ptp(np.asarray(st.attn_pairs)) == 0  # zigzag balance holds


def test_windowed_contig_truncation_visible_in_stats():
    world = 8
    mesh = _mesh(world)
    ql = _qkv(world, layout="contig", seq_per_dev=16)
    w = 20  # band spans ceil((16 + 20 - 2)/16) + 1 = 4 live rounds max
    kw = dict(causal=True, layout="contig", backend="jnp", window=w)
    o0, _, g0 = _fwd_and_grads(ql, mesh, **kw)
    o1, st, g1 = _fwd_and_grads(ql, mesh, collect_stats=True, **kw)
    assert bool(jnp.all(o0 == o1)) and bool(jnp.all(g0 == g1))
    r_live = burst._r_live(
        burst.BurstConfig(causal=True, layout="contig", window=w,
                          intra_axis="sp"), 16, 16, 1, world)
    assert (np.asarray(st.rounds) == r_live).all()
    assert r_live < world  # the truncation actually bit
    # every attended pair lies inside the global band
    S = ql.shape[2]
    rows = np.arange(S)
    band = np.minimum(rows + 1, w).sum()
    assert np.asarray(st.attn_pairs).sum() == band


def test_segments_collect_matches_plain():
    world = 8
    mesh = _mesh(world)
    ql = _qkv(world, layout="zigzag")
    seg = np.repeat(np.arange(4), ql.shape[2] // 4)[None, :]
    seg_l = layouts.to_layout(jnp.asarray(seg, jnp.int32), "zigzag", world,
                              axis=1)
    kw = dict(causal=True, layout="zigzag", backend="jnp",
              segment_ids=seg_l)
    o0, _, g0 = _fwd_and_grads(ql, mesh, **kw)
    o1, st, g1 = _fwd_and_grads(ql, mesh, collect_stats=True, **kw)
    assert bool(jnp.all(o0 == o1)) and bool(jnp.all(g0 == g1))
    # the uniform-spec tally ignores segment masking by design (structural
    # occupancy, not data-dependent) — still the full causal triangle
    S = ql.shape[2]
    assert np.asarray(st.attn_pairs).sum() == S * (S + 1) // 2


# ---------------------------------------------------------------------------
# publish + merge/cross_reduce semantics


def test_publish_catalog_lands_in_registry():
    world = 4
    mesh = _mesh(world)
    ql = _qkv(world)
    _, st = burst_attn(ql, ql, ql, mesh=mesh, causal=True, layout="zigzag",
                       backend="jnp", collect_stats=True)
    reg = Registry()
    st.publish(reg, labels={"layout": "zigzag"})
    for dev in range(world):
        assert reg.gauge("devstats.rounds").get(
            device=dev, layout="zigzag") == world
        occ = reg.gauge("devstats.mask_occupancy").get(
            device=dev, layout="zigzag")
        assert 0 < occ <= 1
    assert reg.gauge("devstats.flop_imbalance").get(layout="zigzag") == 1.0
    assert reg.counter("devstats.nonfinite").get(
        which="lse", layout="zigzag") == 0
    assert reg.counter("devstats.publishes").get() == 1
    # publishing is cumulative over steps: counters advance, gauges rewrite
    st.publish(reg, labels={"layout": "zigzag"})
    assert reg.counter("devstats.publishes").get() == 2


def test_merge_adds_counts_and_folds_extrema():
    a = devstats.ring_stats(4, 4, 10.0, 20.0, 8,
                            jnp.ones((2, 2)), jnp.ones((2, 2)),
                            jnp.ones((2, 2, 4)))
    b = devstats.ring_stats(4, 2, 6.0, 20.0, 8,
                            2 * jnp.ones((2, 2)), 3 * jnp.ones((2, 2)),
                            jnp.ones((2, 2, 4)))
    m = devstats.merge(a, b)
    assert int(m.rounds) == 8 and int(m.rounds_live) == 6
    assert float(m.attn_pairs) == 16.0
    assert float(m.m_max) == 2.0  # max, not sum
    assert float(m.lse_min) == 1.0 and float(m.lse_max) == 3.0


def test_nonfinite_detection():
    lse = jnp.asarray([0.0, jnp.nan, -jnp.inf, jnp.inf])
    acc = jnp.asarray([1.0, jnp.nan, 2.0])
    st = devstats.ring_stats(1, 1, 1.0, 1.0, 8, jnp.ones(2), lse, acc)
    # -inf lse is a legal fully-masked row; nan and +inf are corruption
    assert int(st.nonfinite_lse) == 2
    assert int(st.nonfinite_acc) == 1
    assert float(st.lse_min) == 0.0 and float(st.lse_max) == 0.0


# ---------------------------------------------------------------------------
# occupancy elision: live-vs-executed round accounting


def test_rounds_elided_live_vs_executed():
    """Elided rounds never RAN: the in-shard round counters (incremented
    per executed round) stop at r_live, and rounds_elided makes the split
    sum back to the full ring."""
    world = 8
    mesh = _mesh(world)
    ql = _qkv(world, layout="contig")

    def stats(**kw):
        _, st = burst_attn(ql, ql, ql, mesh=mesh, collect_stats=True,
                           causal=True, layout="contig", **kw)
        return st

    r_live = masks.live_round_prefix("contig", 16, world, causal=True,
                                     window=20)
    assert r_live == 3  # the truncation bites: strictly fewer than world
    st = stats(backend="jnp", window=20)
    assert (np.asarray(st.rounds) == r_live).all(), np.asarray(st.rounds)
    assert (np.asarray(st.rounds_elided) == world - r_live).all()

    # packed segments under the max_segment_len contract: reach 15 < 17
    # kills every offset past delta 1
    seg = jnp.asarray(np.repeat(np.arange(world), 16)[None, :], jnp.int32)
    st = stats(backend="jnp", segment_ids=seg, max_segment_len=16)
    assert (np.asarray(st.rounds) == 2).all()
    assert (np.asarray(st.rounds_elided) == world - 2).all()

    # dense schedules report zero elision
    st = stats(backend="jnp")
    assert (np.asarray(st.rounds) == world).all()
    assert (np.asarray(st.rounds_elided) == 0).all()
