"""Sliding-window (band) causal attention: mask semantics, jnp tile, Pallas
kernels (interpret), the public flash_attention, the contig burst ring, and
ulysses.  Beyond the UPSTREAM reference (MayDomine/Burst-Attention has no
window support); oracle = dense banded softmax (banded_dense here, and
ops/reference.py's dense_attention(window=) since round 4 — both exist so
the two stay mutually checking)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import burst_attn_tpu as bat
from burst_attn_tpu.ops import pallas_flash, tile
from burst_attn_tpu.ops.masks import dense_mask, round_spec

B, N, D = 1, 2, 32
SCALE = D**-0.5


def banded_dense(q, k, v, window):
    s_q, s_kv = q.shape[2], k.shape[2]
    s = jnp.einsum("bnid,bnjd->bnij", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * SCALE
    rows = np.arange(s_q)[:, None]
    cols = np.arange(s_kv)[None, :]
    m = (cols <= rows) & (cols > rows - window)
    s = jnp.where(m, s, -jnp.inf)
    return jnp.einsum("bnij,bnjd->bnid", jax.nn.softmax(s, axis=-1),
                      v.astype(jnp.float32))


def _inputs(s, seed=0, n_kv=N):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, N, s, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, n_kv, s, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, n_kv, s, D), jnp.float32)
    do = jax.random.normal(ks[3], (B, N, s, D), jnp.float32)
    return q, k, v, do


def test_dense_mask_band():
    spec = round_spec(jnp.int32(0), jnp.int32(0), 8, 8, True, "contig",
                      window=3)
    m = np.asarray(dense_mask(spec, 8, 8, window=3))
    rows, cols = np.arange(8)[:, None], np.arange(8)[None, :]
    np.testing.assert_array_equal(m, (cols <= rows) & (cols > rows - 3))


def test_round_spec_window_guards():
    with pytest.raises(ValueError, match="contig"):
        round_spec(jnp.int32(0), jnp.int32(0), 8, 8, True, "zigzag", window=3)
    with pytest.raises(ValueError, match="causal"):
        round_spec(jnp.int32(0), jnp.int32(0), 8, 8, False, "contig", window=3)
    with pytest.raises(ValueError, match=">= 1"):
        round_spec(jnp.int32(0), jnp.int32(0), 8, 8, True, "contig", window=0)


@pytest.mark.parametrize("window", [1, 24, 64])
def test_tile_window_matches_banded_dense(window):
    q, k, v, _ = _inputs(64)
    spec = round_spec(jnp.int32(0), jnp.int32(0), 64, 64, True, "contig")
    st = tile.init_state(B, N, 64, D)
    m, lse, acc = tile.tile_fwd(q, k, v, *st, SCALE, spec, window=window)
    o = tile.finalize(m, lse, acc, jnp.float32)
    np.testing.assert_allclose(o, banded_dense(q, k, v, window),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n_kv", [N, 1])
@pytest.mark.parametrize("window", [1, 24, 64])
def test_flash_fwd_window_matches_tile(window, n_kv):
    # blocks of 16 over seq 64 exercise full, partially-masked, and dead
    # (left-of-band) block classes
    q, k, v, _ = _inputs(64, n_kv=n_kv)
    spec = round_spec(jnp.int32(0), jnp.int32(0), 64, 64, True, "contig")
    st = tile.init_state(B, N, 64, D)
    ref = tile.tile_fwd(q, k, v, *st, SCALE, spec, window=window)
    got = pallas_flash.flash_fwd(q, k, v, *st, SCALE, spec, block_q=16,
                                 block_kv=16, interpret=True, cast_p=False,
                                 window=window)
    for name, x, y in zip(("m", "lse", "acc"), ref, got):
        np.testing.assert_allclose(y, x, rtol=1e-4, atol=1e-4, err_msg=name)


@pytest.mark.parametrize("window", [1, 24, 64])
def test_flash_bwd_window_matches_tile(window):
    q, k, v, do = _inputs(64)
    spec = round_spec(jnp.int32(0), jnp.int32(0), 64, 64, True, "contig")
    st = tile.init_state(B, N, 64, D)
    m, lse, acc = tile.tile_fwd(q, k, v, *st, SCALE, spec, window=window)
    o = tile.finalize(m, lse, acc, q.dtype)
    delta = jnp.sum(o * do, axis=-1)
    ref = tile.tile_bwd(do, q, k, v, delta, lse, SCALE, spec, window=window)
    got = pallas_flash.flash_bwd(do, q, k, v, delta, lse, SCALE, spec,
                                 block_q=16, block_kv=16, interpret=True,
                                 window=window)
    for name, x, y in zip(("dq", "dk", "dv"), ref, got):
        np.testing.assert_allclose(y, x, rtol=1e-4, atol=1e-4, err_msg=name)


def test_flash_attention_window_end_to_end():
    q, k, v, do = _inputs(128)
    ref_o = banded_dense(q, k, v, 32)
    got_o = pallas_flash.flash_attention(q, k, v, None, True, 32, 32,
                                         window=32)
    np.testing.assert_allclose(got_o, ref_o, rtol=2e-4, atol=2e-4)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * do)

    ref_g = jax.grad(loss(lambda q, k, v: banded_dense(q, k, v, 32)),
                     argnums=(0, 1, 2))(q, k, v)
    got_g = jax.grad(loss(lambda q, k, v: pallas_flash.flash_attention(
        q, k, v, None, True, 32, 32, window=32)), argnums=(0, 1, 2))(q, k, v)
    for name, x, y in zip(("dq", "dk", "dv"), ref_g, got_g):
        np.testing.assert_allclose(y, x, rtol=2e-4, atol=2e-4, err_msg=name)


def test_window_one_attends_self_only():
    q, k, v, _ = _inputs(32)
    o = pallas_flash.flash_attention(q, k, v, None, True, 16, 16, window=1)
    np.testing.assert_allclose(np.asarray(o), np.asarray(v),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_burst_ring_contig_window(backend):
    # 8-way contig ring: rounds wholly outside the band are dead; the band
    # crosses shard boundaries (window 24 > local 16)
    q, k, v, _ = _inputs(128, seed=3)
    mesh = Mesh(np.array(jax.devices()[:8]), ("sp",))
    o = jax.jit(lambda q, k, v: bat.burst_attn(
        q, k, v, mesh=mesh, seq_axes=("sp",), causal=True, layout="contig",
        backend=backend, window=24, block_q=16, block_kv=16))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(banded_dense(q, k, v, 24)),
        rtol=2e-4, atol=2e-4)


def test_burst_ring_window_grad():
    q, k, v, do = _inputs(128, seed=4)
    mesh = Mesh(np.array(jax.devices()[:8]), ("sp",))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32)
                                       * do.astype(jnp.float32))

    got = jax.jit(jax.grad(loss(lambda q, k, v: bat.burst_attn(
        q, k, v, mesh=mesh, causal=True, layout="contig", backend="jnp",
        window=24)), argnums=(0, 1, 2)))(q, k, v)
    ref = jax.jit(jax.grad(loss(lambda q, k, v: banded_dense(q, k, v, 24)),
                           argnums=(0, 1, 2)))(q, k, v)
    for name, x, y in zip(("dq", "dk", "dv"), ref, got):
        np.testing.assert_allclose(np.asarray(y, np.float32), x,
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_ulysses_window():
    q, k, v, _ = _inputs(128, seed=5)
    mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
    o = bat.ulysses_attn(q, k, v, mesh=mesh, seq_axis="sp", causal=True,
                         window=24)
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(banded_dense(q, k, v, 24)),
        rtol=2e-4, atol=2e-4)


def test_window_guards():
    q, k, v, _ = _inputs(32)
    mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
    with pytest.raises(ValueError, match="contig"):
        bat.burst_attn(q, k, v, mesh=mesh, causal=True, layout="zigzag",
                       window=8)
    with pytest.raises(ValueError, match="causal"):
        bat.burst_attn(q, k, v, mesh=mesh, causal=False, layout="contig",
                       window=8)
    with pytest.raises(ValueError, match="causal"):
        pallas_flash.flash_attention(q, k, v, None, False, window=8)


def test_model_trains_with_window():
    from burst_attn_tpu.models import ModelConfig, init_params
    from burst_attn_tpu.models.train import (
        TrainConfig, init_train_state, loss_fn, make_batch, make_mesh,
        make_train_step,
    )

    cfg = ModelConfig(
        vocab=128, d_model=64, n_layers=2, n_heads=2, n_kv_heads=2, d_head=32,
        d_ff=128, dtype=jnp.float32, attn_backend="jnp", remat=False,
        batch_axis=None, head_axis=None, layout="contig", window=16,
    )
    mesh = make_mesh({"sp": 2})
    tcfg = TrainConfig()
    state = init_train_state(jax.random.PRNGKey(0), cfg, tcfg, mesh)
    step = make_train_step(cfg, tcfg, mesh)
    batch = make_batch(jax.random.PRNGKey(1), cfg, mesh, batch=2, seq=64)
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    # windowed loss differs from the unwindowed one on the same batch
    params = init_params(jax.random.PRNGKey(0), cfg)
    from dataclasses import replace
    l_w = loss_fn(params, batch["tokens"], batch["positions"],
                  batch["labels"], cfg, mesh)
    l_full = loss_fn(params, batch["tokens"], batch["positions"],
                     batch["labels"], replace(cfg, window=None), mesh)
    assert abs(float(l_w) - float(l_full)) > 1e-6


def test_decode_window_matches_forward():
    # KV-cache decode honors cfg.window: prefill logits == the windowed
    # training forward, and one incremental step == recompute over T+1
    from burst_attn_tpu.models import (
        ModelConfig, forward, forward_cached, init_params, prefill,
    )
    from burst_attn_tpu.models.train import make_mesh

    cfg = ModelConfig(
        vocab=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2, d_head=16,
        d_ff=64, dtype=jnp.float32, attn_backend="jnp", remat=False,
        batch_axis=None, head_axis=None, layout="contig", window=8,
    )
    mesh = make_mesh({"sp": 1})
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, 64)
    pos = jnp.arange(16, dtype=jnp.int32)[None, :]

    full = forward(params, tokens, pos, cfg, mesh)
    pre, cache = prefill(params, tokens, cfg, max_seq=32)
    np.testing.assert_allclose(np.asarray(pre), np.asarray(full),
                               rtol=2e-4, atol=2e-4)

    nxt = jax.random.randint(jax.random.PRNGKey(2), (1, 1), 0, 64)
    inc, _ = forward_cached(params, nxt, jnp.full((1, 1), 16, jnp.int32),
                            cache, cfg)
    tokens17 = jnp.concatenate([tokens, nxt], axis=1)
    pos17 = jnp.arange(17, dtype=jnp.int32)[None, :]
    full17 = forward(params, tokens17, pos17, cfg, mesh)
    np.testing.assert_allclose(np.asarray(inc[:, 0]),
                               np.asarray(full17[:, -1]),
                               rtol=2e-4, atol=2e-4)


def test_dist_decode_window_matches_single_chip():
    # sharded-cache decode applies the band per shard (global positions):
    # logits must match the single-chip cached decode path step by step
    from functools import partial

    from burst_attn_tpu.models import (
        ModelConfig, forward_cached, init_params, prefill,
    )
    from burst_attn_tpu.models.dist_decode import dist_decode_step, dist_prefill
    from burst_attn_tpu.models.train import make_mesh

    cfg = ModelConfig(
        vocab=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2, d_head=16,
        d_ff=64, dtype=jnp.float32, attn_backend="jnp", remat=False,
        batch_axis=None, head_axis=None, layout="contig", window=8,
    )
    mesh = make_mesh({"sp": 2})
    params = init_params(jax.random.PRNGKey(0), cfg)
    s = 16
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, s), 0, 64)

    # gen_budget > window so later steps drive the recent-buffer band
    # (rec_lo > 0) and the all-prompt-shards-masked regime
    budget = 12
    last_d, dcache = jax.jit(partial(dist_prefill, cfg=cfg, mesh=mesh,
                                     gen_budget=budget))(params, tokens)
    ref_logits, cache = prefill(params, tokens, cfg, max_seq=s + budget)
    np.testing.assert_allclose(np.asarray(last_d),
                               np.asarray(ref_logits[:, -1]),
                               rtol=2e-4, atol=2e-4)

    step = jax.jit(partial(dist_decode_step, cfg=cfg, mesh=mesh))
    tok = jnp.argmax(last_d, axis=-1).astype(jnp.int32)
    # 11 steps with window=8: from step 8 on, rec_lo = n_new - 7 > 0 and the
    # whole band lives in the recent buffer (prompt shards fully masked)
    for i in range(11):
        lg_d, dcache = step(params, tok, jnp.int32(s + i), dcache)
        lg_ref, cache = forward_cached(
            params, tok[:, None], jnp.full((1, 1), s + i, jnp.int32), cache,
            cfg)
        np.testing.assert_allclose(np.asarray(lg_d),
                                   np.asarray(lg_ref[:, 0]),
                                   rtol=2e-4, atol=2e-4, err_msg=f"step {i}")
        tok = jnp.argmax(lg_d, axis=-1).astype(jnp.int32)


def test_burst_config_validates_window():
    with pytest.raises(ValueError, match="contig"):
        bat.BurstConfig(causal=True, layout="zigzag", window=8)
    with pytest.raises(ValueError, match="causal"):
        bat.BurstConfig(causal=False, layout="contig", window=8)
    with pytest.raises(ValueError, match=">= 1"):
        bat.BurstConfig(causal=True, layout="contig", window=0)


@pytest.mark.parametrize("window,blocks,seq", [
    (16, 16, 128),   # nb=2 < nkb=8: band active, several full blocks/row
    (24, 16, 128),   # unaligned window crossing block boundaries (nb=3)
    (48, 16, 96),    # band nearly spans the grid (nb=4 < nkb=6)
    (16, 16, 32),    # nb >= nkb: band declines, rect path (guard the gate)
])
def test_band_grid_matches_dense(window, blocks, seq):
    """The banded fwd grid (kv dim = blocks intersecting the window band,
    flash_fwd band_nb) reproduces the dense banded oracle, values and
    grads, wherever the gate enables it."""
    q, k, v, do = _inputs(seq, seed=7)
    ref_o = banded_dense(q, k, v, window)
    got_o = pallas_flash.flash_attention(q, k, v, None, True, blocks, blocks,
                                         window=window)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(ref_o),
                               rtol=2e-4, atol=2e-4)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * do)

    ref_g = jax.grad(loss(lambda q, k, v: banded_dense(q, k, v, window)),
                     argnums=(0, 1, 2))(q, k, v)
    got_g = jax.grad(loss(lambda q, k, v: pallas_flash.flash_attention(
        q, k, v, None, True, blocks, blocks, window=window)),
        argnums=(0, 1, 2))(q, k, v)
    for name, x, y in zip(("dq", "dk", "dv"), ref_g, got_g):
        np.testing.assert_allclose(np.asarray(y), np.asarray(x),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_band_grid_gqa_and_segments():
    """Band grid composes with GQA kv fetching and packed-segment masking
    (segments only widen the masked path, same argument as the tri grid)."""
    from burst_attn_tpu.ops.tile import init_state

    seq, blocks, window = 128, 16, 24
    q, k, v, _ = _inputs(seq, seed=11, n_kv=1)  # group=2
    seg = jnp.asarray(
        np.repeat(np.arange(4), seq // 4)[None], jnp.int32)  # 4 docs
    spec = round_spec(jnp.int32(0), jnp.int32(0), seq, seq, True, "contig")
    st = init_state(B, N, seq, D)
    # banded+segmented kernel vs the jnp oracle tile
    ref = tile.tile_fwd(q, k, v, *st, SCALE, spec, window=window,
                        segments=(seg, seg))
    got = pallas_flash.flash_fwd(q, k, v, *st, SCALE, spec,
                                 block_q=blocks, block_kv=blocks,
                                 interpret=True, triangular=True,
                                 window=window, segments=(seg, seg))
    for name, x, y in zip(("m", "lse", "acc"), ref, got):
        np.testing.assert_allclose(np.asarray(y), np.asarray(x),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


@pytest.mark.parametrize("window,bq,bkv,nqb,qp,kp,layout,causal", [
    (24, 16, 16, 8, 0, 0, "contig", True),
    (16, 16, 16, 8, 0, 0, "contig", True),
    (33, 16, 32, 8, 0, 0, "contig", True),   # bkv != bq, unaligned window
    (None, 16, 16, 8, 0, 0, "contig", True),  # no window: degenerates to rect
    (None, 16, 16, 8, 1, 2, "zigzag", True),  # ring round, partial bounds
    (None, 16, 16, 8, 0, 0, "contig", False),
])
def test_fused_bwd_banded_schedule_coverage(window, bq, bkv, nqb, qp, kp,
                                            layout, causal):
    """Pure-python replay of the fused bwd grid schedule (_bwd_fused_iq +
    the kernel's live/clamped/passthrough conditions): every block with
    work is computed EXACTLY once, clamped steps never write dq, and every
    fetched dq block is written at least once per sweep (the aliased-buffer
    flush contract).  Interpret mode cannot check this — it does not model
    the in-place dq aliasing (test_fused_bwd.py validates numerics
    on-chip); this test pins the schedule logic itself."""
    import numpy as np
    from burst_attn_tpu.ops.pallas_flash import (
        _bwd_fused_iq, _block_has_work, bwd_band_nbq,
    )
    from burst_attn_tpu.ops.masks import round_spec

    s_q = s_kv = bq * nqb
    nkb = s_kv // bkv
    spec = round_spec(jnp.int32(qp), jnp.int32(kp), s_q, s_kv, causal, layout)
    sp = np.asarray([int(x) for x in
                     np.asarray(jnp.stack([spec.q_lo, spec.q_hi, spec.kv_hi,
                                           spec.causal, spec.offset]))])

    class SpecRef:  # indexable like the kernel's prefetched scalar ref
        def __getitem__(self, idx):
            return sp[idx]

    spec_ref = SpecRef()
    nbq = bwd_band_nbq(bq, bkv, nqb, window)
    computed = np.zeros((nqb, nkb), int)
    for j in range(nkb):
        fetched, written = set(), set()
        for c in range(nbq):
            iq, clamped = _bwd_fused_iq(spec_ref, j, c, bq, bkv, nqb, window)
            iq, clamped = int(iq), bool(clamped)
            fetched.add(iq)
            live = (not clamped) and bool(
                _block_has_work(spec_ref, iq * bq, j * bkv, bq, bkv, window))
            if live:
                computed[iq, j] += 1
                written.add(iq)
            elif not clamped:  # passthrough write
                written.add(iq)
        assert fetched == written, (j, fetched - written)

    # oracle: which (i, j) blocks contain at least one visible element
    q_lo, q_hi, kv_hi, cz, off = sp
    rows = np.arange(s_q)[:, None]
    cols = np.arange(s_kv)[None, :]
    m = (rows >= q_lo) & (rows < q_hi) & (cols < kv_hi)
    if cz:
        m &= cols <= rows + off
    if window is not None:
        m &= cols > rows + off - window
    want = m.reshape(nqb, bq, nkb, bkv).any(axis=(1, 3)).astype(int)
    np.testing.assert_array_equal(computed, want)


def _check_against_banded_dense(ring, q, k, v, do, window):
    """Values and grads of `ring` against the dense banded oracle, one
    program a side."""
    def both(fn):
        def loss(q, k, v):
            o = fn(q, k, v)
            return jnp.sum(o * do), o

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))(q, k, v)

    (_, ref), gr = both(lambda q, k, v: banded_dense(q, k, v, window))
    (_, got), g = both(ring)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    for name, a, b in zip(("dq", "dk", "dv"), gr, g):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-5, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("window", [1, 40, 100, 160, 1000])
def test_ring_truncation_matches_dense(window):
    """Static round truncation (windowed single contig ring): r_live spans
    1 (window=1: only the own round), 2, 3, and the no-truncation case
    (window >= seq); fwd and grads must match the dense banded oracle
    through every schedule shape, including the dq multi-hop jump."""
    s_total, w_devs = 512, 8
    mesh = Mesh(np.array(jax.devices()[:w_devs]), ("sp",))
    q, k, v, do = _inputs(s_total, seed=17)

    def ring(q, k, v):
        return bat.burst_attn(q, k, v, mesh=mesh, seq_axes=("sp",),
                              causal=True, layout="contig", backend="jnp",
                              window=window)

    _check_against_banded_dense(ring, q, k, v, do, window)


def test_window_double_ring_matches_dense():
    """Windowed contig attention on the 2x4 DOUBLE ring: the static
    truncation declines (non-prefix live set) and the spec_live lax.cond
    carries the dead-round skipping alone — values and grads vs oracle."""
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("inter", "intra"))
    window = 100
    q, k, v, do = _inputs(512, seed=19)

    def ring(q, k, v):
        return bat.burst_attn(q, k, v, mesh=mesh,
                              seq_axes=("inter", "intra"), causal=True,
                              layout="contig", backend="jnp", window=window)

    _check_against_banded_dense(ring, q, k, v, do, window)
