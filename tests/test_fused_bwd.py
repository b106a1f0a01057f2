"""Fused backward kernel vs split kernels — REAL TPU only.

The fused dq+dk+dv kernel accumulates dq in place through
input_output_aliasing (ops/pallas_flash.py:_bwd_fused_kernel); its
correctness depends on Mosaic pipeline flush/fetch ordering that interpret
mode does not model, so this test self-skips off-TPU.  Shapes cover every
mask regime the ring produces (zigzag three-way split, striped shift, GQA,
rectangular KV) — the on-chip analogue of the reference's all-config sweep
(reference test/test_burst.py:239-247).
"""

import jax
import jax.numpy as jnp
import pytest

from burst_attn_tpu.ops import pallas_flash as pf
from burst_attn_tpu.ops import tile as T
from burst_attn_tpu.ops.masks import round_spec

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu", reason="fused bwd kernel is TPU-only"
)

CASES = [
    # name, b, n, nkv, sq, skv, causal, layout, q_part, kv_part
    ("noncausal", 2, 4, 4, 4096, 4096, False, "contig", 0, 0),
    ("causal_diag", 2, 4, 4, 4096, 4096, True, "contig", 0, 0),
    ("zigzag_eq", 1, 4, 4, 4096, 4096, True, "zigzag", 1, 1),
    ("zigzag_kv_past", 1, 4, 4, 4096, 4096, True, "zigzag", 2, 1),
    ("zigzag_kv_future", 1, 4, 4, 4096, 4096, True, "zigzag", 1, 2),
    ("striped_shift", 1, 4, 4, 4096, 4096, True, "striped", 1, 2),
    ("gqa_g4", 1, 8, 2, 4096, 4096, True, "contig", 0, 0),
    ("rect_kv_half", 1, 4, 4, 4096, 2048, False, "contig", 0, 0),
]


@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_fused_matches_split(case):
    _, b, n, nkv, sq, skv, causal, layout, qp, kp = case
    bq = bkv = 512
    key = jax.random.PRNGKey(42)
    ks = jax.random.split(key, 4)
    dt = jnp.bfloat16
    q = jax.random.normal(ks[0], (b, n, sq, 128), dt)
    k = jax.random.normal(ks[1], (b, nkv, skv, 128), dt)
    v = jax.random.normal(ks[2], (b, nkv, skv, 128), dt)
    do = jax.random.normal(ks[3], (b, n, sq, 128), dt)
    spec = round_spec(jnp.int32(qp), jnp.int32(kp), sq, skv, causal, layout)
    scale = 128**-0.5

    m0, lse0, acc0 = T.init_state(b, n, sq, 128)
    m, lse, acc = pf.flash_fwd(q, k, v, m0, lse0, acc0, scale, spec,
                               block_q=bq, block_kv=bkv)
    o = T.finalize(m, lse, acc, q.dtype)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)

    args = (do, q, k, v, delta, lse, scale, spec)
    split = pf.flash_bwd(*args, block_q=bq, block_kv=bkv, fused=False)
    fused = pf.flash_bwd(*args, block_q=bq, block_kv=bkv, fused=True)
    for name, a, b_ in zip(("dq", "dk", "dv"), split, fused):
        err = float(jnp.max(jnp.abs(a - b_)))
        assert err < 1e-3, f"{name} max abs err {err}"


@pytest.mark.parametrize("block_q,block_kv", [(512, 512), (256, 512)])
def test_triangular_matches_rect_on_tpu(block_q, block_kv):
    """Wrapped-diagonal causal grids (fwd triangular + bwd tri kernel) vs the
    rectangular grids, on-chip: the tri paths rely on revisited-output-buffer
    residency that interpret mode does not model."""
    b, n, s, d = 1, 4, 4096, 128
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 4)
    dt = jnp.bfloat16
    q = jax.random.normal(ks[0], (b, n, s, d), dt)
    k = jax.random.normal(ks[1], (b, n, s, d), dt)
    v = jax.random.normal(ks[2], (b, n, s, d), dt)
    do = jax.random.normal(ks[3], (b, n, s, d), dt)
    spec = round_spec(jnp.int32(0), jnp.int32(0), s, s, True, "contig")
    scale = d**-0.5

    m0, lse0, acc0 = T.init_state(b, n, s, d)
    rect = pf.flash_fwd(q, k, v, m0, lse0, acc0, scale, spec,
                        block_q=block_q, block_kv=block_q)
    tri = pf.flash_fwd(q, k, v, m0, lse0, acc0, scale, spec,
                       block_q=block_q, block_kv=block_q, triangular=True)
    for name, a, b_ in zip(("m", "lse", "acc"), rect, tri):
        err = float(jnp.max(jnp.abs(a - b_)))
        assert err < 1e-3, f"fwd {name} max abs err {err}"

    m, lse, acc = rect
    o = T.finalize(m, lse, acc, q.dtype)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    args = (do, q, k, v, delta, lse, scale, spec)
    rect_b = pf.flash_bwd(*args, block_q=block_q, block_kv=block_kv, fused=True)
    tri_b = pf.flash_bwd(*args, block_q=block_q, block_kv=block_kv,
                         triangular=True)
    for name, a, b_ in zip(("dq", "dk", "dv"), rect_b, tri_b):
        err = float(jnp.max(jnp.abs(a - b_)))
        assert err < 1e-3, f"bwd {name} max abs err {err}"


def test_segments_on_tpu():
    """Packed-sequence masking at production tile sizes, on-chip: fp32
    oracle comparison of flash_attention(segment_ids=...) fwd + grads.  The
    seg-id block specs ((1, bq, 1) / (1, 1, bkv)) only satisfy Mosaic's
    lane tiling at real block sizes, which interpret-mode tests don't
    exercise (tests/test_segments.py covers the numerics at small shapes)."""
    b, n, s, d = 1, 4, 4096, 128
    ks = jax.random.split(jax.random.PRNGKey(13), 4)
    dt = jnp.bfloat16
    q = jax.random.normal(ks[0], (b, n, s, d), dt)
    k = jax.random.normal(ks[1], (b, n, s, d), dt)
    v = jax.random.normal(ks[2], (b, n, s, d), dt)
    do = jax.random.normal(ks[3], (b, n, s, d), dt)
    # three documents, boundaries off the block grid
    seg = jnp.concatenate([
        jnp.zeros((b, 1000), jnp.int32),
        jnp.ones((b, 1500), jnp.int32),
        jnp.full((b, s - 2500), 2, jnp.int32),
    ], axis=1)

    def loss(fn):
        return jax.grad(
            lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32)
                                    * do.astype(jnp.float32)),
            argnums=(0, 1, 2))

    o = pf.flash_attention(q, k, v, None, True, 512, 512, segment_ids=seg)
    o_ref = T.single_device_attention(q, k, v, causal=True, segment_ids=seg)
    assert float(jnp.max(jnp.abs(o.astype(jnp.float32)
                                 - o_ref.astype(jnp.float32)))) < 4e-2
    g = loss(lambda q, k, v: pf.flash_attention(
        q, k, v, None, True, 512, 512, segment_ids=seg))(q, k, v)
    g_ref = loss(lambda q, k, v: T.single_device_attention(
        q, k, v, causal=True, segment_ids=seg))(q, k, v)
    for name, a, b_ in zip(("dq", "dk", "dv"), g, g_ref):
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - b_.astype(jnp.float32))))
        assert err < 5e-2, f"{name} max abs err {err}"


@pytest.mark.parametrize("window", [512, 1024, 2048, 3000])
def test_fused_banded_window_bwd_matches_split(window):
    """The window-banded fused sweep (grid dim 3 = nbq*group instead of
    nqb*group, _bwd_fused_iq) vs the split kernels, production tiles.
    Covers block-aligned and unaligned windows.

    The fused kernel is forced only where flash_bwd's own gate admits it
    (a sweep of >= 4 steps): its in-place dq accumulation is not race-free
    under that.  Measured on the v5e (PR 22, libtpu 0.0.34): forced at a
    2-step sweep of 512x512 blocks (window=512) dq is off by 0.07 while
    dk/dv are exact; 3-step sweeps and 256x256 blocks agree to 1e-7.  Below
    the gate the default dispatch must BE the split pair."""
    b, n, s, d = 1, 4, 4096, 128
    ks = jax.random.split(jax.random.PRNGKey(21), 4)
    dt = jnp.bfloat16
    q = jax.random.normal(ks[0], (b, n, s, d), dt)
    k = jax.random.normal(ks[1], (b, n, s, d), dt)
    v = jax.random.normal(ks[2], (b, n, s, d), dt)
    do = jax.random.normal(ks[3], (b, n, s, d), dt)
    spec = round_spec(jnp.int32(0), jnp.int32(0), s, s, True, "contig")
    scale = d**-0.5
    m0, lse0, acc0 = T.init_state(b, n, s, d)
    m, lse, acc = pf.flash_fwd(q, k, v, m0, lse0, acc0, scale, spec,
                               block_q=512, block_kv=512, window=window)
    o = T.finalize(m, lse, acc, q.dtype)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    args = (do, q, k, v, delta, lse, scale, spec)
    split = pf.flash_bwd(*args, block_q=512, block_kv=512, fused=False,
                         window=window)
    gated_in = pf.bwd_band_nbq(512, 512, s // 512, window) >= 4
    assert gated_in == (window >= 2048)
    other = pf.flash_bwd(*args, block_q=512, block_kv=512, window=window,
                         fused=True if gated_in else None)
    for name, a, b_ in zip(("dq", "dk", "dv"), split, other):
        err = float(jnp.max(jnp.abs(a - b_)))
        assert err < (1e-3 if gated_in else 1e-9), f"{name} max abs err {err}"


def test_fused_segments_bwd_matches_split():
    """Packed-segment masking through the FUSED kernel (seg tiles ride the
    masked path) vs the split kernels, production tiles + GQA."""
    b, n, nkv, s, d = 1, 8, 2, 4096, 128
    ks = jax.random.split(jax.random.PRNGKey(22), 4)
    dt = jnp.bfloat16
    q = jax.random.normal(ks[0], (b, n, s, d), dt)
    k = jax.random.normal(ks[1], (b, nkv, s, d), dt)
    v = jax.random.normal(ks[2], (b, nkv, s, d), dt)
    do = jax.random.normal(ks[3], (b, n, s, d), dt)
    seg = jnp.concatenate([
        jnp.zeros((b, 900), jnp.int32),
        jnp.ones((b, 1600), jnp.int32),
        jnp.full((b, s - 2500), 2, jnp.int32),
    ], axis=1)
    spec = round_spec(jnp.int32(0), jnp.int32(0), s, s, True, "contig")
    scale = d**-0.5
    m0, lse0, acc0 = T.init_state(b, n, s, d)
    m, lse, acc = pf.flash_fwd(q, k, v, m0, lse0, acc0, scale, spec,
                               block_q=512, block_kv=512,
                               segments=(seg, seg))
    o = T.finalize(m, lse, acc, q.dtype)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    args = (do, q, k, v, delta, lse, scale, spec)
    split = pf.flash_bwd(*args, block_q=512, block_kv=512, fused=False,
                         segments=(seg, seg))
    fused = pf.flash_bwd(*args, block_q=512, block_kv=512, fused=True,
                         segments=(seg, seg))
    for name, a, b_ in zip(("dq", "dk", "dv"), split, fused):
        err = float(jnp.max(jnp.abs(a - b_)))
        assert err < 1e-3, f"{name} max abs err {err}"


def test_tri_segments_bwd_matches_split():
    """Packed segments through the WRAPPED-DIAGONAL bwd kernel (seg only
    narrows the fast path, same as the fwd tri grid) vs split kernels."""
    b, n, s, d = 1, 4, 4096, 128
    ks = jax.random.split(jax.random.PRNGKey(23), 4)
    dt = jnp.bfloat16
    q = jax.random.normal(ks[0], (b, n, s, d), dt)
    k = jax.random.normal(ks[1], (b, n, s, d), dt)
    v = jax.random.normal(ks[2], (b, n, s, d), dt)
    do = jax.random.normal(ks[3], (b, n, s, d), dt)
    seg = jnp.concatenate([
        jnp.zeros((b, 700), jnp.int32),
        jnp.ones((b, 1800), jnp.int32),
        jnp.full((b, s - 2500), 2, jnp.int32),
    ], axis=1)
    spec = round_spec(jnp.int32(0), jnp.int32(0), s, s, True, "contig")
    scale = d**-0.5
    m0, lse0, acc0 = T.init_state(b, n, s, d)
    m, lse, acc = pf.flash_fwd(q, k, v, m0, lse0, acc0, scale, spec,
                               block_q=512, block_kv=512,
                               segments=(seg, seg))
    o = T.finalize(m, lse, acc, q.dtype)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    args = (do, q, k, v, delta, lse, scale, spec)
    split = pf.flash_bwd(*args, block_q=512, block_kv=512, fused=False,
                         segments=(seg, seg))
    tri = pf.flash_bwd(*args, block_q=512, block_kv=512, triangular=True,
                       segments=(seg, seg))
    assert pf.tri_bwd_supported(s, s, n, n, d, block_q=512, block_kv=512)
    for name, a, b_ in zip(("dq", "dk", "dv"), split, tri):
        err = float(jnp.max(jnp.abs(a - b_)))
        assert err < 1e-3, f"{name} max abs err {err}"


def test_tall_q_and_empty_carry_on_tpu():
    """Round-4 fwd paths on real Mosaic: the tall-q tri grid (block_q =
    r*block_kv) and the statically-empty carry (no state inputs at all)
    against the square carried grid.  Interpret mode cannot validate the
    dropped-input block plumbing or the r-wide diagonal's revisit
    residency at real tile sizes."""
    b, n, s, d = 1, 4, 4096, 128
    ks = jax.random.split(jax.random.PRNGKey(21), 3)
    dt = jnp.bfloat16
    q = jax.random.normal(ks[0], (b, n, s, d), dt)
    k = jax.random.normal(ks[1], (b, n, s, d), dt)
    v = jax.random.normal(ks[2], (b, n, s, d), dt)
    spec = round_spec(jnp.int32(0), jnp.int32(0), s, s, True, "contig")
    scale = d**-0.5

    m0, lse0, acc0 = T.init_state(b, n, s, d)
    base = pf.flash_fwd(q, k, v, m0, lse0, acc0, scale, spec,
                        block_q=512, block_kv=512, triangular=True)
    tall = pf.flash_fwd(q, k, v, m0, lse0, acc0, scale, spec,
                        block_q=1024, block_kv=256, triangular=True)
    empty = pf.flash_fwd(q, k, v, None, None, None, scale, spec,
                         block_q=1024, block_kv=256, triangular=True)
    # m, lse and the normalized output compare across block shapes; the
    # unnormalized acc does not: p is rounded to bf16 against a running max
    # that depends on the block width, and |acc| reaches 38 here.  Measured
    # on the v5e (PR 22): acc differs by 3.6e-2 between ANY two block widths
    # (256x256 vs 512x512 too), o by 8.3e-4, and every variant is the same
    # 6.6e-3 from the float32 reference.
    def outputs(state):
        m, lse, acc = state
        return m, lse, T.finalize(m, lse, acc, jnp.float32)

    for name, a, b_ in zip(("m", "lse", "o"), outputs(base), outputs(tall)):
        err = float(jnp.max(jnp.abs(a - b_)))
        assert err < (4e-3 if name == "o" else 1e-3), \
            f"tall {name} max abs err {err}"
    # the same blocks with no carried state: the same arithmetic
    for name, a, b_ in zip(("m", "lse", "acc"), tall, empty):
        err = float(jnp.max(jnp.abs(a - b_)))
        assert err < 1e-4, f"empty-carry {name} max abs err {err}"


def test_bwd_loop_sweep_on_tpu():
    """The tri backward's fori_loop sweep on real Mosaic: its dynamic-offset
    scratch stores (dv_scr/dk_scr at traced sub-block rows) have no
    interpret-mode legality analogue — this is the compile-and-numerics
    gate the multi-hour loop sweep depends on."""
    b, n, s, d = 1, 2, 4096, 128
    ks = jax.random.split(jax.random.PRNGKey(23), 4)
    dt = jnp.bfloat16
    q = jax.random.normal(ks[0], (b, n, s, d), dt)
    k = jax.random.normal(ks[1], (b, n, s, d), dt)
    v = jax.random.normal(ks[2], (b, n, s, d), dt)
    do = jax.random.normal(ks[3], (b, n, s, d), dt)
    spec = round_spec(jnp.int32(0), jnp.int32(0), s, s, True, "contig")
    scale = d**-0.5
    m0, lse0, acc0 = T.init_state(b, n, s, d)
    m, lse, acc = pf.flash_fwd(q, k, v, m0, lse0, acc0, scale, spec,
                               block_q=512, block_kv=512)
    o = T.finalize(m, lse, acc, q.dtype)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    args = (do, q, k, v, delta, lse, scale, spec)
    # the gate must hold or both sides silently compile the rectangular
    # kernel (which ignores loop_sweep) and the A/B is vacuous
    assert pf.tri_bwd_supported(s, s, n, n, d, block_q=512, block_kv=1024,
                                block_kv_compute=512)
    kw = dict(block_q=512, block_kv=1024, block_kv_compute=512,
              triangular=True, fused=True)
    base = pf.flash_bwd(*args, **kw)
    loop = pf.flash_bwd(*args, loop_sweep=True, **kw)
    for name, a, b_ in zip(("dq", "dk", "dv"), base, loop):
        err = float(jnp.max(jnp.abs(a - b_)))
        assert err < 1e-3, f"loop {name} max abs err {err}"
