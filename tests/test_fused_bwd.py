"""Fused backward kernel vs split kernels.

The fused dq+dk+dv kernel accumulates dq in place through
input_output_aliasing (ops/pallas_flash.py:_bwd_fused_kernel); its
correctness depends on Mosaic pipeline flush/fetch ordering that interpret
mode does not model, so the `on_tpu` tests self-skip off-TPU
(`BURST_TESTS_TPU=1 python -m pytest tests/test_fused_bwd.py` on the chip).
Shapes cover every mask regime the ring produces (zigzag three-way split,
striped shift, GQA, rectangular KV) — the on-chip analogue of the
reference's all-config sweep (reference test/test_burst.py:239-247).

The tile CONTRACT of a ring round that folds into its carry (`carry`,
`q_range`, `kv_range` of flash_bwd / tile_bwd) is arithmetic, not timing,
and is tested here in interpret mode on the CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from burst_attn_tpu.ops import pallas_flash as pf
from burst_attn_tpu.ops import tile as T
from burst_attn_tpu.ops.masks import (BlockUnits, MaskSpec, full_spec,
                                      round_spec)

on_tpu = pytest.mark.skipif(
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


@on_tpu
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


@on_tpu
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


@on_tpu
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


@on_tpu
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


@on_tpu
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


@on_tpu
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


@on_tpu
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


@on_tpu
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


# ---------------------------------------------------------------------------
# a ring round that folds into its carry: the tile contract, on the CPU


def _round_inputs(n, nkv, s=64, d=32, b=2, seed=31):
    """q-side and kv-side arrays of one half-shard round, a float32 (dk, dv)
    carry of rounds before, and packed-segment ids for both sides."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    f32 = jnp.float32
    q = jax.random.normal(ks[0], (b, n, s, d), f32)
    k = jax.random.normal(ks[1], (b, nkv, s, d), f32)
    v = jax.random.normal(ks[2], (b, nkv, s, d), f32)
    do = jax.random.normal(ks[3], (b, n, s, d), f32)
    # any row statistics do: the round's arithmetic is what is compared
    lse = 3.0 + jax.random.normal(ks[4], (b, n, s), f32)
    delta = jax.random.normal(ks[5], (b, n, s), f32)
    carry = (jax.random.normal(ks[6], (b, nkv, s, d), f32),
             jax.random.normal(ks[7], (b, nkv, s, d), f32))
    seg = jnp.broadcast_to((jnp.arange(s) // 24).astype(jnp.int32), (b, s))
    return do, q, k, v, delta, lse, carry, seg


def _sl(x, rng, axis=2):
    return x if rng is None else jax.lax.slice_in_dim(x, *rng, axis=axis)


RANGES = {"kv_first_half": (None, (0, 32)), "q_second_half": ((32, 64), None)}
BWD_TILES = {
    # fused=True is forced as above: off the chip flash_bwd's own gate takes
    # the split kernels, and the in-place form is the fused kernel's
    "fused_kernel": dict(fused=True),
    "split_kernels": dict(fused=False),
    "jnp_tile": None,
}


@pytest.mark.parametrize("packed", [False, True], ids=["plain", "packed"])
@pytest.mark.parametrize("heads", [(4, 4), (8, 2)], ids=["mha", "gqa4"])
@pytest.mark.parametrize("half", list(RANGES))
@pytest.mark.parametrize("tile", list(BWD_TILES))
def test_round_folds_into_carry(tile, half, heads, packed):
    """flash_bwd / tile_bwd with a carry and a half sub-range equals
    `carry + pad(contribution)` of the sliced call, bit for bit in float32:
    dq is zero outside the q range, dk / dv outside the kv range are the
    carry's bytes.  One call signature for the fused kernel (in place), the
    split kernels and the jnp tile (both the sliced form)."""
    do, q, k, v, delta, lse, carry, seg = _round_inputs(*heads)
    q_range, kv_range = RANGES[half]
    s, scale = q.shape[2], q.shape[3] ** -0.5
    spec = full_spec(32 if q_range else s, 32 if kv_range else s)
    segments = (seg, seg) if packed else None
    kw = BWD_TILES[tile]
    if kw is None:
        run = T.tile_bwd
    else:
        kw = dict(kw, block_q=8, block_kv=8, interpret=True)
        run = lambda *a, **r: pf.flash_bwd(*a, **kw, **r)  # noqa: E731
        assert pf.bwd_folds_carry(
            *heads, s, s, q.shape[3], q_range, kv_range, **kw) == (
                tile == "fused_kernel")

    got = run(do, q, k, v, delta, lse, scale, spec, segments=segments,
              q_range=q_range, kv_range=kv_range, carry=carry)

    # today's sliced call, by hand
    part = run(_sl(do, q_range), _sl(q, q_range), _sl(k, kv_range),
               _sl(v, kv_range), _sl(delta, q_range), _sl(lse, q_range),
               scale, spec,
               segments=(_sl(seg, q_range, 1), _sl(seg, kv_range, 1))
               if packed else None)
    lo_q = q_range[0] if q_range else 0
    lo_kv = kv_range[0] if kv_range else 0
    want_dq = jnp.zeros_like(got[0]).at[:, :, lo_q:lo_q + part[0].shape[2]
                                        ].set(part[0])
    pad_kv = lambda g: jnp.zeros_like(got[1]).at[  # noqa: E731
        :, :, lo_kv:lo_kv + g.shape[2]].set(g)
    want = (want_dq, carry[0] + pad_kv(part[1]), carry[1] + pad_kv(part[2]))
    for name, a, w in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(w), name)
    if kv_range is not None:
        for a, c in zip(got[1:], carry):
            np.testing.assert_array_equal(np.asarray(a[:, :, 32:]),
                                          np.asarray(c[:, :, 32:]))
    if q_range is not None:
        assert not np.asarray(got[0][:, :, :32]).any()


@pytest.mark.parametrize("tile", ["fused_kernel", "split_kernels"])
def test_carry_alone_and_range_alone(tile):
    """The two halves of the contract apart: a carry over the whole arrays
    is the round's gradients added to it; a kv range with NO carry leaves
    exact zeros outside the range."""
    do, q, k, v, delta, lse, carry, _ = _round_inputs(4, 4)
    s, scale = q.shape[2], q.shape[3] ** -0.5
    kw = dict(BWD_TILES[tile], block_q=8, block_kv=8, interpret=True)
    args = (do, q, k, v, delta, lse, scale)
    plain = pf.flash_bwd(*args, full_spec(s, s), **kw)
    carried = pf.flash_bwd(*args, full_spec(s, s), carry=carry, **kw)
    np.testing.assert_array_equal(np.asarray(carried[0]), np.asarray(plain[0]))
    for a, c, p in zip(carried[1:], carry, plain[1:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c + p))
    ranged = pf.flash_bwd(*args, full_spec(s, 32), kv_range=(0, 32), **kw)
    for a in ranged[1:]:
        assert a.shape == k.shape and not np.asarray(a[:, :, 32:]).any()


# ---------------------------------------------------------------------------
# the same contract on the chip, where the in-place forms are Mosaic's to
# schedule: shard 8,192, half 4,096, four q blocks of 1,024 — the shortest
# sweep flash_bwd's own gate admits for the in-place dq (PR 22 met a race
# at a 2-step sweep; the gate is asked on the RANGE's sweep, and this is
# the test that shows it holds there)


def _max_err(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


# ---------------------------------------------------------------------------
# the blocks the causal diagonal cuts, in their live sub-squares (PR 35)

# (kernel, query heads, kv heads, d_qk, d_v, block_q, block_kv, edge, mask
# unit, offset, carry): the triangular kernel (one head a kv head), the
# rectangular one at the cells' head groupings (4 and 8 query heads a kv
# head) with and without the ring's carry; offset 0 and -1 in tokens and in
# blocks of 4 (train_sdar_bd_1x8k's `clean` and `below`); block_kv = block_q
# and 2 x block_q; q and k wider than v (the kanana cell's 192 / 128)
CUT_CASES = [
    ("tri", 2, 2, 16, 16, 32, 64, 16, 1, 0, False),
    ("tri", 2, 2, 16, 16, 32, 64, 8, 1, -1, False),
    ("tri", 2, 2, 16, 16, 64, 64, 16, 1, 0, False),
    ("tri", 2, 2, 16, 16, 64, 64, 32, 1, -1, False),
    ("tri", 2, 2, 192, 128, 32, 64, 16, 1, 0, False),
    ("tri", 2, 2, 192, 128, 64, 64, 32, 1, -1, False),
    # a carry with one head a kv head: the sliced form around the kernel
    ("tri", 2, 2, 16, 16, 32, 64, 16, 1, 0, True),
    ("rect", 4, 1, 16, 16, 32, 64, 16, 1, 0, False),
    ("rect", 4, 1, 16, 16, 32, 64, 16, 4, 0, False),
    ("rect", 4, 1, 16, 16, 32, 64, 8, 4, -1, True),
    ("rect", 8, 1, 16, 16, 32, 64, 32, 1, -1, True),
    ("rect", 8, 1, 16, 16, 64, 64, 16, 4, 0, True),
    ("rect", 8, 2, 16, 16, 64, 64, 32, 1, 0, False),
    ("rect", 4, 1, 24, 16, 32, 64, 16, 1, -1, True),
    ("rect", 8, 1, 192, 128, 32, 64, 16, 4, 0, True),
]


def _cut_inputs(n, n_kv, d, d_v, s, unit, offset, carry):
    ks = jax.random.split(jax.random.PRNGKey(n * 7 + d + unit - offset), 6)
    q = jax.random.normal(ks[0], (1, n, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (1, n_kv, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (1, n_kv, s, d_v), jnp.float32)
    do = jax.random.normal(ks[3], (1, n, s, d_v), jnp.float32)
    nb = jnp.int32(s // unit)
    spec = MaskSpec(jnp.int32(0), nb, nb, jnp.int32(1), jnp.int32(offset))
    window = BlockUnits(unit) if unit != 1 else None
    m, lse, acc = T.tile_fwd(q, k, v, *T.init_state(1, n, s, d_v), d ** -0.5,
                             spec, window=window)
    delta = jnp.sum(T.finalize(m, lse, acc, jnp.float32) * do, -1)
    # offset -1: the first rows see nothing; any finite lse does for them
    lse = jnp.where(jnp.isneginf(lse), 0.0, lse)
    dkv = None
    if carry:
        dkv = (jax.random.normal(ks[4], k.shape), jax.random.normal(
            ks[5], v.shape))
    return (do, q, k, v, delta, lse, d ** -0.5, spec), window, dkv


@pytest.mark.parametrize(
    "kernel,n,n_kv,d,d_v,bq,bkv,edge,unit,offset,carry", CUT_CASES)
def test_cut_blocks_in_sub_squares_against_the_oracle_and_the_whole_tile(
        kernel, n, n_kv, d, d_v, bq, bkv, edge, unit, offset, carry):
    """dq, dk, dv of both fused kernels with the blocks the diagonal cuts
    computed in sub-squares of `edge` (pallas_flash._bwd_cut_tile) against
    the same call with the whole tile on the masked path (`diag_block=0`)
    and against ops/tile.py.  Interpret mode reads the rectangular kernel's
    aliased dq input as the zeros it was handed at EVERY visit, so its dq
    holds each q block's LAST visit alone, which is the block's cut one:
    against the whole tile that is a comparison of the cut blocks' dq rows,
    and no comparison with the oracle (the chip-gated tests hold that)."""
    s = 256
    args, window, dkv = _cut_inputs(n, n_kv, d, d_v, s, unit, offset, carry)
    kw = dict(block_q=bq, block_kv=bkv, interpret=True, fused=True,
              triangular=True, window=window)
    assert pf._bwd_kernel_of(n, n_kv, s, s, d, d_v=d_v, **kw) == kernel
    assert pf.bwd_diag_path(
        n, n_kv, s, s, d, d_v=d_v, diag_block=edge, **kw) == (
        "sub", s // bq, edge)
    got = pf.flash_bwd(*args, carry=dkv, diag_block=edge, **kw)
    whole = pf.flash_bwd(*args, carry=dkv, diag_block=0, **kw)
    want = T.tile_bwd(*args, window=window, carry=dkv)
    for name, a, b, c in zip(("dq", "dk", "dv"), got, whole, want):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5, err_msg=name)
        if name != "dq" or kernel == "tri":
            np.testing.assert_allclose(a, c, rtol=1e-4, atol=1e-4,
                                       err_msg=name + " against the oracle")


@pytest.mark.parametrize("ranges", [((128, 256), (0, 128)),
                                    ((128, 256), (128, 256)), (None, None)])
def test_cut_blocks_of_a_round_on_ranges_are_local_to_the_ranges(ranges):
    """No caller promises `triangular` on a sub-range round, but flash_bwd
    takes one: the rectangular kernel runs it in place (block offsets in the
    index maps, the spec local to the ranges), and which blocks the diagonal
    cuts is local to the ranges too.  dk, dv against the sliced form."""
    q_range, kv_range = ranges
    args, _, dkv = _cut_inputs(4, 1, 16, 16, 256, 1, 0, True)
    nb = jnp.int32(128 if q_range else 256)
    spec = MaskSpec(jnp.int32(0), nb, nb, jnp.int32(1), jnp.int32(0))
    args = (*args[:7], spec)
    kw = dict(block_q=32, block_kv=64, interpret=True, fused=True,
              triangular=True)
    rng = dict(q_range=q_range, kv_range=kv_range)
    assert pf.bwd_folds_carry(4, 1, 256, 256, 16, q_range, kv_range, **kw)
    assert pf.bwd_diag_path(4, 1, 256, 256, 16, diag_block=16, **rng,
                            **kw).path == "sub"
    got = pf.flash_bwd(*args, diag_block=16, carry=dkv, **rng, **kw)
    want = T.tile_bwd(*args, q_range=q_range, kv_range=kv_range, carry=dkv)
    for name, a, b in zip(("dk", "dv"), got[1:], want[1:]):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4, err_msg=name)


def test_the_cut_blocks_run_under_the_kernels_own_names_and_grids():
    """The benchmark's readers join on `burst_flash_bwd_tri` / `_rect`, and
    the in-place dq's argument rests on the grid and the index maps: a call
    on the sub path has the whole tile's name, grid and block mappings."""
    from burst_attn_tpu.analysis.jaxpr_tools import iter_eqns

    def calls(n_kv, edge):
        args, window, _ = _cut_inputs(4, n_kv, 16, 16, 256, 1, 0, False)
        jaxpr = jax.make_jaxpr(lambda *xs: pf.flash_bwd(
            *xs, *args[6:], block_q=32, block_kv=64, interpret=True,
            fused=True, triangular=True, diag_block=edge))(*args[:6])
        return [(e.params["name"], e.params["grid_mapping"].grid,
                 str(e.params["grid_mapping"].block_mappings),
                 e.params["input_output_aliases"])
                for e in iter_eqns(jaxpr) if e.primitive.name == "pallas_call"]

    for n_kv, name in ((4, "burst_flash_bwd_tri"), (1, "burst_flash_bwd_rect")):
        sub, whole = calls(n_kv, 16), calls(n_kv, 0)
        assert [c[0] for c in sub] == [name]
        assert sub == whole


@on_tpu
@pytest.mark.parametrize("half", ["kv_first_half", "q_second_half"])
def test_round_in_place_matches_sliced_on_tpu(half):
    b, n, s, d = 1, 4, 8192, 128
    h = s // 2
    q_range, kv_range = {"kv_first_half": (None, (0, h)),
                         "q_second_half": ((h, s), None)}[half]
    ks = jax.random.split(jax.random.PRNGKey(26), 8)
    dt = jnp.bfloat16
    q = jax.random.normal(ks[0], (b, n, s, d), dt)
    k = jax.random.normal(ks[1], (b, n, s, d), dt)
    v = jax.random.normal(ks[2], (b, n, s, d), dt)
    do = jax.random.normal(ks[3], (b, n, s, d), dt)
    carry = (jax.random.normal(ks[4], (b, n, s, d), jnp.float32),
             jax.random.normal(ks[5], (b, n, s, d), jnp.float32))
    scale = d ** -0.5
    spec = full_spec(h if q_range else s, h if kv_range else s)
    blocks = dict(block_q=1024, block_kv=1024)

    # forward: a state left by the own-partition round, then this round
    st = pf.flash_fwd(q, k, v, None, None, None, scale,
                      round_spec(jnp.int32(0), jnp.int32(0), s, s, True,
                                 "contig"), **blocks)
    assert pf.fwd_covers_ranges(s, s, q_range, kv_range, **blocks)
    got = pf.flash_fwd(q, k, v, *st, scale, spec, q_range=q_range,
                       kv_range=kv_range, **blocks)
    part = pf.flash_fwd(_sl(q, q_range), _sl(k, kv_range), _sl(v, kv_range),
                        *(_sl(x, q_range) for x in st), scale, spec, **blocks)
    lo = q_range[0] if q_range else 0
    errs = {}
    for name, a, c, p_ in zip(("m", "lse", "acc"), got, st, part):
        want = c.at[:, :, lo:lo + p_.shape[2]].set(p_)
        errs["fwd_" + name] = _max_err(jnp.nan_to_num(a, neginf=-1e30),
                                       jnp.nan_to_num(want, neginf=-1e30))
    m, lse, acc = got
    delta = jnp.sum(T.finalize(m, lse, acc, jnp.float32)
                    * do.astype(jnp.float32), axis=-1)

    # backward: the gate takes the in-place kernel on the range's own sweep
    assert pf.bwd_folds_carry(n, n, s, s, d, q_range, kv_range, **blocks)
    args = (do, q, k, v, delta, lse, scale, spec)
    got = pf.flash_bwd(*args, q_range=q_range, kv_range=kv_range, carry=carry,
                       **blocks)
    # the sliced form through the SPLIT kernels: no in-place dq, no alias
    sliced = T.bwd_on_ranges(
        lambda *a, segments: pf.flash_bwd(*a, fused=False, **blocks),
        *args, q_range=q_range, kv_range=kv_range, carry=carry)
    for name, a, w in zip(("dq", "dk", "dv"), got, sliced):
        errs["bwd_" + name] = _max_err(a, w)
    print("\nPARITY", half, errs)
    assert max(errs[k_] for k_ in ("fwd_m", "fwd_lse", "fwd_acc")) < 1e-5, errs
    assert max(errs[k_] for k_ in ("bwd_dq", "bwd_dk", "bwd_dv")) < 1e-3, errs
    if kv_range is not None:  # the carry's bytes outside the range
        for a, c in zip(got[1:], carry):
            assert bool(jnp.all(a[:, :, h:] == c[:, :, h:]))
    else:
        assert not bool(jnp.any(got[0][:, :, :h]))


@on_tpu
@pytest.mark.parametrize("rows,heads,kv_heads,unit,offset,carry,bq,bkv", [
    (8192, 8, 2, 1, 0, False, 1024, 2048),  # train_mistral's grouping
    (8192, 8, 1, 4, -1, True, 1024, 2048),  # train_sdar_bd_1x8k's `below`
    # the gate's edge, a sweep of 2 q blocks x 2 heads: q block 1 is
    # written in kv block 0's sweep and read again, cut, four steps later
    (2048, 2, 1, 1, 0, False, 1024, 1024),
    (4096, 2, 1, 1, -1, False, 1024, 2048),  # 4 x 2 steps, two cut a sweep
])
def test_cut_blocks_in_place_dq_matches_split_on_tpu(
        rows, heads, kv_heads, unit, offset, carry, bq, bkv):
    """The rectangular kernel with its cut blocks in sub-squares (PR 35)
    adds dq in place exactly as before: one read and one store a visit, the
    parent's grid, index maps and order.  Against the split kernels (no
    alias), at a shard of 8,192 rows in the row's tiles and at the gate's
    edge, where the separation between a block's write and its next read is
    the shortest the gate admits."""
    d = 128
    ks = jax.random.split(jax.random.PRNGKey(35), 6)
    dt = jnp.bfloat16
    q, do = (jax.random.normal(k_, (1, heads, rows, d), dt) for k_ in ks[:2])
    k, v = (jax.random.normal(k_, (1, kv_heads, rows, d), dt)
            for k_ in ks[2:4])
    nb = jnp.int32(rows // unit)
    spec = MaskSpec(jnp.int32(0), nb, nb, jnp.int32(1), jnp.int32(offset))
    window = BlockUnits(unit) if unit != 1 else None
    scale = d ** -0.5
    dkv = None
    if carry:
        dkv = tuple(jax.random.normal(k_, (1, kv_heads, rows, d), jnp.float32)
                    for k_ in ks[4:])
    blocks = dict(block_q=bq, block_kv=bkv)
    m, lse, acc = pf.flash_fwd(q, k, v, None, None, None, scale, spec,
                               block_q=1024, block_kv=1024, window=window)
    delta = jnp.sum(T.finalize(m, lse, acc, jnp.float32)
                    * do.astype(jnp.float32), axis=-1)
    lse = jnp.where(jnp.isneginf(lse), 0.0, lse)
    args = (do, q, k, v, delta, lse, scale, spec)
    kw = dict(triangular=True, window=window, carry=dkv, **blocks)
    assert pf.bwd_diag_path(heads, kv_heads, rows, rows, d, triangular=True,
                            window=window, **blocks).path == "sub"
    assert "burst_flash_bwd_rect" in str(jax.make_jaxpr(
        lambda *a: pf.flash_bwd(*a, scale, spec, **kw))(*args[:6]))
    got = pf.flash_bwd(*args, **kw)
    split = pf.flash_bwd(*args, fused=False, **kw)
    whole = pf.flash_bwd(*args, diag_block=0, **kw)
    errs = {}
    for name, a, b_, c in zip(("dq", "dk", "dv"), got, split, whole):
        errs[name] = (_max_err(a, b_), _max_err(a, c))
    print("\nPARITY cut blocks in place,", rows, heads, kv_heads, unit,
          offset, carry, "(vs split, vs whole tile):", errs)
    assert max(e for pair in errs.values() for e in pair) < 2e-3, errs


@on_tpu
def test_ring_sp4_equals_one_chip_on_tpu():
    """The ring over sp=4 at 4 x 8,192 against the same call on one chip:
    both half-shard branches run on some chip in every round, through the
    in-place kernels (the benchmark's own parity at 8,192 tokens is 2,048 a
    shard, where the gate takes the split kernels)."""
    import burst_attn_tpu as bat
    from burst_attn_tpu import obs
    from jax.sharding import Mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs four chips")
    b, n, s, d = 1, 8, 4 * 8192, 128
    ks = jax.random.split(jax.random.PRNGKey(27), 4)
    q, k, v, do = (jax.random.normal(x, (b, n, s, d), jnp.bfloat16)
                   for x in ks)

    def grads(world):
        mesh = Mesh(np.array(jax.devices()[:world]), ("sp",))
        lay = lambda x: bat.layouts.to_layout(x, "zigzag", world, 2)  # noqa: E731
        unlay = lambda x: bat.layouts.from_layout(x, "zigzag", world, 2)  # noqa: E731

        def loss(q, k, v):
            o = bat.burst_attn(lay(q), lay(k), lay(v), mesh=mesh, causal=True,
                               layout="zigzag", backend="auto")
            return jnp.sum(unlay(o).astype(jnp.float32)
                           * do.astype(jnp.float32)), o

        (_, o), g = jax.jit(jax.value_and_grad(loss, (0, 1, 2),
                                               has_aux=True))(q, k, v)
        return (unlay(o),) + g

    counter = obs.counter("burst.inplace_rounds")
    before = {p_: counter.get(**{"pass": p_, "path": "kernel"})
              for p_ in ("fwd", "bwd")}
    ring = grads(4)
    rounds = {p_: counter.get(**{"pass": p_, "path": "kernel"}) - before[p_]
              for p_ in ("fwd", "bwd")}
    one = [jax.device_get(x) for x in grads(1)]
    errs = {name: _max_err(jnp.asarray(jax.device_get(a)), jnp.asarray(w))
            for name, a, w in zip(("o", "dq", "dk", "dv"), ring, one)}
    print("\nRING_PARITY", errs, rounds)
    assert rounds == {"fwd": 3, "bwd": 3}
    assert errs["o"] < 1e-2 and max(
        errs[x] for x in ("dq", "dk", "dv")) < 3e-2, errs
