"""Pallas kernel family vs the jnp oracle tile, in interpret mode on CPU —
the TPU build's analogue of validating lao.py's Triton kernels against the
pure-torch tile (reference burst_utils.py:42-148); run per ring-round mask
spec, with carry-in state, GQA, and both backward kernels."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from burst_attn_tpu.analysis.jaxpr_tools import iter_eqns
from burst_attn_tpu.ops import pallas_flash, tile
from burst_attn_tpu.ops.masks import full_spec, round_spec
from burst_attn_tpu.ops.reference import dense_attention

B, N, NK, S, D = 2, 4, 2, 64, 32
SCALE = D**-0.5


@pytest.fixture(scope="module")
def qkv():
    q = jax.random.normal(jax.random.PRNGKey(0), (B, N, S, D), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, NK, S, D), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, NK, S, D), jnp.float32)
    do = jax.random.normal(jax.random.PRNGKey(3), (B, N, S, D), jnp.float32)
    return q, k, v, do


CASES = [
    ("contig", 1, 1, True),
    ("zigzag", 2, 1, True),
    ("zigzag", 1, 2, True),
    ("striped", 1, 2, True),
    ("striped", 2, 1, True),
    ("contig", 0, 0, False),
    ("contig", 0, 1, True),  # fully masked round
]


@pytest.mark.parametrize("layout,qp,kp,causal", CASES)
def test_fwd_and_carry_matches_tile(qkv, layout, qp, kp, causal):
    q, k, v, _ = qkv
    spec = round_spec(jnp.int32(qp), jnp.int32(kp), S, S, causal, layout)
    st = tile.init_state(B, N, S, D)
    ref = tile.tile_fwd(q, k, v, *st, SCALE, spec)
    got = pallas_flash.flash_fwd(
        q, k, v, *st, SCALE, spec, block_q=16, block_kv=16, interpret=True,
        cast_p=False,
    )
    for name, x, y in zip(("m", "lse", "acc"), ref, got):
        np.testing.assert_allclose(y, x, rtol=1e-4, atol=1e-4, err_msg=name)

    # second ring round continues the online softmax from carried state
    spec2 = round_spec(jnp.int32(qp), jnp.int32(qp), S, S, causal, layout)
    ref2 = tile.tile_fwd(q, k, v, *ref, SCALE, spec2)
    got2 = pallas_flash.flash_fwd(
        q, k, v, *got, SCALE, spec2, block_q=16, block_kv=16, interpret=True,
        cast_p=False,
    )
    for name, x, y in zip(("m", "lse", "acc"), ref2, got2):
        np.testing.assert_allclose(y, x, rtol=1e-4, atol=1e-4, err_msg=f"carry {name}")


@pytest.mark.parametrize("layout,qp,kp,causal", CASES)
def test_bwd_matches_tile(qkv, layout, qp, kp, causal):
    q, k, v, do = qkv
    spec = round_spec(jnp.int32(qp), jnp.int32(kp), S, S, causal, layout)
    # final state over two rounds so lse is a true multi-round lse
    st = tile.init_state(B, N, S, D)
    st = tile.tile_fwd(q, k, v, *st, SCALE, spec)
    spec_self = round_spec(jnp.int32(qp), jnp.int32(qp), S, S, causal, layout)
    m, lse, acc = tile.tile_fwd(q, k, v, *st, SCALE, spec_self)
    o = tile.finalize(m, lse, acc, q.dtype)
    delta = jnp.sum(o * do, axis=-1)

    ref = tile.tile_bwd(do, q, k, v, delta, lse, SCALE, spec)
    got = pallas_flash.flash_bwd(
        do, q, k, v, delta, lse, SCALE, spec, block_q=16, block_kv=16,
        interpret=True,
    )
    for name, x, y in zip(("dq", "dk", "dv"), ref, got):
        np.testing.assert_allclose(y, x, rtol=1e-4, atol=1e-4, err_msg=name)


@pytest.mark.parametrize(
    "block_q,block_kv,block_kv_compute",
    [(16, 32, None), (32, 16, None), (64, 64, None),
     # sub-block pipeline (_fwd_kernel._sweep with n_sub > 1) — the
     # production default is two 1024-wide sub-blocks per 2048 memory block
     (16, 32, 8), (32, 32, 16), (64, 64, 16)],
)
def test_block_shape_independence(qkv, block_q, block_kv, block_kv_compute):
    """Different tilings must give the same numerics (mask/bounds logic)."""
    q, k, v, _ = qkv
    spec = round_spec(jnp.int32(1), jnp.int32(1), S, S, True, "zigzag")
    st = tile.init_state(B, N, S, D)
    ref = tile.tile_fwd(q, k, v, *st, SCALE, spec)
    got = pallas_flash.flash_fwd(
        q, k, v, *st, SCALE, spec, block_q=block_q, block_kv=block_kv,
        block_kv_compute=block_kv_compute, interpret=True, cast_p=False,
    )
    np.testing.assert_allclose(got[2], ref[2], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("block,block_kv_compute", [(16, None), (16, 8), (32, 16)])
def test_triangular_grid_matches_rect(qkv, block, block_kv_compute):
    """The wrapped-diagonal all-live causal grid (flash_fwd triangular=True)
    must reproduce the rectangular grid exactly."""
    q, k, v, _ = qkv
    spec = round_spec(jnp.int32(0), jnp.int32(0), S, S, True, "contig")
    st = tile.init_state(B, N, S, D)
    ref = tile.tile_fwd(q, k, v, *st, SCALE, spec)
    got = pallas_flash.flash_fwd(
        q, k, v, *st, SCALE, spec, block_q=block, block_kv=block,
        block_kv_compute=block_kv_compute, interpret=True, cast_p=False,
        triangular=True,
    )
    for name, x, y in zip(("m", "lse", "acc"), ref, got):
        np.testing.assert_allclose(y, x, rtol=1e-4, atol=1e-4, err_msg=name)


@pytest.mark.parametrize("block_q,block_kv", [(16, 16), (8, 16), (16, 32)])
def test_triangular_bwd_matches_tile(qkv, block_q, block_kv):
    """The wrapped-diagonal causal backward (flash_bwd triangular=True,
    group=1) must match the jnp oracle."""
    q, k, v, do = qkv
    q1, do1 = q[:, :2], do[:, :2]  # group=1: match kv head count
    spec = round_spec(jnp.int32(0), jnp.int32(0), S, S, True, "contig")
    st = tile.init_state(B, NK, S, D)
    m, lse, acc = tile.tile_fwd(q1, k, v, *st, SCALE, spec)
    o = tile.finalize(m, lse, acc, q1.dtype)
    delta = jnp.sum(o * do1, axis=-1)
    ref = tile.tile_bwd(do1, q1, k, v, delta, lse, SCALE, spec)
    got = pallas_flash.flash_bwd(
        do1, q1, k, v, delta, lse, SCALE, spec, block_q=block_q,
        block_kv=block_kv, interpret=True, triangular=True,
    )
    for name, x, y in zip(("dq", "dk", "dv"), ref, got):
        np.testing.assert_allclose(y, x, rtol=1e-4, atol=1e-4, err_msg=name)


def test_burst_no_tri_escape_hatch(qkv, monkeypatch):
    """BURST_NO_TRI=1 must route triangular=True calls onto the rectangular
    grids.  The routing itself is asserted (the tri paths' only coordinate
    helper is made to explode), not just numerics — the two grids produce
    identical results so a numerics check could not catch a routing bug."""
    q, k, v, _ = qkv
    spec = round_spec(jnp.int32(0), jnp.int32(0), S, S, True, "contig")
    st = tile.init_state(B, N, S, D)
    ref = tile.tile_fwd(q, k, v, *st, SCALE, spec)

    def _boom(*a, **k):
        raise AssertionError("triangular path taken despite BURST_NO_TRI")

    monkeypatch.setattr(pallas_flash, "_tri_coords", _boom)
    monkeypatch.setattr(pallas_flash, "_bwd_fused_tri_kernel", _boom)
    # flash_fwd's body is traced once a distinct call (PR 33): the switch is
    # a static keyword of that trace, a patched helper is not
    jax.clear_caches()
    monkeypatch.setenv("BURST_NO_TRI", "1")
    got = pallas_flash.flash_fwd(
        q, k, v, *st, SCALE, spec, block_q=16, block_kv=16, interpret=True,
        cast_p=False, triangular=True,
    )
    np.testing.assert_allclose(got[2], ref[2], rtol=1e-4, atol=1e-4)
    # "0"/"false"/"" mean off -> triangular path runs again
    monkeypatch.setenv("BURST_NO_TRI", "0")
    with pytest.raises(AssertionError, match="triangular path taken"):
        pallas_flash.flash_fwd(
            q, k, v, *st, SCALE, spec, block_q=16, block_kv=16, interpret=True,
            cast_p=False, triangular=True,
        )


def test_block_tuning_table():
    from burst_attn_tpu.ops.tuning import BlockTable, block_defaults
    from burst_attn_tpu.ops.pallas_flash import resolve_blocks

    t = block_defaults()
    assert isinstance(t, BlockTable)

    class FakeDev:
        def __init__(self, kind):
            self.device_kind = kind

    # device-kind matching over the strings real runtimes report
    from burst_attn_tpu.ops import tuning as _tuning

    assert block_defaults(FakeDev("TPU v5 lite")).measured
    assert block_defaults(FakeDev("TPU v5e")).measured
    assert block_defaults(FakeDev("TPU v5p")) is _tuning._TABLE["v5p"]
    # some runtimes report bare "TPU v5" for v5p — must not fall to _DEFAULT
    assert block_defaults(FakeDev("TPU v5")) is _tuning._TABLE["v5p"]
    assert block_defaults(FakeDev("TPU v4")) is _tuning._TABLE["v4"]
    assert not block_defaults(FakeDev("TPU v4")).measured
    # an unknown kind gets the CPU's row off-chip and is an error on a TPU
    weird = FakeDev("weird-accelerator")
    assert not block_defaults(weird).measured
    weird.platform = "tpu"
    with pytest.raises(ValueError, match="no kernel block row"):
        block_defaults(weird)
    assert block_defaults(FakeDev("TPU v6e")) is _tuning._TABLE["v6"]
    assert block_defaults(FakeDev("TPU v6 lite")) is _tuning._TABLE["v6"]
    # resolve_blocks always returns the uniform 5-field shape
    rb = resolve_blocks()
    assert rb == (t.fwd_block_q, t.fwd_block_kv,
                  min(t.bwd_block_q, t.fwd_block_q),
                  min(t.bwd_block_kv, t.fwd_block_kv),
                  min(t.fwd_block_kv_compute, t.fwd_block_kv))

    # the VMEM-cliff clamp is generation-aware: v5e's measured budget must
    # not bind a generation with twice the VMEM (round-2 verdict weak #6)
    v5e = FakeDev("TPU v5 lite")
    v5p = FakeDev("TPU v5p")
    assert _tuning._TABLE["v5p"].fwd_cliff_area == 2 * _tuning._TABLE["v5e"].fwd_cliff_area
    assert _tuning._TABLE["v5p"].bwd_cliff_area == 2 * _tuning._TABLE["v5e"].bwd_cliff_area
    # 2048x4096 fwd: past the v5e cliff (clamped to 2048x2048), inside v5p's
    r_e = resolve_blocks(block_q=2048, block_kv=4096, device=v5e)
    r_p = resolve_blocks(block_q=2048, block_kv=4096, device=v5p)
    assert (r_e.block_q, r_e.block_kv) == (2048, 2048)
    assert (r_p.block_q, r_p.block_kv) == (2048, 4096)
    # bwd likewise: 1024x4096 clamps on v5e, passes on v5p
    r_e = resolve_blocks(block_q_bwd=1024, block_kv_bwd=4096, device=v5e)
    r_p = resolve_blocks(block_q_bwd=1024, block_kv_bwd=4096, device=v5p)
    assert (r_e.block_q_bwd, r_e.block_kv_bwd) == (1024, 2048)
    assert (r_p.block_q_bwd, r_p.block_kv_bwd) == (1024, 4096)
    # unknown kinds fall back to the conservative v5e-measured budgets
    r_u = resolve_blocks(block_q=2048, block_kv=4096,
                         device=FakeDev("weird-accelerator"))
    assert (r_u.block_q, r_u.block_kv) == (2048, 2048)
    # explicit values win; unspecified bwd blocks never exceed the fwd ones;
    # the compute sub-block never exceeds the kv memory block
    assert resolve_blocks(256, 512)[:4] == (256, 512, 256, 512)
    assert resolve_blocks(256, 512).block_kv_compute == 512
    assert resolve_blocks(256, 512, 128, 256)[:4] == (256, 512, 128, 256)
    assert resolve_blocks(block_kv_compute=512).block_kv_compute == 512


@pytest.mark.parametrize("causal,tri,window,segs",
                         [(False, False, None, False),
                          (True, False, None, False),
                          (True, True, None, False),
                          (True, False, 48, False),
                          (True, True, None, True),
                          (True, False, 48, True)])
def test_loop_sweep_matches_unrolled(causal, tri, window, segs):
    """The fori_loop sub-block sweep (loop_sweep=True — the VMEM-cliff
    probe variant) is numerically identical to the unrolled pipeline,
    including its independently-implemented window band and segment
    terms in mask_of."""
    from burst_attn_tpu.ops.masks import full_spec, round_spec
    from burst_attn_tpu.ops.tile import init_state

    b, n, s, d = 1, 2, 128, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k, v = (jax.random.normal(x, (b, n, s, d), jnp.float32) for x in ks)
    spec = round_spec(jnp.int32(0), jnp.int32(0), s, s, causal, "contig")
    st = init_state(b, n, s, d)
    seg = None
    if segs:
        ids = jnp.concatenate([jnp.zeros((b, 50), jnp.int32),
                               jnp.ones((b, s - 50), jnp.int32)], axis=1)
        seg = (ids, ids)
    kw = dict(block_q=32, block_kv=32, block_kv_compute=16, triangular=tri,
              window=window, segments=seg)
    base = pallas_flash.flash_fwd(q, k, v, *st, d**-0.5, spec, **kw)
    got = pallas_flash.flash_fwd(q, k, v, *st, d**-0.5, spec,
                                 loop_sweep=True, **kw)
    for name, a, b_ in zip(("m", "lse", "acc"), base, got):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-6, atol=1e-6, err_msg=name)


def test_cliff_clamp(monkeypatch):
    """Configs past the measured VMEM-cliff area are clamped (kv block
    shrunk at fixed bq); BURST_ALLOW_CLIFF=1 lets sweeps measure them."""
    from burst_attn_tpu.ops.pallas_flash import resolve_blocks
    from burst_attn_tpu.ops.tuning import block_defaults

    monkeypatch.delenv("BURST_ALLOW_CLIFF", raising=False)
    rb = resolve_blocks(2048, 4096)  # the measured fwd cliff config
    assert (rb.block_q, rb.block_kv) == (2048, 2048)
    assert rb.block_kv_compute <= rb.block_kv
    # bwd cliff sits one power of two lower
    rb = resolve_blocks(1024, 2048, 2048, 2048)
    assert (rb.block_q_bwd, rb.block_kv_bwd) == (2048, 1024)
    # defaults are exactly at the budget — never clamped (compare against
    # the raw table row, which bypasses the clamp)
    t = block_defaults()
    assert resolve_blocks()[:2] == (t.fwd_block_q, t.fwd_block_kv)
    rb = resolve_blocks()
    assert (rb.block_q_bwd, rb.block_kv_bwd) == (
        min(t.bwd_block_q, t.fwd_block_q), min(t.bwd_block_kv, t.fwd_block_kv))
    monkeypatch.setenv("BURST_ALLOW_CLIFF", "1")
    rb = resolve_blocks(2048, 4096)
    assert (rb.block_q, rb.block_kv) == (2048, 4096)


@pytest.mark.parametrize("causal", [False, True])
def test_single_device_flash_attention(qkv, causal):
    q, k, v, do = qkv
    o_ref = dense_attention(q, k, v, causal=causal)
    o = pallas_flash.flash_attention(q, k, v, None, causal, 16, 16)
    np.testing.assert_allclose(o, o_ref, rtol=1e-4, atol=1e-4)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * do)

    g_ref = jax.grad(
        loss(lambda q, k, v: dense_attention(q, k, v, causal=causal)),
        argnums=(0, 1, 2),
    )(q, k, v)
    g = jax.grad(
        loss(lambda q, k, v: pallas_flash.flash_attention(q, k, v, None, causal, 16, 16)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for name, x, y in zip(("dq", "dk", "dv"), g_ref, g):
        np.testing.assert_allclose(y, x, rtol=1e-4, atol=1e-4, err_msg=name)


@pytest.mark.parametrize("layout,qp,kp,causal", CASES)
def test_empty_carry_matches_explicit_init_state(qkv, layout, qp, kp, causal):
    """m = lse = acc = None (the statically-empty carry that skips the
    three state inputs and their DMAs entirely) is bit-equivalent to
    passing a fresh init_state explicitly."""
    q, k, v, _ = qkv
    spec = round_spec(jnp.int32(qp), jnp.int32(kp), S, S, causal, layout)
    ref = pallas_flash.flash_fwd(
        q, k, v, *tile.init_state(B, N, S, D), SCALE, spec,
        block_q=16, block_kv=16, interpret=True, cast_p=False)
    got = pallas_flash.flash_fwd(
        q, k, v, None, None, None, SCALE, spec,
        block_q=16, block_kv=16, interpret=True, cast_p=False)
    for name, x, y in zip(("m", "lse", "acc"), ref, got):
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x),
                                      err_msg=name)


def test_empty_carry_emit_o_and_ragged(qkv):
    """The None-carry path composes with emit_o (the fused finalize the
    single-device forward uses) and with ragged pad-and-mask recursion."""
    q, k, v, _ = qkv
    spec = round_spec(jnp.int32(0), jnp.int32(0), S, S, True, "contig")
    st = tile.init_state(B, N, S, D)
    m, lse, acc = tile.tile_fwd(q, k, v, *st, SCALE, spec)
    want = tile.finalize(m, lse, acc, q.dtype)
    _, _, o = pallas_flash.flash_fwd(
        q, k, v, None, None, None, SCALE, spec,
        block_q=16, block_kv=16, interpret=True, cast_p=False, emit_o=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want),
                               rtol=1e-4, atol=1e-4)

    # ragged: S not a block multiple forces the pad-run-slice recursion
    s_r = S - 10
    qr, kr, vr = q[:, :, :s_r], k[:, :, :s_r], v[:, :, :s_r]
    spec_r = round_spec(jnp.int32(0), jnp.int32(0), s_r, s_r, True, "contig")
    str_ = tile.init_state(B, N, s_r, D)
    ref_r = tile.tile_fwd(qr, kr, vr, *str_, SCALE, spec_r)
    got_r = pallas_flash.flash_fwd(
        qr, kr, vr, None, None, None, SCALE, spec_r,
        block_q=16, block_kv=16, interpret=True, cast_p=False)
    for name, x, y in zip(("m", "lse", "acc"), ref_r, got_r):
        np.testing.assert_allclose(np.asarray(y), np.asarray(x),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


@pytest.mark.parametrize("block_q,block_kv,bkc",
                         [(32, 16, None), (32, 8, 8), (16, 8, None),
                          (32, 16, 8)])
@pytest.mark.parametrize("offset", [0, -1])
def test_triangular_tall_q_matches_tile(qkv, block_q, block_kv, bkc, offset):
    """The tall-q generalization of the wrapped-diagonal grid (block_q =
    r * block_kv — same step count, 1/r the K/V streaming traffic) must
    match the oracle at both offsets the ring layouts produce."""
    from burst_attn_tpu.ops.masks import MaskSpec

    q, k, v, _ = qkv
    spec = MaskSpec(jnp.int32(0), jnp.int32(S), jnp.int32(S), jnp.int32(1),
                    jnp.int32(offset))
    st = tile.init_state(B, N, S, D)
    ref = tile.tile_fwd(q, k, v, *st, SCALE, spec)
    got = pallas_flash.flash_fwd(
        q, k, v, *st, SCALE, spec, block_q=block_q, block_kv=block_kv,
        block_kv_compute=bkc, interpret=True, cast_p=False, triangular=True,
    )
    for name, x, y in zip(("m", "lse", "acc"), ref, got):
        np.testing.assert_allclose(y, x, rtol=1e-4, atol=1e-4, err_msg=name)


def test_triangular_tall_q_empty_carry_emit_o(qkv):
    """Tall-q tri grid composed with the single-device fast path flags
    (None carry + fused finalize) — the exact headline-bench configuration
    shape."""
    q, k, v, _ = qkv
    spec = round_spec(jnp.int32(0), jnp.int32(0), S, S, True, "contig")
    st = tile.init_state(B, N, S, D)
    m, lse, acc = tile.tile_fwd(q, k, v, *st, SCALE, spec)
    want = tile.finalize(m, lse, acc, q.dtype)
    _, _, o = pallas_flash.flash_fwd(
        q, k, v, None, None, None, SCALE, spec, block_q=32, block_kv=8,
        interpret=True, cast_p=False, triangular=True, emit_o=True,
    )
    np.testing.assert_allclose(np.asarray(o), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_triangular_tall_q_segments(qkv):
    """Packed segments through the tall-q tri grid: the seg_ok fast-path
    narrowing must compose with the r-wide masked diagonal."""
    q, k, v, _ = qkv
    seg = jnp.concatenate([jnp.zeros((B, S // 4), jnp.int32),
                           jnp.ones((B, S // 4), jnp.int32),
                           jnp.full((B, S // 2), 2, jnp.int32)], axis=1)
    spec = round_spec(jnp.int32(0), jnp.int32(0), S, S, True, "contig")
    st = tile.init_state(B, N, S, D)
    ref = tile.tile_fwd(q, k, v, *st, SCALE, spec, segments=(seg, seg))
    got = pallas_flash.flash_fwd(
        q, k, v, *st, SCALE, spec, block_q=32, block_kv=16, interpret=True,
        cast_p=False, triangular=True, segments=(seg, seg),
    )
    for name, x, y in zip(("m", "lse", "acc"), ref, got):
        np.testing.assert_allclose(y, x, rtol=1e-4, atol=1e-4, err_msg=name)


def test_triangular_tall_q_loop_sweep(qkv):
    """fori_loop sweep variant through the tall-q tri grid — identical to
    the unrolled pipeline."""
    q, k, v, _ = qkv
    spec = round_spec(jnp.int32(0), jnp.int32(0), S, S, True, "contig")
    st = tile.init_state(B, N, S, D)
    kw = dict(block_q=32, block_kv=8, block_kv_compute=8, interpret=True,
              cast_p=False, triangular=True)
    base = pallas_flash.flash_fwd(q, k, v, *st, SCALE, spec, **kw)
    got = pallas_flash.flash_fwd(q, k, v, *st, SCALE, spec,
                                 loop_sweep=True, **kw)
    for name, x, y in zip(("m", "lse", "acc"), base, got):
        np.testing.assert_allclose(np.asarray(y), np.asarray(x), rtol=1e-6,
                                   atol=1e-6, err_msg=name)


@pytest.mark.parametrize("block_q,block_kv,bkc,segs",
                         [(16, 16, 8, False), (16, 32, 16, False),
                          (16, 32, 8, True), (8, 32, 16, False)])
def test_tri_bwd_loop_sweep_matches_unrolled(qkv, block_q, block_kv, bkc,
                                             segs):
    """The tri backward's fori_loop sub-block sweep (loop_sweep=True — the
    bwd VMEM-cliff probe) is numerically identical to the unrolled
    pipeline, including the traced-u mask builder and segments, at square
    and wide-kv (ratio > 1) tilings."""
    q, k, v, do = qkv
    q1, do1 = q[:, :2], do[:, :2]  # tri bwd: group=1
    spec = round_spec(jnp.int32(0), jnp.int32(0), S, S, True, "contig")
    st = tile.init_state(B, NK, S, D)
    m, lse, acc = tile.tile_fwd(q1, k, v, *st, SCALE, spec)
    o = tile.finalize(m, lse, acc, q1.dtype)
    delta = jnp.sum(o * do1, axis=-1)
    seg = None
    if segs:
        ids = jnp.concatenate([jnp.zeros((B, S // 2 - 6), jnp.int32),
                               jnp.ones((B, S // 2 + 6), jnp.int32)], axis=1)
        seg = (ids, ids)
    kw = dict(block_q=block_q, block_kv=block_kv, block_kv_compute=bkc,
              interpret=True, triangular=True, fused=True, segments=seg)
    base = pallas_flash.flash_bwd(do1, q1, k, v, delta, lse, SCALE, spec,
                                  **kw)
    got = pallas_flash.flash_bwd(do1, q1, k, v, delta, lse, SCALE, spec,
                                 loop_sweep=True, **kw)
    for name, x, y in zip(("dq", "dk", "dv"), base, got):
        np.testing.assert_allclose(np.asarray(y), np.asarray(x), rtol=1e-6,
                                   atol=1e-6, err_msg=name)




def test_fwd_random_config_property_sweep():
    """Property sweep vs the jnp oracle: 18 seeded random configurations
    PLUS pinned trials for the interactions the random draws happen to
    miss (window x segments, window x ragged, window x ragged x segments
    x GQA, effective tall-q tri) — with a coverage assertion so a future
    seed/trial tweak cannot silently drop a claimed pair."""
    rng = np.random.RandomState(2024)
    configs = []
    for trial in range(18):
        b = int(rng.choice([1, 2]))
        group = int(rng.choice([1, 2]))
        nk = int(rng.choice([1, 2]))
        s = int(rng.choice([48, 64, 96]))
        d = int(rng.choice([16, 32]))
        bq = int(rng.choice([16, 32]))
        bkv = int(rng.choice([8, 16, 32]))
        causal = bool(rng.rand() < 0.7)
        wnd = int(rng.choice([24, 40])) if (causal and rng.rand() < 0.4) else None
        tri = causal and wnd is None and rng.rand() < 0.5 and bq % bkv == 0
        empty = rng.rand() < 0.5
        seg_cut = int(rng.randint(8, s - 8)) if rng.rand() < 0.4 else None
        configs.append(dict(b=b, group=group, nk=nk, s=s, d=d, bq=bq,
                            bkv=bkv, causal=causal, wnd=wnd, tri=tri,
                            empty=empty, seg_cut=seg_cut))
    configs += [
        # pinned: the pairs the 2024 seed never draws (verified by RNG
        # simulation during review) — keep these regardless of seed
        dict(b=1, group=1, nk=2, s=64, d=16, bq=16, bkv=16, causal=True,
             wnd=24, tri=False, empty=False, seg_cut=30),   # window x segs
        dict(b=1, group=1, nk=1, s=90, d=16, bq=16, bkv=16, causal=True,
             wnd=24, tri=False, empty=True, seg_cut=None),  # window x ragged
        dict(b=2, group=2, nk=1, s=90, d=16, bq=16, bkv=16, causal=True,
             wnd=40, tri=False, empty=False, seg_cut=40),   # all four
        dict(b=1, group=2, nk=1, s=64, d=32, bq=32, bkv=16, causal=True,
             wnd=None, tri=True, empty=True, seg_cut=None),  # tall-q tri
    ]

    seen = {"wnd_seg": 0, "wnd_ragged": 0, "tri_eff": 0}
    for trial, c in enumerate(configs):
        n = c["nk"] * c["group"]
        b, s, d = c["b"], c["s"], c["d"]
        segs = None
        if c["seg_cut"] is not None:
            ids = jnp.concatenate(
                [jnp.zeros((b, c["seg_cut"]), jnp.int32),
                 jnp.ones((b, s - c["seg_cut"]), jnp.int32)], axis=1)
            segs = (ids, ids)
        ragged = s % c["bq"] != 0 or s % c["bkv"] != 0
        if c["wnd"] is not None and segs is not None:
            seen["wnd_seg"] += 1
        if c["wnd"] is not None and ragged:
            seen["wnd_ragged"] += 1
        if c["tri"] and not ragged and c["bq"] % c["bkv"] == 0 \
                and (s // c["bq"]) % 2 == 0 and s // c["bq"] >= 2:
            seen["tri_eff"] += 1
        q = jax.random.normal(jax.random.PRNGKey(trial), (b, n, s, d),
                              jnp.float32)
        k = jax.random.normal(jax.random.PRNGKey(100 + trial),
                              (b, c["nk"], s, d), jnp.float32)
        v = jax.random.normal(jax.random.PRNGKey(200 + trial),
                              (b, c["nk"], s, d), jnp.float32)
        spec = round_spec(jnp.int32(0), jnp.int32(0), s, s, c["causal"],
                          "contig", window=c["wnd"])
        st = tile.init_state(b, n, s, d)
        ref = tile.tile_fwd(q, k, v, *st, d**-0.5, spec, window=c["wnd"],
                            segments=segs)
        carry = (None, None, None) if c["empty"] else st
        got = pallas_flash.flash_fwd(
            q, k, v, *carry, d**-0.5, spec, block_q=c["bq"],
            block_kv=c["bkv"], interpret=True, cast_p=False,
            triangular=c["tri"], window=c["wnd"], segments=segs)
        msg = f"trial={trial} {c}"
        for name, x, y in zip(("m", "lse", "acc"), ref, got):
            np.testing.assert_allclose(
                np.asarray(y), np.asarray(x), rtol=1e-4, atol=1e-4,
                err_msg=f"{name} @ {msg}")
    # the claimed interactions must actually have been exercised
    assert seen["wnd_seg"] >= 1 and seen["wnd_ragged"] >= 2 \
        and seen["tri_eff"] >= 1, seen


def test_bwd_random_config_property_sweep():
    """Backward property sweep vs the jnp oracle: random + pinned configs
    across the fused/split/tri kernel variants x GQA x window x segments
    x ragged x wide-kv blocks, with coverage assertions (fwd sibling
    test's methodology).  The bwd has the most variant dispatch
    (fused/split/tri/banded) — this guards the dispatch seams."""
    rng = np.random.RandomState(77)
    configs = []
    for _ in range(10):
        group = int(rng.choice([1, 2]))
        nk = int(rng.choice([1, 2]))
        s = int(rng.choice([48, 64, 96]))
        configs.append(dict(
            b=int(rng.choice([1, 2])), group=group, nk=nk, s=s,
            d=int(rng.choice([16, 32])),
            bq=int(rng.choice([16, 32])), bkv=int(rng.choice([16, 32])),
            causal=bool(rng.rand() < 0.7),
            wnd=int(rng.choice([24, 40])) if rng.rand() < 0.3 else None,
            tri=False,  # set below: tri requires a causal spec (contract)
            fused=[True, False, None][int(rng.randint(3))],
            seg_cut=int(rng.randint(8, s - 8)) if rng.rand() < 0.4 else None))
    configs += [
        # pinned seams: windowed banded fused + segments; tri wide-kv with
        # segments; split kernels with GQA + window; ragged fused
        dict(b=1, group=1, nk=2, s=64, d=16, bq=16, bkv=16, causal=True,
             wnd=24, tri=False, fused=True, seg_cut=30),
        dict(b=1, group=1, nk=2, s=64, d=16, bq=16, bkv=32, causal=True,
             wnd=None, tri=True, fused=True, seg_cut=28),
        dict(b=1, group=2, nk=1, s=64, d=16, bq=16, bkv=16, causal=True,
             wnd=40, tri=False, fused=False, seg_cut=None),
        dict(b=1, group=1, nk=1, s=90, d=16, bq=16, bkv=16, causal=True,
             wnd=None, tri=False, fused=True, seg_cut=None),
    ]
    for c in configs[:10]:
        # tri's caller contract requires a statically causal full-window
        # spec; re-draw it only where legal
        c["tri"] = c["causal"] and c["wnd"] is None and rng.rand() < 0.5
    seen = {"wnd_seg": 0, "tri_eff": 0, "split": 0, "ragged": 0}
    for trial, c in enumerate(configs):
        n = c["nk"] * c["group"]
        b, s, d = c["b"], c["s"], c["d"]
        causal = c["causal"] or c["wnd"] is not None  # window implies causal
        segs = None
        if c["seg_cut"] is not None:
            ids = jnp.concatenate(
                [jnp.zeros((b, c["seg_cut"]), jnp.int32),
                 jnp.ones((b, s - c["seg_cut"]), jnp.int32)], axis=1)
            segs = (ids, ids)
        ragged = s % c["bq"] != 0 or s % c["bkv"] != 0
        if c["wnd"] is not None and segs is not None:
            seen["wnd_seg"] += 1
        # under interpret, fused=None resolves to the split kernels
        # (flash_bwd: fused = not interpret and ...) unless tri wins
        if ragged:
            seen["ragged"] += 1
        # mirror flash_bwd's dispatch with the REAL gate: explicit
        # fused=False (split) beats triangular; ragged pads with
        # triangular=False; otherwise tri_bwd_supported decides
        tri_eff = (c["tri"] and c["fused"] is not False
                   and c["wnd"] is None and not ragged
                   and pallas_flash.tri_bwd_supported(
                       s, s, n, c["nk"], d, block_q=c["bq"],
                       block_kv=c["bkv"]))
        if tri_eff:
            seen["tri_eff"] += 1
        kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(300 + trial), 4)
        q = jax.random.normal(kq, (b, n, s, d), jnp.float32)
        k = jax.random.normal(kk, (b, c["nk"], s, d), jnp.float32)
        v = jax.random.normal(kv, (b, c["nk"], s, d), jnp.float32)
        do = jax.random.normal(kg, (b, n, s, d), jnp.float32)
        spec = round_spec(jnp.int32(0), jnp.int32(0), s, s, causal, "contig",
                          window=c["wnd"])
        st = tile.init_state(b, n, s, d)
        m, lse, acc = tile.tile_fwd(q, k, v, *st, d**-0.5, spec,
                                    window=c["wnd"], segments=segs)
        o = tile.finalize(m, lse, acc, q.dtype)
        delta = jnp.sum(o * do, axis=-1)
        ref = tile.tile_bwd(do, q, k, v, delta, lse, d**-0.5, spec,
                            window=c["wnd"], segments=segs)
        got = pallas_flash.flash_bwd(
            do, q, k, v, delta, lse, d**-0.5, spec, block_q=c["bq"],
            block_kv=c["bkv"], interpret=True, fused=c["fused"],
            triangular=c["tri"], window=c["wnd"], segments=segs)
        msg = f"trial={trial} {c}"
        # interpret mode does not model the FUSED kernels' dq transport
        # (rect: HBM input/output aliasing is last-write-only; tri: the
        # revisited resident out buffer) — dq validates on-chip only
        # (tests/test_fused_bwd.py); dk/dv ride scratch and DO validate.
        # The EFFECTIVE split path validates all three: explicit
        # fused=False, or fused=None under interpret with tri not taken.
        split_eff = c["fused"] is False or (c["fused"] is None
                                            and not tri_eff)
        if split_eff:
            seen["split"] += 1
        check = ("dq", "dk", "dv") if split_eff else ("dk", "dv")
        named = dict(zip(("dq", "dk", "dv"), zip(ref, got)))
        for name in check:
            x, y = named[name]
            np.testing.assert_allclose(
                np.asarray(y), np.asarray(x), rtol=1e-4, atol=1e-4,
                err_msg=f"{name} @ {msg}")
    assert seen["wnd_seg"] >= 1 and seen["tri_eff"] >= 1 \
        and seen["split"] >= 1 and seen["ragged"] >= 1, seen


# ---------------------------------------------------------------------------
# a ring round over part of a shard, written into the carried state in place
# (flash_fwd / tile_fwd `q_range`, `kv_range`; the backward's half of the
# contract is in tests/test_fused_bwd.py)

FWD_RANGES = {"kv_first_half": (None, (0, S // 2)),
              "q_second_half": ((S // 2, S), None)}
FWD_TILES = {
    "kernel_in_place": dict(block_q=8, block_kv=8),
    # blocks that do not tile the half: the kernel call takes the sliced form
    "kernel_sliced": dict(block_q=24, block_kv=24),
    "jnp_tile": None,
}


def _rows(x, rng, axis=2):
    return x if rng is None else jax.lax.slice_in_dim(x, *rng, axis=axis)


@pytest.mark.parametrize("packed", [False, True], ids=["plain", "packed"])
@pytest.mark.parametrize("heads", [(4, 4), (8, 2)], ids=["mha", "gqa4"])
@pytest.mark.parametrize("half", list(FWD_RANGES))
@pytest.mark.parametrize("tile_name", list(FWD_TILES))
def test_fwd_round_over_a_range_updates_the_carry_in_place(
        tile_name, half, heads, packed):
    """A forward round with a carried state and a half sub-range equals the
    sliced call of the same round written back into the state, bit for bit
    in float32; the rows outside the q range are the carry's bytes.  One
    call signature for the kernel (its grid over the range, state aliased),
    the kernel's sliced form and the jnp tile."""
    n, nk = heads
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    q = jax.random.normal(ks[0], (B, n, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, nk, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, nk, S, D), jnp.float32)
    # a carry as an earlier round leaves it, with some rows still empty
    m0 = jax.random.normal(ks[3], (B, n, S), jnp.float32)
    m0 = jnp.where(jnp.arange(S) % 7 == 3, -jnp.inf, m0)
    lse0 = jnp.where(jnp.isneginf(m0), -jnp.inf, m0 + 1.5)
    acc0 = jnp.where(jnp.isneginf(m0)[..., None], 0.0,
                     jax.random.normal(ks[4], (B, n, S, D), jnp.float32))
    seg = jnp.broadcast_to((jnp.arange(S) // 24).astype(jnp.int32), (B, S))
    q_range, kv_range = FWD_RANGES[half]
    spec = full_spec(S // 2 if q_range else S, S // 2 if kv_range else S)
    kw = FWD_TILES[tile_name]
    if kw is None:
        run = tile.tile_fwd
    else:
        kw = dict(kw, interpret=True, cast_p=False)
        run = lambda *a, **r: pallas_flash.flash_fwd(*a, **kw, **r)  # noqa: E731
        assert pallas_flash.fwd_covers_ranges(
            S, S, q_range, kv_range, block_q=kw["block_q"],
            block_kv=kw["block_kv"]) == (tile_name == "kernel_in_place")

    got = run(q, k, v, m0, lse0, acc0, SCALE, spec, q_range=q_range,
              kv_range=kv_range, segments=(seg, seg) if packed else None)

    part = run(_rows(q, q_range), _rows(k, kv_range), _rows(v, kv_range),
               _rows(m0, q_range), _rows(lse0, q_range), _rows(acc0, q_range),
               SCALE, spec,
               segments=(_rows(seg, q_range, 1), _rows(seg, kv_range, 1))
               if packed else None)
    lo = q_range[0] if q_range else 0
    for name, a, c, p_ in zip(("m", "lse", "acc"), got, (m0, lse0, acc0), part):
        want = c.at[:, :, lo:lo + p_.shape[2]].set(p_)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(want), name)
        if q_range is not None:
            np.testing.assert_array_equal(np.asarray(a[:, :, :lo]),
                                          np.asarray(c[:, :, :lo]), name)
    # and the round did fold something in: the visited rows moved
    assert not np.array_equal(np.asarray(got[2]), np.asarray(acc0))


def test_no_carry_no_range_lowers_to_the_kernel_it_was():
    """A call with no carry and no range (every one-device program) has no
    operand and no alias more than before: spec, q, k, v in, three out."""
    q = jax.ShapeDtypeStruct((1, 2, 64, 32), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda q, k, v: pallas_flash.flash_fwd(
        q, k, v, None, None, None, SCALE,
        round_spec(jnp.int32(0), jnp.int32(0), 64, 64, True, "contig"),
        block_q=16, block_kv=16, interpret=True))(q, q, q)
    # the forward's body sits behind flash_fwd's one jit (PR 33)
    (call,) = [e for e in iter_eqns(jaxpr.jaxpr)
               if e.primitive.name == "pallas_call"]
    assert len(call.invars) == 4 and len(call.outvars) == 3
    assert not call.params["input_output_aliases"]
    do = q
    st = jax.ShapeDtypeStruct((1, 2, 64), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda do, q, k, v, delta, lse: pallas_flash.flash_bwd(
        do, q, k, v, delta, lse, SCALE, full_spec(64, 64), block_q=16,
        block_kv=16, interpret=True, fused=True))(do, q, q, q, st, st)
    # the fused backward's launch sits behind flash_bwd's one jit (PR 35)
    (call,) = [e for e in iter_eqns(jaxpr.jaxpr)
               if e.primitive.name == "pallas_call"]
    # spec, do, q, k, v, delta, lse and the zeros dq accumulates into
    assert len(call.invars) == 8
    assert tuple(call.params["input_output_aliases"]) == ((7, 0),)
