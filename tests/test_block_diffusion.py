"""The block-diffusion mask (ops/masks.bd_quadrants) through the tiles: the
three live quadrants against the rule written densely, the interpreted Pallas
kernels against the dense mask forward and backward, which tiles the kernels
compute, the refusal over a ring, and that a call with no block mask traces
what it traced before the mask existed."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import burst_attn_tpu as bat
from burst_attn_tpu.analysis.jaxpr_tools import iter_eqns
from burst_attn_tpu.ops import masks, pallas_flash as pf
from burst_attn_tpu.ops.masks import BlockUnits, round_spec
from burst_attn_tpu.ops.tile import finalize


def _dense(q, k, v, mask):
    g = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, 1), jnp.repeat(v, g, 1)
    s = jnp.einsum("bnid,bnjd->bnij", q, k) * q.shape[-1] ** -0.5
    return jnp.einsum("bnij,bnjd->bnid",
                      jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1), v)


def _inputs(length, seed, heads=2, kv_heads=1, d=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = lambda n: (1, n, 2 * length, d)
    return (jax.random.normal(ks[0], shape(heads), jnp.float32),
            jax.random.normal(ks[1], shape(kv_heads), jnp.float32),
            jax.random.normal(ks[2], shape(kv_heads), jnp.float32),
            jax.random.normal(ks[3], shape(heads), jnp.float32))


@pytest.mark.parametrize("length,block", [(16, 4), (64, 32), (24, 4),
                                          (96, 32), (8, 8)])
def test_quadrants_are_the_rule(length, block):
    """The union of the three quadrants' dense masks is the rule's mask, the
    fourth quadrant is empty, and the pairs are L^2 + L*B."""
    rule = masks.bd_dense_mask(2 * length, block)
    i, j = np.indices(rule.shape)
    bi, bj = (i % length) // block, (j % length) // block
    by_hand = np.where(i < length,
                       np.where(j < length, bi == bj, bj < bi),
                       (j >= length) & (bj <= bi))
    assert (rule == by_hand).all()
    union = np.zeros_like(rule)
    for quad in masks.bd_quadrants(2 * length, block):
        (q0, q1), (k0, k1) = quad.q_range, quad.kv_range
        part = np.asarray(masks.dense_mask(quad.spec, q1 - q0, k1 - k0,
                                           quad.window))
        assert not union[q0:q1, k0:k1].any()
        union[q0:q1, k0:k1] = part
    assert (union == rule).all()
    assert not rule[length:, :length].any()
    assert rule.sum() == masks.bd_pairs(2 * length, block) \
        == length ** 2 + length * block
    assert rule.sum() < 2 * length ** 2 or block == length


def test_stream_length_must_be_twice_whole_blocks():
    with pytest.raises(ValueError, match="twice a multiple"):
        masks.bd_quadrants(2 * 30, 4)
    with pytest.raises(ValueError, match="twice a multiple"):
        masks.bd_quadrants(31, 1)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("length,block,tile,heads,kv_heads", [
    (192, 4, 128, 2, 1), (160, 32, 128, 2, 1),
    # whole tiles, so the diagonal quadrant runs on the band grid (4 q
    # tiles x 2 steps, folded into the `below` call's state), GQA 32 / 4
    (256, 4, 64, 32, 4), (128, 32, 32, 32, 4)])
def test_kernels_against_the_dense_mask(backend, length, block, tile, heads,
                                        kv_heads):
    """o, dq, dk, dv through burst_attn at sp=1 (the Pallas kernels
    interpreted), L not a multiple of the tile or in small whole tiles,
    against dense softmax attention under the rule's mask."""
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    q, k, v, do = _inputs(length, length + block, heads, kv_heads)
    mask = jnp.asarray(masks.bd_dense_mask(2 * length, block))

    def system(q, k, v):
        return bat.burst_attn(q, k, v, mesh=mesh, backend=backend,
                              block_diffusion=block, block_q=tile,
                              block_kv=tile, block_q_bwd=tile,
                              block_kv_bwd=tile)

    got, vjp = jax.vjp(system, q, k, v)
    want, vjp_ref = jax.vjp(lambda q, k, v: _dense(q, k, v, mask), q, k, v)
    for name, a, b in zip(("o", "dq", "dk", "dv"), (got, *vjp(do)),
                          (want, *vjp_ref(do))):
        assert float(jnp.max(jnp.abs(a - b))) < 2e-5, name


@pytest.mark.parametrize("heads,kv_heads,blk", [(4, 2, 64), (32, 4, 32)])
@pytest.mark.parametrize("quadrant", [0, 1, 2])
def test_fused_backward_kernel_against_the_dense_mask(quadrant, heads,
                                                      kv_heads, blk):
    """The chip's backward for these shapes is the fused rectangular kernel
    (interpret mode picks the split ones): forced here, with a carried
    dk / dv, one quadrant at a time against the jnp tile.  dk and dv only:
    the kernel's in-place dq rests on Mosaic's flush order, which interpret
    mode does not model (tests/test_fused_bwd.py); the chip-gated test below
    holds all three to the dense mask."""
    from burst_attn_tpu.ops import tile

    length, block = 256, 4
    quad = masks.bd_quadrants(2 * length, block)[quadrant]
    q, k, v, do = (x[:, :, :length] for x in _inputs(
        length, 7, heads=heads, kv_heads=kv_heads))
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    lse = jax.random.normal(ks[0], q.shape[:3]) + 3.0
    delta = jax.random.normal(ks[1], q.shape[:3])
    carry = (jax.random.normal(ks[2], k.shape), jax.random.normal(ks[3], k.shape))
    got = pf.flash_bwd(do, q, k, v, delta, lse, 0.25, quad.spec, block_q=blk,
                       block_kv=blk, interpret=True, fused=True,
                       triangular=True, window=quad.window, carry=carry)
    want = tile.tile_bwd(do, q, k, v, delta, lse, 0.25, quad.spec,
                         window=quad.window, carry=carry)
    # the block-diagonal quadrant's sweep is the banded one (2 of the 4 or
    # 8 q tiles a kv tile), the other two sweep every q tile
    text = str(jax.make_jaxpr(lambda *xs: pf.flash_bwd(
        *xs, 0.25, quad.spec, block_q=blk, block_kv=blk, interpret=True,
        fused=True, triangular=True, window=quad.window, carry=carry))(
        do, q, k, v, delta, lse))
    assert ("burst_flash_bwd_band" in text) == (quadrant == 2)
    assert ("burst_flash_bwd_rect" in text) == (quadrant != 2)
    for name, a, b in zip(("dk", "dv"), got[1:], want[1:]):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4, name


on_the_chip = pytest.mark.skipif(jax.default_backend() != "tpu",
                                 reason="the compiled kernels, on the chip "
                                        "(BURST_TESTS_TPU=1)")


@on_the_chip
@pytest.mark.parametrize("block", [4, 32])
def test_compiled_kernels_against_the_dense_mask_on_the_chip(block):
    """o, dq, dk, dv of the compiled kernels (the fused rectangular
    backward, GQA 32/4 as the cell runs it) against dense float32 softmax
    attention under the rule's mask, at 2 x 2,048 stream tokens in tiles
    of 512 (4 x 4 tiles a quadrant; the dense scores are 2 GiB)."""
    length = 2048
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    q, k, v, do = (x.astype(jnp.bfloat16) for x in _inputs(
        length, block, heads=32, kv_heads=4, d=128))
    mask = jnp.asarray(masks.bd_dense_mask(2 * length, block))
    got, vjp = jax.vjp(lambda q, k, v: bat.burst_attn(
        q, k, v, mesh=mesh, block_diffusion=block, block_q=512, block_kv=512,
        block_q_bwd=512, block_kv_bwd=512), q, k, v)
    f32 = lambda x: x.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, vjp_ref = jax.vjp(lambda q, k, v: _dense(q, k, v, mask),
                                f32(q), f32(k), f32(v))
        grads = vjp_ref(f32(do))
    for name, a, b, tol in zip(("o", "dq", "dk", "dv"), (got, *vjp(do)),
                               (want, *grads), (4e-2, 5e-2, 5e-2, 5e-2)):
        err = float(jnp.max(jnp.abs(f32(a) - b)))
        assert err < tol * max(1.0, float(jnp.max(jnp.abs(b)))), (name, err)


@on_the_chip
def test_the_cell_s_attention_against_the_dense_mask_on_the_chip():
    """`train_sdar_bd_1x8k`'s attention as the cell runs it (a stream of
    2 x 8,192 rows, 32 / 4 heads x 128, blocks of 4, every tile and grid
    from ops/tuning.call_row: the block-diagonal quadrant on the band grids
    in tiles of 512, folded into the `below` call's state) against dense
    float32 softmax attention under the rule's mask, one query head at a
    time (a head's scores are 1 GiB)."""
    length, block, heads, kv_heads = 8192, 4, 32, 4
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    q, k, v, do = (x.astype(jnp.bfloat16) for x in _inputs(
        length, block, heads=heads, kv_heads=kv_heads, d=128))
    attn = lambda q, k, v: bat.burst_attn(q, k, v, mesh=mesh,
                                          block_diffusion=block)
    assert re.findall(r"burst_flash_\w+", str(jax.make_jaxpr(
        lambda *x: jax.vjp(attn, *x[:3])[1](x[3]))(q, k, v, do))) == [
        "burst_flash_fwd", "burst_flash_fwd", "burst_flash_fwd_band",
        "burst_flash_bwd_rect", "burst_flash_bwd_rect",
        "burst_flash_bwd_band"]
    # `clean` and `below` (the latter with the former's dk / dv as its
    # carry) compute their cut blocks in sub-squares (PR 35)
    from burst_attn_tpu.parallel.burst import BurstConfig
    for quad in masks.bd_quadrants(2 * length, block)[:2]:
        rb = BurstConfig().resolved_blocks(length, length, quad.window)
        assert pf.bwd_diag_path(
            heads, kv_heads, length, length, 128, block_q=rb.block_q_bwd,
            block_kv=rb.block_kv_bwd, triangular=True,
            window=quad.window).path == "sub"
    got, vjp = jax.vjp(attn, q, k, v)
    got = (got, *vjp(do))
    mask = jnp.asarray(masks.bd_dense_mask(2 * length, block))

    @jax.jit
    def head(q, k, v, do, mask):
        want, vjp_ref = jax.vjp(lambda q, k, v: _dense(q, k, v, mask),
                                q, k, v)
        return (want, *vjp_ref(do))

    f32 = lambda x: x.astype(jnp.float32)
    group = heads // kv_heads
    errs, peak = np.zeros(4), np.zeros(4)
    with jax.default_matmul_precision("highest"):
        for g in range(kv_heads):
            dkv = [0.0, 0.0]
            for h in range(g * group, (g + 1) * group):
                o, dq, dk, dv = head(f32(q[:, h:h + 1]), f32(k[:, g:g + 1]),
                                     f32(v[:, g:g + 1]), f32(do[:, h:h + 1]),
                                     mask)
                dkv = [dkv[0] + dk, dkv[1] + dv]
                for i, (a, b) in enumerate(((got[0][:, h:h + 1], o),
                                            (got[1][:, h:h + 1], dq))):
                    errs[i] = max(errs[i], float(jnp.max(jnp.abs(f32(a) - b))))
                    peak[i] = max(peak[i], float(jnp.max(jnp.abs(b))))
            for i, b in zip((2, 3), dkv):
                a = got[i][:, g:g + 1]
                errs[i] = max(errs[i], float(jnp.max(jnp.abs(f32(a) - b))))
                peak[i] = max(peak[i], float(jnp.max(jnp.abs(b))))
    print("PARITY cell attention vs dense f32, max abs err (max |ref|):",
          {n: (float(e), float(p)) for n, e, p in zip(
              ("o", "dq", "dk", "dv"), errs, peak)})
    for name, err, top, tol in zip(("o", "dq", "dk", "dv"), errs, peak,
                                   (4e-2, 5e-2, 5e-2, 5e-2)):
        assert err < tol * max(1.0, top), (name, err)


@on_the_chip
def test_the_diagonal_call_s_fused_backward_equals_the_split_kernels_on_the_chip():
    """The block-diagonal call of the cell (8,192 rows, 32 / 4 heads) in the
    tiles the rule gives it: the fused kernel on the banded sweep, where `dq`
    is added in place and a race would show, against the split kernels."""
    from burst_attn_tpu.ops import tile
    from burst_attn_tpu.parallel.burst import BurstConfig

    length, block = 8192, 4
    quad = masks.bd_quadrants(2 * length, block)[2]
    rb = BurstConfig().resolved_blocks(length, length, quad.window)
    q, k, v, do = (x[:, :, :length].astype(jnp.bfloat16) for x in _inputs(
        length, 11, heads=32, kv_heads=4, d=128))
    scale = 128 ** -0.5

    @jax.jit
    def prep(q, k, v, do):
        m, lse, acc = pf.flash_fwd(q, k, v, None, None, None, scale,
                                   quad.spec, block_q=rb.block_q,
                                   block_kv=rb.block_kv, triangular=True,
                                   window=quad.window)
        o = tile.finalize(m, lse, acc, jnp.float32)
        return lse, jnp.sum(o * do.astype(jnp.float32), -1)

    lse, delta = prep(q, k, v, do)

    def bwd(fused):
        return jax.jit(lambda *xs: pf.flash_bwd(
            *xs, scale, quad.spec, block_q=rb.block_q_bwd,
            block_kv=rb.block_kv_bwd, triangular=True, window=quad.window,
            fused=fused))

    assert "burst_flash_bwd_band" in str(jax.make_jaxpr(bwd(None))(
        do, q, k, v, delta, lse))
    errs = {name: float(jnp.max(jnp.abs(a - b))) for name, a, b in zip(
        ("dq", "dk", "dv"), bwd(None)(do, q, k, v, delta, lse),
        bwd(False)(do, q, k, v, delta, lse))}
    print("PARITY diagonal call, fused (band) vs split, tiles",
          tuple(rb[2:4]), errs)
    assert max(errs.values()) < 1e-6, errs


@on_the_chip
@pytest.mark.parametrize("rows,batch,kv_heads,unit", [
    (8192, 1, 8, 1),   # train_mistral_1x8k, op_causal_64k's parity check
    (1024, 8, 8, 1),   # train_mistral_8x1k: one tile a head
    (8192, 1, 4, 4),   # train_sdar_bd_1x8k's `clean` and `below`
])
def test_the_compiled_forward_s_diagonal_sweep_against_dense_on_the_chip(
        rows, batch, kv_heads, unit):
    """The compiled forward at the cells' own geometries (32 query heads x
    128, the tiles ops/tuning.py resolves, the generation's sub-square edge),
    offset 0 and, in blocks, -1, against dense float32 softmax attention
    under the rule's mask, one query head at a time: `o` and `lse`."""
    heads, d = 32, 128
    ks = jax.random.split(jax.random.PRNGKey(rows + unit), 3)
    q = jax.random.normal(ks[0], (batch, heads, rows, d), jnp.bfloat16)
    k, v = (jax.random.normal(k_, (batch, kv_heads, rows, d), jnp.bfloat16)
            for k_ in ks[1:])
    window = BlockUnits(unit) if unit != 1 else None
    rb = pf.resolve_blocks(s_q=rows, s_kv=rows, window=window)
    path = pf.fwd_diag_path(rows, rows, block_q=rb.block_q,
                            block_kv=rb.block_kv, triangular=True,
                            window=window)
    nb = jnp.int32(rows // unit)
    blk = np.arange(rows) // unit

    @jax.jit
    def head(q, k, v, mask):
        s = jnp.einsum("bid,bjd->bij", q, k) * d ** -0.5
        s = jnp.where(mask, s, -jnp.inf)
        lse = jax.nn.logsumexp(s, -1)
        return jnp.einsum("bij,bjd->bid", jnp.exp(s - lse[..., None]), v), lse

    f32 = lambda x: x.astype(jnp.float32)
    for offset in (0, -1) if unit != 1 else (0,):
        spec = masks.MaskSpec(jnp.int32(0), nb, nb, jnp.int32(1),
                              jnp.int32(offset))
        m, lse, acc = jax.jit(lambda q, k, v: pf.flash_fwd(
            q, k, v, None, None, None, d ** -0.5, spec, block_q=rb.block_q,
            block_kv=rb.block_kv, triangular=True, window=window))(q, k, v)
        o = finalize(m, lse, acc, jnp.float32)
        mask = jnp.asarray(blk[None, :] <= blk[:, None] + offset)
        errs, peak = np.zeros(2), np.zeros(2)
        with jax.default_matmul_precision("highest"):
            for h in range(heads):
                g = h // (heads // kv_heads)
                want = head(f32(q[:, h]), f32(k[:, g]), f32(v[:, g]), mask)
                # offset -1: the first block's rows see nothing (lse -inf)
                live = jnp.isfinite(want[1])
                for i, (a, b) in enumerate(((o[:, h], want[0]),
                                            (lse[:, h], want[1]))):
                    w = live[..., None] if i == 0 else live
                    a, b = jnp.where(w, a, 0.0), jnp.where(w, b, 0.0)
                    errs[i] = max(errs[i], float(jnp.max(jnp.abs(a - b))))
                    peak[i] = max(peak[i], float(jnp.max(jnp.abs(b))))
                assert bool(jnp.all(jnp.isneginf(lse[:, h]) == ~live))
        print(f"PARITY forward {batch} x {rows} rows 32 / {kv_heads} unit "
              f"{unit} offset {offset} {path} vs dense f32, max abs err "
              f"(max |ref|):", {n: (float(e), float(p)) for n, e, p in zip(
                  ("o", "lse"), errs, peak)})
        assert path.path == "sub"
        for name, err, top in zip(("o", "lse"), errs, peak):
            assert err < 4e-2 * max(1.0, top), (name, err)


@on_the_chip
@pytest.mark.parametrize("rows,batch,kv_heads", [
    (8192, 1, 8),    # train_mistral_1x8k: the rectangular kernel, 32 / 8
    (1024, 8, 8),    # train_mistral_8x1k: one 1024 x 1024 block a head
    (8192, 1, 32),   # op_causal_64k's parity check: the triangular kernel
])
def test_the_compiled_backward_s_cut_blocks_against_dense_on_the_chip(
        rows, batch, kv_heads):
    """The compiled fused backward at the cells' own geometries (32 query
    heads x 128, the tiles ops/tuning.py resolves, the generation's
    sub-square edge) against the gradients of dense float32 softmax
    attention, one query head at a time: dq, dk, dv.  In the rectangular
    kernel dq is added in place: a race would show here."""
    heads, d = 32, 128
    ks = jax.random.split(jax.random.PRNGKey(rows + kv_heads), 4)
    q, do = (jax.random.normal(k_, (batch, heads, rows, d), jnp.bfloat16)
             for k_ in ks[:2])
    k, v = (jax.random.normal(k_, (batch, kv_heads, rows, d), jnp.bfloat16)
            for k_ in ks[2:])
    rb = pf.resolve_blocks(s_q=rows, s_kv=rows)
    path = pf.bwd_diag_path(heads, kv_heads, rows, rows, d,
                            block_q=rb.block_q_bwd, block_kv=rb.block_kv_bwd,
                            triangular=True)
    assert path.path == "sub"
    spec = masks.round_spec(jnp.int32(0), jnp.int32(0), rows, rows, True,
                            "contig")
    scale = d ** -0.5

    @jax.jit
    def mine(q, k, v, do):
        m, lse, acc = pf.flash_fwd(q, k, v, None, None, None, scale, spec,
                                   block_q=rb.block_q, block_kv=rb.block_kv,
                                   triangular=True)
        o = finalize(m, lse, acc, jnp.float32)
        delta = jnp.sum(o * do.astype(jnp.float32), -1)
        return pf.flash_bwd(do, q, k, v, delta, lse, scale, spec,
                            block_q=rb.block_q_bwd, block_kv=rb.block_kv_bwd,
                            triangular=True)

    name = "burst_flash_bwd_" + ("tri" if kv_heads == heads else "rect")
    assert name in str(jax.make_jaxpr(mine)(q, k, v, do))
    got = mine(q, k, v, do)

    @jax.jit
    def head(q, k, v, do):
        def dense(q, k, v):
            s = jnp.einsum("bid,bjd->bij", q, k) * scale
            s = jnp.where(jnp.tril(jnp.ones((rows, rows), bool)), s, -jnp.inf)
            return jnp.einsum("bij,bjd->bid", jax.nn.softmax(s, -1), v)

        return jax.vjp(dense, q, k, v)[1](do)

    f32 = lambda x: x.astype(jnp.float32)
    group = heads // kv_heads
    errs, peak = np.zeros(3), np.zeros(3)

    def hold(i, a, b):
        errs[i] = max(errs[i], float(jnp.max(jnp.abs(a - b))))
        peak[i] = max(peak[i], float(jnp.max(jnp.abs(b))))

    with jax.default_matmul_precision("highest"):
        for g in range(kv_heads):
            dkv = [0.0, 0.0]
            for h in range(g * group, (g + 1) * group):
                dq, dk, dv = head(f32(q[:, h]), f32(k[:, g]), f32(v[:, g]),
                                  f32(do[:, h]))
                hold(0, got[0][:, h], dq)
                dkv = [dkv[0] + dk, dkv[1] + dv]
            hold(1, got[1][:, g], dkv[0])
            hold(2, got[2][:, g], dkv[1])
    print(f"PARITY backward {batch} x {rows} rows 32 / {kv_heads} {name} "
          f"{path} vs dense f32, max abs err (max |ref|):",
          {n: (float(e), float(p)) for n, e, p in zip(
              ("dq", "dk", "dv"), errs, peak)})
    for n_, err, top in zip(("dq", "dk", "dv"), errs, peak):
        assert err < 5e-2 * max(1.0, top), (n_, err)


@pytest.mark.parametrize("length,block,bq,bkv", [(256, 4, 64, 64),
                                                 (256, 32, 64, 128),
                                                 (512, 4, 128, 64),
                                                 (512, 4, 32, 32),
                                                 (512, 4, 32, 64),
                                                 (512, 32, 64, 32),
                                                 (2048, 4, 512, 512)])
def test_tiles_computed_are_the_tiles_with_a_visible_pair(length, block, bq,
                                                          bkv):
    """The kernels compute a (q tile, kv tile) iff `_block_has_work` and the
    row's `_kv_jmax` clamp admit it (every kernel's `live`): over the three
    quadrants that is exactly the tiles of the rule's mask that hold a
    visible pair, and no tile of the empty quadrant.  The block-diagonal
    quadrant's band grids (the forward's `band_nb` kv steps a q tile, the
    backward's `nbq` q steps a kv tile) step over every one of its live
    tiles, in a small multiple of their number of steps."""
    rule = masks.bd_dense_mask(2 * length, block)
    nq, nk = length // bq, length // bkv
    i, j = np.indices((nq, nk))
    computed = np.zeros((2 * nq, 2 * nk), bool)
    for quad in masks.bd_quadrants(2 * length, block):
        spec = np.asarray(pf._spec_array(quad.spec))
        live = np.asarray(pf._block_has_work(spec, i * bq, j * bkv, bq, bkv,
                                             quad.window))
        live &= j <= np.asarray(pf._kv_jmax(spec, i, bq, bkv, nk,
                                            quad.window))
        live_bwd = np.asarray(pf._block_has_work(
            spec, i * bq, j * bkv, bq, bkv, quad.window)) & (
            i >= np.asarray(pf._q_imin(spec, j, bq, bkv, nq, quad.window)))
        assert (live == live_bwd).all()
        unit, win = masks.unit_of(quad.window)
        if win is not None:
            nb = min(nk, pf.fwd_band_nb(bq // unit, bkv // unit, win))
            first = np.asarray(pf._kv_jmin(spec, i, bq, bkv, nk, quad.window))
            assert (live <= ((j >= first) & (j < first + nb))).all()
            nbq = pf.bwd_band_nbq(bq, bkv, nq, quad.window)
            swept = np.zeros_like(live)
            for c in range(nbq):
                iq, clamped = pf._bwd_fused_iq(spec, j[0], c, bq, bkv, nq,
                                               quad.window)
                swept[np.asarray(iq)[~np.asarray(clamped)],
                      j[0][~np.asarray(clamped)]] = True
            assert (live <= swept).all()
            # steps of the two band grids against the live tiles
            assert nq * nb <= 3 * live.sum() and nk * nbq <= 3 * live.sum()
            assert (nb < nk and nbq < nq) or min(nq, nk) < 4
        q0, k0 = quad.q_range[0] // bq, quad.kv_range[0] // bkv
        computed[q0:q0 + nq, k0:k0 + nk] = live
    visible = rule.reshape(2 * nq, bq, 2 * nk, bkv).any(axis=(1, 3))
    assert (computed == visible).all()
    assert not computed[nq:, :nk].any()
    # a causal sweep over the stream would compute every tile on or under
    # the diagonal of the 2L x 2L grid
    assert computed.sum() < np.tril(np.ones((2 * nq, 2 * nq))).sum() * (
        nk / nq) or nq < 4


def test_a_ring_refuses_the_mask():
    mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
    q, k, v, _ = _inputs(64, 0)
    with pytest.raises(ValueError, match="one sequence shard.*layout"):
        bat.burst_attn(q, k, v, mesh=mesh, block_diffusion=4)
    one = Mesh(np.array(jax.devices()[:1]), ("sp",))
    with pytest.raises(ValueError, match="segment_ids"):
        bat.burst_attn(q, k, v, mesh=one, block_diffusion=4,
                       segment_ids=jnp.zeros((1, 128), jnp.int32))
    from burst_attn_tpu.parallel.burst import BurstConfig

    with pytest.raises(ValueError, match="sliding window"):
        BurstConfig(causal=True, layout="contig", window=8, block_diffusion=4)


def test_mask_blocks_must_divide_the_tiles():
    quad = masks.bd_quadrants(2 * 96, 32)[0]
    q, k, v, _ = (x[:, :, :96] for x in _inputs(96, 1))
    with pytest.raises(ValueError, match="must divide the kernel's tiles"):
        pf.flash_fwd(q, k, v, None, None, None, 0.25, quad.spec, block_q=48,
                     block_kv=48, window=quad.window, interpret=True)


def _digest(fn, *args):
    """Of the jaxpr as the parent of PR 27 printed it: since PR 29 a call on
    a band grid runs under a name of its own, the one thing that PR changed
    in such a call (test_band_grids_run_under_their_own_names)."""
    text = str(jax.make_jaxpr(fn)(*args))
    text = text.replace("burst_flash_fwd_band", "burst_flash_fwd").replace(
        "burst_flash_bwd_band", "burst_flash_bwd_rect")
    return hashlib.sha256(text.encode()).hexdigest()[:16]


# sha256[:16] of str(make_jaxpr(...)) of calls with NO block mask, taken on
# the parent commit of PR 27 (13b6104) with this file's shapes, under
# conftest's CPU settings (matmul precision "highest"): the kernels
# are specialised at trace time, and a call that carries no block mask must
# trace what it traced before the mask existed.  A PR that changes these
# kernels on purpose pins them anew.
PARENT_JAXPRS = {
    "fwd_tri": "3ba8284980c741aa",
    "fwd_carry_range": "4cd52d0285b83415",
    "fwd_window": "880c777e2641587f",
    # a segmented call that promises `triangular`: taken on the parent of
    # PR 33 (9f85e4b), whose diagonal sweep must leave it the whole tile
    "fwd_segments": "53615c08c18166d2",
    "bwd_split": "79e57846668dbb7d",
    "bwd_rect_carry": "1ad2e3725d9f4175",
    "bwd_rect_window": "cb17c00346ac39f2",
    "bwd_tri": "bdfefcf26e8c8b27",
}


def _plain_calls(window=lambda w: w):
    """{name: (fn, args)} of the kernel calls PARENT_JAXPRS was taken on;
    `window` maps a call's window argument (None or an int)."""
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    q, kv, st = shape(1, 4, 256, 32), shape(1, 2, 256, 32), shape(1, 4, 256)
    spec = lambda: round_spec(jnp.int32(0), jnp.int32(0), 256, 256, True,
                              "contig")
    kw = dict(block_q=64, block_kv=64, interpret=True)
    return {
        "fwd_tri": (lambda q, k, v: pf.flash_fwd(
            q, k, v, None, None, None, 0.2, spec(), triangular=True,
            window=window(None), **kw), (q, kv, kv)),
        "fwd_carry_range": (lambda q, k, v, m, l, a: pf.flash_fwd(
            q, k, v, m, l, a, 0.2, masks.full_spec(256, 128),
            kv_range=(0, 128), window=window(None), **kw),
            (q, kv, kv, st, st, q)),
        "fwd_window": (lambda q, k, v: pf.flash_fwd(
            q, k, v, None, None, None, 0.2, spec(), triangular=True,
            window=window(48), **kw), (q, kv, kv)),
        "fwd_segments": (lambda q, k, v, s: pf.flash_fwd(
            q, k, v, None, None, None, 0.2, spec(), triangular=True,
            segments=(s, s), window=window(None), **kw),
            (q, kv, kv, jax.ShapeDtypeStruct((1, 256), jnp.int32))),
        "bwd_split": (lambda do, q, k, v, d, l: pf.flash_bwd(
            do, q, k, v, d, l, 0.2, spec(), window=window(None), **kw),
            (q, q, kv, kv, st, st)),
        "bwd_rect_carry": (lambda do, q, k, v, d, l, dk, dv: pf.flash_bwd(
            do, q, k, v, d, l, 0.2, spec(), fused=True, carry=(dk, dv),
            window=window(None), **kw), (q, q, kv, kv, st, st, kv, kv)),
        "bwd_rect_window": (lambda do, q, k, v, d, l: pf.flash_bwd(
            do, q, k, v, d, l, 0.2, spec(), fused=True, window=window(48),
            **kw), (q, q, kv, kv, st, st)),
        "bwd_tri": (lambda do, q, k, v, d, l: pf.flash_bwd(
            do, q, k, v, d, l, 0.2, spec(), fused=True, triangular=True,
            window=window(None), **kw), (q, q, q, q, st, st)),
    }


def _kernel_texts(fn, *args):
    return [str(e.params["jaxpr"]) for e in iter_eqns(jax.make_jaxpr(fn)(*args))
            if e.primitive.name == "pallas_call"]


@pytest.mark.parametrize("name", sorted(PARENT_JAXPRS))
def test_a_call_with_no_block_mask_traces_the_parent_s_jaxpr(name,
                                                             monkeypatch):
    """The forward's with flash_fwd's body traced in line; behind its one
    jit (PR 33) the kernel is that one, text for text."""
    fn, args = _plain_calls()[name]
    behind_the_jit = _kernel_texts(fn, *args)
    monkeypatch.setattr(pf, "_fwd_launch_traced", pf._fwd_launch)
    monkeypatch.setattr(pf, "_bwd_launch_traced", pf._bwd_launch)
    fn, args = _plain_calls()[name]  # make_jaxpr remembers a function
    assert _digest(fn, *args) == PARENT_JAXPRS[name]
    assert behind_the_jit == _kernel_texts(fn, *args) != []


@pytest.mark.parametrize("name,kernel", [
    ("fwd_tri", "burst_flash_fwd"), ("fwd_window", "burst_flash_fwd_band"),
    ("bwd_rect_carry", "burst_flash_bwd_rect"),
    ("bwd_rect_window", "burst_flash_bwd_band"),
    ("bwd_tri", "burst_flash_bwd_tri")])
def test_band_grids_run_under_their_own_names(name, kernel):
    """`flash_ms_per_step` sums `burst_flash_*`; a trace lists a windowed
    call's time beside the others' under `_band`."""
    fn, args = _plain_calls()[name]
    assert set(re.findall(r"burst_flash_\w+",
                          str(jax.make_jaxpr(fn)(*args)))) == {kernel}


@pytest.mark.parametrize("name", ["fwd_carry_range", "fwd_window",
                                  "bwd_split", "bwd_rect_window"])
def test_units_of_one_token_are_no_units(name):
    """BlockUnits(1, w) is the token window w: the same jaxpr, so the unit's
    code is all behind `unit != 1`."""
    plain, args = _plain_calls()[name]
    unit, _ = _plain_calls(lambda w: BlockUnits(1, w))[name]
    assert _digest(unit, *args) == _digest(plain, *args)
