"""Which blocks, which grid and which sweep of its diagonal tiles a tile call
gets (ops/tuning.call_row through BurstConfig.resolved_blocks and
burst._tile_fwd / _tile_bwd; pallas_flash.fwd_diag_path): the benchmark
cells' calls under the v5e row, a caller's own blocks, that the op cells'
programs trace what they traced before the resolution saw a call's geometry,
and the forward's diagonal sweep against the oracle and the whole tile."""

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import burst_attn_tpu as bat
from burst_attn_tpu import obs
from burst_attn_tpu.analysis.jaxpr_tools import iter_eqns
from burst_attn_tpu.ops import pallas_flash as pf, tile, tuning
from burst_attn_tpu.ops.masks import BlockUnits, MaskSpec
from burst_attn_tpu.parallel.burst import BurstConfig

V5E = tuning.generation_row("v5e")
ROW = (2048, 2048, 1024, 2048)  # measured at 64K rows x 32 heads


@pytest.fixture
def v5e(monkeypatch):
    monkeypatch.setattr(tuning, "block_defaults", lambda device=None: V5E)


# (rows of q, rows of kv, window) of every tile call the five cells make
CELL_CALLS = {
    "op_causal_64k": (65536, 65536, None),
    "op_causal_64k.parity_8k": (8192, 8192, None),
    "ring4_causal_128k.self_round": (32768, 32768, None),
    "ring4_causal_128k.half_kv_round": (32768, 16384, None),
    "ring4_causal_128k.half_q_round": (16384, 32768, None),
    "train_mistral_1x8k": (8192, 8192, None),
    "train_mistral_8x1k": (1024, 1024, None),
    "train_sdar_bd_1x8k.clean": (8192, 8192, BlockUnits(4)),
    "train_sdar_bd_1x8k.below": (8192, 8192, BlockUnits(4)),
}


@pytest.mark.parametrize("call", sorted(CELL_CALLS))
def test_an_unwindowed_call_gets_the_measured_row(call, v5e):
    """Part 2 of PR 29 was a wash (the numbers are at tuning.call_row): at
    8,192 and at 1,024 rows no block beat the 64K row by 3 % on the call."""
    s_q, s_kv, window = CELL_CALLS[call]
    rb = BurstConfig().resolved_blocks(s_q, s_kv, window)
    assert rb[:4] == ROW and rb.block_kv_compute == 1024
    assert tuning.resolve_blocks(s_q=s_q, s_kv=s_kv, window=window) == rb
    assert tuning.resolve_blocks() == rb  # no geometry: the row


@pytest.mark.parametrize("window,edge", [
    (BlockUnits(4, 1), 512),   # train_sdar_bd_1x8k's block-diagonal call
    (BlockUnits(32, 1), 512), (1, 512), (256, 512), (512, 512),
    (513, 1024), (700, 1024), (1024, 1024),
    (1025, 2048), (4096, 2048), (BlockUnits(4, 1024), 2048)])
def test_a_banded_call_gets_tiles_of_the_band_s_width(window, edge, v5e):
    """The band's width in tokens, rounded up to a power of two, between
    the measured floor (512) and the row's own tiles."""
    rb = tuning.resolve_blocks(s_q=8192, s_kv=8192, window=window)
    assert rb[:4] == tuple(min(b, edge) for b in ROW)
    assert rb.block_kv_compute == min(1024, edge)
    assert rb == BurstConfig().resolved_blocks(8192, 8192, window)


def test_blocks_the_caller_sets_win(v5e):
    diagonal = BlockUnits(4, 1)
    cfg = BurstConfig(block_q=256, block_kv_bwd=1024)
    assert cfg.resolved_blocks(8192, 8192, diagonal)[:4] == (
        256, 512, 256, 1024)
    assert cfg.resolved_blocks(65536, 65536)[:4] == (256, 2048, 256, 1024)
    # burst_attn hands its block arguments down to the tile call
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    q = jax.ShapeDtypeStruct((1, 2, 1024, 16), jnp.float32)
    for block, grid in ((128, (1, 2, 4, 2)), (64, (1, 2, 8, 2))):
        calls = _pallas_calls(lambda q, k, v: bat.burst_attn(
            q, k, v, mesh=mesh, backend="pallas", block_diffusion=4,
            block_q=block, block_kv=block), q, q, q)
        assert ("burst_flash_fwd_band", grid) in calls
    # a cliff clamp still applies to what was resolved
    assert tuning.resolve_blocks(2048, 4096, s_q=65536, s_kv=65536,
                                 table=V5E)[:2] == (2048, 2048)


def _kernels(jaxpr):
    """[(name, grid, kernel jaxpr's text)] of every pallas_call, in order,
    through whatever call wraps it."""
    return [(e.params["name"], tuple(e.params["grid_mapping"].grid),
             str(e.params["jaxpr"])) for e in iter_eqns(jaxpr)
            if e.primitive.name == "pallas_call"]


def _pallas_calls(fn, *args):
    """[(kernel name, grid)] of every pallas_call in fn's jaxpr."""
    return [k[:2] for k in _kernels(jax.make_jaxpr(fn)(*args))]


def test_the_block_diagonal_call_takes_the_band_grids(v5e, monkeypatch):
    """`train_sdar_bd_1x8k`'s attention (a stream of 2 x 8,192 rows, 32 / 4
    heads x 128) as the chip traces it: the two causal quadrants on the
    triangular forward grid and the full backward sweep in the row's tiles,
    the block-diagonal one on the band grids in tiles of 512, the backward's
    sweep long enough for the fused kernel, and no seventh call."""
    monkeypatch.setattr(pf, "_interpret_default", lambda: False)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    q = jax.ShapeDtypeStruct((1, 32, 16384, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 4, 16384, 128), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(bat.burst_attn(q, k, v, mesh=mesh, backend="pallas",
                                      block_diffusion=4).astype(jnp.float32))

    calls = _pallas_calls(jax.grad(loss, (0, 1, 2)), q, kv, kv)
    tri, band = ("burst_flash_fwd", (1, 32, 2, 5)), (
        "burst_flash_fwd_band", (1, 32, 16, 2))
    assert calls[:3] == [tri, tri, band]
    assert sorted(calls[3:]) == [
        ("burst_flash_bwd_band", (1, 4, 16, 2 * 8)),
        ("burst_flash_bwd_rect", (1, 4, 4, 8 * 8)),
        ("burst_flash_bwd_rect", (1, 4, 4, 8 * 8))]
    diagonal = BlockUnits(4, 1)
    assert pf.bwd_band_nbq(512, 512, 16, diagonal) * 8 >= 4  # the gate
    assert pf._bwd_kernel_of(32, 4, 8192, 8192, 128, block_q=512,
                             block_kv=512, interpret=False, triangular=True,
                             window=diagonal) == "rect"


def test_flash_attention_resolves_from_its_window(v5e, monkeypatch):
    """The single-device op under a token window of a few hundred: tiles of
    512 on both band grids; without one, the row."""
    monkeypatch.setattr(pf, "_interpret_default", lambda: False)
    q = jax.ShapeDtypeStruct((1, 8, 8192, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 2, 8192, 128), jnp.bfloat16)

    def grads(window):
        return _pallas_calls(jax.grad(lambda q, k, v: jnp.sum(
            pf.flash_attention(q, k, v, None, True, window=window).astype(
                jnp.float32)), (0, 1, 2)), q, kv, kv)

    assert grads(300) == [("burst_flash_fwd_band", (1, 8, 16, 2)),
                          ("burst_flash_bwd_band", (1, 2, 16, 2 * 4))]
    assert grads(None) == [("burst_flash_fwd", (1, 8, 2, 5)),
                           ("burst_flash_bwd_rect", (1, 2, 4, 8 * 4))]


def _op_cell_program(world, seq):
    """The jaxpr of an op cell's program (chipbench/runners/op.py: forward +
    backward of burst_attn, causal, zigzag, 32 heads x 128, bf16), with the
    Pallas tile named outright (on the CPU "auto" is jnp)."""
    mesh = Mesh(np.array(jax.devices()[:world]), ("sp",))
    q = jax.ShapeDtypeStruct((1, 32, seq, 128), jnp.bfloat16)

    def run(q, k, v, do):
        def loss(q, k, v):
            o = bat.burst_attn(q, k, v, mesh=mesh, causal=True,
                               layout="zigzag", backend="pallas")
            return jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32)), o

        (_, o), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(
            q, k, v)
        return (o, *grads)

    return jax.make_jaxpr(run)(q, q, q, q)


def _digest(jaxpr):
    return hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16]


# taken on the parent commit of PR 29 (759fd86) with these functions, under
# conftest's CPU settings: the resolution now sees each call's rows, and at
# the op cells' rows it must return what the parent's one row gave
PARENT_OP_CELLS = {
    "op_causal_64k": (1, 65536, "6cdbcf9fe50b186a"),
    "ring4_causal_128k": (4, 131072, "c46725882cf5b8fa"),
}


# the same programs' kernels (name, grid, kernel jaxpr of every pallas_call)
# under the generation's diagonal edges, taken on PR 35's tree: the forward's
# (PR 33) and the triangular backward's (PR 35) differ from the parent's, and
# a PR that changes a kernel pins them anew
SWEPT_OP_CELLS = {
    "op_causal_64k": "9f4cdf83a2f729fe",
    "ring4_causal_128k": "a0041017195b3a98",
}


@pytest.mark.parametrize("cell", sorted(PARENT_OP_CELLS))
def test_the_op_cells_trace_the_parent_s_jaxprs(cell, monkeypatch):
    """With the diagonal sweeps off (an edge of 0 keeps the whole tile on
    the masked path, in either pass) and the kernel launches traced in line,
    not behind flash_fwd's and flash_bwd's jits (PRs 33, 35): the program is
    the parent's, text for text.  Behind the jits every kernel is still that
    program's; with the sweeps on the forward's and the triangular
    backward's differ (the self round's; the ring's off-diagonal rounds run
    the rectangular kernel on full tiles), and no other."""
    world, seq, digest = PARENT_OP_CELLS[cell]
    row = tuning.block_defaults()
    swept = _kernels(_op_cell_program(world, seq))
    assert _digest(swept) == SWEPT_OP_CELLS[cell]
    monkeypatch.setattr(tuning, "block_defaults",
                        lambda device=None: row._replace(diag_block=0))
    whole = _kernels(_op_cell_program(world, seq))
    monkeypatch.setattr(pf, "_fwd_launch_traced", pf._fwd_launch)
    monkeypatch.setattr(pf, "_bwd_launch_traced", pf._bwd_launch)
    parent = _op_cell_program(world, seq)
    assert _digest(parent) == digest
    assert whole == _kernels(parent)
    assert [k[:2] for k in swept] == [k[:2] for k in whole]
    differ = {a[0] for a, b in zip(swept, whole) if a != b}
    assert differ == {"burst_flash_fwd", "burst_flash_bwd_tri"}


# ---------------------------------------------------------------------------
# the diagonal sweep (flash_fwd's tile i == j in sub-squares, PR 32)

DIAG_TILE = 64  # stands for the row's 2048; the edges for 1024 ... 128


def _block_causal(s, unit, offset):
    nb = jnp.int32(s // unit)
    return MaskSpec(jnp.int32(0), nb, nb, jnp.int32(1), jnp.int32(offset))


def _finite(x):
    x = np.asarray(x, np.float32)
    return np.where(np.isneginf(x), 0.0, x)


# every grid (one tile on the rectangular grid; 2 and 4 q blocks on the
# triangular one) with every edge under every mask (offset 0 / -1 in tokens
# and in blocks of 4: train_sdar_bd_1x8k's `clean` and `below`), the cells'
# two head groupings (4 and 8 query heads a kv head: 32 / 8 and 32 / 4) and
# the three states (empty, carried, the fused finalize) dealt over them so
# that each meets every grid, every edge and every mask
DIAG_GRIDS = [(1, 32, 2), (1, 16, 1), (1, 8, 1), (1, 4, 2), (2, 32, 1),
              (2, 16, 2), (2, 8, 2), (2, 4, 1), (4, 32, 1), (4, 16, 1),
              (4, 8, 2), (4, 4, 2)]
DIAG_MASKS = [(1, 0), (1, -1), (4, 0), (4, -1)]
DIAG_CASES = [(*g, *m, ("empty", "carried", "emit_o")[(i + i // 4 + j) % 3])
              for i, g in enumerate(DIAG_GRIDS)
              for j, m in enumerate(DIAG_MASKS)]


@pytest.mark.parametrize("nqb,edge,kv_heads,unit,offset,state", DIAG_CASES)
def test_diag_sweep_against_the_oracle_and_the_whole_tile(
        nqb, edge, kv_heads, unit, offset, state):
    """flash_fwd's diagonal tiles in sub-squares of `edge` against ops/tile.py
    and against the same call with the whole tile on the masked path (forced
    by `triangular=False`, or by a single segment), over offset 0 / -1 in
    tokens and in blocks of 4 (train_sdar_bd_1x8k's `clean` and `below`)."""
    n, d, s = 8, 8, nqb * DIAG_TILE
    ks = jax.random.split(jax.random.PRNGKey(nqb * 100 + edge + offset), 6)
    q = jax.random.normal(ks[0], (1, n, s, d), jnp.float32)
    k, v = (jax.random.normal(k_, (1, kv_heads, s, d), jnp.float32)
            for k_ in ks[1:3])
    spec = _block_causal(s, unit, offset)
    window = BlockUnits(unit) if unit != 1 else None
    st = (None,) * 3
    if state == "carried":
        m0 = jax.random.normal(ks[3], (1, n, s), jnp.float32)
        st = (m0, m0 + jnp.abs(jax.random.normal(ks[4], (1, n, s))),
              jax.random.normal(ks[5], (1, n, s, d), jnp.float32))
    run = functools.partial(
        pf.flash_fwd, q, k, v, *st, d**-0.5, spec, block_q=DIAG_TILE,
        block_kv=DIAG_TILE, window=window, emit_o=state == "emit_o",
        interpret=True, cast_p=False, diag_block=edge)
    assert pf.fwd_diag_path(
        s, s, block_q=DIAG_TILE, block_kv=DIAG_TILE, triangular=True,
        window=window, diag_block=edge) == ("sub", nqb, edge)
    got = run(triangular=True)
    seg = jnp.zeros((1, s), jnp.int32)
    whole = (run(triangular=True, segments=(seg, seg)) if offset else
             run(triangular=False))
    for name, a, b in zip(("m", "lse", "acc"), got, whole):
        np.testing.assert_allclose(_finite(a), _finite(b), rtol=2e-5,
                                   atol=2e-5, err_msg=name)
    ref = tile.tile_fwd(q, k, v, *(st if state == "carried" else
                                   tile.init_state(1, n, s, d)),
                        d**-0.5, spec, window=window)
    # m depends on the fold order where a row's carry exceeds its scores
    o = got[2] if state == "emit_o" else tile.finalize(*got, jnp.float32)
    for name, a, b in (("lse", got[1], ref[1]),
                       ("o", o, tile.finalize(*ref, jnp.float32))):
        np.testing.assert_allclose(_finite(a), _finite(b), rtol=1e-4,
                                   atol=1e-4, err_msg=name)


def test_the_diagonal_sweep_runs_under_the_forward_s_name():
    """The benchmark's readers join on `burst_flash_fwd`."""
    q = jax.ShapeDtypeStruct((1, 2, 256, 16), jnp.float32)
    assert _pallas_calls(lambda q, k, v: pf.flash_fwd(
        q, k, v, None, None, None, 0.25, _block_causal(256, 1, 0),
        block_q=64, block_kv=64, triangular=True, diag_block=16,
        interpret=True), q, q, q) == [("burst_flash_fwd", (1, 2, 2, 5))]


def test_diag_path_is_static_and_keeps_the_whole_tile_elsewhere(monkeypatch):
    path = functools.partial(pf.fwd_diag_path, 256, 256, block_q=64,
                             block_kv=64, diag_block=16)
    assert path(triangular=False) is None
    assert path(triangular=True) == ("sub", 4, 16)
    assert path(triangular=True, q_range=(128, 256), kv_range=(0, 128)) == (
        "sub", 2, 16)  # the sliced form of a promised sub-range
    assert path(triangular=True, kv_range=(0, 128)) is None  # s_q != s_kv
    assert path(triangular=True, window=BlockUnits(4)).path == "sub"
    for kw in (dict(window=48), dict(window=BlockUnits(4, 1)),
               dict(segments=True), dict(loop_sweep=True),
               dict(window=BlockUnits(32)),  # the edge is half a mask unit
               dict(diag_block=0), dict(diag_block=64), dict(diag_block=24)):
        assert path(**{"triangular": True, **kw}) == ("whole", 4, None), kw
    assert pf.fwd_diag_path(256, 256, block_q=128, block_kv=64,
                            triangular=True, diag_block=16) == (
        "whole", 2, None)
    # a ragged length is padded and runs on the rectangular masked path
    assert pf.fwd_diag_path(200, 200, block_q=64, block_kv=64,
                            triangular=True, diag_block=16) == (
        "whole", 4, None)
    monkeypatch.setenv("BURST_FWD_LOOP", "1")
    assert path(triangular=True) == ("whole", 4, None)
    monkeypatch.delenv("BURST_FWD_LOOP")
    monkeypatch.setattr(tuning, "block_defaults", lambda device=None: V5E)
    assert pf.fwd_diag_path(8192, 8192, block_q=2048, block_kv=2048,
                            triangular=True) == ("sub", 4, V5E.diag_block)


# bwd_diag_path beside fwd_diag_path: 256 rows, 4 query heads x 16, the
# backward's tall kv block (64 = 2 x 32), an edge of 16, a fused kernel
# forced (off the chip flash_bwd's own gate takes the split kernels)
BWD_PATH = dict(block_q=32, block_kv=64, triangular=True, diag_block=16,
                interpret=True, fused=True)
BWD_PATH_CASES = {
    # case: (keywords over BWD_PATH, kv heads, the answer)
    "tri_kernel": ({}, 4, ("sub", 8, 16)),
    "rect_kernel": ({}, 1, ("sub", 8, 16)),
    "block_units": (dict(window=BlockUnits(4)), 1, ("sub", 8, 16)),
    "square_blocks": (dict(block_kv=32), 4, ("sub", 8, 16)),
    "edge_is_block_q": (dict(diag_block=32), 1, ("sub", 8, 32)),
    "sliced_sub_range": (dict(q_range=(128, 256), kv_range=(0, 128)), 1,
                         ("sub", 4, 16)),
    "no_promise": (dict(triangular=False), 1, None),
    "s_q_ne_s_kv": (dict(kv_range=(0, 128)), 1, None),
    "token_window": (dict(window=48), 1, ("whole", 8, None)),
    "block_diagonal_band": (dict(window=BlockUnits(4, 1)), 1,
                            ("whole", 8, None)),
    "segments": (dict(segments=True), 1, ("whole", 8, None)),
    "loop_sweep": (dict(loop_sweep=True), 4, ("whole", 8, None)),
    "edge_half_a_mask_unit": (dict(window=BlockUnits(32)), 1,
                              ("whole", 8, None)),
    "edge_off": (dict(diag_block=0), 1, ("whole", 8, None)),
    "edge_is_block_kv": (dict(diag_block=64), 1, ("whole", 8, None)),
    "edge_does_not_divide": (dict(diag_block=24), 1, ("whole", 8, None)),
    "block_kv_not_a_multiple": (dict(block_q=64, block_kv=32), 1,
                                ("whole", 4, None)),
    "split_kernels": (dict(fused=False), 1, ("whole", 8, None)),
    "the_gate_off_the_chip": (dict(fused=None), 1, ("whole", 8, None)),
    "ragged_length": (dict(), 1, ("whole", 8, None)),  # padded to 256
}


@pytest.mark.parametrize("case", sorted(BWD_PATH_CASES))
def test_bwd_diag_path_is_static_and_keeps_the_whole_tile_elsewhere(case):
    """Every condition of the backward's sub-square sweep, one a case: the
    answer is read off what the call states, as fwd_diag_path's is."""
    kw, n_kv, want = BWD_PATH_CASES[case]
    rows = 200 if case == "ragged_length" else 256
    assert pf.bwd_diag_path(4, n_kv, rows, rows, 16,
                            **{**BWD_PATH, **kw}) == want


def test_bwd_diag_path_reads_the_environment_and_the_table(monkeypatch):
    path = functools.partial(pf.bwd_diag_path, 4, 4, 256, 256, 16)
    monkeypatch.setenv("BURST_BWD_LOOP", "1")
    assert path(**BWD_PATH) == ("whole", 8, None)
    monkeypatch.delenv("BURST_BWD_LOOP")
    monkeypatch.setenv("BURST_NO_TRI", "1")  # the rectangular kernel instead
    assert path(**BWD_PATH) == ("sub", 8, 16)
    monkeypatch.delenv("BURST_NO_TRI")
    monkeypatch.setattr(tuning, "block_defaults", lambda device=None: V5E)
    monkeypatch.setattr(pf, "_interpret_default", lambda: False)
    for n_kv in (32, 8):  # op_causal_64k's kernel, train_mistral_1x8k's
        assert pf.bwd_diag_path(
            32, n_kv, 8192, 8192, 128, block_q=1024, block_kv=2048,
            triangular=True) == ("sub", 8, V5E.diag_block)


def _diag_counted(fn, *args, pass_="fwd"):
    """(sub, whole) that flash.diag_tiles advances by when `fn` is traced."""
    c = obs.counter("flash.diag_tiles")
    read = lambda: [c.get(**{"pass": pass_, "path": p})
                    for p in ("sub", "whole")]
    before = read()
    jax.make_jaxpr(fn)(*args)
    return tuple(a - b for a, b in zip(read(), before))


# the five cells' causal forward calls at a sixteenth of their rows, the row
# cut by as much (tiles of 128, the edge of 128 is 8): (batch, heads, kv
# heads, rows a shard, ring, block diffusion)
CELL_DISPATCHES = {
    "op_causal_64k": (1, 32, 32, 4096, 1, None),
    "ring4_causal_128k": (1, 32, 32, 2048, 4, None),
    "train_mistral_1x8k": (1, 32, 8, 512, 1, None),
    "train_mistral_8x1k": (8, 32, 8, 64, 1, None),
    "train_sdar_bd_1x8k": (1, 32, 4, 512, 1, 4),
}


@pytest.mark.parametrize("cell", sorted(CELL_DISPATCHES))
def test_every_diagonal_tile_of_the_cells_is_swept_in_sub_squares(
        cell, monkeypatch):
    b, n, n_kv, rows, world, bd = CELL_DISPATCHES[cell]
    row = V5E._replace(fwd_block_q=128, fwd_block_kv=128, bwd_block_q=64,
                       bwd_block_kv=128, band_block=32, diag_block=8)
    monkeypatch.setattr(tuning, "block_defaults", lambda device=None: row)
    mesh = Mesh(np.array(jax.devices()[:world]), ("sp",))
    stream = rows * world * (2 if bd else 1)
    q = jax.ShapeDtypeStruct((b, n, stream, 16), jnp.float32)
    kv = jax.ShapeDtypeStruct((b, n_kv, stream, 16), jnp.float32)
    sub, whole = _diag_counted(lambda q, k, v: bat.burst_attn(
        q, k, v, mesh=mesh, causal=bd is None, backend="pallas",
        block_diffusion=bd), q, kv, kv)
    tiles = b * n * max(1, rows // 128)
    if bd:
        # `clean` and `below`; the block-diagonal call has a window (a band
        # grid in tiles of 32), so its diagonal tiles stay whole
        assert (sub, whole) == (2 * tiles, b * n * rows // 32)
    else:
        # the self round; a zigzag ring's later rounds promise nothing
        assert (sub, whole) == (tiles, 0)


@pytest.mark.parametrize("cell", sorted(CELL_DISPATCHES))
def test_every_cut_block_of_the_cells_backward_is_counted_once_a_dispatch(
        cell, monkeypatch):
    """flash.diag_tiles{pass=bwd}: the q blocks of the backward's own tiling
    (half the forward's tile) that the diagonal cuts, on the kernels the chip
    takes (the gate is asked as the chip would answer: a trace, never a
    run)."""
    b, n, n_kv, rows, world, bd = CELL_DISPATCHES[cell]
    row = V5E._replace(fwd_block_q=128, fwd_block_kv=128, bwd_block_q=64,
                       bwd_block_kv=128, band_block=32, diag_block=8)
    monkeypatch.setattr(tuning, "block_defaults", lambda device=None: row)
    monkeypatch.setattr(pf, "_interpret_default", lambda: False)
    mesh = Mesh(np.array(jax.devices()[:world]), ("sp",))
    stream = rows * world * (2 if bd else 1)
    q = jax.ShapeDtypeStruct((b, n, stream, 16), jnp.float32)
    kv = jax.ShapeDtypeStruct((b, n_kv, stream, 16), jnp.float32)
    sub, whole = _diag_counted(lambda q, k, v: bat.burst_attn(
        q, k, v, mesh=mesh, causal=bd is None, backend="pallas",
        block_diffusion=bd), q, kv, kv, pass_="bwd")
    blocks = b * n * max(1, rows // 64)
    if bd:
        # `clean` and `below`; the block-diagonal call sweeps a band
        assert (sub, whole) == (2 * blocks, b * n * rows // 32)
    else:
        # the own round; a zigzag ring's later rounds promise nothing
        assert (sub, whole) == (blocks, 0)


@pytest.mark.parametrize("case", ["segments", "contig", "split_kernels",
                                  "striped_round", "jnp_tile", "forward"])
def test_bwd_diag_counter_by_what_the_call_states(case, monkeypatch):
    n, s, d = 4, 256, 16
    q = jax.ShapeDtypeStruct((1, n, s, d), jnp.float32)
    kw = dict(causal=True, backend="pallas", block_q=64, block_kv=64,
              block_q_bwd=32, block_kv_bwd=64)
    world = 1
    if case == "segments":
        kw["segment_ids"] = jnp.zeros((1, s), jnp.int32)
    elif case == "contig":  # its backward promises nothing
        kw["layout"] = "contig"
    elif case == "striped_round":  # every round is full-window causal
        world = 2
        kw["layout"] = "striped"
    elif case == "jnp_tile":
        kw["backend"] = "jnp"
    if case != "split_kernels":  # off the chip the gate takes them
        monkeypatch.setattr(pf, "_interpret_default", lambda: False)
    mesh = Mesh(np.array(jax.devices()[:world]), ("sp",))
    blocks = n * (s // world) // 32
    monkeypatch.setattr(tuning, "block_defaults",
                        lambda device=None: V5E._replace(diag_block=16))
    kv = jax.ShapeDtypeStruct((1, 2, s, d), jnp.float32)
    assert _diag_counted(
        lambda q, k, v: bat.burst_attn(q, k, v, mesh=mesh, **kw),
        q, kv, kv, pass_="bwd") == {
        "segments": (0, blocks), "contig": (0, 0),
        "split_kernels": (0, blocks), "striped_round": (2 * blocks, 0),
        "jnp_tile": (0, 0), "forward": (blocks, 0)}[case]


@pytest.mark.parametrize("case", ["segments", "token_window", "bq_ne_bkv",
                                  "q_range_round", "striped_round",
                                  "jnp_tile"])
def test_diag_counter_reads_whole_where_a_condition_fails(case, monkeypatch):
    n, s, d = 4, 256, 16
    q = jax.ShapeDtypeStruct((1, n, s, d), jnp.float32)
    kw = dict(causal=True, backend="pallas", block_q=64, block_kv=64)
    world = 1
    if case == "segments":
        kw["segment_ids"] = jnp.zeros((1, s), jnp.int32)
    elif case == "token_window":
        kw.update(layout="contig", window=300)
    elif case == "bq_ne_bkv":
        kw.update(block_q=128)
    elif case in ("q_range_round", "striped_round"):
        world = 2
        kw["layout"] = "zigzag" if case == "q_range_round" else "striped"
    elif case == "jnp_tile":
        kw["backend"] = "jnp"
    mesh = Mesh(np.array(jax.devices()[:world]), ("sp",))
    blocks = n * (s // world) // kw["block_q"]
    # an edge that divides the tiles: the conditions decide.  A zigzag ring's
    # later rounds (q_range / kv_range rounds) promise nothing and count
    # under neither path; a striped ring's are full-window causal too
    monkeypatch.setattr(tuning, "block_defaults",
                        lambda device=None: V5E._replace(diag_block=16))
    assert _diag_counted(
        lambda q, k, v: bat.burst_attn(q, k, v, mesh=mesh, **kw),
        q, q, q) == {
        "segments": (0, blocks), "token_window": (0, blocks),
        "bq_ne_bkv": (0, blocks), "q_range_round": (blocks, 0),
        "striped_round": (2 * blocks, 0), "jnp_tile": (0, 0)}[case]


# ---------------------------------------------------------------------------
# one trace of the forward's body a distinct call (flash_fwd's jit, PR 33)


def _fwd_bodies_traced(monkeypatch, world, blocks):
    """How often Pallas traces _fwd_kernel for jax.grad over `blocks`
    chained jax.checkpoint(burst_attn) blocks on a ring of `world`."""
    traced = []
    kernel = pf._fwd_kernel

    def counted(*args, **kw):
        traced.append(1)
        return kernel(*args, **kw)

    monkeypatch.setattr(pf, "_fwd_kernel", counted)
    mesh = Mesh(np.array(jax.devices()[:world]), ("sp",))
    q = jax.ShapeDtypeStruct((1, 8, 256 * world, 16), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, 2, 256 * world, 16), jnp.float32)

    def model(q, k, v):
        block = jax.checkpoint(lambda x: bat.burst_attn(
            x, k, v, mesh=mesh, causal=True, layout="zigzag",
            backend="pallas", block_q=64, block_kv=64))
        for _ in range(blocks):
            q = block(q)
        return jnp.sum(q)

    jax.clear_caches()
    jaxpr = jax.make_jaxpr(jax.grad(model))(q, kv, kv)
    sites = sum(e.primitive.name == "pallas_call"
                and e.params["name"].startswith("burst_flash_fwd")
                for e in iter_eqns(jaxpr))
    return len(traced), sites


@pytest.mark.parametrize("world", [1, 4])
def test_the_forward_s_body_is_traced_once_a_distinct_call_not_once_a_layer(
        world, monkeypatch):
    """PR 32's sub-square sweep cost half a second of trace a call site and
    a model has one a layer (and jax.checkpoint's forward again): behind
    flash_fwd's jit every layer's call hits the trace of the first.  On a
    ring the distinct calls are the self round and the two half-shard
    rounds of the zigzag split."""
    one, sites_one = _fwd_bodies_traced(monkeypatch, world, 1)
    four, sites_four = _fwd_bodies_traced(monkeypatch, world, 4)
    assert one == four == {1: 1, 4: 3}[world]
    # the call sites are all still there: the primal and the recomputed
    # forward of every block (the last block's primal feeds nothing)
    assert sites_four > 4 * one and sites_four > sites_one


def test_what_the_forward_s_trace_answers_for_is_in_its_static_keywords(
        monkeypatch):
    """The switches and the table flash_fwd reads are resolved before its
    jit: a second call of the same shapes under another switch is another
    trace, with no cache cleared between."""
    q = jax.ShapeDtypeStruct((1, 2, 256, 16), jnp.float32)

    def grid():
        return _pallas_calls(lambda q, k, v: pf.flash_fwd(
            q, k, v, None, None, None, 0.25, _block_causal(256, 1, 0),
            block_q=64, block_kv=64, triangular=True, interpret=True),
            q, q, q)

    def kernel():
        return _kernels(jax.make_jaxpr(lambda q, k, v: pf.flash_fwd(
            q, k, v, None, None, None, 0.25, _block_causal(256, 1, 0),
            block_q=64, block_kv=64, triangular=True, interpret=True))(
                q, q, q))

    monkeypatch.setattr(tuning, "block_defaults",
                        lambda device=None: V5E._replace(diag_block=16))
    assert grid() == [("burst_flash_fwd", (1, 2, 2, 5))]
    swept = kernel()
    monkeypatch.setenv("BURST_NO_TRI", "1")
    assert grid() == [("burst_flash_fwd", (1, 2, 4, 4))]
    monkeypatch.delenv("BURST_NO_TRI")
    monkeypatch.setattr(tuning, "block_defaults",
                        lambda device=None: V5E._replace(diag_block=0))
    whole = kernel()
    assert whole != swept
    monkeypatch.setenv("BURST_FWD_LOOP", "1")
    assert kernel() not in (whole, swept)
