"""Which blocks and which grid a tile call gets (ops/tuning.call_row through
BurstConfig.resolved_blocks and burst._tile_fwd / _tile_bwd): the benchmark
cells' calls under the v5e row, a caller's own blocks, and that the op cells'
programs trace what they traced before the resolution saw a call's geometry."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import burst_attn_tpu as bat
from burst_attn_tpu.ops import pallas_flash as pf, tuning
from burst_attn_tpu.ops.masks import BlockUnits
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


def _pallas_calls(fn, *args):
    """[(kernel name, grid)] of every pallas_call in fn's jaxpr."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append((eqn.params["name"],
                              tuple(eqn.params["grid_mapping"].grid)))
            for value in eqn.params.values():
                for x in value if isinstance(value, (list, tuple)) else [value]:
                    inner = getattr(x, "jaxpr", x)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


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


def _op_cell_digest(world, seq):
    """sha256[:16] of the jaxpr of an op cell's program (chipbench/runners/
    op.py: forward + backward of burst_attn, causal, zigzag, 32 heads x 128,
    bf16), with the Pallas tile named outright (on the CPU "auto" is jnp)."""
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

    return hashlib.sha256(
        str(jax.make_jaxpr(run)(q, q, q, q)).encode()).hexdigest()[:16]


# taken on the parent commit of PR 29 (759fd86) with this function, under
# conftest's CPU settings: the resolution now sees each call's rows, and at
# the op cells' rows it must return what the parent's one row gave
PARENT_OP_CELLS = {
    "op_causal_64k": (1, 65536, "6cdbcf9fe50b186a"),
    "ring4_causal_128k": (4, 131072, "c46725882cf5b8fa"),
}


@pytest.mark.parametrize("cell", sorted(PARENT_OP_CELLS))
def test_the_op_cells_trace_the_parent_s_jaxprs(cell):
    world, seq, digest = PARENT_OP_CELLS[cell]
    assert _op_cell_digest(world, seq) == digest
