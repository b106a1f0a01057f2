"""The main path compiled for a described (not attached) TPU v5e 2x2, at real
widths: what the chip's compiler refuses, it refuses here, at no chip time.

Shapes only — nothing runs, so nothing here is a time or a result.  The
topology is described inside a module-scoped fixture and every test compiles
in this process: only one process may hold libtpu, so all of these stay in
this one file (see the on-chip-measurement guide, section 2).
"""

import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

import burst_attn_tpu as bat
from burst_attn_tpu import obs
from burst_attn_tpu.ops import pallas_flash, tuning

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import chip_smoke  # noqa: E402  (the sizes the chip run uses)

HBM_BYTES = 16e9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu from loading
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these compiles
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture
def on_chip(monkeypatch):
    """The code asks jax.default_backend() which tile and which kernel mode
    to take, and the first device for its block row; answer as the chip
    would (here both would say CPU).  conftest's "highest" matmul precision
    is for the CPU oracle; Mosaic refuses it on bf16 dots, and no chip run
    sets it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(tuning, "block_defaults",
                        lambda device=None: tuning.generation_row("v5e"))
    with jax.default_matmul_precision("default"):
        yield


def _seq_mesh(topo, world):
    return chip_smoke._seq_mesh(topo.devices[:world])


def _device_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _kernels(text):
    return chip_smoke._kernel_facts(text)["kernels"]


def _mosaic_calls(text):
    return chip_smoke._kernel_facts(text)["mosaic_calls"]


def _inplace_rounds():
    """burst.inplace_rounds as {(pass, path): count} (a dispatch counter: it
    advances when the program is traced)."""
    c = obs.counter("burst.inplace_rounds")
    return {(p, path): c.get(**{"pass": p, "path": path})
            for p in ("fwd", "bwd") for path in ("kernel", "xla")}


def _compile_attn_grad(mesh, *, seq, heads=32, kv_heads=32, backend="auto",
                       layout="zigzag", window=None, grad=True, d_qk=128,
                       d_v=128):
    sharding = NamedSharding(mesh, P(None, None, "sp", None))
    q = jax.ShapeDtypeStruct((1, heads, seq, d_qk), jnp.bfloat16,
                             sharding=sharding)
    k = jax.ShapeDtypeStruct((1, kv_heads, seq, d_qk), jnp.bfloat16,
                             sharding=sharding)
    v = jax.ShapeDtypeStruct((1, kv_heads, seq, d_v), jnp.bfloat16,
                             sharding=sharding)

    def fwd(q, k, v):
        return bat.burst_attn(q, k, v, mesh=mesh, causal=True, layout=layout,
                              backend=backend, window=window)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32) ** 2)

    fn = jax.grad(loss, (0, 1, 2)) if grad else fwd
    return jax.jit(fn).lower(q, k, v).compile()


def test_grad_burst_attn_one_chip_64k(topo, on_chip):
    """The paper's op shape (BASELINE.json) on one chip: both passes are
    Mosaic kernels (no kernel gave way to the jnp tile), the backward is
    the triangular fused one, and a one-device ring has no hops."""
    before = _inplace_rounds()
    c = _compile_attn_grad(_seq_mesh(topo, 1), **{
        k: chip_smoke.REAL["op"][k] for k in ("seq", "heads")})
    assert _inplace_rounds() == before  # no round after the self round
    text = c.as_text()
    assert _mosaic_calls(text) == 2
    assert _kernels(text) == ["burst_flash_bwd_tri", "burst_flash_fwd"]
    assert "collective-permute" not in text
    assert _device_bytes(c) < HBM_BYTES


def test_grad_burst_attn_sp4_at_the_multichip_length(topo, on_chip):
    """chip_smoke.py --multichip's ring: hops are collective-permutes and a
    chip's share fits it.  (At 64K per shard, ROADMAP S2's shape, the same
    program needs 16.1 GB a chip.)"""
    size = chip_smoke.REAL_MULTICHIP["op"]
    c = _compile_attn_grad(_seq_mesh(topo, 4), seq=size["seq"],
                           heads=size["heads"])
    text = c.as_text()
    assert text.count("collective-permute-start") > 0
    assert _mosaic_calls(text) > 2
    assert _device_bytes(c) < HBM_BYTES


@pytest.mark.parametrize("world,seq", [(1, 16384), (4, 131072)],
                         ids=["the_cell_s_call", "sp4"])
def test_grad_burst_attn_at_192_128(topo, on_chip, world, seq):
    """Latent attention's widths (q, k 192; v 128) through the kernels and,
    over four chips, through the ring's carries and payloads, at
    `train_kanana2_mla_1x16k`'s call and at the multichip length: Mosaic
    takes a 192-deep contraction and 192-wide dq / dk beside 128-wide
    o / dv, nothing is padded to 256, and a chip's share fits it.  No cell
    measures the ring at these widths (PERF.md section 7)."""
    c = _compile_attn_grad(_seq_mesh(topo, world), seq=seq, d_qk=192,
                           d_v=128)
    text = c.as_text()
    if world == 1:
        assert _kernels(text) == ["burst_flash_bwd_tri", "burst_flash_fwd"]
        assert _mosaic_calls(text) == 2
    else:
        assert text.count("collective-permute-start") > 0
        assert _kernels(text) == ["burst_flash_bwd_rect",
                                  "burst_flash_bwd_tri", "burst_flash_fwd"]
    assert not re.findall(r"bf16\[1,32,\d+,256\]", text)
    assert _device_bytes(c) < HBM_BYTES


def _computations(text):
    """{computation name: its instruction lines} of an HLO module's text."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", line)
        if head and not line.startswith(" "):
            name = head.group(1)
            comps[name] = []
        elif name is not None and line.startswith(" "):
            comps[name].append(line)
    return comps


def _reachable(comps, root):
    """`root` and every computation it calls (fusions, branches, loops)."""
    seen, todo = set(), [root]
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            for called in re.findall(
                    r"(?:calls|body|condition|to_apply|true_computation|"
                    r"false_computation)=%([\w.\-]+)", line):
                todo.append(called)
            for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
                todo += re.findall(r"%([\w.\-]+)", group)
    return seen


def _instructions(lines, opcode, shapes):
    """Names of the `opcode` instructions whose result is one of `shapes`."""
    pat = re.compile(r"\s+(?:ROOT )?%([\w.\-]+) = (\S+?)\{[^ ]* "
                     + re.escape(opcode) + r"\(")
    return [m.group(1) for m in map(pat.match, lines)
            if m and m.group(2) in shapes]


def test_ring_rounds_write_into_their_carries_at_the_cell_length(topo,
                                                                 on_chip):
    """`ring4_causal_128k`'s program (1 x 32 x 131,072 x 128 over sp=4): a
    round after the self round hands its kernel the full-size carry, so XLA
    has nothing left to do around the kernels but add the arriving dq.  The
    instruction names are the chip's (PERF.md section 6, PR 26): before,
    the scan bodies held `add.172`-`174`, `copy.164`, pads and slices of
    the 512 MiB float32 arrays, and the program needed 7.52 GiB a chip."""
    assert chip_smoke.REAL_MULTICHIP["op"]["seq"] == 131072
    before = _inplace_rounds()
    c = _compile_attn_grad(_seq_mesh(topo, 4), seq=131072)
    rounds = {k: v - before[k] for k, v in _inplace_rounds().items()}
    assert rounds == {("fwd", "kernel"): 3, ("bwd", "kernel"): 3,
                      ("fwd", "xla"): 0, ("bwd", "xla"): 0}
    text = c.as_text()
    comps = _computations(text)
    full = "f32[1,32,32768,128]"
    halves = ["f32[1,32,16384,128]", "bf16[1,32,16384,128]"]
    everything = [line for lines in comps.values() for line in lines]

    loops = re.findall(r" while\(.*?body=%([\w.\-]+)", text)
    assert len(loops) == 2  # the forward's scan and the backward's
    for body in loops:
        adds = [name for comp in _reachable(comps, body)
                for name in _instructions(comps[comp], "add", [full])]
        assert len(adds) <= 1, (body, adds)  # dq_rot + dqc, and no other
    for opcode in ("pad", "concatenate", "slice"):
        assert not _instructions(everything, opcode, [full] + halves)
    # the forward's accumulator is updated where it lies (was copy.164)
    assert not _instructions(everything, "copy", [full])
    assert not _instructions(everything, "dynamic-update-slice", [full])

    assert _kernels(text) == ["burst_flash_bwd_rect", "burst_flash_bwd_tri",
                              "burst_flash_fwd"]
    assert _device_bytes(c) < (7.52 - 1.0) * 2 ** 30


@pytest.mark.parametrize("kw,kernels", [
    (dict(window=4096, layout="contig"),
     ["burst_flash_bwd_band", "burst_flash_fwd_band"]),
    (dict(kv_heads=4), ["burst_flash_bwd_rect", "burst_flash_fwd"]),
], ids=["window4k_band_grid", "gqa_32q_4kv"])
def test_grad_variants_one_chip_64k(topo, on_chip, kw, kernels):
    """The band grids (under their own names, in the row's tiles: a window
    of 4096 is no narrower than they) and a GQA group of 8 at 64K: neither
    admits the triangular backward, both take the rectangular fused kernel."""
    c = _compile_attn_grad(_seq_mesh(topo, 1), seq=65536, **kw)
    text = c.as_text()
    assert _mosaic_calls(text) == 2
    assert _kernels(text) == kernels


def test_grad_block_diffusion_at_the_cell_geometry(topo, on_chip):
    """`train_sdar_bd_1x8k`'s attention (a stream of 2 x 8,192 rows, 32 / 4
    heads x 128, blocks of 4) forward and backward: the block-diagonal
    quadrant's two calls are the band-grid kernels, in the tiles
    ops/tuning.call_row gives a band of 4 tokens, and the program holds one
    call a live quadrant and pass: none over the empty quadrant."""
    sharding = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((1, 32, 16384, 128), jnp.bfloat16,
                             sharding=sharding)
    kv = jax.ShapeDtypeStruct((1, 4, 16384, 128), jnp.bfloat16,
                              sharding=sharding)
    mesh = _seq_mesh(topo, 1)

    def loss(q, k, v):
        return jnp.sum(bat.burst_attn(q, k, v, mesh=mesh, backend="auto",
                                      block_diffusion=4).astype(
                                          jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, kv, kv).compile(
        ).as_text()
    assert _kernels(text) == ["burst_flash_bwd_band", "burst_flash_bwd_rect",
                              "burst_flash_fwd", "burst_flash_fwd_band"]
    assert _mosaic_calls(text) == 6
    calls = re.findall(r"%(burst_flash_\w+?)(?:\.\d+)? = ", text)
    assert sorted(calls) == ["burst_flash_bwd_band"] + [
        "burst_flash_bwd_rect"] * 2 + ["burst_flash_fwd"] * 2 + [
        "burst_flash_fwd_band"]


@pytest.mark.parametrize("packed", [False, True], ids=["plain", "packed"])
def test_tri_bwd_compiles_at_the_largest_shape_it_admits(topo, on_chip,
                                                         packed):
    """tri_bwd_supported is a hand model of Mosaic's VMEM use and nothing
    catches a compile failure behind it: the largest sequence it admits at
    the default blocks must compile, with and without segment ids."""
    from burst_attn_tpu.ops.masks import round_spec

    rb = tuning.resolve_blocks()
    bq, bkv = rb.block_q_bwd, rb.block_kv_bwd
    s = max(s for s in range(2 * bkv, 1 << 18, 2 * bkv)
            if pallas_flash.tri_bwd_supported(s, s, 1, 1, 128, block_q=bq,
                                              block_kv=bkv))
    assert s >= 65536  # the paper's per-chip length takes this kernel
    one = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    args = ([shape((1, 1, s, 128), jnp.bfloat16)] * 4
            + [shape((1, 1, s), jnp.float32)] * 2
            + ([shape((1, s), jnp.int32)] * 2 if packed else []))

    def bwd(do, q, k, v, delta, lse, *segments):
        spec = round_spec(jnp.int32(0), jnp.int32(0), s, s, True, "contig")
        return pallas_flash.flash_bwd(
            do, q, k, v, delta, lse, 128 ** -0.5, spec, block_q=bq,
            block_kv=bkv, triangular=True, segments=segments or None)

    text = jax.jit(bwd).lower(*args).compile().as_text()
    assert _kernels(text) == ["burst_flash_bwd_tri"]
    assert _mosaic_calls(text) == 1


def test_train_step_at_the_chip_smoke_size(topo, on_chip):
    """chip_smoke.py's model at its sequence length: the whole jitted step
    (forward, backward, AdamW) fits one chip."""
    from burst_attn_tpu.models import runner, train
    from burst_attn_tpu.models.transformer import init_params

    cfg, tcfg, run, _ = runner.parse_args(chip_smoke._train_argv(
        "unused.batd", mesh="sp=1", seed=0, **chip_smoke.REAL["train"]))
    mesh = train.make_mesh({"sp": 1}, devices=topo.devices)
    opt = train._optimizer(tcfg)

    def init(key):
        params = init_params(key, cfg)
        return params, opt.init(params)

    params, opt_state = jax.eval_shape(init, jax.random.PRNGKey(0))
    specs = train.state_specs(cfg, tcfg, params)

    def placed(shapes, specs):
        return jax.tree.map(
            lambda spec, x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, spec)),
            specs, shapes, is_leaf=lambda x: isinstance(x, P))

    state = (placed(params, specs[0]), placed(opt_state, specs[1]))
    tokens = jax.ShapeDtypeStruct((run.batch, run.seq_len), jnp.int32,
                                  sharding=NamedSharding(mesh, P(None, "sp")))
    batch = {"tokens": tokens, "positions": tokens, "labels": tokens}
    c = train.jit_train_step(cfg, tcfg, mesh).lower(state, batch).compile()
    text = c.as_text()
    assert sum(x.size for x in jax.tree.leaves(params)) == 1_208_027_136
    assert _kernels(text) == ["burst_flash_bwd_tri", "burst_flash_fwd"]
    # one forward, one recomputed forward and one backward a layer
    assert _mosaic_calls(text) == 3 * cfg.n_layers
    assert _device_bytes(c) < 15.75 * 2**30 - 2**30  # a GiB to spare


def test_ragged_paged_attention_compiles(topo, on_chip):
    """The serving kernel at d_head 128, page 128, 32 q / 4 kv heads, a
    mixed prefill chunk: the next bring-up's first fact."""
    from burst_attn_tpu.ops.ragged_paged import ragged_paged_attention

    one = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    slots, n_q, n_kv, chunk, d, page, n_pages, cols = 8, 32, 4, 128, 128, 128, 512, 64
    pool = shape((n_pages, n_kv, page, d), jnp.bfloat16)
    text = jax.jit(ragged_paged_attention).lower(
        shape((slots, n_q, chunk, d), jnp.bfloat16), pool, pool,
        shape((slots, cols), jnp.int32), shape((slots,), jnp.int32),
        shape((slots,), jnp.int32)).compile().as_text()
    assert _mosaic_calls(text) == 1


def test_grad_mhc_pass_at_the_motif_cell_geometry(topo, on_chip):
    """One mHC sublayer pass of `train_motif3_gdla_1x4k` (1 x 4,096 tokens,
    4 streams of 4,096, 20 Sinkhorn-Knopp rounds) and its gradient: the
    four kernels of ops/mhc.py and nothing float32 of the streams' size
    around them (the float32 views of the streams the jnp path makes)."""
    from burst_attn_tpu.ops import mhc

    one = SingleDeviceSharding(topo.devices[0])
    shape = lambda s, dtype: jax.ShapeDtypeStruct(s, dtype, sharding=one)
    n, d, rows = 4, 4096, 4096

    def loss(x, phi, alpha, bias):
        u, maps, x = mhc.mhc_pre(x, phi, alpha, bias, streams=n, eps=1e-6,
                                 iters=20)
        y = mhc.mhc_post(x, maps, u, streams=n, clamp=1e6)
        return jnp.sum(y[:, :8].astype(jnp.float32) ** 2)

    c = jax.jit(jax.grad(loss, (0, 1, 2, 3))).lower(
        shape((1, rows, n * d), jnp.bfloat16),
        shape((n * d, 2 * n + n * n), jnp.float32),
        shape((3,), jnp.float32), shape((2 * n + n * n,), jnp.float32),
    ).compile()
    text = c.as_text()
    assert sorted(set(re.findall(r"%(mhc_\w+?)(?:\.\d+)? = ", text))) == [
        "mhc_post_bwd", "mhc_post_fwd", "mhc_pre_bwd", "mhc_pre_fwd"]
    assert _mosaic_calls(text) == 4
    assert f"f32[1,{rows},{n * d}]" not in text
    assert _device_bytes(c) < 1e9
