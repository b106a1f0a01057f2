"""Attention whose q and k are wider than its v (latent attention: 192 / 128),
through every entry that took one `d`: the Pallas kernels in interpret mode
and the jnp tile against the dense oracle (triangular, rectangular and band
grids, a carried state, sub-range rounds), the ring at world 2 and 4, and a
pin that at equal widths the five accepted cells' calls trace the kernels
they traced before widths could differ.  Then what PR 34's model adds beside
the kernels: the router's choice-only bias, the interleaved rotary pairing,
the program against `chipbench/references/mla_moe_lm.py` at a tiny size, and
the shares of one sparse layer adding up to the whole."""

import hashlib
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import burst_attn_tpu as bat
from burst_attn_tpu import obs
from burst_attn_tpu.analysis.jaxpr_tools import iter_eqns
from burst_attn_tpu.models import train
from burst_attn_tpu.models.transformer import (
    DenseMLP, ExpertMLP, LatentAttn, LayerSpec, ModelConfig,
    _rope_interleaved, forward_with_aux, init_params)
from burst_attn_tpu.ops import pallas_flash as pf, tile, tuning
from burst_attn_tpu.ops.masks import full_spec, round_spec
from burst_attn_tpu.ops.reference import dense_attention
from burst_attn_tpu.parallel import moe
from burst_attn_tpu.utils.testing import check_close, random_qkv

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench.references import mla_moe_lm  # noqa: E402
from test_burst import run_case  # noqa: E402

KEY = jax.random.PRNGKey(34)
S = 256
WIDTHS = [(48, 32), (192, 128)]


def _inputs(d_qk, d_v, s=S, heads=(4, 2)):
    return random_qkv(KEY, 1, heads[0], s, d_qk, kv_heads=heads[1],
                      dtype=jnp.float32, d_v=d_v)


def _causal_spec(s=S):
    return round_spec(jnp.int32(0), jnp.int32(0), s, s, True, "contig")


def _dense_grads(q, k, v, do, **kw):
    def loss(q, k, v):
        o = dense_attention(q, k, v, **kw)
        return jnp.sum(o * do), o

    (_, o), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(q, k, v)
    return o, grads


# --------------------------------------------------------------------------
# (c) the kernels and the jnp tile at d_qk != d_v


FWD_GRIDS = {
    # name: (flash_fwd keywords, dense_attention keywords)
    "triangular": (dict(triangular=True), dict(causal=True)),
    "rectangular": (dict(triangular=False), dict(causal=True)),
    "band": (dict(triangular=True, window=48), dict(causal=True, window=48)),
}


@pytest.mark.parametrize("grid", sorted(FWD_GRIDS))
@pytest.mark.parametrize("d_qk,d_v", WIDTHS)
def test_forward_kernel_and_tile_match_the_dense_oracle(d_qk, d_v, grid):
    """One 192 / 128 tile a head, and a small pair over a 4 x 4 grid."""
    s, block = (128, 128) if d_qk == 192 else (S, 64)
    q, k, v, _ = _inputs(d_qk, d_v, s)
    kw, dense_kw = FWD_GRIDS[grid]
    scale = d_qk ** -0.5
    want = dense_attention(q, k, v, **dense_kw)
    m, lse, acc = pf.flash_fwd(q, k, v, None, None, None, scale,
                               _causal_spec(s), block_q=block,
                               block_kv=block, interpret=True, **kw)
    assert acc.shape == (1, 4, s, d_v)
    check_close(tile.finalize(m, lse, acc, q.dtype), want, rtol=2e-4,
                atol=2e-4, msg=f"kernel {grid}")
    state = tile.init_state(1, 4, s, d_v)
    m, lse, acc = tile.tile_fwd(q, k, v, *state, scale, _causal_spec(s),
                                window=kw.get("window"))
    check_close(tile.finalize(m, lse, acc, q.dtype), want, rtol=2e-4,
                atol=2e-4, msg=f"tile {grid}")
    o = pf.flash_attention(q, k, v, block_q=block, block_kv=block,
                           **dense_kw)
    check_close(o, want, rtol=2e-4, atol=2e-4, msg=f"flash_attention {grid}")


@pytest.mark.parametrize("ranges", ["sliced", "in_place", "q_rows"])
@pytest.mark.parametrize("backend", ["kernel", "tile"])
def test_a_carried_state_folds_two_rounds(backend, ranges):
    """Non-causal attention as two rounds over the halves of kv into one
    carried state (the zigzag ring's past case), with the halves sliced by
    the caller, taken in place by `kv_range`, or one round over the second
    half of the q rows by `q_range`."""
    d_qk, d_v = 48, 32
    q, k, v, _ = _inputs(d_qk, d_v)
    half, scale = S // 2, d_qk ** -0.5
    if backend == "kernel":
        fwd = lambda *a, **kw: pf.flash_fwd(*a, block_q=64, block_kv=64,
                                            interpret=True, **kw)
    else:
        fwd = tile.tile_fwd
    state = tile.init_state(1, 4, S, d_v)
    if ranges == "sliced":
        state = fwd(q, k[:, :, :half], v[:, :, :half], *state, scale,
                    full_spec(S, half))
        state = fwd(q, k[:, :, half:], v[:, :, half:], *state, scale,
                    full_spec(S, half))
        want = dense_attention(q, k, v)
    elif ranges == "in_place":
        for rng in ((0, half), (half, S)):
            state = fwd(q, k, v, *state, scale, full_spec(S, half),
                        kv_range=rng)
        want = dense_attention(q, k, v)
    else:
        state = fwd(q, k, v, *state, scale, full_spec(S - half, S),
                    q_range=(half, S))
        want = dense_attention(q, k, v).at[:, :, :half].set(0.0)
    check_close(tile.finalize(*state, q.dtype), want, rtol=2e-4, atol=2e-4,
                msg=f"{backend} {ranges}")


BWD_KERNELS = {
    # name: (flash_bwd keywords, dense keywords, gradients interpret mode
    # models: the fused rectangular kernel's in-place dq is the chip's)
    "split": (dict(), dict(causal=True), "dq dk dv"),
    "rect": (dict(fused=True), dict(causal=True), "dk dv"),
    "tri": (dict(fused=True, triangular=True), dict(causal=True), "dq dk dv"),
    "band": (dict(fused=True, window=48), dict(causal=True, window=48),
             "dk dv"),
}


@pytest.mark.parametrize("kernel", sorted(BWD_KERNELS))
@pytest.mark.parametrize("d_qk,d_v", WIDTHS)
def test_backward_kernels_and_tile_match_the_dense_oracle(d_qk, d_v, kernel):
    s, block = (128, 64) if d_qk == 192 else (S, 64)
    heads = (4, 4) if kernel == "tri" else (4, 2)
    q, k, v, do = _inputs(d_qk, d_v, s, heads)
    kw, dense_kw, held = BWD_KERNELS[kernel]
    scale = d_qk ** -0.5
    o, want = _dense_grads(q, k, v, do, **dense_kw)
    state = tile.init_state(1, 4, s, d_v)
    _, lse, _ = tile.tile_fwd(q, k, v, *state, scale, _causal_spec(s),
                              window=kw.get("window"))
    delta = jnp.sum(o * do, axis=-1)
    got = pf.flash_bwd(do, q, k, v, delta, lse, scale, _causal_spec(s),
                       block_q=block, block_kv=block, interpret=True, **kw)
    assert [g.shape[-1] for g in got] == [d_qk, d_qk, d_v]
    for name, g, w in zip(("dq", "dk", "dv"), got, want):
        if name in held:
            check_close(g, w, rtol=3e-4, atol=3e-4, msg=f"{kernel} {name}")
    got = tile.tile_bwd(do, q, k, v, delta, lse, scale, _causal_spec(s),
                        window=kw.get("window"))
    for name, g, w in zip(("dq", "dk", "dv"), got, want):
        check_close(g, w, rtol=3e-4, atol=3e-4, msg=f"tile {name}")


@pytest.mark.parametrize("fused", [True, None], ids=["in_kernel", "sliced"])
def test_a_backward_round_over_a_range_adds_into_its_carry(fused):
    """The zigzag ring's two half-shard rounds at d_qk != d_v: `kv_range`
    with a carried (dk, dv), in the fused rectangular kernel and in the
    sliced form, against the jnp tile's."""
    d_qk, d_v = 48, 32
    q, k, v, do = _inputs(d_qk, d_v)
    half, scale = S // 2, d_qk ** -0.5
    state = tile.init_state(1, 4, S, d_v)
    m, lse, acc = tile.tile_fwd(q, k, v, *state, scale, full_spec(S, S))
    delta = jnp.sum(tile.finalize(m, lse, acc, q.dtype) * do, axis=-1)
    carry = (jnp.ones((1, 2, S, d_qk)), jnp.ones((1, 2, S, d_v)))
    kw = dict(kv_range=(0, half), carry=carry)
    want = tile.tile_bwd(do, q, k, v, delta, lse, scale, full_spec(S, half),
                         **kw)
    got = pf.flash_bwd(do, q, k, v, delta, lse, scale, full_spec(S, half),
                       block_q=64, block_kv=64, interpret=True, fused=fused,
                       **kw)
    assert pf.bwd_folds_carry(4, 2, S, S, d_qk, None, (0, half), block_q=64,
                              block_kv=64, interpret=True, fused=fused,
                              d_v=d_v) == bool(fused)
    for name, g, w in zip(("dq", "dk", "dv"), got, want):
        if name != "dq" or not fused:  # interpret mode has no in-place dq
            check_close(g, w, rtol=3e-4, atol=3e-4, msg=name)


def test_the_vmem_gate_reckons_with_both_widths():
    """The triangular backward's residents: q, k, dq, dk at d_qk, do, v, dv
    at d_v; equal widths read what one `d` read."""
    one = pf._tri_bwd_other_residents(1024, 2048, 128)
    assert one == pf._tri_bwd_other_residents(1024, 2048, 128, d_v=128)
    both = pf._tri_bwd_other_residents(1024, 2048, 192, d_v=128)
    wide = pf._tri_bwd_other_residents(1024, 2048, 192)
    assert one < both < wide
    # the cell's call (16,384 rows x 192 / 128) takes the triangular kernel
    assert pf._bwd_kernel_of(32, 32, 16384, 16384, 192, block_q=1024,
                             block_kv=2048, interpret=False,
                             triangular=True, d_v=128) == "tri"


# --------------------------------------------------------------------------
# (d) the ring


@pytest.mark.parametrize("world", [2, 4])
@pytest.mark.parametrize("layout", ["zigzag", "contig"])
def test_the_ring_takes_v_of_its_own_width(world, layout):
    """Forward and jax.grad through burst_attn against the dense oracle."""
    run_case((world,), layout, causal=True, d=48, d_v=32)


def test_the_double_ring_and_the_interpreted_kernels_take_it_too():
    run_case((2, 2), "zigzag", causal=True, d=48, d_v=32, kv_heads=2)
    run_case((2,), "zigzag", causal=True, d=48, d_v=32, backend="pallas",
             seq_per_dev=32, block_q=16, block_kv=16)


def test_the_dispatch_counter_carries_both_widths():
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    counter = obs.counter("burst.dispatch")
    labels = dict(backend="jnp", tile="jnp", d_qk="24", d_v="16")
    before = counter.get(**labels)
    q, k, v, _ = random_qkv(KEY, 1, 2, 32, 24, dtype=jnp.float32, d_v=16)
    o = bat.burst_attn(q, k, v, mesh=mesh, causal=True, backend="jnp")
    assert o.shape == (1, 2, 32, 16)
    assert counter.get(**labels) == before + 1


# --------------------------------------------------------------------------
# (g) at equal widths the accepted cells' calls trace what they traced

# sha256[:16] over every pallas_call (name, grid, kernel jaxpr) of
# jax.grad(burst_attn) at each accepted cell's shapes, bf16, the v5e row, the
# chip's kernel choice, under conftest's settings with the matmul precision
# set back to "default": taken on the parent of PR 34 (ffd1666), whose
# kernels knew one width.  A PR that changes these
# kernels on purpose pins them anew.  PR 35 changed the fused backward's cut
# blocks on purpose: with their sub-square sweep declined (the whole tile on
# the masked path) every kernel is still the one taken then.
PARENT_KERNELS = {
    "op_causal_64k": ("39d296814919625c", 2, 1, (1, 32, 32, 65536), {}),
    "op_causal_64k.parity_8k": ("d3eb7f2a22bca9bc", 2, 1,
                                (1, 32, 32, 8192), {}),
    "ring4_causal_128k": ("9ab85af0dc86a535", 10, 4,
                          (1, 32, 32, 131072), {}),
    "train_mistral_1x8k": ("6f82817f77760d98", 2, 1, (1, 32, 8, 8192), {}),
    "train_mistral_8x1k": ("f6727d30c582432e", 2, 1, (8, 32, 8, 1024), {}),
    "train_sdar_bd_1x8k": ("9d4e99be6eea39ef", 6, 1, (1, 32, 4, 16384),
                           {"block_diffusion": 4}),
}


@pytest.mark.parametrize("cell", sorted(PARENT_KERNELS))
def test_equal_widths_trace_the_parent_s_kernels(cell, monkeypatch):
    digest, calls, world, (b, n, n_kv, s), kw = PARENT_KERNELS[cell]
    monkeypatch.setattr(tuning, "block_defaults",
                        lambda device=None: tuning.generation_row("v5e"))
    monkeypatch.setattr(pf, "_bwd_diag_edge", lambda *a, **kw: None)
    monkeypatch.setattr(pf, "_interpret_default", lambda: False)
    mesh = Mesh(np.array(jax.devices()[:world]), ("sp",))
    q = jax.ShapeDtypeStruct((b, n, s, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((b, n_kv, s, 128), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(bat.burst_attn(
            q, k, v, mesh=mesh, backend="pallas", causal=not kw,
            **kw).astype(jnp.float32))

    with jax.default_matmul_precision("default"):
        jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, kv, kv)
    kernels = [(e.params["name"], tuple(e.params["grid_mapping"].grid),
                str(e.params["jaxpr"])) for e in iter_eqns(jaxpr)
               if e.primitive.name == "pallas_call"]
    text = "\n".join(f"{name} {grid}\n{body}" for name, grid, body in kernels)
    assert len(kernels) == calls
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


# --------------------------------------------------------------------------
# (e) the router


def _router_inputs(t=64, d=16, e=8):
    kx, kr, kb = jax.random.split(KEY, 3)
    return (jax.random.normal(kx, (t, d)),
            0.3 * jax.random.normal(kr, (d, e)),
            0.5 * jax.random.normal(kb, (e,)))


def test_the_bias_moves_a_choice_and_leaves_its_gate_unchanged():
    x, router, bias = _router_inputs()
    plain = moe.route(router, x, 3, score="sigmoid")
    biased = moe.route(router, x, 3, score="sigmoid", bias=bias)
    moved = jnp.any(jnp.sort(plain[1], -1) != jnp.sort(biased[1], -1), -1)
    assert 0 < int(jnp.sum(moved)) < x.shape[0]
    # the choice is the top k of s + b ...
    s = jax.nn.sigmoid(x @ router)
    assert jnp.array_equal(jnp.sort(biased[1], -1),
                           jnp.sort(jax.lax.top_k(s + bias, 3)[1], -1))
    # ... and a gate is s at the chosen expert over the chosen's sum, with
    # no trace of b: where the bias moved nothing, nothing moved
    picked = jnp.take_along_axis(s, biased[1], -1)
    np.testing.assert_allclose(
        biased[0], picked / jnp.sum(picked, -1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(jnp.sort(biased[0][~moved], -1),
                               jnp.sort(plain[0][~moved], -1), rtol=1e-6)
    # a bias in the GATE would read otherwise
    wrong = jnp.take_along_axis(s + bias, biased[1], -1)
    assert not np.allclose(biased[0],
                           wrong / jnp.sum(wrong, -1, keepdims=True),
                           atol=1e-3)
    # no gradient reaches the bias
    grad = jax.grad(lambda b: jnp.sum(moe.route(
        router, x, 3, score="sigmoid", bias=b)[0] ** 2))(bias)
    assert not jnp.any(grad)


def test_gates_sum_to_the_scale():
    x, router, bias = _router_inputs()
    gates, _, probs = moe.route(router, x, 3, score="sigmoid", bias=bias,
                                gate_scale=2.448)
    np.testing.assert_allclose(jnp.sum(gates, -1), 2.448, rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(probs, -1), 1.0, rtol=1e-6)
    soft, _, _ = moe.route(router, x, 3)
    np.testing.assert_allclose(jnp.sum(soft, -1), 1.0, rtol=1e-6)
    with pytest.raises(ValueError, match="sigmoid"):
        moe.route(router, x, 3, bias=bias)
    with pytest.raises(ValueError, match="softmax"):
        moe.route(router, x, 3, score="tanh")


# --------------------------------------------------------------------------
# (f) the interleaved rotary pairing


def test_interleaved_rotary_is_a_complex_rotation():
    x = jax.random.normal(KEY, (2, 3, 16, 8))
    positions = jnp.stack([jnp.arange(16), jnp.arange(16) + 5])
    theta = 1e6
    got = _rope_interleaved(x, positions, theta)
    z = np.asarray(x[..., 0::2]) + 1j * np.asarray(x[..., 1::2])
    freqs = theta ** (-np.arange(0, 8, 2) / 8)
    turn = np.exp(1j * np.asarray(positions)[:, None, :, None] * freqs)
    want = np.stack([(z * turn).real, (z * turn).imag], -1).reshape(x.shape)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # scores turn on position DIFFERENCES only
    a, b = got[0, 0, 3], got[0, 0, 7]
    shifted = _rope_interleaved(x[:1], positions[:1] + 11, theta)
    np.testing.assert_allclose(jnp.dot(a, b),
                               jnp.dot(shifted[0, 0, 3], shifted[0, 0, 7]),
                               rtol=1e-4)


# --------------------------------------------------------------------------
# (a), (b) the program against the reference, and the shares of a layer

ATTN = LatentAttn(kv_latent=24, qk_nope=16, qk_rope=8, v_head=16)
MODEL_KW = dict(top_k=3, gate_scale=2.448, qk_nope=16, kv_latent=24,
                rope_theta=1e6, rms_norm_eps=1e-6)


def _tiny(held=None, backend="jnp"):
    sparse = ExpertMLP(d_ff=12, n_experts=8, top_k=3, held=held,
                       score="sigmoid", choice_bias=True, gate_scale=2.448,
                       shared_ff=24)
    pattern = (LayerSpec(DenseMLP(40), ATTN), LayerSpec(sparse, ATTN))
    return ModelConfig(vocab=64, d_model=32, n_layers=2, n_heads=4,
                       rope_theta=1e6, dtype=jnp.float32, seq_axes=("sp",),
                       batch_axis=None, head_axis=None, pattern=pattern,
                       layout="contig", attn_backend=backend)


def _batch(seq=64):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, seq), 0, 64)
    labels = jnp.concatenate(
        [tokens[:, 1:], -jnp.ones((1, 1), jnp.int32)], axis=1)
    return tokens, labels, jnp.arange(seq)[None]


@pytest.mark.parametrize("held", [None, (2, 5)], ids=["all_held", "a_share"])
def test_the_program_matches_the_reference(held):
    """Logits, loss, chosen sets and every gradient, float32, seeded
    weights: nothing flips, so every leaf is held (the bias's gradient is
    zero on both sides)."""
    cfg = _tiny(held)
    mesh = train.make_mesh({"sp": 1}, devices=jax.devices()[:1])
    params = init_params(jax.random.PRNGKey(0), cfg)
    assert float(jnp.abs(params["layers"][1]["router_bias"]).min()) > 0
    tokens, labels, positions = _batch()

    def scalar(p):
        logits, (_, stats) = forward_with_aux(p, tokens, positions, cfg,
                                              mesh, moe_stats=True)
        value = train.masked_nll_sum(logits, labels) / jnp.sum(labels >= 0)
        return value, (logits, stats.choice)

    (loss, (logits, chosen)), grads = jax.jit(
        jax.value_and_grad(scalar, has_aux=True))(params)
    want = mla_moe_lm.reference(params, tokens, labels, grads_of="all",
                                held=held or (0, 8), **MODEL_KW)
    check_close(logits, want["logits"], rtol=2e-4, atol=2e-4, msg="logits")
    assert abs(float(loss) - float(want["loss"])) < 2e-5
    assert mla_moe_lm.routing_flips(chosen, want["chosen"]) == (0, 64)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    ref = dict(jax.tree_util.tree_flatten_with_path(want["grads"])[0])
    for path, g in flat:
        check_close(g, ref[path], rtol=2e-4, atol=2e-5,
                    msg=jax.tree_util.keystr(path))
    assert not jnp.any(grads["layers"][1]["router_bias"])


def test_the_train_step_holds_the_bias_and_moves_the_rest():
    cfg = _tiny()
    mesh = train.make_mesh({"sp": 1}, devices=jax.devices()[:1])
    tcfg = train.TrainConfig(moe_aux_weight=0.0)
    state = train.init_train_state(jax.random.PRNGKey(0), cfg, tcfg, mesh)
    before = jax.tree.map(np.asarray, state[0])
    tokens, labels, positions = _batch()
    batch = {"tokens": tokens, "positions": positions, "labels": labels}
    (params, _), metrics = train.jit_train_step(cfg, tcfg, mesh)(state, batch)
    assert metrics["moe_slots_here"] == 64 * 3
    for path, new in jax.tree_util.tree_flatten_with_path(params)[0]:
        old = before
        for key in path:
            old = old[getattr(key, "key", getattr(key, "idx", None))]
        same = np.array_equal(old, np.asarray(new))
        assert same == ("router_bias" in jax.tree_util.keystr(path)), path


def test_the_shares_of_a_sparse_layer_add_up_to_the_whole():
    """moe_held over each of four shares of eight experts (every share
    computes the router, and the shared experts for its own tokens: counted
    once) against the uncut reference's layer."""
    cfg = _tiny()
    p = init_params(jax.random.PRNGKey(3), cfg)["layers"][1]
    h = jax.random.normal(KEY, (48, 32))
    kw = dict(top_k=3, score="sigmoid", bias=p["router_bias"],
              gate_scale=2.448)
    shared = (p["shared_gate"], p["shared_up"], p["shared_down"])
    parts, choices = [], []
    for lo in range(0, 8, 2):
        mp = moe.MoEParams(p["router"], *(p[k][lo:lo + 2] for k in
                                          ("w_gate", "w_up", "w_down")))
        y, _, stats = moe.moe_held(mp, h, held=(lo, lo + 2), **kw)
        parts.append(y)
        choices.append(stats.choice)
        with_shared, _, _ = moe.moe_held(mp, h, held=(lo, lo + 2),
                                         shared=shared, **kw)
        np.testing.assert_allclose(
            with_shared - y, mla_moe_lm._swiglu(h, *shared), atol=1e-5)
    assert all(jnp.array_equal(c, choices[0]) for c in choices)
    with jax.default_matmul_precision("highest"):
        want, chosen = mla_moe_lm._experts(h, p, held=(0, 8), top_k=3,
                                           gate_scale=2.448)
    assert jnp.array_equal(jnp.sort(choices[0], -1), jnp.sort(chosen, -1))
    np.testing.assert_allclose(
        sum(parts) + mla_moe_lm._swiglu(h, *shared), want, atol=2e-5)


def test_paths_that_read_the_scalar_knobs_refuse_a_pattern():
    from burst_attn_tpu.models.transformer import _mlp, param_specs

    cfg = _tiny()
    with pytest.raises(ValueError, match="layer pattern"):
        _mlp({}, jnp.zeros((1, 4, 32)), cfg)
    import dataclasses

    with pytest.raises(ValueError, match="layer pattern"):
        param_specs(dataclasses.replace(cfg, pp_axis="pp"))
    with pytest.raises(ValueError, match="describes 2 layers"):
        init_params(KEY, dataclasses.replace(cfg, n_layers=4))


# --------------------------------------------------------------------------
# the compiled kernels at the cell's own geometry (BURST_TESTS_TPU=1, one chip)

on_the_chip = pytest.mark.skipif(jax.default_backend() != "tpu",
                                 reason="the compiled kernels, on the chip "
                                        "(BURST_TESTS_TPU=1)")


@on_the_chip
def test_the_cell_s_attention_at_192_128_against_dense_on_the_chip():
    """`train_kanana2_mla_1x16k`'s attention as the cell runs it (1 x 16,384
    rows, 32 heads, q and k 192 wide, v 128, every tile and grid from
    ops/tuning.py: the triangular forward with the diagonal sweep, the
    triangular fused backward) against dense float32 softmax attention, one
    head at a time (a head's scores are 1 GiB): o, lse, dq, dk, dv."""
    import re

    rows, heads, d_qk, d_v = 16384, 32, 192, 128
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    q, k, v, do = random_qkv(KEY, 1, heads, rows, d_qk, d_v=d_v)
    attn = lambda q, k, v: bat.burst_attn(q, k, v, mesh=mesh, causal=True)
    assert re.findall(r"burst_flash_\w+", str(jax.make_jaxpr(
        lambda *x: jax.vjp(attn, *x[:3])[1](x[3]))(q, k, v, do))) == [
        "burst_flash_fwd", "burst_flash_bwd_tri"]
    o, vjp = jax.vjp(attn, q, k, v)
    got = (o, *vjp(do))
    assert [g.shape[-1] for g in got] == [d_v, d_qk, d_qk, d_v]
    # lse from the kernel the call runs (burst_attn keeps it to itself)
    rb = pf.resolve_blocks(s_q=rows, s_kv=rows)
    # the backward's cut blocks in their live sub-squares (PR 35)
    assert pf.bwd_diag_path(
        heads, heads, rows, rows, d_qk, d_v=d_v, block_q=rb.block_q_bwd,
        block_kv=rb.block_kv_bwd, triangular=True).path == "sub"
    _, lse, _ = jax.jit(lambda q, k, v: pf.flash_fwd(
        q, k, v, None, None, None, d_qk ** -0.5, _causal_spec(rows),
        block_q=rb.block_q, block_kv=rb.block_kv, triangular=True))(q, k, v)

    @jax.jit
    def head(q, k, v, do):
        def dense(q, k, v):
            s = (q @ k.T) * d_qk ** -0.5
            s = jnp.where(jnp.tril(jnp.ones((rows, rows), bool)), s, -jnp.inf)
            lse = jax.nn.logsumexp(s, -1)
            return jnp.exp(s - lse[:, None]) @ v, lse

        (o, lse), vjp_ref = jax.vjp(dense, q, k, v)
        return (o, lse, *vjp_ref((do, jnp.zeros_like(lse))))

    f32 = lambda x: x.astype(jnp.float32)
    names = ("o", "lse", "dq", "dk", "dv")
    errs, peak = np.zeros(5), np.zeros(5)
    with jax.default_matmul_precision("highest"):
        for h in range(heads):
            want = head(f32(q[0, h]), f32(k[0, h]), f32(v[0, h]),
                        f32(do[0, h]))
            mine = (got[0][0, h], lse[0, h], got[1][0, h], got[2][0, h],
                    got[3][0, h])
            for i, (a, b) in enumerate(zip(mine, want)):
                errs[i] = max(errs[i], float(jnp.max(jnp.abs(f32(a) - b))))
                peak[i] = max(peak[i], float(jnp.max(jnp.abs(b))))
    print("PARITY 1 x 16,384 rows x 32 heads, 192 / 128 vs dense f32, max "
          "abs err (max |ref|):",
          {n: (float(e), float(p)) for n, e, p in zip(names, errs, peak)})
    for name, err, top, tol in zip(names, errs, peak,
                                   (4e-2, 4e-2, 5e-2, 5e-2, 5e-2)):
        assert err < tol * max(1.0, top), (name, err)
