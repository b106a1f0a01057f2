"""ops/mhc.py's kernels (Pallas, interpreted here) against the jnp mHC of
models/transformer.py (`_mhc_pre`, `_mhc_post`) at a small size: n = 4
streams of d = 256, 256 tokens, 20 Sinkhorn-Knopp rounds, the clamp hit by
some entries; the forward outputs and the gradients through a pre -> post ->
pre -> post chain; then a two-layer mHC model's train step with the kernels
taken, its kernels under `obs.model.mhc` in every phase and `mhc.fused`'s
count; and, on the chip, the kernels at `train_motif3_gdla_1x4k`'s geometry
against the float32 reference's maps."""

import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from burst_attn_tpu import obs
from burst_attn_tpu.models import train, transformer
from burst_attn_tpu.models.transformer import (
    MHC, DenseMLP, LayerSpec, ModelConfig)
from burst_attn_tpu.ops import mhc

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from chipbench.references import motif_lm  # noqa: E402

N, D, S, ITERS, EPS = 4, 256, 256, 20, 1e-6
WIDTH = 2 * N + N * N


def _inputs(dtype, key=0):
    """phi (its values bfloat16's, so that both sides multiply the same
    numbers), gains and biases that move the maps well away from uniform,
    the streams [1, S, n d], a sublayer output [1, S, d], and a cotangent of
    the chain's streams."""
    k = jax.random.split(jax.random.PRNGKey(key), 5)
    phi = (0.02 * jax.random.normal(k[0], (N * D, WIDTH))).astype(
        jnp.bfloat16).astype(jnp.float32)
    alpha = jnp.array([1.0, 0.7, 1.3], jnp.float32)
    bias = 0.5 * jax.random.normal(k[1], (WIDTH,), jnp.float32)
    x = (1.5 * jax.random.normal(k[2], (1, S, N * D))).astype(dtype)
    f = jax.random.normal(k[3], (1, S, D)).astype(dtype)
    return (x, f, phi, alpha, bias), jax.random.normal(k[4], (1, S, N * D))


def _cfg(clamp):
    return ModelConfig(d_model=D, mhc=MHC(streams=N, sinkhorn_iters=ITERS,
                                          clamp=clamp), norm_eps=EPS)


def _kernels(clamp):
    """(pre, post) on the flat streams through ops/mhc.py."""
    def pre(x, phi, alpha, bias):
        return mhc.mhc_pre(x, phi, alpha, bias, streams=N, eps=EPS,
                           iters=ITERS)

    def post(x, maps, f):
        return mhc.mhc_post(x, maps, f, streams=N, clamp=clamp)

    return pre, post


def _jnp(clamp):
    """The same through transformer._mhc_pre / _mhc_post."""
    cfg = _cfg(clamp)
    split = lambda x: x.reshape(1, S, N, D)

    def pre(x, phi, alpha, bias):
        p = {"mhc_attn_phi": phi, "mhc_attn_alpha": alpha,
             "mhc_attn_bias": bias}
        return (*transformer._mhc_pre(p, split(x), "attn", cfg), x)

    def post(x, maps, f):
        return transformer._mhc_post(split(x), maps, f, cfg).reshape(x.shape)

    return pre, post


def _chain(passes, wt):
    """pre -> post (the sublayer's output f times its input u) -> pre ->
    post (the output u): the loss sum(wt * streams), every map, u and the
    streams on the path to it."""
    pre, post = passes

    def loss(x, f, phi, alpha, bias):
        u, maps, x = pre(x, phi, alpha, bias)
        x = post(x, maps, (f.astype(jnp.float32)
                           * u.astype(jnp.float32)).astype(f.dtype))
        u, maps, x = pre(x, phi, alpha, bias)
        x = post(x, maps, u)
        return jnp.sum(x.astype(jnp.float32) * wt)

    return loss


def _rel(a, b):
    a, b = (jnp.asarray(t, jnp.float32) for t in (a, b))
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("dtype, clamp", [
    (jnp.float32, 3.0), (jnp.bfloat16, 3.0), (jnp.bfloat16, None)],
    ids=["f32", "bf16", "bf16-no-clamp"])
def test_the_forward_is_the_jnp_one(dtype, clamp):
    (x, f, phi, alpha, bias), _ = _inputs(dtype)

    @jax.jit
    def both(x, f, phi, alpha, bias):
        out = []
        for pre, post in (_kernels(clamp), _jnp(clamp)):
            u, maps, x1 = pre(x, phi, alpha, bias)
            out.append((u, maps, post(x1, maps, f)))
        return out

    (u, packed, y), (want_u, want_maps, want_y) = both(x, f, phi, alpha,
                                                       bias)
    for got, want in zip(mhc.unpack_maps(packed, N), want_maps):
        np.testing.assert_allclose(got, want, atol=2e-6)
    # the streams' dtype: equal to rounding, one unit in the last place
    tol = 1e-5 if dtype == jnp.float32 else 2.0 ** -7
    for got, want in ((u, want_u), (y, want_y)):
        got, want = (np.asarray(t, np.float32) for t in (got, want))
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    if clamp is not None:
        hit = float(jnp.mean(jnp.abs(want_y.astype(jnp.float32)) >= clamp))
        assert 0.005 < hit < 0.2, hit  # the clamp binds, on some entries


@pytest.mark.parametrize("dtype, clamp", [
    (jnp.float32, 3.0), (jnp.float32, None), (jnp.bfloat16, 3.0)],
    ids=["f32", "f32-no-clamp", "bf16"])
def test_the_gradients_are_the_jnp_ones(dtype, clamp):
    """In float32 the kernels' value and gradients are the jnp path's to
    float32's rounding; in bfloat16 they are as far from the float32 chain
    as the jnp path in bfloat16 is (both round the streams and their
    cotangents to bfloat16 at every pass)."""
    args, wt = _inputs(dtype)
    grad = lambda passes: jax.jit(jax.value_and_grad(
        _chain(passes, wt), argnums=range(5)))
    (got_loss, got), (want_loss, want) = (
        grad(_kernels(clamp))(*args), grad(_jnp(clamp))(*args))
    names = ("x", "f", "phi", "alpha", "bias")
    if dtype == jnp.float32:
        assert abs(float(got_loss - want_loss)) < 1e-5 * abs(float(want_loss))
        for name, a, b in zip(names, got, want):
            assert _rel(a, b) < 2e-6, name
        return
    f32_args = tuple(a.astype(jnp.float32) for a in args)
    _, truth = grad(_jnp(clamp))(*f32_args)
    for name, a, b, t in zip(names, got, want, truth):
        assert _rel(a, t) < 2.0 * _rel(b, t) + 1e-3, (name, _rel(a, t),
                                                      _rel(b, t))


# --------------------------------------------------------------------------
# the model's train step with the kernels taken

def _model(mesh_devices=1):
    """A float32 two-layer mHC model whose layers are of two kinds (each
    traces its own block), its train config, mesh, state and a batch."""
    cfg = ModelConfig(
        vocab=128, d_model=128, n_layers=2, n_heads=2, n_kv_heads=2,
        d_head=64, seq_axes=("sp",), batch_axis=None, head_axis=None,
        attn_backend="jnp", layout="contig", remat=True, dtype=jnp.float32,
        pattern=(LayerSpec(DenseMLP(256)), LayerSpec(DenseMLP(128))),
        mhc=MHC(streams=N, sinkhorn_iters=ITERS, clamp=1e6))
    tcfg = train.TrainConfig()
    mesh = train.make_mesh({"sp": mesh_devices},
                           devices=jax.devices()[:mesh_devices])
    state = train.init_train_state(jax.random.PRNGKey(1), cfg, tcfg, mesh)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 128), 0, 128)
    return cfg, tcfg, mesh, state, {
        "tokens": tokens, "positions": jnp.arange(128)[None],
        "labels": jnp.roll(tokens, -1, axis=1)}


def _fused_count():
    c = obs.counter("mhc.fused")
    return np.array([c.get(op="pre"), c.get(op="post")])


def test_the_train_step_takes_the_kernels_under_the_mhc_scope(monkeypatch):
    """With the kernels taken (interpreted): mhc.fused counts each layer's
    two passes, every kernel's op_name is under `obs.model.mhc` in the
    forward, the recomputed forward and the backward (so mhc_ms_per_step
    reads all of mHC), and the step's loss and gradient norm are the jnp
    path's."""
    cfg, tcfg, mesh, state, batch = _model()
    # the step donates its state: each side is given a copy
    want = train.jit_train_step(cfg, tcfg, mesh)(
        jax.tree.map(jnp.copy, state), batch)[1]
    monkeypatch.setattr(mhc, "engaged", lambda: True)
    before = _fused_count()
    step = train.jit_train_step(cfg, tcfg, mesh).lower(state, batch).compile()
    # two sublayer passes a layer, each one pre and one post
    assert list(_fused_count() - before) == [4, 4]
    op_names = set(re.findall(
        r'op_name="(jit\(step\)/[^"]*/(mhc_(?:pre|post)_(?:fwd|bwd)))/',
        step.as_text()))
    phases = {}
    for op_name, kernel in op_names:
        assert "obs.model.mhc" in op_name, op_name
        phases.setdefault(kernel, set()).add(obs.phase_of(op_name)[0])
    assert phases == {"mhc_pre_fwd": {"fwd", "remat"},
                      "mhc_post_fwd": {"fwd", "remat"},
                      "mhc_pre_bwd": {"bwd"}, "mhc_post_bwd": {"bwd"}}
    got = step(state, batch)[1]
    for key in ("loss", "grad_norm"):
        assert abs(float(got[key] - want[key])) < 1e-5 * float(want[key]), key


@pytest.mark.parametrize("chip, devices", [(False, 1), (True, 2)],
                         ids=["off-the-chip", "sp2-on-the-chip"])
def test_the_jnp_passes_serve_elsewhere(chip, devices, monkeypatch):
    """Off the chip, and on the chip on a mesh that shards the tokens, the
    model keeps the jnp passes: no kernel, no count."""
    monkeypatch.setattr(mhc, "engaged", lambda: chip)
    cfg, tcfg, mesh, state, batch = _model(devices)
    before = _fused_count()
    text = str(jax.make_jaxpr(train.jit_train_step(cfg, tcfg, mesh))(
        state, batch))
    assert "pallas_call" not in text and list(_fused_count() - before) == [
        0, 0]


# --------------------------------------------------------------------------
# the compiled kernels at the cell's own geometry (BURST_TESTS_TPU=1)

on_the_chip = pytest.mark.skipif(jax.default_backend() != "tpu",
                                 reason="the compiled kernels, on the chip "
                                        "(BURST_TESTS_TPU=1)")


@on_the_chip
def test_the_cell_s_passes_against_the_float32_reference_on_the_chip():
    """`train_motif3_gdla_1x4k`'s sublayer pass (1 x 4,096 tokens, 4
    streams of 4,096, 20 rounds, the clamp 1e6) through the kernels against
    the float32 one of `motif_lm.mhc_maps` (highest precision): u, the
    maps, the streams after, and the gradients with respect to x, f, phi,
    alpha and bias of a seeded cotangent of u and the streams."""
    rows, n, d, clamp = 4096, 4, 4096, 1e6
    k = jax.random.split(jax.random.PRNGKey(38), 6)
    width = 2 * n + n * n
    phi = 0.02 * jax.random.normal(k[0], (n * d, width), jnp.float32)
    alpha = jnp.array([0.8, 0.5, 1.2], jnp.float32)
    bias = 0.5 * jax.random.normal(k[1], (width,), jnp.float32)
    x = jax.random.normal(k[2], (1, rows, n * d)).astype(jnp.bfloat16)
    f = jax.random.normal(k[3], (1, rows, d)).astype(jnp.bfloat16)
    du = jax.random.normal(k[4], (1, rows, d)).astype(jnp.bfloat16)
    dy = jax.random.normal(k[5], (1, rows, n * d)).astype(jnp.bfloat16)

    def kernels(x, f, phi, alpha, bias):
        u, maps, x1 = mhc.mhc_pre(x, phi, alpha, bias, streams=n, eps=EPS,
                                  iters=ITERS)
        return u, mhc.unpack_maps(maps, n), mhc.mhc_post(
            x1, maps, f, streams=n, clamp=clamp)

    def reference(x, f, phi, alpha, bias):
        p = {"mhc_attn_phi": phi, "mhc_attn_alpha": alpha,
             "mhc_attn_bias": bias}
        xs = x[0].reshape(rows, n, d)
        pre, post, res = maps = motif_lm.mhc_maps(xs, p, "attn", eps=EPS,
                                                  iters=ITERS)
        y = jnp.clip(jnp.einsum("sij,sjd->sid", res, xs)
                     + post[:, :, None] * f[0][:, None], -clamp, clamp)
        return (jnp.einsum("sn,snd->sd", pre, xs)[None],
                tuple(m[None] for m in maps), y.reshape(1, rows, n * d))

    def run(fn, *args):
        out, vjp = jax.vjp(fn, *args)
        zeros = jax.tree.map(jnp.zeros_like, out[1])
        return out, vjp((du.astype(out[0].dtype), zeros,
                         dy.astype(out[2].dtype)))

    f32 = lambda t: t.astype(jnp.float32)
    got = jax.jit(lambda *a: run(kernels, *a))(x, f, phi, alpha, bias)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *a: run(reference, *a))(
            f32(x), f32(f), phi, alpha, bias)
    names = ["u", "pre", "post", "res", "y", "dx", "df", "dphi", "dalpha",
             "dbias"]
    errs = {}
    for name, a, b in zip(names, jax.tree.leaves(got), jax.tree.leaves(want)):
        a, b = f32(a), f32(b)
        errs[name] = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
    print("PARITY mhc 1 x 4,096 x 4 x 4,096, 20 rounds, kernels (bf16 "
          "streams) vs float32 reference, max abs err / max |ref|:", errs)
    for name, err in errs.items():
        assert err < 2e-2, (name, err)
