"""The layers the Motif-3 configuration brought into the trainer, each against
its own definition at a small size (d 64, 10 heads of which 2 are noise heads,
2 KV heads, a window of 8 at 64 tokens, 4 streams, 20 Sinkhorn iterations, 8
experts): grouped differential latent attention in a sliding and a full layer
against a dense masked softmax, the mHC maps, PolyNorm, the shares of a
PolyNorm sparse layer adding up to the whole; then that the configurations
the benchmark ran before trace the train step they traced before these
existed, and, on the chip, the 80 / 16 call at 192 / 128 at the cell's
length."""

import dataclasses
import hashlib
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import burst_attn_tpu as bat
from burst_attn_tpu.models import train, transformer
from burst_attn_tpu.models.transformer import (
    MHC, DenseMLP, ExpertMLP, GDLAttn, LayerSpec, ModelConfig, init_params)
from burst_attn_tpu.ops.polynorm import PolyNorm, init_weights, poly_norm
from burst_attn_tpu.ops.reference import dense_attention
from burst_attn_tpu.parallel import moe
from burst_attn_tpu.utils.testing import random_qkv

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from chipbench.references import motif_lm  # noqa: E402

KEY = jax.random.PRNGKey(37)
SEQ, WINDOW = 64, 8
ATTN = GDLAttn(kv_latent=32, qk_nope=16, qk_rope=8, v_head=16, q_latent=48,
               kv_heads=2, noise_heads=2)
ACT = PolyNorm()


def _cfg(window, backend="jnp", **kw):
    return ModelConfig(
        vocab=128, d_model=64, n_layers=1, n_heads=10, rope_theta=1e4,
        dtype=jnp.float32, seq_axes=("sp",), batch_axis=None, head_axis=None,
        pattern=(LayerSpec(DenseMLP(96), ATTN, window=window, act=ACT),),
        layout="contig", attn_backend=backend, norm_eps=1e-5, **kw)


def _mesh():
    return Mesh(np.array(jax.devices()[:1]), ("sp",))


# --------------------------------------------------------------------------
# grouped differential latent attention, a sliding and a full layer


def _dense_gdla(p, x, positions, cfg, window):
    """The sublayer from its projections by hand: every query head against
    its KV group's k and v under a dense masked softmax, each group's last
    head its noise head, the difference, the gate and wo."""
    h = transformer._rms_norm(x, p["attn_norm"], cfg.norm_eps)
    q, k, v = transformer._gdla_qkv(p, h, positions, cfg, ATTN)
    o = dense_attention(q, k, v, scale=q.shape[-1] ** -0.5, causal=True,
                        window=window)
    b, n, s, dv = o.shape
    per = n // ATTN.kv_heads
    signal = [i for i in range(n) if i % per != per - 1]
    noise = [i - i % per + per - 1 for i in signal]
    lam = jax.nn.sigmoid(jnp.einsum("bsd,dn->bns", h, p["w_lambda"]))
    gate = jax.nn.sigmoid(jnp.einsum("bsd,dnh->bnsh", h, p["w_attn_gate"]))
    d = o[:, signal] - lam[..., None] * o[:, noise]
    return jnp.einsum("bnsh,nhd->bsd", gate * d, p["wo"])


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("window", [WINDOW, None], ids=["sliding", "full"])
def test_a_gdla_layer_is_a_dense_masked_softmax(window, backend):
    """The sublayer the trainer runs (burst_attn over the 10 / 2 heads at 24 /
    16, the jnp tile or the interpreted kernels) against the same
    projections through dense attention, forward and gradient."""
    cfg = _cfg(window, backend)
    p = init_params(KEY, cfg)["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(1), (1, SEQ, 64))
    positions = jnp.arange(SEQ)[None]
    probe = jax.random.normal(jax.random.PRNGKey(2), (1, SEQ, 64))

    def system(p, x):
        return jnp.sum(probe * transformer._attention(
            p, x, positions, cfg, _mesh(), kind=ATTN, window=window))

    def dense(p, x):
        return jnp.sum(probe * _dense_gdla(p, x, positions, cfg, window))

    got, g_got = jax.value_and_grad(system, (0, 1))(p, x)
    want, g_want = jax.value_and_grad(dense, (0, 1))(p, x)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(a, b, atol=2e-5 * max(1.0, float(
            jnp.max(jnp.abs(b)))))


def test_the_window_reaches_back_to_itself_and_seven_more():
    """A token 8 or more places back leaves a sliding layer's output as it
    was, and moves a full layer's."""
    p = init_params(KEY, _cfg(WINDOW))["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(1), (1, SEQ, 64))
    positions = jnp.arange(SEQ)[None]
    bumped = x.at[:, 40].add(1.0)
    for window, end in ((WINDOW, 40 + WINDOW), (None, SEQ)):
        out = [transformer._attention(p, y, positions, _cfg(window), _mesh(),
                                      kind=ATTN, window=window)[0]
               for y in (x, bumped)]
        changed = np.nonzero(np.abs(out[0] - out[1]).max(-1) > 1e-6)[0]
        assert (changed.min(), changed.max() + 1) == (40, end)


# --------------------------------------------------------------------------
# mHC


def test_h_res_is_doubly_stochastic():
    """20 Sinkhorn-Knopp rounds on maps well away from uniform (logits of
    deviation about 0.6, five times the seeded ones): every row and every
    column of each token's 4 x 4 mix sums to 1 within 1e-5; pre in (0, 1),
    post in (0, 2)."""
    cfg = _cfg(WINDOW, mhc=MHC(streams=4, sinkhorn_iters=20))
    p = dict(init_params(KEY, cfg)["layers"][0])
    p["mhc_attn_alpha"] = jnp.ones((3,))
    p["mhc_attn_bias"] = 0.5 * jax.random.normal(KEY, (24,))
    streams = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, 4, 64))
    pre, post, res = transformer.mhc_maps(p, streams, "attn", cfg)
    assert res.shape == (2, SEQ, 4, 4) and res.dtype == jnp.float32
    assert float(jnp.max(res)) > 0.45  # far from the uniform 1/4
    np.testing.assert_allclose(jnp.sum(res, -1), 1.0, atol=1e-5)
    np.testing.assert_allclose(jnp.sum(res, -2), 1.0, atol=1e-5)
    assert bool(jnp.all((pre > 0) & (pre < 1) & (post > 0) & (post < 2)))
    ref = motif_lm.mhc_maps(streams[0], p, "attn", eps=cfg.norm_eps,
                            iters=cfg.mhc.sinkhorn_iters)
    for a, b in zip((pre[0], post[0], res[0]), ref):
        np.testing.assert_allclose(a, b, atol=1e-5)


# --------------------------------------------------------------------------
# PolyNorm


def test_polynorm_is_its_formula():
    """0.5 (w1 n(u) + w2 n(u^2) + w3 n(u^3) + clip(b, -0.5, 0.5)), n over the
    last axis, in float64 by hand; a bias past the clamp is clamped, and one
    row of weights an expert broadcasts over its rows."""
    u = jax.random.normal(KEY, (3, 5, 24))
    w = init_weights(jax.random.PRNGKey(1), (3, 1)).at[1, 0, 3].set(2.0)

    def n(z):
        return z / np.sqrt(np.mean(z * z, -1, keepdims=True) + 1e-6)

    u64, w64 = np.asarray(u, np.float64), np.asarray(w, np.float64)
    want = 0.5 * (w64[..., :1] * n(u64) + w64[..., 1:2] * n(u64 ** 2)
                  + w64[..., 2:3] * n(u64 ** 3)
                  + np.clip(w64[..., 3:], -0.5, 0.5))
    np.testing.assert_allclose(poly_norm(u, w, ACT), want, rtol=1e-5,
                               atol=1e-5)
    assert poly_norm(u.astype(jnp.bfloat16), w, ACT).dtype == jnp.bfloat16
    # the four terms are seeded apart
    assert len(set(np.round(np.asarray(w[0, 0]), 6))) == 4


def test_the_shares_of_a_polynorm_sparse_layer_add_up_to_the_whole():
    """moe_held with PolyNorm experts over each of four shares of eight
    experts (every share computes the router and, for its own tokens, the
    shared expert: counted once) against the uncut reference's layer."""
    sparse = ExpertMLP(d_ff=12, n_experts=8, top_k=3, score="sigmoid",
                       choice_bias=True, gate_scale=2.0, shared_ff=12)
    cfg = dataclasses.replace(_cfg(None), pattern=(
        LayerSpec(sparse, ATTN, act=ACT),))
    p = init_params(jax.random.PRNGKey(3), cfg)["layers"][0]
    h = jax.random.normal(KEY, (48, 64))
    kw = dict(top_k=3, score="sigmoid", bias=p["router_bias"],
              gate_scale=2.0, act=lambda u, w: poly_norm(u, w, ACT))
    shared = (p["shared_gate"], p["shared_up"], p["shared_down"],
              p["shared_poly"])
    act = dict(scale=0.5, clamp=0.5, eps=1e-6)
    shared_ref = motif_lm._mlp(h, *shared, act)
    parts, choices = [], []
    for lo in range(0, 8, 2):
        mp = moe.MoEParams(p["router"], *(p[k][lo:lo + 2] for k in
                                          ("w_gate", "w_up", "w_down")))
        rows = dict(kw, act_weights=p["expert_poly"][lo:lo + 2])
        y, _, stats = moe.moe_held(mp, h, held=(lo, lo + 2), **rows)
        parts.append(y)
        choices.append(stats.choice)
        with_shared, _, _ = moe.moe_held(mp, h, held=(lo, lo + 2),
                                         shared=shared, **rows)
        np.testing.assert_allclose(with_shared - y, shared_ref, atol=1e-5)
    assert all(jnp.array_equal(c, choices[0]) for c in choices)
    with jax.default_matmul_precision("highest"):
        want, chosen = motif_lm._experts(h, p, held=(0, 8), top_k=3,
                                         gate_scale=2.0, act=act)
    assert jnp.array_equal(jnp.sort(choices[0], -1), jnp.sort(chosen, -1))
    np.testing.assert_allclose(sum(parts) + shared_ref, want, atol=2e-5)


# --------------------------------------------------------------------------
# what the configurations the benchmark ran before trace


def test_mhc_the_per_layer_window_and_the_activation_are_off_by_default():
    spec = LayerSpec(DenseMLP(8))
    assert (spec.attn, spec.window, spec.act) == (None, None, None)
    cfg = ModelConfig()
    assert (cfg.mhc, cfg.norm_eps) == (None, 1e-6)


# sha256[:16] of str(make_jaxpr(...)) of each configuration's timed program
# at 256 tokens, its published widths, one CPU device, under conftest's
# settings: the train step (`train.jit_train_step`) of the three trainer
# configurations as their runners build them, and the op's forward and
# backward (`runners/op.py`).  Taken on the parent (0ceeddc) of the commit that added
# grouped differential latent attention, mHC, a window a layer and PolyNorm;
# none of them is on by default, so nothing of these programs may move.
PARENT_STEPS = {
    "burst_op_mha32x128": "d7ccbe87e209ddcb",
    "kanana2_30b_a3b_ep8_d8": "2b909943a4ddd698",
    "mistral_7b_v02_d4": "d12e501824274d29",
    "sdar_30b_a3b_ep8_d8": "42d617a0be7fed0a",
}


def _config(name):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    file = {c["name"]: c["file"] for c in spec["configs"]}[name]
    return json.loads((ROOT / file).read_text())


def _step_jaxpr(name, seq=256):
    import importlib

    model = _config(name)
    runner = importlib.import_module(f"chipbench.runners.{model['runner']}")
    mesh = train.make_mesh({"sp": 1}, devices=jax.devices()[:1])
    if model["runner"] == "op":
        from chipbench.references import dense_attention as ref

        q = jax.ShapeDtypeStruct((1, model["num_attention_heads"], seq,
                                  model["head_dim"]), jnp.dtype(model["dtype"]))
        attn = lambda q, k, v: bat.burst_attn(
            q, k, v, mesh=Mesh(np.array(jax.devices()[:1]), ("sp",)),
            causal=model["causal"], layout=model["layout"],
            backend=model["backend"])
        return jax.make_jaxpr(ref.fwd_bwd(attn))(q, q, q, q)
    cfg = runner.model_config(model)
    tcfg = (runner.train_config(model, 0) if model["runner"] == "train_bd_moe"
            else getattr(runner, "train_config", lambda m: train.TrainConfig())(
                model))
    opt = train._optimizer(tcfg)
    params, opt_state = jax.eval_shape(
        lambda k: (lambda p: (p, opt.init(p)))(init_params(k, cfg)), KEY)
    specs = train.state_specs(cfg, tcfg, params)

    def placed(shapes, specs):
        return jax.tree.map(lambda spec, x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, spec)),
            specs, shapes, is_leaf=lambda x: isinstance(x, P))

    tokens = jax.ShapeDtypeStruct((1, seq), jnp.int32,
                                  sharding=NamedSharding(mesh, P(None, "sp")))
    batch = {"tokens": tokens, "positions": tokens, "labels": tokens}
    return jax.make_jaxpr(train.jit_train_step(cfg, tcfg, mesh))(
        (placed(params, specs[0]), placed(opt_state, specs[1])), batch)


@pytest.mark.parametrize("name", sorted(PARENT_STEPS))
def test_the_configurations_before_trace_the_parent_s_program(name):
    text = str(_step_jaxpr(name))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        PARENT_STEPS[name]


# --------------------------------------------------------------------------
# the refusals say what they met


@pytest.mark.parametrize("cfg, met", [
    (_cfg(None), "grouped differential latent attention"),
    (_cfg(WINDOW), "a window a layer"),
    (_cfg(None), "PolyNorm MLPs"),
    (ModelConfig(mhc=MHC(streams=4, sinkhorn_iters=20)),
     "the mHC residual of 4 streams"),
], ids=["gdla", "window", "polynorm", "mhc"])
def test_a_caller_that_refuses_names_what_it_met(cfg, met):
    with pytest.raises(ValueError, match=met):
        transformer._mlp({}, jnp.zeros((1, 4, 64)), cfg)


# --------------------------------------------------------------------------
# the compiled kernels at the cell's own geometry (BURST_TESTS_TPU=1, one chip)

on_the_chip = pytest.mark.skipif(jax.default_backend() != "tpu",
                                 reason="the compiled kernels, on the chip "
                                        "(BURST_TESTS_TPU=1)")


@on_the_chip
@pytest.mark.parametrize("window", [None, 128], ids=["full", "window128"])
def test_the_cell_s_80_16_call_against_dense_on_the_chip(window):
    """`train_motif3_gdla_1x4k`'s attention call as the cell runs it (1 x
    4,096 rows, 80 query heads over 16 KV heads, q and k 192 wide, v 128,
    causal or within 128, every tile and grid from ops/tuning.py) against
    dense float32 softmax attention: o, dq, dk, dv."""
    import re

    rows, heads, kv_heads, d_qk, d_v = 4096, 80, 16, 192, 128
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    q, k, v, do = random_qkv(KEY, 1, heads, rows, d_qk, kv_heads=kv_heads,
                             d_v=d_v)
    attn = lambda q, k, v: bat.burst_attn(q, k, v, mesh=mesh, causal=True,
                                          layout="contig", window=window)
    kernels = re.findall(r"burst_flash_\w+", str(jax.make_jaxpr(
        lambda *x: jax.vjp(attn, *x[:3])[1](x[3]))(q, k, v, do)))
    assert len(kernels) == 2 and all(
        ("_band" in name) == (window is not None) for name in kernels), kernels
    o, vjp = jax.vjp(attn, q, k, v)
    got = (o, *vjp(do))
    f32 = lambda x: x.astype(jnp.float32)
    per = heads // kv_heads

    @jax.jit
    def group(q, k, v, do):
        """One KV group: its five query heads against its k and v (a
        group's scores are 320 MiB in float32, all 80 heads' 5 GiB)."""
        o, vjp_ref = jax.vjp(lambda q, k, v: dense_attention(
            q, k, v, causal=True, window=window), q, k, v)
        return (o, *vjp_ref(do))

    errs, peak = np.zeros(4), np.zeros(4)
    with jax.default_matmul_precision("highest"):
        for g in range(kv_heads):
            heads_g = slice(g * per, (g + 1) * per)
            want = group(f32(q[:, heads_g]), f32(k[:, g:g + 1]),
                         f32(v[:, g:g + 1]), f32(do[:, heads_g]))
            mine = (got[0][:, heads_g], got[1][:, heads_g],
                    got[2][:, g:g + 1], got[3][:, g:g + 1])
            for i, (a, b) in enumerate(zip(mine, want)):
                errs[i] = max(errs[i], float(jnp.max(jnp.abs(f32(a) - b))))
                peak[i] = max(peak[i], float(jnp.max(jnp.abs(b))))
    errs = {n: (float(e), float(t))
            for n, e, t in zip(("o", "dq", "dk", "dv"), errs, peak)}
    print(f"PARITY 1 x 4,096 rows x 80 / 16 heads, 192 / 128, window "
          f"{window}, kernels {kernels}, vs dense f32, max abs err "
          "(max |ref|):", errs)
    for name, tol in zip(("o", "dq", "dk", "dv"), (4e-2, 5e-2, 5e-2, 5e-2)):
        err, top = errs[name]
        assert err < tol * max(1.0, top), (name, err)
