"""CPU rehearsal of the Motif-3 cell: the trainer cell end to end at a tiny
size (Pallas kernels interpreted, the grouped product through
`lax.ragged_dot`), the system against the float32 reference at seeded
weights, the cell's entry, the FLOP and byte counts against the
configuration's own arithmetic, the new readers finding nothing to read in
a program without their scopes, and the bounds refusing an 8-bit path."""

import dataclasses
import json
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import flops, flops_motif, moe_readings, peaks, run  # noqa: E402
from chipbench.references import motif_lm  # noqa: E402
from chipbench.runners import train_motif  # noqa: E402

CELL = "train_motif3_gdla_1x4k"
NEW_METRICS = ("mhc_ms_per_step", "mhc_roofline", "gdla_diff_ms_per_step")
# the small size: d 64, 10 heads of which 2 are noise heads, 2 KV
# heads, a window of 8 at 64 tokens, 4 streams, 20 Sinkhorn iterations, 8
# experts (4 held here)
TINY_MOTIF = {
    **run.load_cell(CELL)["config"],
    "name": "tiny_motif", "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 24, "num_attention_heads": 10,
    "num_key_value_heads": 2, "num_noise_heads": 2, "q_lora_rank": 48,
    "kv_lora_rank": 32, "qk_head_dim": 24, "head_dim": 24,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "vocab_size": 512, "sliding_window": 8, "num_experts": 4,
    "router_outputs": 8, "experts_held": [0, 4], "experts_top_k": 2}
TINY_MIX = {"batch": 1, "seq": 64, "sp": 1, "check_seq": 64,
            "file_windows": 8, "token_ids": 64}


@pytest.fixture
def interpreted_kernels(monkeypatch):
    """backend="auto" picks the jnp tile off-chip; the rehearsal wants the
    chip's choice, the Pallas kernels, which then run interpreted."""
    from burst_attn_tpu.parallel import burst

    monkeypatch.setattr(burst, "_resolve_backend",
                        lambda b: "pallas" if b == "auto" else b)


def tiny_cell():
    cell = run.load_cell(CELL)
    cell["config"], cell["traffic"] = TINY_MOTIF, TINY_MIX
    return cell


@pytest.mark.parametrize("trace", [0, 1])
def test_new_cell_runs_end_to_end_at_a_tiny_size(trace, tmp_path,
                                                 interpreted_kernels,
                                                 monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    cell = tiny_cell()
    result, record = run.measure(
        cell, seed=2**31 + 37, seconds=0.5, trace=bool(trace),
        devices=jax.devices()[:1], out_dir=str(tmp_path))
    # interpreted kernels leave no Mosaic call, and half a second is a few
    # steps: whether the loss fell is the chip run's question
    false = {k for k, v in record["checks"].items() if not v}
    assert false <= {"kernels_compiled", "warmup_settled",
                     "loss_fell"}, record["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    listed = cell["per_layer"] if trace else cell["end_to_end"]
    if trace:
        host = {m["name"] for m in listed if m["source"] != "device_trace"}
        assert set(result["metrics"]) == host == {"compile_s"}
    else:
        assert set(result["metrics"]) == {"step_ms", "hbm_gib", "setup_s"}
    json.dumps(result), json.dumps(record)
    assert os.listdir(tmp_path) == []
    session = record["session"]
    errs = session["reference_errors"]
    assert record["checks"]["matches_reference"], errs
    # the last layer's leaves but the bias, which has no gradient
    assert set(errs["grad_rel_by_leaf"]) == {
        "attn_norm", "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b",
        "w_lambda", "w_attn_gate", "wo", "mlp_norm", "router", "w_gate",
        "w_up", "w_down", "shared_gate", "shared_up", "shared_down",
        "expert_poly", "shared_poly", *(f"mhc_{s}_{leaf}" for s in (
            "attn", "mlp") for leaf in ("phi", "alpha", "bias"))}
    assert record["checks"]["params_changed"]
    assert record["checks"]["state_leaves_held"]
    assert session["state_leaves"] == [4, 4]
    assert "bias_balance" in [name for name, _ in record["setup_phases"]]
    assert len(session["bias_balance"]["held_share"]) == 4
    # 4 sparse layers x 64 tokens x 2 choices, half the experts held
    assert 0 < session["moe_slots_here_mean"] < 4 * 64 * 2


def _tiny_f32():
    cfg = train_motif.model_config(TINY_MOTIF)
    return dataclasses.replace(cfg, dtype=jnp.float32)


def test_the_system_is_the_reference_on_seeded_weights():
    """In float32 at the tiny size: logits, loss, chosen sets and every
    leaf's gradient, through the trainer's forward_with_aux (the Pallas-free
    tile) against references/motif_lm.py."""
    from burst_attn_tpu.models import train

    cfg = _tiny_f32()
    mesh = train.make_mesh({"sp": 1}, devices=jax.devices()[:1])
    params = train_motif.init_params(jax.random.PRNGKey(3), cfg, mesh)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, 65), 0, 512)
    x, y = tokens[:, :-1], tokens[:, 1:]
    batch = train.batch_from_host(x, y, cfg, mesh)

    def scalar(params):
        from burst_attn_tpu.models import transformer

        logits, (_, moe) = transformer.forward_with_aux(
            params, batch["tokens"], batch["positions"], cfg, mesh,
            moe_stats=True)
        value = train.masked_nll_sum(logits, batch["labels"]) / y.size
        return value, {"logits": logits, "loss": value, "chosen": moe.choice}

    (_, got), grads = jax.jit(jax.value_and_grad(scalar, has_aux=True))(
        params)
    want = motif_lm.reference(params, x, y, grads_of="all",
                              **train_motif.reference_keywords(TINY_MOTIF))
    np.testing.assert_allclose(got["logits"], want["logits"], atol=2e-4,
                               rtol=2e-4)
    assert abs(float(got["loss"]) - float(want["loss"])) < 1e-5
    assert motif_lm.routing_flips(got["chosen"], want["chosen"])[0] == 0
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(want["grads"])):
        if "router_bias" in jax.tree_util.keystr(path):
            continue
        scale = float(jnp.max(jnp.abs(w))) + 1e-12
        assert float(jnp.max(jnp.abs(g - w))) < 2e-4 * max(scale, 1.0), (
            jax.tree_util.keystr(path))


def test_the_cell_reports_its_metrics():
    cell = run.load_cell(CELL)
    assert {m["name"] for m in cell["end_to_end"]} == {
        "step_ms", "hbm_gib", "setup_s"}
    assert {m["name"] for m in cell["per_layer"]} == {
        *NEW_METRICS, "flash_ms_per_step", "flash_roofline",
        "device_idle_share", "compile_s"}
    assert cell["chips"] == 1 and cell["traffic"]["seq"] == 4096
    assert cell["traffic"]["check_seq"] == 4096
    model = cell["config"]
    assert model["layer_types"] == train_motif.layer_kinds(model) == [
        "sliding_attention", "sliding_attention", "full_attention",
        "sliding_attention", "sliding_attention"]
    cfg = train_motif.model_config(model)
    from burst_attn_tpu.models.transformer import (DenseMLP, ExpertMLP,
                                                   init_params)

    assert [type(s.mlp) for s in cfg.pattern] == [DenseMLP] + [ExpertMLP] * 4
    assert [s.window for s in cfg.pattern] == [128, 128, None, 128, 128]
    shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    # the configuration's 1,411,645,440 matrix parameters + norms (attn, mlp, the
    # two latents a layer and the final), mHC's gains and biases, PolyNorm's
    # weights and the 4 biases
    norms = 5 * (4096 + 4096 + 1024 + 512) + 4096
    mhc = 5 * 2 * (3 + 24)
    poly = 4 + 4 * (8 * 4 + 4)
    assert sum(x.size for x in jax.tree.leaves(shapes)) == (
        1_411_645_440 + norms + mhc + poly + 4 * 384)
    # the new metrics are the new cell's alone
    for name in ("train_kanana2_mla_1x16k", "train_mistral_1x16k"):
        assert not {m["name"] for m in run.load_cell(name)["per_layer"]} & \
            set(NEW_METRICS)


def test_the_configuration_keeps_the_catalog_s_numbers():
    """Every key of the catalog's config, at its value, but the cut's four,
    whose published values the file keeps beside them."""
    model = run.load_cell(CELL)["config"]
    assert set(model["reduced"]) == set(model["reduced_from"]) == {
        "num_hidden_layers", "num_experts", "vocab_size",
        "num_nextn_predict_layers"}
    assert model["reduced_from"] == {"num_hidden_layers": 53,
                                     "num_experts": 384,
                                     "vocab_size": 220160,
                                     "num_nextn_predict_layers": 1}
    assert (model["num_hidden_layers"], model["num_experts"],
            model["vocab_size"]) == (5, 8, 220160 // 8)
    assert model["router_outputs"] == 384 and model["experts_top_k"] == 8


@pytest.mark.parametrize("key, value", [
    ("attention_cls", "mla"), ("diff_v2", False), ("hidden_act", "silu"),
    ("mhc_enabled", False), ("k_ratio", 2), ("num_noise_heads", 8),
    ("sliding_window_pattern", "block"), ("num_nextn_predict_layers", 1),
    ("rope_scaling", {"apply_yarn_scaling": True})])
def test_the_runner_refuses_what_the_program_does_not_compute(key, value):
    train_motif.model_config(TINY_MOTIF)
    with pytest.raises(ValueError, match="this configuration has"):
        train_motif.model_config({**TINY_MOTIF, key: value})


def test_a_layer_pattern_that_is_not_the_source_s_is_refused():
    with pytest.raises(ValueError, match="is not the pattern's"):
        train_motif.model_config({**TINY_MOTIF, "layer_types": [
            "sliding_attention"] * 5})


def test_flop_and_byte_counts_are_the_configuration_s_arithmetic():
    cell = run.load_cell(CELL)
    model, mix = cell["config"], cell["traffic"]
    assert flops_motif.attention_params(model) == 91_750_400
    assert flops_motif.mhc_params(model) == 2 * 16384 * 24
    assert flops_motif.dense_mlp_params(model) == 3 * 4096 * 12288
    assert flops_motif.shared_params(model) == 3 * 4096 * 1280
    assert flops_motif.dense_layers(model) == 1
    assert flops_motif.token_params(model) == (
        5 * (91_750_400 + 786_432) + 150_994_944
        + 4 * (4096 * 384 + 15_728_640) + 27520 * 4096)
    # 806 M matrix parameters a token at the held experts' share of the
    # choices: 4,096 x 8 / 384 pairs an expert, 8 held, 4 sparse layers
    slots = 4 * 4096 * 8 * 8 / 384
    per_token = (flops_motif.token_params(model)
                 + 3 * 4096 * 1280 * slots / 4096)
    assert per_token == pytest.approx(806e6, rel=2e-3)
    step = flops_motif.step_model_flops(model, mix, slots)
    assert step == 6.0 * per_token * 4096 + 3.0 * flops.attention_fwd_flops(
        model, mix)
    assert 6.0 * per_token * 4096 == pytest.approx(19.8e12, rel=2e-3)
    assert 1e3 * step / 197e12 == pytest.approx(109, abs=2)
    # mHC: (2 x 4 + 2) x 4,096 x 2 bytes a token and sublayer pass, 10
    # sublayer passes a forward, 4 forwards' worth a step
    assert flops_motif.mhc_bytes(model, mix) == 81_920 * 4096 * 10 * 4
    assert 1e3 * flops_motif.mhc_least_seconds(
        model, mix, peaks.peak("TPU v5 lite")) == pytest.approx(16.39,
                                                                abs=0.01)


def test_readers_find_nothing_in_a_program_without_their_scopes():
    """The parent's side of a traced run: no device trace, and a trace of a
    program without the scopes; none raises."""
    cell = run.load_cell(CELL)
    reading = {"cell": cell, "steps": [], "trace": None}
    for metric in NEW_METRICS:
        assert run.read_layer_metric(cell, metric, dict(reading)) is None
    trace = {"devices": {"/device:TPU:0": [("%fusion.1 = f32[] fusion()",
                                            0, 10)]}, "steps": 1}
    traced = {"cell": cell, "steps": [], "trace": trace}
    for scope in ("obs.model.mhc", "obs.model.gdla.diff"):
        assert moe_readings.scope_ms_per_step(dict(traced), scope) is None


def test_mhc_roofline_is_the_least_bytes_over_the_scope_s_time(monkeypatch):
    cell = run.load_cell(CELL)
    monkeypatch.setattr(moe_readings, "scope_ms_per_step",
                        lambda reading, scope: 40.0)
    reading = {"cell": cell, "steps": [], "trace": None,
               "device_kind": "TPU v5 lite"}
    got = run.read_layer_metric(cell, "mhc_roofline", reading)
    assert got == pytest.approx(100 * 16.387 / 40, rel=1e-3)
    kanana = run.load_cell("train_kanana2_mla_1x16k")
    assert run.read_layer_metric(kanana, "mhc_roofline",
                                 {**reading, "cell": kanana}) is None


def test_the_bounds_refuse_an_eight_bit_activation_path(monkeypatch):
    """At the tiny size and the cell's check state: the reference with
    every activation rounded through float8 against itself in float32 out of
    at least one bound, beside the program's and bfloat16's readings (the
    bounds are set at the cell's size, not this one)."""
    cell = tiny_cell()
    import chipbench.run as runmod

    monkeypatch.setattr(runmod, "load_cell", lambda name: cell)
    readings, balance = train_motif.check_readings(2**31 + 7)
    assert set(readings) == {"system", "bfloat16", "float8_e4m3fn"}
    for errs, _ in readings.values():
        assert set(errs["token_sum_err"]) == set(motif_lm.TOKEN_SUM_LEAVES)
    errs, ok = readings["float8_e4m3fn"]
    assert not ok, errs
    assert errs["logits_rel_rms"] > motif_lm.TOL_LOGITS_REL_RMS
    # each rounding reads something, bfloat16 less than 8 bits; a rounded
    # reference brings its terms, the program its sums
    low = readings["bfloat16"][0]
    assert 0 < low["logits_rel_rms"] < errs["logits_rel_rms"]
    assert 0 < low["token_term_err"]["mhc_attn_alpha"] < errs[
        "token_term_err"]["mhc_attn_alpha"]
    assert "token_term_err" not in readings["system"][0]
    assert len(balance["held_share"]) == 4


def test_a_gain_is_held_by_its_error_over_its_per_token_terms():
    """The mHC gains' (and biases') gradients come from the reference one a
    token; their sum is the whole gradient, and the bound reads each
    component's error over the sum of its terms' magnitudes, not over the
    sum's."""
    terms = jnp.array([[1.0, 2.0, -1.0], [-1.0, -2.0, 1.5]])  # sums 0, 0, .5
    want = {"logits": jnp.ones((1, 2, 3)), "loss": jnp.float32(1.0),
            "chosen": jnp.zeros((1, 1, 2, 2), jnp.int32),
            "grads": {"wo": jnp.ones(4), "mhc_attn_alpha": terms}}
    off = jnp.array([0.001, -0.001, 0.001])
    got = {**want, "grads": {"wo": jnp.ones(4),
                             "mhc_attn_alpha": terms.sum(0) + off}}
    errs, ok = motif_lm.compare(got, want)
    assert errs["grad_rel_by_leaf"]["mhc_attn_alpha"] == pytest.approx(
        float(jnp.linalg.norm(off)) / 0.5, rel=1e-4)
    # the terms' summed magnitudes 2, 4, 2.5; the sums 0, 0, .5
    mass = float(jnp.linalg.norm(jnp.array([2.0, 4.0, 2.5])))
    assert errs["token_sum_err"]["mhc_attn_alpha"] == pytest.approx(
        float(jnp.linalg.norm(off)) / mass, rel=1e-4)
    assert errs["token_sum_err"]["mhc_attn_alpha"] < errs[
        "grad_rel_by_leaf"]["mhc_attn_alpha"]
    assert errs["token_sum_cancellation"]["mhc_attn_alpha"] == (
        pytest.approx(mass / 0.5))
    assert errs["token_sum_err_max"] < motif_lm.TOL_TOKEN_SUM_ERR and ok
    assert "token_term_err" not in errs
    big = {**got, "grads": {"wo": jnp.ones(4), "mhc_attn_alpha": terms.sum(0)
                            + off * 2 * motif_lm.TOL_TOKEN_SUM_ERR * mass
                            / float(jnp.linalg.norm(off))}}
    assert not motif_lm.compare(big, want)[1]
    # a reference against a reference: its terms' own error too
    own = {**want, "grads": {"wo": jnp.ones(4),
                             "mhc_attn_alpha": terms * 1.1}}
    assert motif_lm.compare(own, want)[0]["token_term_err"][
        "mhc_attn_alpha"] == pytest.approx(0.1)


@pytest.mark.parametrize("dtype", ["bfloat16", "float8_e4m3fn"])
def test_the_reference_s_rounding_is_the_type_s_inside_a_fusion(dtype):
    """Jitted between elementwise products it fuses with, each value is the
    type's nearest (numpy's cast, outside XLA), and the backward is the
    identity: XLA may drop a float32 -> bfloat16 -> float32 round trip
    inside a fusion as excess precision."""
    t = jnp.dtype(dtype).type
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    # magnitudes 2^-14 .. 2^8: an 8-bit float's subnormals to its largest
    x = jax.random.normal(k1, (256, 256)) * 2.0 ** jax.random.uniform(
        k2, (256, 256), minval=-14, maxval=7.5)
    got = jax.jit(lambda a: motif_lm.rounding(t)(a * 1.5) * 3)(x)
    want = np.asarray(x * 1.5).astype(t).astype(np.float32) * 3
    np.testing.assert_array_equal(np.asarray(got), want)
    assert float(jnp.nanmax(jnp.abs(got - x * 4.5))) > 0
    grad = jax.grad(lambda a: jnp.sum(motif_lm.rounding(t)(a) * 2))(x)
    np.testing.assert_array_equal(np.asarray(grad), 2.0)
