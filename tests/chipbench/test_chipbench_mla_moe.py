"""CPU rehearsal of what PR 34 added to the benchmark: the latent-attention
MoE trainer cell end to end at a tiny size (Pallas kernels interpreted, the
grouped product through `lax.ragged_dot`), the second 16K cell's entry, the new
FLOP counts against the issue's arithmetic, the new readers finding nothing to
read in a program without their scopes, and the bounds refusing an 8-bit
path."""

import json
import os
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import flops_mla_moe, moe_readings, peaks, run  # noqa: E402
from chipbench.runners import train_mla_moe  # noqa: E402

CELL = "train_kanana2_mla_1x16k"
NEW_METRICS = ("mla_flash_roofline", "mla_proj_ms_per_step",
               "moe_shared_ms_per_step", "mla_step_mfu")
TINY_KANANA = {
    "name": "tiny_kanana", "runner": "train_mla_moe",
    "reference": "mla_moe_lm", "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 2,
    "first_k_dense_replace": 1, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 8, "kv_lora_rank": 32,
    "qk_head_dim": 24, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "q_lora_rank": None, "rope_interleave": True,
    "vocab_size": 512, "rope_theta": 1e6, "rms_norm_eps": 1e-6,
    "n_routed_experts": 4, "router_outputs": 8, "experts_held": [0, 4],
    "num_experts_per_tok": 3, "n_shared_experts": 2, "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "routed_scaling_factor": 2.448,
    "router_aux_loss_coef": 0.0}
TINY_MIX = {"batch": 1, "seq": 128, "sp": 1, "check_seq": 64,
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
    cell["config"], cell["traffic"] = TINY_KANANA, TINY_MIX
    return cell


@pytest.mark.parametrize("trace", [0, 1])
def test_new_cell_runs_end_to_end_at_a_tiny_size(trace, tmp_path,
                                                 interpreted_kernels,
                                                 monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    cell = tiny_cell()
    result, record = run.measure(
        cell, seed=2**31 + 34, seconds=0.5, trace=bool(trace),
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
        assert set(result["metrics"]) == host >= {"mla_step_mfu", "compile_s"}
        assert 0 < result["metrics"]["mla_step_mfu"]["value"] < 100
    else:
        assert set(result["metrics"]) == {"step_ms", "hbm_gib", "setup_s"}
    json.dumps(result), json.dumps(record)
    assert os.listdir(tmp_path) == []
    session = record["session"]
    errs = session["reference_errors"]
    assert record["checks"]["matches_reference"], errs
    assert set(errs) >= {"logits_rel_rms", "loss_abs", "routing_flips",
                         "grad_rel_max", "grad_rel_max_routed"}
    # the last layer's leaves but the bias, which has no gradient
    assert set(errs["grad_rel_by_leaf"]) == {
        "attn_norm", "wq", "wkv_a", "kv_norm", "wkv_b", "wo", "mlp_norm",
        "router", "w_gate", "w_up", "w_down", "shared_gate", "shared_up",
        "shared_down"}
    # every trained leaf moved, the sparse layer's bias did not
    assert record["checks"]["params_changed"]
    assert record["checks"]["state_leaves_held"]
    assert session["state_leaves"] == [1, 1]
    assert session["leaves_changed"][0] == session["leaves_changed"][1] > 20
    # the bias was balanced on the first batch before the check and the
    # steps, in a set-up phase of its own
    assert "bias_balance" in [name for name, _ in record["setup_phases"]]
    balance = session["bias_balance"]
    assert len(balance["held_share"]) == 1 and 0.3 < balance["held_share"][0] < 0.7
    assert balance["load_max_over_mean"][0] < 1.5
    # 1 sparse layer x 128 tokens x 3 choices, half the experts held
    assert 0 < session["moe_slots_here_mean"] < 128 * 3


def test_the_cells_report_what_the_issue_lists():
    cell = run.load_cell(CELL)
    assert {m["name"] for m in cell["end_to_end"]} == {
        "step_ms", "hbm_gib", "setup_s"}
    assert {m["name"] for m in cell["per_layer"]} == {
        *NEW_METRICS, "flash_ms_per_step", "flash_roofline",
        "device_idle_share", "compile_s"}
    assert cell["chips"] == 1 and cell["traffic"]["seq"] == 16384
    assert cell["traffic"]["check_seq"] == 4096
    model = cell["config"]
    assert model["experts_held"] == [0, model["n_routed_experts"]]
    assert model["qk_head_dim"] == (model["qk_nope_head_dim"]
                                    + model["qk_rope_head_dim"]) == 192
    cfg = train_mla_moe.model_config(model)
    from burst_attn_tpu.models.transformer import (DenseMLP, ExpertMLP,
                                                   init_params)

    kinds = [type(spec.mlp) for spec in cfg.pattern]
    assert kinds == [DenseMLP] + [ExpertMLP] * 7
    shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    # the issue's 910,557,184 matrix parameters + norms and the 7 biases
    norms = 8 * (2048 + 2048 + 512) + 2048
    assert sum(x.size for x in jax.tree.leaves(shapes)) == (
        910_557_184 + norms + 7 * 128)
    control = run.load_cell("train_mistral_1x16k")
    assert control["config"]["name"] == "mistral_7b_v02_d4"
    assert control["traffic"]["seq"] == 16384 and control["chips"] == 1
    assert {m["name"] for m in control["end_to_end"]} == {
        "step_ms", "hbm_gib", "setup_s"}
    assert {m["name"] for m in control["per_layer"]} == {
        "flash_ms_per_step", "flash_roofline", "device_idle_share",
        "compile_s"}
    # the new metrics are the new cell's alone
    for name in ("train_sdar_bd_1x8k", "train_mistral_1x16k"):
        assert not {m["name"] for m in run.load_cell(name)["per_layer"]} & set(
            NEW_METRICS)


@pytest.mark.parametrize("key, value", [
    ("rope_interleave", False), ("q_lora_rank", 1536), ("n_group", 8),
    ("norm_topk_prob", False)])
def test_the_runner_refuses_what_the_program_does_not_compute(key, value):
    """One rotary pairing, q at full rank, no expert groups, gates
    renormalised: a configuration that says otherwise is refused by name,
    not run as something else."""
    train_mla_moe.model_config(TINY_KANANA)
    with pytest.raises(ValueError, match="interleaved"):
        train_mla_moe.model_config({**TINY_KANANA, key: value})


def test_flop_counts_are_the_issue_s_arithmetic():
    cell = run.load_cell(CELL)
    model, mix = cell["config"], cell["traffic"]
    assert flops_mla_moe.attention_params(model) == 26_345_472
    assert flops_mla_moe.shared_params(model) == 9_437_184
    assert flops_mla_moe.router_params(model) == 262_144
    assert flops_mla_moe.expert_params(model) == 4_718_592
    assert flops_mla_moe.dense_mlp_params(model) == 37_748_736
    fwd = flops_mla_moe.attention_fwd_flops(model, mix)
    assert fwd == (2 * 192 + 2 * 128) * 32 * 16384 ** 2 / 2
    assert fwd == pytest.approx(2.75e12, rel=2e-3)
    assert flops_mla_moe.flash_kernel_flops(model, mix) == pytest.approx(
        77e12, rel=2e-3)
    # `flash_roofline` reads the cell at head_dim 64: 4 x 64 FLOPs a pair
    # where the kernels do 640, 0.4 of mla_flash_roofline by construction
    from chipbench import flops

    assert 8 * flops.attention_kernel_flops(1, 16384, 32, 64) == \
        pytest.approx(0.4 * flops_mla_moe.flash_kernel_flops(model, mix))
    # the matrix parameters every token meets, and the step at the mean
    # load: 16,384 x 6 / 8 pairs a sparse layer
    assert flops_mla_moe.token_params(model) == (
        8 * 26_345_472 + 37_748_736 + 7 * (262_144 + 9_437_184)
        + 16032 * 2048)
    slots = 7 * 16384 * 6 / 8
    step = flops_mla_moe.step_model_flops(model, mix, slots)
    assert step == 6.0 * (flops_mla_moe.token_params(model) * 16384
                          + 4_718_592 * slots) + 3.0 * 8 * fwd
    assert step == pytest.approx(102.74e12, rel=1e-3)
    assert flops_mla_moe.share_of_peak(197e12, 2.0,
                                       peaks.peak("TPU v5 lite")) == 50.0


@pytest.mark.parametrize("cell_name", ["train_mistral_1x8k", CELL])
def test_readers_find_nothing_in_a_program_without_their_scopes(cell_name):
    """The parent's side of a traced run: no device trace, a trace of a cell
    whose runner has no `step_text` (or of a runner the parent lacks), and
    records without the attr; none raises."""
    cell = run.load_cell(cell_name)
    reading = {"cell": cell, "steps": [], "trace": None}
    for metric in NEW_METRICS:
        assert run.read_layer_metric(cell, metric, dict(reading)) is None
    trace = {"devices": {"/device:TPU:0": [("%fusion.1 = f32[] fusion()",
                                            0, 10)]}, "steps": 1}
    traced = {"cell": cell, "steps": [], "trace": trace}
    for scope in ("obs.model.mla.", "obs.model.moe.shared"):
        assert moe_readings.scope_ms_per_step(dict(traced), scope) is None


def test_the_bounds_refuse_an_eight_bit_activation_path(monkeypatch):
    """The reference with every activation rounded through float8 against
    itself in float32, at the tiny size: out of at least one bound."""
    cell = tiny_cell()
    import chipbench.run as runmod

    monkeypatch.setattr(runmod, "load_cell", lambda name: cell)
    errs, ok = train_mla_moe.lower_precision_reading(2**31 + 5)
    assert not ok, errs
    from chipbench.references import mla_moe_lm

    assert errs["logits_rel_rms"] > mla_moe_lm.TOL_LOGITS_REL_RMS
