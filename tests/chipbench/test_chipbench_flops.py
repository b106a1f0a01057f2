"""The benchmark's arithmetic against hand-worked values: attention FLOPs in
the reference's convention, matmul parameters, MFU and the roofline, for
both configurations; and the table of peaks refusing a device it lacks."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import flops, peaks, run  # noqa: E402

V5E = peaks.peak("TPU v5 lite")


def config(name):
    with open(ROOT / "chipbench" / "configs" / f"{name}.json") as f:
        return json.load(f)


def test_peaks_are_the_published_v5e_numbers_and_unknown_kinds_raise():
    assert V5E["bf16_flops_s"] == 197e12 and V5E["hbm_bytes_s"] == 819e9
    assert V5E["ici_bits_s"] == 1600e9
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peak("TPU v9 imaginary")


OP = {"num_attention_heads": 32, "num_key_value_heads": 32, "head_dim": 128}
MIX_64K = {"batch": 1, "seq": 65536, "sp": 1}


def test_attention_flops_follow_the_reference_convention():
    # 4 * b * s^2 * h * d / 2 causal: 4 * 65536^2 * 32 * 128 / 2 = 2^45
    assert flops.attention_pairs(OP, 65536) == [65536**2 / 2]
    assert flops.attention_fwd_flops(OP, MIX_64K) == 2.0**45
    # forward + backward = 3.5 x (the backward's recomputation counted)
    assert flops.KERNEL_PASSES * flops.attention_fwd_flops(OP, MIX_64K) == \
        3.5 * 2.0**45
    # 1.2315e14 FLOPs at 197 TFLOP/s: 625.1 ms, the floor of op_causal_64k
    assert 3.5 * 2.0**45 / 197e12 == pytest.approx(0.62510, rel=1e-4)


def test_attention_bytes_count_each_tensor_once_per_pass():
    # MHA at 64K: every tensor is 1*32*65536*128*2 B = 512 MiB; forward
    # touches 4, backward 8
    assert flops.attention_calls(OP, MIX_64K)[0][2] == 12 * 2**29
    # GQA 32/8: the six KV-shaped tensors are a quarter the size
    gqa = {**OP, "num_key_value_heads": 8}
    assert flops.attention_calls(gqa, {"batch": 1, "seq": 8192})[0][2] == \
        6 * 2**26 + 6 * 2**24
    # two widths: q, dq, k, dk at d_qk; o twice, do, v twice, dv at d_v
    mla = {**OP, "qk_head_dim": 192, "v_head_dim": 128}
    assert flops.attention_calls(mla, {"batch": 1, "seq": 8192})[0][2] == \
        8192 * (32 + 32) * (3 * 192 + 3 * 128) * 2


def test_roofline_says_which_roof_binds():
    [(_, f, b)] = flops.attention_calls(OP, MIX_64K)
    share, bound = flops.roofline_share(3.5 * f, b, 0.720, V5E)
    assert bound == "compute"  # 625 ms of FLOPs against 7.9 ms of bytes
    assert share == pytest.approx(100 * 0.62510 / 0.720, rel=1e-4)
    share, bound = flops.roofline_share(1e9, 819e9, 2.0, V5E)
    assert bound == "memory" and share == pytest.approx(50.0)


def test_mistral_matmul_parameters_by_hand():
    model = config("mistral_7b_v02_d4")
    # a layer: wq 4096*4096 + wk, wv 2 * 4096*1024 + wo 4096*4096
    #          + 3 * 4096*14336 = 218,103,808; the head 32000*4096
    assert flops.matmul_params(model) == 4 * 218_103_808 + 131_072_000
    # the embedding table (a lookup) and the norms are not in it
    total = flops.matmul_params(model) + 131_072_000 + 9 * 4096
    assert total == 1_134_596_096  # the 1,134.6 M of the configuration file


def test_model_flops_per_token_and_util_by_hand():
    model = config("mistral_7b_v02_d4")
    # attention forward per token at 8192: 4 layers * 4*8192*32*128/2
    attn = 4 * 4 * 8192 * 32 * 128 / 2
    per_token = 6 * 1_003_487_232 + 3 * attn
    assert flops.model_flops_per_token(model, 8192) == per_token
    # 8192 tokens in 499.2 ms on one chip
    util = flops.model_flops_util(model, 8192, 8192 / 0.4992, V5E)
    assert util == pytest.approx(100 * per_token * 8192 / 0.4992 / 197e12)
    assert 56.5 < util < 57.0
    # an eighth of the attention at 1024 tokens a sequence
    assert flops.model_flops_per_token(model, 1024) == \
        6 * 1_003_487_232 + 3 * attn / 8


def flash_roofline():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "flash_roofline", ROOT / "chipbench/layer_metrics/flash_roofline.py")
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    return reader


def parent_least_seconds(cell):
    """PR 35's flash_roofline, arithmetic for arithmetic: every layer one
    causal call over `seq` at `head_dim`."""
    model, mix = cell["config"], cell["traffic"]
    calls, b, s = model.get("num_hidden_layers", 1), mix["batch"], mix["seq"]
    n, n_kv, d = (model["num_attention_heads"], model["num_key_value_heads"],
                  model["head_dim"])
    share, _ = flops.roofline_share(
        calls * (3.5 * (4.0 * b * s * s * n * d / 2)),
        calls * (6 * (b * s * n * d * 2) + 6 * (b * s * n_kv * d * 2)),
        1.0, V5E)
    return share / 100.0 / mix["sp"]


@pytest.mark.parametrize("cell,floor_ms", [
    ("op_causal_64k", 625.10),       # 1.2315e14 / 197e12
    ("ring4_causal_128k", 625.10),   # 4 x the FLOPs over 4 chips
    ("train_mistral_1x8k", 39.07),   # 4 layers * 3.5 * 2^39 / 197e12
    ("train_mistral_8x1k", 4.884),   # an eighth of it
    ("train_mistral_1x16k", 156.28),  # 4 x the 8K floor
])
def test_flash_roofline_floor_of_every_cell(cell, floor_ms):
    cell = run.load_cell(cell)
    least = flash_roofline().least_seconds(cell, "TPU v5 lite")
    assert 1e3 * least == pytest.approx(floor_ms, rel=1e-3)
    # one mask, one width: the count the reader had before it read either
    assert least == parent_least_seconds(cell)


def retired_fork_flops(model, mix):
    """What PR 34's mla_flash_roofline and PR 27's bd_flash_roofline
    counted, arithmetic for arithmetic."""
    if "block_length" in model:
        pairs = mix["seq"] ** 2 + mix["seq"] * model["block_length"]
        return 3.5 * model["num_hidden_layers"] * (
            4.0 * mix["batch"] * pairs * model["num_attention_heads"]
            * model["head_dim"])
    pair = 2.0 * model["qk_head_dim"] + 2.0 * model["v_head_dim"]
    return 3.5 * model["num_hidden_layers"] * (
        pair * mix["batch"] * mix["seq"] * mix["seq"] / 2
        * model["num_attention_heads"])


@pytest.mark.parametrize("cell,floor_ms", [
    ("train_sdar_bd_1x8k", 156.35),       # L^2 + L*B pairs, 8 layers
    ("train_kanana2_mla_1x16k", 390.69),  # 640 FLOPs a pair, 8 layers
])
def test_flash_roofline_reads_what_the_retired_forks_read(cell, floor_ms):
    cell = run.load_cell(cell)
    least = flash_roofline().least_seconds(cell, "TPU v5 lite")
    assert 1e3 * least == pytest.approx(floor_ms, rel=1e-4)
    # compute binds: the forks' FLOPs over the peak
    fork = retired_fork_flops(cell["config"], cell["traffic"])
    assert least == pytest.approx(fork / 197e12, rel=1e-12)
    assert flops.KERNEL_PASSES * flops.attention_fwd_flops(
        cell["config"], cell["traffic"]) == fork


def dense_mask(seq, window=None, block=None):
    """[rows, cols] bool, the live entries of one sequence's mask:
    ops/reference.py's rule (causal, a window keeping `cols > rows -
    window`) or references/bd_moe_lm.py's block-diffusion mask."""
    if block is not None:
        from chipbench.references.bd_moe_lm import block_diffusion_mask

        return block_diffusion_mask(seq, block)
    rows, cols = np.arange(seq)[:, None], np.arange(seq)[None, :]
    mask = cols <= rows
    return mask & (cols > rows - window) if window is not None else mask


@pytest.mark.parametrize("seq,window,block,half_diagonal", [
    (96, None, None, 96 / 2),     # causal
    (96, 16, None, 16 / 2),       # a window shorter than the sequence
    (96, 96, None, 96 / 2),       # a window as long: causal
    (96, 200, None, 96 / 2),      # a window longer: causal
    (96, None, 4, 0),             # block diffusion, the stream of 2 x 96
    (96, None, 8, 0),
])
def test_pairs_are_the_live_entries_of_a_dense_mask(seq, window, block,
                                                    half_diagonal):
    model = {"num_hidden_layers": 2}
    if window is not None:
        model.update(layer_types=["sliding_attention", "full_attention"],
                     sliding_window=window)
    if block is not None:
        model["block_length"] = block
    live = int(dense_mask(seq, window, block).sum())
    first, second = flops.attention_pairs(model, seq)
    # the /2 convention leaves out half of the diagonal's live entries
    assert first == live - half_diagonal
    assert second == (first if block is not None
                      else int(dense_mask(seq).sum()) - seq / 2)


MOTIF_SHAPED = {
    "num_hidden_layers": 5, "sliding_window": 128,
    "layer_types": ["sliding_attention"] * 3 + [
        "full_attention", "sliding_attention"],
    "num_attention_heads": 80, "num_key_value_heads": 16, "head_dim": 128,
    "qk_head_dim": 192, "v_head_dim": 128}


def test_a_stack_of_windowed_and_full_layers_is_counted_by_its_windows():
    """ISSUE 36's Motif-shaped stack at 1 x 4,096: one full layer at 640
    FLOPs a pair, 7.6 ms at 197 TFLOP/s, and four windowed layers of
    4,096 x 128 - 128^2 / 2 pairs, 0.47 ms each: 9.5 ms of FLOPs, not the
    45.8 ms of five full layers at head_dim.  Each windowed call's bytes
    (96 heads x 1,920 B a token, 0.92 ms at 819 GB/s) bind it, so its least
    time is 11.3 ms."""
    mix = {"batch": 1, "seq": 4096, "sp": 1}
    window = 4096 * 128 - 128**2 / 2
    calls = flops.attention_calls(MOTIF_SHAPED, mix)
    nbytes = 4096 * 96 * (3 * 192 + 3 * 128) * 2
    assert calls == [(4, 640 * window * 80, nbytes),
                     (1, 640 * 4096**2 / 2 * 80, nbytes)]
    fwd = flops.attention_fwd_flops(MOTIF_SHAPED, mix)
    assert 1e3 * 3.5 * fwd / 197e12 == pytest.approx(9.51, abs=0.01)
    full_stack = 5 * 3.5 * 4 * 4096**2 * 80 * 192 / 2
    assert 1e3 * full_stack / 197e12 == pytest.approx(45.8, abs=0.1)
    least = flash_roofline().least_seconds(
        {"config": MOTIF_SHAPED, "traffic": mix}, "TPU v5 lite")
    assert least == pytest.approx(
        3.5 * 640 * 4096**2 / 2 * 80 / 197e12 + 4 * nbytes / 819e9)
    assert 1e3 * least == pytest.approx(11.32, abs=0.01)
    with pytest.raises(ValueError, match="layer_types names 5 layers"):
        flops.attention_pairs({**MOTIF_SHAPED, "num_hidden_layers": 4}, 4096)


def step_mfu():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "step_mfu", ROOT / "chipbench/layer_metrics/step_mfu.py")
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    return reader


@pytest.mark.parametrize("missing", [None, "flop_count", "slots"])
@pytest.mark.parametrize("cell,count", [
    ("train_sdar_bd_1x8k", "flops_bd_moe"),
    ("train_kanana2_mla_1x16k", "flops_mla_moe"),
])
def test_step_mfu_reads_the_count_that_the_configuration_names(
        cell, count, missing, monkeypatch):
    import importlib

    from chipbench import moe_readings

    cell = run.load_cell(cell)
    assert cell["config"]["flop_count"] == count
    if missing == "flop_count":
        del cell["config"]["flop_count"]
    slots = None if missing == "slots" else 7 * 16384 * 6 / 8
    monkeypatch.setattr(moe_readings, "mean_slots_here", lambda r: slots)
    reading = {"cell": cell, "steps": [{"step_s": s} for s in (0.9, 0.8, 1)],
               "trace": None, "device_kind": "TPU v5 lite"}
    got = step_mfu().read(reading)
    if missing:
        assert got is None
        return
    module = importlib.import_module(f"chipbench.{count}")
    assert got == flops.share_of_peak(
        module.step_model_flops(cell["config"], cell["traffic"], slots), 0.9,
        V5E)
    assert 0 < got < 100
