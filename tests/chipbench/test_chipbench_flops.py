"""The benchmark's arithmetic against hand-worked values: attention FLOPs in
the reference's convention, matmul parameters, MFU and the roofline, for
both configurations; and the table of peaks refusing a device it lacks."""

import json
import sys
from pathlib import Path

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


def test_attention_flops_follow_the_reference_convention():
    # 4 * b * s^2 * h * d / 2 causal: 4 * 65536^2 * 32 * 128 / 2 = 2^45
    assert flops.attention_fwd_flops(1, 65536, 32, 128) == 2.0**45
    assert flops.attention_fwd_flops(1, 65536, 32, 128, causal=False) == 2.0**46
    # forward + backward = 3.5 x (the backward's recomputation counted)
    assert flops.attention_kernel_flops(1, 65536, 32, 128) == 3.5 * 2.0**45
    # 1.2315e14 FLOPs at 197 TFLOP/s: 625.1 ms, the floor of op_causal_64k
    assert flops.attention_kernel_flops(1, 65536, 32, 128) / 197e12 == \
        pytest.approx(0.62510, rel=1e-4)


def test_attention_bytes_count_each_tensor_once_per_pass():
    # MHA at 64K: every tensor is 1*32*65536*128*2 B = 512 MiB; forward
    # touches 4, backward 8
    assert flops.attention_kernel_bytes(1, 65536, 32, 32, 128) == 12 * 2**29
    # GQA 32/8: the six KV-shaped tensors are a quarter the size
    assert flops.attention_kernel_bytes(1, 8192, 32, 8, 128) == \
        6 * 2**26 + 6 * 2**24


def test_roofline_says_which_roof_binds():
    f = flops.attention_kernel_flops(1, 65536, 32, 128)
    b = flops.attention_kernel_bytes(1, 65536, 32, 32, 128)
    share, bound = flops.roofline_share(f, b, 0.720, V5E)
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


@pytest.mark.parametrize("cell,floor_ms", [
    ("op_causal_64k", 625.10),       # 1.2315e14 / 197e12
    ("ring4_causal_128k", 625.10),   # 4 x the FLOPs over 4 chips
    ("train_mistral_1x8k", 39.07),   # 4 layers * 3.5 * 2^39 / 197e12
    ("train_mistral_8x1k", 4.884),   # an eighth of it
])
def test_flash_roofline_floor_of_every_cell(cell, floor_ms):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "flash_roofline", ROOT / "chipbench/layer_metrics/flash_roofline.py")
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    least = reader.least_seconds(run.load_cell(cell), "TPU v5 lite")
    assert 1e3 * least == pytest.approx(floor_ms, rel=1e-3)
