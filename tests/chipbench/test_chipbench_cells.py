"""CPU rehearsal of the benchmark's runners: every cell's runner end to end
at a tiny size (Pallas kernels interpreted; the ring on four of the virtual
CPU devices), the last line's keys, and the command refusing to pass off
the chip."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import peaks, run  # noqa: E402

TINY_OP = {"name": "tiny_op", "runner": "op", "reference": "dense_attention",
           "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
           "dtype": "bfloat16", "causal": True, "layout": "zigzag",
           "backend": "auto"}
TINY_LM = {"name": "tiny_lm", "runner": "train", "reference": "decoder_lm",
           "hidden_size": 64, "intermediate_size": 128,
           "num_hidden_layers": 2, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 4096,
           "rope_theta": 1e6, "rms_norm_eps": 1e-6}
# the four cells of BENCHMARK.json, cut to what a CPU runs in seconds
TINY = {
    "op_causal_64k": (TINY_OP, {"batch": 1, "seq": 256, "sp": 1,
                                "parity_seq": 128}),
    "ring4_causal_128k": (TINY_OP, {"batch": 1, "seq": 256, "sp": 4,
                                    "parity_seq": 128}),
    "train_mistral_1x8k": (TINY_LM, {"batch": 1, "seq": 512, "sp": 1,
                                     "check_seq": 256, "file_windows": 8,
                                     "token_ids": 256}),
    "train_mistral_8x1k": (TINY_LM, {"batch": 8, "seq": 64, "sp": 1,
                                     "check_seq": 64, "file_windows": 8,
                                     "token_ids": 256}),
}


@pytest.fixture
def interpreted_kernels(monkeypatch):
    """backend="auto" picks the jnp tile off-chip; the rehearsal wants the
    chip's choice, the Pallas kernels, which then run interpreted."""
    from burst_attn_tpu.parallel import burst

    monkeypatch.setattr(burst, "_resolve_backend",
                        lambda b: "pallas" if b == "auto" else b)


def tiny_cell(name):
    cell = run.load_cell(name)
    cell["config"], cell["traffic"] = TINY[name]
    return cell


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("name", sorted(TINY))
def test_cell_runs_end_to_end_at_a_tiny_size(name, trace, tmp_path,
                                             interpreted_kernels,
                                             monkeypatch):
    # an unknown device kind is an error; the rehearsal borrows a row
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    cell = tiny_cell(name)
    result, record = run.measure(
        cell, seed=2**31 + 11, seconds=0.5, trace=bool(trace),
        devices=jax.devices()[:cell["chips"]], out_dir=str(tmp_path))
    # interpreted kernels leave no Mosaic call: the one check only a chip
    # meets (and only the op runner makes it)
    false = {k for k, v in record["checks"].items() if not v}
    assert false <= {"kernels_compiled", "warmup_settled"}, record["checks"]
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["device"]["count"] == cell["chips"]
    listed = cell["per_layer"] if trace else cell["end_to_end"]
    names = {m["name"] for m in listed}
    assert set(result["metrics"]) <= names
    if trace:
        # no device plane on the CPU: the trace's readers return nothing and
        # are left out; the host-clock and counter readers are all there
        host = {m["name"] for m in listed if m["source"] != "device_trace"}
        assert set(result["metrics"]) == host
    else:
        assert set(result["metrics"]) == names
        assert all(v["value"] > 0 for v in result["metrics"].values())
    json.dumps(result), json.dumps(record)
    assert len(record["steps"]) == result["attempted"]
    assert os.listdir(tmp_path) == []  # token file and trace are removed


@pytest.mark.parametrize("name", ["op_causal_64k", "ring4_causal_128k"])
def test_command_fails_off_the_chip(name, tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", name,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""  # no result line
    assert "TPU chip" in out.stderr
