"""CPU rehearsal of chip_smoke.py: its phases at a tiny size with the Pallas
kernels interpreted (one chip's, then the four-chip ones on four of the
virtual CPU devices), and the script itself refusing to pass off the chip."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

TINY_MODEL = dict(d_model=64, n_layers=1, n_heads=4, d_ff=128, vocab=4096)


@pytest.fixture
def interpreted_kernels(monkeypatch, tmp_path):
    """backend="auto" picks the jnp tile off-chip; the rehearsal wants the
    chip's choice, the Pallas kernels, which then run interpreted.  And the
    runner is told its compile cache is placed already, so a test run leaves
    none in the checkout."""
    from burst_attn_tpu.parallel import burst

    monkeypatch.setattr(burst, "_resolve_backend",
                        lambda b: "pallas" if b == "auto" else b)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def _only_false(checks):
    return {k for k, v in checks.items() if not v}


def test_one_chip_phases(interpreted_kernels):
    clock = chip_smoke._CompileClock()
    devices = jax.devices()
    rec, checks = chip_smoke.op_phase(devices, clock, heads=4, d_head=16,
                                      seq=256, ref_seq=128, seed=0)
    # interpreted kernels leave no Mosaic call: the one check only a chip meets
    assert _only_false(checks) == {"kernels_compiled"}, (rec, checks)
    # ... and take the split backward pair, not the chip's fused kernel
    assert rec["kernels"] == ["burst_flash_bwd_dkdv", "burst_flash_bwd_dq",
                              "burst_flash_fwd"]
    assert rec["compile_s"] > 0
    json.dumps(rec)

    rec, checks = chip_smoke.train_phase(devices, clock, seq=512, steps=4,
                                         seed=0, **TINY_MODEL)
    assert not _only_false(checks), (rec, checks)
    assert len(rec["losses"]) == 4 and rec["step_s"] > 0
    json.dumps(rec)


def test_multichip_phases_on_four_virtual_devices(interpreted_kernels):
    clock = chip_smoke._CompileClock()
    devices = jax.devices()[:4]
    rec, checks = chip_smoke.ring_phase(devices, clock, heads=4, d_head=16,
                                        seq=512, cmp_seq=256, seed=0)
    assert not _only_false(checks), (rec, checks)
    assert rec["shard_devices"] == [d.id for d in devices]
    json.dumps(rec)

    rec, checks = chip_smoke.sharded_train_phase(
        devices, clock, seq=1024, cmp_seq=512, steps=3, seed=0, **TINY_MODEL)
    assert not _only_false(checks), (rec, checks)
    assert rec["collective_permute_starts"] > 0 or checks["collective_permutes"]
    json.dumps(rec)


@pytest.mark.parametrize("argv", [[], ["--multichip"]], ids=["one", "multi"])
def test_script_fails_off_the_chip(argv, tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py"), *argv],
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
