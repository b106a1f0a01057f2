"""A later PR adds a configuration, a traffic mix, a per-layer metric and a
cell as new files and new entries, editing no file of the benchmark: shown
here with throw-away ones in a temporary root; and BENCHMARK.json itself
held to the parts of its contract that a test can read."""

import json
import re
import shutil
import sys
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import run  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_a_throwaway_config_mix_metric_and_cell_are_only_new_files(tmp_path):
    # the benchmark's data files as they stand, untouched ...
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(ROOT / "chipbench" / sub, tmp_path / "chipbench" / sub)
    before = {p: p.read_bytes() for p in tmp_path.rglob("*") if p.is_file()}
    # ... plus one new file of each kind ...
    (tmp_path / "chipbench/configs/throwaway_op.json").write_text(json.dumps({
        "name": "throwaway_op", "runner": "op",
        "reference": "dense_attention", "num_attention_heads": 2,
        "num_key_value_heads": 2, "head_dim": 16, "dtype": "bfloat16",
        "causal": True, "layout": "zigzag", "backend": "auto",
        "source": "none: a test's", "reduced": [], "assumed": {}}))
    (tmp_path / "chipbench/traffic/steps_1x128.json").write_text(json.dumps(
        {"batch": 1, "seq": 128, "sp": 1, "parity_seq": 64}))
    (tmp_path / "chipbench/layer_metrics/steps_seen.py").write_text(
        "def read(reading):\n    return len(reading['steps'])\n")
    # ... and new entries in BENCHMARK.json
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({
        "name": "throwaway_op", "source": "none: a test's",
        "file": "chipbench/configs/throwaway_op.json", "reduced": [],
        "why": "shows that a configuration is a file"})
    spec["workloads"].append({
        "name": "throwaway_cell", "config": "throwaway_op",
        "traffic": "steps_1x128", "chips": 1, "why": "shows that a cell "
        "is an entry"})
    spec["per_layer"].append({
        "name": "steps_seen", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "job loop", "moves": "step_ms",
        "workloads": ["throwaway_cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = run.load_cell("throwaway_cell", root=tmp_path)
    assert cell["config"]["head_dim"] == 16 and cell["traffic"]["seq"] == 128
    out = tmp_path / "out"
    result, _ = run.measure(cell, seed=3, seconds=0.2, trace=True,
                            devices=jax.devices()[:1], out_dir=str(out))
    assert result["metrics"]["steps_seen"] == {
        "value": result["attempted"], "unit": "count"}
    # the readers that are there serve the new cell too
    assert result["metrics"]["compile_s"]["value"] > 0
    plain = run.measure(cell, seed=3, seconds=0.2, trace=False,
                        devices=jax.devices()[:1], out_dir=str(out))[0]
    assert set(plain["metrics"]) == {"step_ms", "hbm_gib", "setup_s"}
    # the old cells do not see the new metric, and no old file was edited
    assert "steps_seen" not in {
        m["name"] for m in run.load_cell("op_causal_64k",
                                         root=tmp_path)["per_layer"]}
    assert all(p.read_bytes() == data for p, data in before.items())


def test_benchmark_json_keeps_to_its_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    configs = {c["name"] for c in SPEC["configs"]}
    cells = {w["name"]: w for w in SPEC["workloads"]}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["reduced"] == \
            c["reduced"]
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and NAME.match(w["name"])
        assert (ROOT / "chipbench/traffic" / f"{w['traffic']}.json").is_file()
    assert {c["name"] for c in SPEC["configs"]} == {
        w["config"] for w in SPEC["workloads"]}
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(cells) // 4)
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert (ROOT / "chipbench/layer_metrics" / f"{m['name']}.py").is_file()
        # the metric it moves is reported in every cell where this one is
        mine = set(m.get("workloads", cells))
        assert mine <= set(e2e[m["moves"]].get("workloads", cells))
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        assert set(m.get("workloads", [])) <= set(cells)
    for name in cells:  # every cell: setup_s, another, and a per-layer one
        loaded = run.load_cell(name)
        assert len(loaded["end_to_end"]) >= 2 and loaded["per_layer"]
