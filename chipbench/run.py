"""Run one cell of BENCHMARK.json once, on the machine this is started on:

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result: one JSON object with
`correct`, `attempted`, `failed`, `metrics` (the cell's end-to-end metrics,
or with `--trace 1` its per-layer metrics) and `device`, and with
`--trace 1` a `breakdown`.  Without the cell's number of TPU chips it exits
1 and prints no result.  The run's record (every step's times, the set-up's
parts, both memory numbers, the errors of the check) goes to a file under
`chipbench_out/` in the checkout.
"""

import time

_T0 = time.perf_counter()  # as near to the process's start as Python gets

import argparse
import importlib
import importlib.util
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
OUT_DIR = "chipbench_out"


def _json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(name, root=ROOT):
    """The cell `name` of `<root>/BENCHMARK.json` with its configuration and
    traffic files read in, and the metrics it reports."""
    spec = _json(Path(root) / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has "
                         f"{sorted(cells)}")
    cell = dict(cells[name])
    bench_dir = Path(root) / spec["paths"][0]
    files = {c["name"]: c["file"] for c in spec["configs"]}
    cell["config"] = _json(Path(root) / files[cell["config"]])
    cell["traffic"] = _json(bench_dir / "traffic" / f"{cell['traffic']}.json")

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    cell["end_to_end"] = mine(spec["end_to_end"])
    cell["per_layer"] = mine(spec["per_layer"])
    cell["layer_metrics_dir"] = str(bench_dir / "layer_metrics")
    return cell


def read_layer_metric(cell, metric, reading):
    """Call `<layer_metrics>/<metric>.py`'s read(reading); None when it
    finds nothing to read."""
    path = Path(cell["layer_metrics_dir"]) / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_layer_metric_{metric.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(reading)


def measure(cell, *, seed, seconds, trace, devices, out_dir, t0=None):
    """Run the cell on `devices`; the contract's result object and the
    run's record."""
    import jax

    from . import harness, trace as tracelib

    os.makedirs(out_dir, exist_ok=True)
    ctx = harness.Context(cell=cell, seed=seed, seconds=seconds, trace=trace,
                          devices=devices, out_dir=out_dir,
                          t_process_start=time.perf_counter()
                          if t0 is None else t0)
    runner = importlib.import_module(
        f"chipbench.runners.{cell['config']['runner']}")
    ctx.mark("imports_and_devices")
    m = runner.run(ctx)

    if trace:
        values = {p["name"]: read_layer_metric(cell, p["name"], m.reading)
                  for p in cell["per_layer"]}
        listed = cell["per_layer"]
    else:
        values, listed = m.end_to_end, cell["end_to_end"]
    metrics = {p["name"]: {"value": values[p["name"]], "unit": p["unit"]}
               for p in listed if values.get(p["name"]) is not None}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": m.memory_peak_bytes}
    result = {"correct": all(m.checks.values()), "attempted": m.attempted,
              "failed": m.failed, "metrics": metrics, "device": device}
    if m.reading["trace"] is not None:
        device["busy_s"] = tracelib.busy_seconds(m.reading["trace"])
        device["window_s"] = tracelib.window_seconds(m.reading["trace"])
        result["breakdown"] = tracelib.breakdown(m.reading["trace"])
    record = {"workload": cell["name"], "seed": seed, "seconds": seconds,
              "trace": int(trace), "jax": jax.__version__, "result": result,
              "checks": m.checks, "end_to_end": m.end_to_end, **m.detail}
    return result, record


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)

    import jax

    from burst_attn_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    # every program goes to the cache, the small ones too, so that a second
    # run of a cell compiles nothing and set-up repeats
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"chipbench: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"found {len(devices)} x {devices[0].platform}",
              file=sys.stderr)
        return 1

    out_dir = str(ROOT / OUT_DIR)
    result, record = measure(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        devices=devices[:cell["chips"]], out_dir=out_dir, t0=_T0)
    record_path = os.path.join(
        out_dir, f"{args.workload}.seed{args.seed}.trace{args.trace}."
                 f"{int(time.time())}.json")
    with open(record_path, "w") as f:
        json.dump(record, f)
    print(json.dumps({"record": record_path, "checks": record["checks"]}))
    print(json.dumps(result))
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
