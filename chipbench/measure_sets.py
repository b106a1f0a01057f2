"""Make sets of runs of cells, one process a run, and print each metric's
spread as the benchmark's contract defines it (the distance between the
first and third quartile of `statistics.quantiles(values, n=4)`, as a share
of the median).  The parent never touches JAX: a chip belongs to one process.

    python3 -m chipbench.measure_sets --cells a,b --sets 2 --runs 6 \
        --seconds 30 [--traced] [--out chiprun_out/sets]

Every result line goes to `<out>/results.jsonl`, and the runs' records are
copied beside it.
"""

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
FIRST_SEED = 2**31 + 1000  # the driver's seeds are large


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def one_run(workload, seed, seconds, trace):
    t0 = time.time()
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace",
         str(trace)], cwd=ROOT, capture_output=True, text=True,
        timeout=1500)
    lines = out.stdout.strip().splitlines()
    if out.returncode != 0 or not lines:
        sys.stderr.write(out.stderr[-4000:])
    result = json.loads(lines[-1]) if lines else {"correct": False}
    return {"workload": workload, "seed": seed, "trace": trace,
            "rc": out.returncode, "wall_s": time.time() - t0, **result}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--traced", action="store_true",
                    help="one more run of each cell with --trace 1")
    ap.add_argument("--out", default="chiprun_out/sets")
    args = ap.parse_args(argv)
    out = ROOT / args.out
    out.mkdir(parents=True, exist_ok=True)

    rows = []
    with open(out / "results.jsonl", "a") as f:
        def keep(row, **more):
            row.update(more)
            rows.append(row)
            f.write(json.dumps(row) + "\n")
            f.flush()
            print(json.dumps({k: row.get(k) for k in (
                "workload", "set", "seed", "trace", "rc", "correct",
                "wall_s", "metrics")}), flush=True)

        for cell in args.cells.split(","):
            for s in range(args.sets):
                for r in range(args.runs):  # the same seeds in every set
                    keep(one_run(cell, FIRST_SEED + r, args.seconds, 0),
                         set=s)
            if args.traced:
                keep(one_run(cell, FIRST_SEED, args.seconds, 1), set=None)
    records = ROOT / "chipbench_out"
    if records.is_dir():
        shutil.copytree(records, out / "records", dirs_exist_ok=True)

    print("\ncell metric set n median spread first_run")
    for cell in args.cells.split(","):
        for s in range(args.sets):
            runs = [r for r in rows if r["workload"] == cell
                    and r["set"] == s and r.get("metrics")]
            for name in (runs[0]["metrics"] if runs else ()):
                vals = [r["metrics"][name]["value"] for r in runs]
                if len(vals) >= 2:
                    print(cell, name, s, len(vals), statistics.median(vals),
                          f"{spread(vals):.5f}", vals[0])
    return 0 if all(r["rc"] == 0 for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
