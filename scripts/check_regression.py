#!/usr/bin/env python
"""Perf-regression gate: compare fresh headline records against history.

The bench trajectory lives in two places: `results/headline*.json` (the
freshest on-chip records bench.py fsyncs) and the driver-captured
`BENCH_*.json` round files (plus `BASELINE.json`'s published reference
numbers, when it carries any).  This gate fails — exit 1 — when any current
headline value drops more than `--tolerance` below the BEST prior value for
the same metric string, so a perf regression is caught at bench time
instead of three rounds later.

    python scripts/check_regression.py                # gate (exit 1 on regression)
    python scripts/check_regression.py --dry-run      # report only, exit 0
    python scripts/check_regression.py --tolerance 0.05

Matching is by the exact `metric` string (configs self-describe:
"... TFLOPs/s/chip @ seq=65536 causal bf16").  Value direction defaults to
higher-is-better; a headline record carrying `"direction": "lower"`
(latency-style metrics — serve.ttft_p99) gates the other way: regression
means rising more than `--tolerance` ABOVE the best (lowest) prior.
Metrics with no history PASS with a note — a brand-new
config cannot regress.  Cached headline replays still gate: a cached record
IS a prior on-chip measurement, and history only moves when fresh runs land.
Cached provenance (`cached` / `cached_age_hours` from bench.py's replay
path) is surfaced on every verdict line, and `--max-cached-age HOURS` adds
a STALE-CACHE warning — warn only by default: a stale replay is an honest
old number, not a regression, but a driver round gating on a 58-hour-old
record should say so out loud.  `--strict-cache` escalates those warnings
to exit 1 for lanes that must run on fresh measurements.  `--summary-json
PATH` additionally writes the machine-readable verdict summary (gate,
exit_code, per-metric verdicts) for CI annotation; each verdict carries
a `predicted` field — the static cost model's analytic roofline
expectation for the metric (burst_attn_tpu.analysis.costmodel), so a
stale cached number is read beside its analytic ceiling.  That one
import is lazy and best-effort (predicted: null where the package or
jax can't import) — the gate itself still runs stdlib-only.

Exit status: 0 clean (or --dry-run), 1 regression, 2 internal error
(missing/unparseable current headline counts as 2 — the gate cannot run).

No third-party imports — runs anywhere the repo checks out.
"""

import argparse
import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_json(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_headlines(patterns):
    """[(path, metric, value, record)] from headline-style records — the
    full record rides along so the verdicts can surface cached provenance."""
    out = []
    for pat in patterns:
        for path in sorted(glob.glob(pat)):
            try:
                rec = _load_json(path)
            except (OSError, ValueError) as e:
                raise RuntimeError(f"unreadable headline {path}: {e}")
            if not isinstance(rec, dict) or "metric" not in rec:
                raise RuntimeError(f"{path}: not a headline record")
            out.append((path, str(rec["metric"]), float(rec["value"]), rec))
    return out


def load_history(patterns, baseline_path):
    """metric -> [(value, source), ...] over BENCH round files + BASELINE
    published numbers.  ALL readings are kept — which one is "best" depends
    on the headline's direction (max for throughput, min for latency), so
    the choice belongs to check().  Files that don't parse or carry no
    number are skipped silently — history is best-effort evidence, the
    gate only needs what it can read."""
    best = {}

    def _offer(metric, value, source):
        metric = str(metric)
        try:
            value = float(value)
        except (TypeError, ValueError):
            return
        best.setdefault(metric, []).append((value, source))

    for pat in patterns:
        for path in sorted(glob.glob(pat)):
            try:
                rec = _load_json(path)
            except (OSError, ValueError):
                continue
            parsed = rec.get("parsed") if isinstance(rec, dict) else None
            if isinstance(parsed, dict) and "metric" in parsed:
                _offer(parsed.get("metric"), parsed.get("value"),
                       os.path.basename(path))
            elif isinstance(rec, dict) and "metric" in rec:
                _offer(rec.get("metric"), rec.get("value"),
                       os.path.basename(path))
    if baseline_path and os.path.exists(baseline_path):
        try:
            base = _load_json(baseline_path)
        except (OSError, ValueError):
            base = {}
        # BASELINE.json "published": {metric: value} when the reference
        # published comparable numbers; empty for this paper's TPU port
        for metric, value in (base.get("published") or {}).items():
            _offer(metric, value, os.path.basename(baseline_path))
    return best


def _cached_note(rec):
    """' [cached, NNh old]' provenance suffix for replayed records."""
    if not rec.get("cached"):
        return ""
    age = rec.get("cached_age_hours")
    if age is None:
        return " [cached, age unknown]"
    return f" [cached, {float(age):.1f}h old]"


_PREDICTED_CACHE = {}


def predicted_value(metric):
    """Analytic roofline expectation for this metric from the static cost
    model (burst_attn_tpu.analysis.costmodel.predict_metric), or None
    when the model can't price it.  Lazy best-effort import behind a
    broad except: this script's no-third-party contract stands — where
    the package (and jax) can't import, verdicts carry predicted: null
    instead of failing the gate."""
    if metric in _PREDICTED_CACHE:
        return _PREDICTED_CACHE[metric]
    try:
        if ROOT not in sys.path:
            sys.path.insert(0, ROOT)
        from burst_attn_tpu.analysis import costmodel

        value = costmodel.predict_metric(metric)
    except Exception:  # noqa: BLE001 — model absence must not gate
        value = None
    _PREDICTED_CACHE[metric] = value
    return value


def check(headlines, history, tolerance, max_cached_age=None):
    """[(status, line, direction, metric)] verdicts; status in PASS/
    REGRESSION/NO-HISTORY/STALE-CACHE, direction in "higher"/"lower" (the
    metric's regression sense).  STALE-CACHE entries are warnings riding
    NEXT TO the metric's real verdict — they never gate."""
    verdicts = []
    for path, metric, value, rec in headlines:
        note = _cached_note(rec)
        prior = history.get(metric)
        # headline records self-describe their sense: direction "lower"
        # (latency-style — serve.ttft_p99) regresses UP past a ceiling;
        # the default "higher" (throughput-style) regresses DOWN past a
        # floor.  History's best follows the same sense.
        lower = str(rec.get("direction", "higher")).lower() == "lower"
        sense = "lower" if lower else "higher"
        if prior is None:
            verdicts.append(("NO-HISTORY",
                             f"NO-HISTORY  {metric}: {value:g} "
                             f"({os.path.basename(path)}){note} — nothing "
                             "to compare against", sense, metric))
        else:
            best, source = (min if lower else max)(prior,
                                                   key=lambda vs: vs[0])
            ratio = value / best if best else float("inf")
            if lower:
                bound = best * (1.0 + tolerance)
                regressed = value > bound
                bound_word = "ceiling"
            else:
                bound = best * (1.0 - tolerance)
                regressed = value < bound
                bound_word = "floor"
            line = (f"{metric}: current {value:g}{note} vs best {best:g} "
                    f"[{source}] = {ratio:.4f} ({bound_word} {bound:g} at "
                    f"tolerance {tolerance:g}"
                    + (", direction=lower)" if lower else ")"))
            if regressed:
                verdicts.append(("REGRESSION", f"REGRESSION  {line}",
                                 sense, metric))
            else:
                verdicts.append(("PASS", f"PASS        {line}", sense,
                                 metric))
        if (max_cached_age is not None and rec.get("cached")
                and float(rec.get("cached_age_hours", float("inf")))
                > max_cached_age):
            age = rec.get("cached_age_hours", "unknown")
            verdicts.append((
                "STALE-CACHE",
                f"STALE-CACHE {metric}: replayed record is {age}h old "
                f"(> --max-cached-age {max_cached_age:g}) — warn only; "
                "land a fresh on-chip run to refresh the cache", sense,
                metric))
    return verdicts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python scripts/check_regression.py",
        description="fail when a headline metric regresses vs the "
                    "BENCH/BASELINE trajectory")
    ap.add_argument("--headline", action="append", metavar="GLOB",
                    default=[],
                    help="current headline record(s) "
                         "(default: results/headline*.json)")
    ap.add_argument("--history", action="append", metavar="GLOB",
                    default=[],
                    help="prior bench records (default: BENCH_*.json)")
    ap.add_argument("--baseline", default=os.path.join(ROOT, "BASELINE.json"),
                    help="baseline record with published reference numbers")
    ap.add_argument("--tolerance", type=float, default=0.1,
                    help="allowed fractional drop below the best prior "
                         "value (default: 0.10)")
    ap.add_argument("--max-cached-age", type=float, default=None,
                    metavar="HOURS",
                    help="warn when a cached headline replay is older than "
                         "this many hours (gates only with --strict-cache)")
    ap.add_argument("--strict-cache", action="store_true",
                    help="escalate STALE-CACHE warnings to gate failures "
                         "(exit 1): a lane that MUST run on fresh numbers "
                         "refuses to pass on an old replay")
    ap.add_argument("--summary-json", metavar="PATH", default=None,
                    help="also write the machine-readable verdict summary "
                         "to PATH (CI annotation; independent of --json)")
    ap.add_argument("--dry-run", action="store_true",
                    help="report verdicts but always exit 0 (CI smoke lane)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit machine-readable JSON verdicts")
    args = ap.parse_args(argv)

    headline_pats = args.headline or [
        os.path.join(ROOT, "results", "headline*.json")]
    history_pats = args.history or [os.path.join(ROOT, "BENCH_*.json")]

    try:
        headlines = load_headlines(headline_pats)
        if not headlines:
            raise RuntimeError(
                f"no headline records match {headline_pats!r} — "
                "run bench.py first")
        history = load_history(history_pats, args.baseline)
        verdicts = check(headlines, history, args.tolerance,
                         max_cached_age=args.max_cached_age)
    except RuntimeError as e:
        print(f"check_regression: {e}", file=sys.stderr)
        return 2

    regressed = [line for st, line, _, _ in verdicts if st == "REGRESSION"]
    stale = [line for st, line, _, _ in verdicts if st == "STALE-CACHE"]
    gate_fail = bool(regressed) or (args.strict_cache and bool(stale))
    exit_code = 1 if gate_fail and not args.dry_run else 0
    summary = {
        "tolerance": args.tolerance,
        "dry_run": args.dry_run,
        "strict_cache": args.strict_cache,
        "n_regressions": len(regressed),
        "n_stale_cached": len(stale),
        "exit_code": exit_code,
        "gate": "FAIL" if gate_fail else "PASS",
        # `predicted` is the static cost model's analytic expectation for
        # the metric (burstcost roofline) — null when the model can't
        # price it or can't import; it sits beside stale cached numbers
        # so a 5-day-old replay is read against the analytic ceiling
        "verdicts": [{"status": st, "detail": line, "direction": sense,
                      "predicted": predicted_value(metric)}
                     for st, line, sense, metric in verdicts],
    }
    if args.as_json:
        print(json.dumps(summary, indent=1))
    else:
        for _, line, _, _ in verdicts:
            print(line)
        print(f"check_regression: {len(regressed)} regression(s), "
              f"{len(stale)} stale-cache "
              + ("violation(s) [strict-cache]" if args.strict_cache
                 else "warning(s)")
              + f" across {len(verdicts) - len(stale)} metric(s), tolerance "
              f"{args.tolerance:g}"
              + (" [dry-run]" if args.dry_run else ""))
    if args.summary_json:
        d = os.path.dirname(os.path.abspath(args.summary_json))
        os.makedirs(d, exist_ok=True)
        with open(args.summary_json, "w", encoding="utf-8") as f:
            json.dump(summary, f, indent=1)
            f.write("\n")
    return exit_code


if __name__ == "__main__":
    sys.exit(main())
