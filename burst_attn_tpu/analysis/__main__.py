"""burstlint CLI:  python -m burst_attn_tpu.analysis [--json] [paths...]

Exit status: 0 clean, 1 findings, 2 internal error.  Runs CPU-only (the
jaxpr family traces abstractly on simulated host devices); wired into
scripts/test.sh as the pre-test gate.
"""

import argparse
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m burst_attn_tpu.analysis",
        description="burstlint: static ring/sharding/numerics verifier")
    ap.add_argument("paths", nargs="*",
                    help="files/dirs for the AST rules (default: package)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit machine-readable JSON")
    ap.add_argument("--sarif", metavar="PATH",
                    help="also write findings as SARIF 2.1.0 to PATH "
                         "(CI annotations); stdout output is unchanged")
    ap.add_argument("--changed-only", action="store_true",
                    help="incremental mode: lint files changed since the "
                         "merge-base with the default branch and skip "
                         "dynamic rule families whose watched sources "
                         "are untouched; falls back to a FULL run when "
                         "git is unavailable")
    ap.add_argument("--ast-only", action="store_true",
                    help="skip the jaxpr tracing family (fast editor hook)")
    ap.add_argument("--disable", action="append", default=[],
                    metavar="RULE", help="disable a rule by name")
    ap.add_argument("--list-rules", action="store_true",
                    help="print registered rules and exit")
    ap.add_argument("--cost-json", action="store_true",
                    help="print the burstcost roofline table (schema "
                         "burstcost-v3) as JSON and exit: the generation x "
                         "topology x wire-dtype x pass matrix fleet/sim.py "
                         "prices replicas with")
    args = ap.parse_args(argv)

    # the jaxpr family needs 8 simulated devices and must never grab a TPU:
    # set up the backend BEFORE jax initializes (importing the package does
    # not import jax; the rule modules do, lazily)
    if not args.ast_only:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=8")
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from .core import RULES, render, render_sarif, run_analysis

    if args.list_rules:
        # force registration of the lazy rule families
        from . import (astlint, costcheck, numerics,  # noqa: F401
                       obscheck, policycheck, poolcheck, protocheck,
                       ringcheck, servecheck)

        for name in sorted(RULES):
            r = RULES[name]
            print(f"{name:22s} [{r.kind}]  {r.doc}")
        return 0

    if args.cost_json:
        import json

        from . import costmodel

        try:
            print(json.dumps(costmodel.cost_table(), indent=1))
        except Exception as e:  # noqa: BLE001 — CLI boundary
            print(f"burstcost: internal error: {type(e).__name__}: {e}",
                  file=sys.stderr)
            return 2
        return 0

    paths = None
    if args.paths:
        from .astlint import default_paths

        paths = []
        for p in args.paths:
            paths += default_paths(p) if os.path.isdir(p) else [p]
    try:
        findings = run_analysis(disable=args.disable, ast_only=args.ast_only,
                                paths=paths,
                                changed_only=args.changed_only)
    except Exception as e:  # noqa: BLE001 — CLI boundary: report, exit 2
        print(f"burstlint: internal error: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 2
    if args.sarif:
        sarif_dir = os.path.dirname(os.path.abspath(args.sarif))
        os.makedirs(sarif_dir, exist_ok=True)
        with open(args.sarif, "w") as fh:
            fh.write(render_sarif(findings))
    print(render(findings, args.as_json))
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
