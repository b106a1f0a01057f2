#!/usr/bin/env bash
# Test launcher (reference test/test.sh:6 analogue).  No torchrun, no GPU
# fleet: the distributed tests run on a simulated 8-device CPU mesh anywhere;
# pass --tpu to also run the real-hardware kernel tests (on a machine with a
# chip: one process at a time holds it).
# --fast selects the <10-min lane (-m "not slow"); default runs everything.
set -euo pipefail
cd "$(dirname "$0")/.."
args=("$@")
filtered=()
fast=0; tpu=0; obs=0; schedule=0; serve=0; loadgen=0; fleet=0
quant=0; sim=0
for a in "${args[@]}"; do
  case "$a" in
    --fast) fast=1 ;;
    --tpu) tpu=1 ;;
    --obs) obs=1 ;;
    --schedule) schedule=1 ;;
    --serve) serve=1 ;;
    --loadgen) loadgen=1 ;;
    --fleet) fleet=1 ;;
    --quant) quant=1 ;;
    --sim) sim=1 ;;
    *) filtered+=("$a") ;;
  esac
done
# burstlint pre-test gate: CPU-only static verification (ring invariants,
# numerics contract, AST hygiene, protocol model checking, and the
# burstcost roofline family, sub-second) in a few seconds —
# tier-1 fails on new violations before any test runs.  The
# SARIF copy feeds CI annotation uploaders; the gate itself keys off the
# exit status.
echo "== burstlint (python -m burst_attn_tpu.analysis) =="
mkdir -p results
JAX_PLATFORMS=cpu python -m burst_attn_tpu.analysis \
  --sarif results/burstlint.sarif

if [[ $obs == 1 ]]; then
  # focused lane for the observability subsystem (registry math, spans,
  # exporters, devstats, serve/ring instrumentation) + its burstlint rule
  # mutations — the quick iteration loop while working on burst_attn_tpu/obs/
  python -m pytest tests/test_obs.py tests/test_devstats.py \
    tests/test_analysis.py -q ${filtered[@]+"${filtered[@]}"}
  # end-to-end CLI smoke: the multi-process merge on synthetic per-process
  # snapshots, and the perf-regression gate in dry-run — both exercised on
  # every --obs run so a CLI/gate regression can't hide behind unit tests
  obs_tmp=$(mktemp -d)
  trap 'rm -rf "$obs_tmp"' EXIT
  python - "$obs_tmp" <<'PY'
import sys
from burst_attn_tpu.obs.registry import Registry

tmp = sys.argv[1]
for p in range(2):
    r = Registry()
    r.counter("smoke.count").inc(p + 1)
    r.gauge("smoke.depth").set(p)
    r.export_jsonl(f"{tmp}/obs_{p}.jsonl", process_index=p)
PY
  python -m burst_attn_tpu.obs --merge "$obs_tmp/obs*.jsonl" > /dev/null
  # request-tracing smoke (ISSUE 19): a tiny traced fleet burst must yield
  # >= 1 COMPLETE cross-stage trace tree (router -> prefill -> KV transfer
  # -> decode, spanning >= 2 processes) whose phase breakdown sums to the
  # TTFT within tolerance; then the CLI renders the trees and one
  # waterfall from the merged per-process exports.  Written to a real file
  # (not stdin) so multiprocessing spawn can re-import __main__.
  cat > "$obs_tmp/trace_smoke.py" <<'PY'
import os
import sys

# the script lives in a tmp dir: put the invoking repo root (cwd) on the
# path; spawn children inherit sys.path, so the workers resolve it too
sys.path.insert(0, os.getcwd())


def main():
    tmp = sys.argv[1]
    from burst_attn_tpu.fleet import FleetCluster
    from burst_attn_tpu.loadgen.trace import Trace, TraceRequest
    from burst_attn_tpu.obs.aggregate import build_trace_trees
    from burst_attn_tpu.obs.trace import ttft_breakdown

    model = dict(vocab=97, d_model=32, n_layers=1, n_heads=2, n_kv_heads=1,
                 d_head=16, d_ff=64, block_q=8, block_kv=8, seed=0)
    reqs = [TraceRequest(rid=i, t_arrival=0.05 * i, prompt_len=128,
                         prompt_seed=100 + i, max_new_tokens=4)
            for i in range(2)]
    trace = Trace(meta={"vocab": 97}, requests=reqs)
    with FleetCluster(model,
                      prefill_spec=dict(sp=2, page=128, n_pages=4,
                                        max_pages_per_seq=8),
                      decode_spec=dict(sp=2, slots=2, page=128, n_pages=8,
                                       max_pages_per_seq=4),
                      n_prefill=1, n_decode=1, out_dir=tmp,
                      transport="queue", trace=True) as fc:
        rep = fc.replay(trace, speed=25.0, max_wall_s=420.0)
    assert all(o.status == "done" for o in rep.outcomes.values()), rep.outcomes
    # workers flush their final export at shutdown: merge AFTER the exit
    _metrics, _spans, meta = fc.merged()
    trees = build_trace_trees(meta["traces"],
                              meta.get("truncated_processes", ()))
    need = {"fleet.request", "fleet.prefill", "fleet.ship", "fleet.transfer",
            "fleet.commit", "fleet.decode"}
    ok = []
    for t in trees:
        bd = ttft_breakdown(t["spans"])
        procs = {str(s.get("process_index")) for s in t["spans"]}
        if (t["complete"] and need <= {s["name"] for s in t["spans"]}
                and len(procs) >= 2 and bd
                and abs(sum(bd["phases"].values()) - bd["ttft_s"])
                <= 0.01 * bd["ttft_s"]):
            ok.append(t["trace_id"])
    assert ok, [(t["trace_id"], t["complete"],
                 sorted({s["name"] for s in t["spans"]})) for t in trees]
    print(f"obs --trace smoke: {len(ok)}/{len(trees)} complete "
          f"cross-stage tree(s)")
    with open(f"{tmp}/trace_id", "w") as f:
        f.write(ok[0])


if __name__ == "__main__":
    main()
PY
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python "$obs_tmp/trace_smoke.py" "$obs_tmp"
  python -m burst_attn_tpu.obs --trace --merge "$obs_tmp/obs_*.jsonl"
  python -m burst_attn_tpu.obs --waterfall "$(cat "$obs_tmp/trace_id")" \
    --merge "$obs_tmp/obs_*.jsonl"
  python scripts/check_regression.py --dry-run
elif [[ $serve == 1 ]]; then
  # focused lane for the ragged paged serving subsystem: the one-launch
  # ragged kernel's interpret-mode parity + probe tests, the continuous-
  # batching engine (admission/eviction/speculative policy, load-shed
  # ordering), the pipelined engine's parity matrix (deferred readback,
  # fused multi-step launches, reconcile), and the ring->pages handoff —
  # the quick iteration loop while working on burst_attn_tpu/serving/
  # and ops/ragged_paged.py
  python -m pytest tests/test_ragged_paged.py tests/test_serving.py \
    tests/test_serving_pipeline.py \
    tests/test_serving_handoff.py tests/test_check_regression.py -q \
    ${filtered[@]+"${filtered[@]}"}
  # bench smoke + perf gate: drive the engine end to end, emit the
  # serve.ttft_p99 (direction: lower) and serve.tokens_per_s headlines,
  # then gate them against BENCH history in dry-run — a serving-path
  # slowdown surfaces on every lane run without flaking CI on noise
  python scripts/bench_serve.py
  python scripts/check_regression.py \
    --headline 'results/headline_serve_*.json' --dry-run
elif [[ $loadgen == 1 ]]; then
  # production-serve hardening lane: trace/driver/SLO unit tests, the FULL
  # multi-process fault matrix (kill mid-decode, forced pool exhaustion,
  # stall, hang, restart-from-checkpoint, legacy engine — slow-marked tests
  # included here on purpose), the checkpoint/journal recovery tests, the
  # handoff-path fault matrix, and the admission/drain/typed-rejection
  # engine tests
  python -m pytest tests/test_loadgen.py tests/test_loadgen_cluster.py \
    tests/test_checkpoint_serve.py tests/test_handoff_faults.py -q \
    ${filtered[@]+"${filtered[@]}"}
  python -m pytest tests/test_serving.py -q \
    -k "drain or typed_rejections or admission" \
    ${filtered[@]+"${filtered[@]}"}
  # checkpoint-recovery fuzz: seeded random kill points through the
  # snapshot+journal AND journal-only recovery paths — token-exact vs the
  # uninterrupted oracle every time, recomputation bounded by journal lag.
  # --pipeline-seeds: kills inside the pipelined engine's delivery-lag
  # window (mid-flight / mid-multi-step-scan / mid-readback), recovery
  # token-exact vs the synchronous oracle
  python scripts/fuzz_checkpoint.py --seeds 3 --pipeline-seeds 2
  # bench + REAL perf gate (not dry-run): replay the canonical trace, emit
  # serve.load_p99_ttft (lower) + serve.load_goodput (higher) +
  # serve.load_recovery_p99 (lower; kill-mid-trace cluster recovery)
  # headlines, then gate them against BENCH history with a machine-readable
  # verdict.
  # --strict-cache: this lane must run the bench fresh, never a stale replay.
  python scripts/bench_loadgen.py
  python scripts/check_regression.py \
    --headline 'results/headline_loadgen_*.json' \
    --strict-cache --summary-json results/loadgen_gate.json
elif [[ $fleet == 1 ]]; then
  # disaggregated prefill/decode fleet lane: the wire-protocol unit +
  # fuzz canaries, then the FULL cross-boundary fault matrix (kill /
  # restart / hog / stall / hang on both pools, kills mid-KV-transfer in
  # both directions, heartbeat detection, autoscale) — slow-marked tests
  # included here on purpose — plus the refactored loadgen cluster and
  # handoff precondition tests the fleet builds on
  python -m pytest tests/test_fleet_transport.py tests/test_fleet.py \
    tests/test_loadgen_cluster.py tests/test_serving_handoff.py -q \
    ${filtered[@]+"${filtered[@]}"}
  # seeded frame-transport fuzz, the full sweep: truncated / bit-flipped /
  # duplicated frame streams — CRC rejects every mangled frame, dedup
  # holds under redelivery, the retry path always completes byte-exactly
  python scripts/fuzz_checkpoint.py --seeds 0 --transport-seeds 50
  # fleet bench + REAL perf gate: disaggregated replay (KV pages over the
  # frame transport) for serve.fleet_goodput (higher), then a decode
  # SIGKILL mid-stream for serve.fleet_recovery_p99 (lower) — both
  # token-exact vs the single-process oracle, gated against BENCH history.
  # --strict-cache: this lane must run the bench fresh, never a stale replay.
  python scripts/bench_loadgen.py --fleet
  python scripts/check_regression.py \
    --headline 'results/headline_fleet_*.json' \
    --strict-cache --summary-json results/fleet_gate.json
elif [[ $sim == 1 ]]; then
  # burstsim lane (fleet/sim.py + fleet/policy.py): fast canaries first —
  # engine determinism (bit-identical event digests), the spy-asserted
  # FleetCluster->policy delegation, policy bit-identity vs the
  # pre-refactor inline router, the policy-pure lint mutations, and the
  # fidelity gate on a toy trace — then the slow-marked 1000-replica /
  # 1M-request diurnal sweep (<60s wall, digest-pinned) and the real
  # process-backed --fleet fidelity replay
  python -m pytest tests/test_fleet_sim.py -q -m "not slow" \
    ${filtered[@]+"${filtered[@]}"}
  python -m pytest tests/test_fleet_sim.py -q -m slow \
    ${filtered[@]+"${filtered[@]}"}
  # policy-space sweep bench + perf gate: best simulated goodput over
  # POLICIES becomes serve.sim_policy_goodput (higher); virtual-time and
  # seeded, so the gate compares real numbers, not scheduler noise.
  # --strict-cache: this lane must run the bench fresh, never a stale replay.
  python scripts/bench_fleet_sim.py
  python scripts/check_regression.py \
    --headline 'results/headline_sim_*.json' \
    --strict-cache --summary-json results/sim_gate.json
elif [[ $schedule == 1 ]]; then
  # focused lane for the ring-schedule IR + compiler (parallel/schedule.py):
  # compiler/oracle unit tests and the schedule-proof mutation suite
  # (flipped direction, shortened prefetch, aliased slot, broken elider —
  # each must fire).  The burstlint gate above already simulation-proved
  # the full emitted matrix (including the occupancy-elided r_live entries).
  python -m pytest tests/test_schedule_ir.py tests/test_schedule.py -q \
    ${filtered[@]+"${filtered[@]}"}
  python -m pytest tests/test_analysis.py -q -k "ring_program or elision or elided" \
    ${filtered[@]+"${filtered[@]}"}
  # occupancy compilation: closed-form/live-set unit tests, then the
  # windowed + packed-segment round accounting on the ring (incl. slow)
  python -m pytest tests/test_masks.py -q \
    -k "pair_count or elided or elision or truncate or segment or prefix" \
    ${filtered[@]+"${filtered[@]}"}
  python -m pytest tests/test_devstats.py -q \
    -k "window or segment or elided or elision" \
    ${filtered[@]+"${filtered[@]}"}
elif [[ $quant == 1 ]]; then
  # focused lane for the wire-precision layer (cfg.wire_dtype): fwd/grad
  # parity vs the fp32 ring, wire_dtype=None bit-identity, byte-accounting
  # replay against schedule.wire_round_bytes, and the scale-proof burstlint
  # mutations (dropped rescale, escaped unscaled output, raw quantized dot,
  # fp16 accum behind quant, credit-neutral recompile)
  python -m pytest tests/test_wire_quant.py -q ${filtered[@]+"${filtered[@]}"}
  python -m pytest tests/test_analysis.py -q -k "wire" \
    ${filtered[@]+"${filtered[@]}"}
elif [[ $fast == 1 ]]; then
  python -m pytest tests/ -q -m "not slow" ${filtered[@]+"${filtered[@]}"}
else
  python -m pytest tests/ -q ${filtered[@]+"${filtered[@]}"}
fi
if [[ $tpu == 1 ]]; then
  BURST_TESTS_TPU=1 python -m pytest tests/test_fused_bwd.py tests/test_pallas.py -q
fi
