"""Driver benchmark: one JSON line with the headline metric.

Metric = per-chip fwd+bwd TFLOPs/s of causal flash attention at the largest
reference config that fits one chip, using the reference's FLOPs convention
(reference benchmarks/benchmark.py:17-24): fwd FLOPs = 4*b*s^2*n*d / 2
(causal), fwd+bwd = 3.5x fwd, divided by elapsed seconds / 1e12, per chip.

Baseline = the reference's 8xA100 per-chip fwd+bwd TFLOPs/s at the same
sequence length (reference README.md:81-85; BASELINE.md).
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp

from benchmarks.benchmark import bench_fn as _time  # single timing impl
from burst_attn_tpu.utils.compile_cache import place_compile_cache

# Every on-chip run's record is persisted here (scripts/check_regression.py
# reads it).
HEADLINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "results", "headline.json")

# Incremental phase log: every phase transition — compile start/end, each
# warmup call, each rep — is appended and fsynced IMMEDIATELY, and a daemon
# heartbeat ticks every ~15 s, so a run killed at its time limit still
# leaves enough evidence to tell a slow compile from a mid-rep death.
EVENTS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "results", "bench_events.jsonl")


class _EventLog:
    """Append-only JSONL phase log; every write is flushed AND fsynced so
    a SIGKILL loses at most the event in flight.  All failures are
    swallowed — diagnostics must never kill the benchmark."""

    def __init__(self, path=EVENTS_PATH):
        self._t0 = time.time()
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            self._f = open(path, "a", encoding="utf-8")
        except OSError:
            self._f = None

    def event(self, phase: str, **fields) -> None:
        if self._f is None:
            return
        rec = {
            "ts_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "t_rel_s": round(time.time() - self._t0, 3),
            "phase": phase,
        }
        rec.update(fields)
        try:
            self._f.write(json.dumps(rec) + "\n")
            self._f.flush()
            os.fsync(self._f.fileno())
        except (OSError, ValueError):
            self._f = None

    def start_heartbeat(self, interval_s: float = 15.0) -> None:
        import threading

        def beat():
            n = 0
            while True:
                time.sleep(interval_s)
                n += 1
                self.event("heartbeat", n=n)

        threading.Thread(target=beat, daemon=True,
                         name="bench-heartbeat").start()


EVENTS = _EventLog()


def _save_headline(rec: dict, path: str = HEADLINE) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    rec = dict(rec, timestamp=time.time(),
               timestamp_utc=time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()))
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
        # fsync, not just flush: a run killed at its time limit must still
        # find the record on disk
        f.flush()
        os.fsync(f.fileno())


# obs JSONL export target: written after the run and REQUIRED to parse
# (ISSUE 3 satellite: the exporter's artifact is asserted, fsynced
# alongside results/headline.json) — `python -m burst_attn_tpu.obs` reads it
OBS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "results", "obs.jsonl")

# seq -> reference per-chip fwd+bwd TFLOPs/s (README.md:81-85)
BASELINE_FWDBWD = {65536: 170.0, 131072: 184.0, 262144: 191.0, 524288: 195.0, 1048576: 196.0}


def flops_fwd(b, s, n, d, causal):
    return 4 * b * s * s * n * d / (2 if causal else 1)


# Fast first config: compiles in a fraction of the seq=65536 time, so even a
# run that dies mid-big-compile leaves one fresh on-chip number.  Its record
# is fsynced to results/headline_small.json BEFORE the big config's arrays
# are even allocated.
SMALL_SEQ = 8192
HEADLINE_SMALL = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "results", "headline_small.json")

def _bench_tpu_config(seq, b, n, d, causal):
    """Time fwd+bwd flash attention at one config; returns the headline
    record."""
    from burst_attn_tpu.ops.pallas_flash import flash_attention

    dtype = jnp.bfloat16
    key = jax.random.PRNGKey(0)
    kq, kk, kv, kg = jax.random.split(key, 4)
    q = jax.random.normal(kq, (b, n, seq, d), dtype)
    k = jax.random.normal(kk, (b, n, seq, d), dtype)
    v = jax.random.normal(kv, (b, n, seq, d), dtype)
    do = jax.random.normal(kg, (b, n, seq, d), dtype)

    @jax.jit
    def fwdbwd(q, k, v, do):
        def loss(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, None, causal).astype(jnp.float32)
                * do.astype(jnp.float32)
            )

        dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        # force all three grads but fetch only one element of each: the
        # pallas bwd kernels compute whole arrays regardless, and full
        # [B,N,S,D] f32 sum reductions would add ~4 ms of pure harness
        # cost the reference's torch-Timer convention (y.backward(), no
        # reduction) does not pay
        return (dq[0, 0, 0, 0].astype(jnp.float32)
                + dk[0, 0, 0, 0].astype(jnp.float32)
                + dv[0, 0, 0, 0].astype(jnp.float32))

    EVENTS.event("bench_start", seq=seq, heads=n, dim=d, dtype="bfloat16")
    t = _time(fwdbwd, q, k, v, do, on_event=EVENTS.event)
    tflops = 3.5 * flops_fwd(b, seq, n, d, causal) / t / 1e12
    baseline = BASELINE_FWDBWD.get(seq)
    rec = {
        "metric": f"flash-attn fwd+bwd TFLOPs/s/chip @ seq={seq} causal bf16",
        "value": round(tflops, 2),
        "unit": "TFLOPs/s",
        # the reference published no 8xA100 number at the small config:
        # 0.0 marks "no baseline"
        "vs_baseline": round(tflops / baseline, 4) if baseline else 0.0,
    }
    return rec


def _record_headline_obs(rec: dict, seq: int) -> None:
    """Mirror a headline record into the obs registry so BENCH JSON and obs
    output share one schema (gauge value == the printed headline value)."""
    from burst_attn_tpu import obs

    labels = dict(seq=seq, unit=rec.get("unit", ""))
    obs.gauge("bench.headline", "headline per-chip TFLOPs/s by config"
              ).set(rec["value"], **labels)
    if rec.get("vs_baseline"):
        obs.gauge("bench.headline_vs_baseline").set(rec["vs_baseline"],
                                                    seq=seq)
    obs.counter("bench.runs").inc()


def _export_and_check_obs(path: str = OBS_PATH) -> None:
    """Export the registry to JSONL and ASSERT the artifact parses — an
    exporter regression must fail the bench loudly, not ship an unreadable
    observability file next to a healthy headline.json."""
    from burst_attn_tpu import obs
    from burst_attn_tpu.obs.__main__ import load_records, merge_records

    obs.export_jsonl(path)
    records = load_records(path)  # raises ValueError on any bad line
    if not records:
        raise RuntimeError(f"obs export {path} is empty")
    metrics, _spans, _meta = merge_records(records)
    if not metrics:
        raise RuntimeError(f"obs export {path} contains no metric records")
    EVENTS.event("obs_export", path=path, n_records=len(records))


def main():
    place_compile_cache()
    if jax.default_backend() != "tpu":
        print(f"bench: needs a TPU, JAX found {jax.default_backend()!r}; "
              f"nothing measured", file=sys.stderr)
        return 1
    EVENTS.start_heartbeat()
    EVENTS.event("start", argv=sys.argv)
    b, n, d = 1, 32, 128
    causal = True

    # cheap config FIRST: its record is printed and fsynced before the
    # seq=65536 arrays exist, so a time limit hit during the big config's
    # compile still leaves a fresh on-chip number
    rec_small = _bench_tpu_config(SMALL_SEQ, b, n, d, causal)
    rec_small["warmup_config"] = True
    _save_headline(rec_small, HEADLINE_SMALL)
    EVENTS.event("small_done", **rec_small)
    print(json.dumps(rec_small), flush=True)
    _record_headline_obs(rec_small, SMALL_SEQ)

    seq = 65536
    rec = _bench_tpu_config(seq, b, n, d, causal)
    dev = jax.devices()[0]
    rec["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(jax.devices())}
    _save_headline(rec)
    EVENTS.event("done", **rec)
    print(json.dumps(rec))
    _record_headline_obs(rec, seq)
    _export_and_check_obs()
    return 0


if __name__ == "__main__":
    sys.exit(main())
