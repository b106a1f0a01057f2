"""Device-side ring telemetry: a purely functional stats pytree.

Everything else in `burst_attn_tpu.obs` is host-only by contract (the
burstlint `obs-jit-safe` rule proves no registry/span call is reachable
under jit).  That contract makes the *inside* of a ring step invisible:
per-round work distribution, mask occupancy under the causal layouts,
softmax-stat health — all of it lives in the compiled program, where host
instrumentation must never go.

`DevStats` closes the gap without breaking the contract.  It is a NamedTuple
of plain device arrays that the ring forward accumulates IN-GRAPH
(`burst_attn(..., collect_stats=True)` returns `(out, DevStats)`): no host
callbacks, no clocks, no registry writes — just extra pure equations whose
cost is O(rounds * s_local) scalar work, invisible next to the attention
itself.  After the step the caller folds the (now concrete) arrays into the
host registry with `DevStats.publish(...)` — the device->host hop happens at
the host boundary, exactly where `obs-jit-safe` wants it.  The companion
burstlint rule `devstats-pure` (analysis/obscheck.py) proves both halves of
the bargain: the stats-enabled forward/backward traces contain zero
host-callback primitives, and the stats-OFF trace is bit-identical to the
plain (pre-devstats) ring program.

Per-shard, every field is a scalar; at the `burst_attn` boundary the shards
are stacked over the ring axis, so the caller sees per-device arrays of
leading length `world`:

  rounds         executed ring rounds (truncated rings count live schedule)
  rounds_live    rounds whose mask had ANY attending pair (ops/masks.spec_live)
  attn_pairs     attended (q, kv) pairs summed over rounds (f32)
  total_pairs    s_q * s_kv summed over executed rounds (occupancy denom)
  flops          ~4 * head_dim * attn_pairs — the per-device balance
                 measure; the burstcost roofline carries the same algebra
                 in closed form (analysis/costmodel.pass_flops), with the
                 cost-model-consistent lint rule pinning the closed-form
                 pair count to the per-round sum these counters integrate
  m_max          max running row-max after the ring
  lse_min/max    finite range of the final log-sum-exp
  nonfinite_lse  count of nan/+inf lse entries (-inf is a legal fully-masked
                 row, not an error)
  nonfinite_acc  count of non-finite accumulator/output entries
  fused_rounds   always 0: the ring kernel that counted here is gone, the
                 field goes with the rest of this module (ROADMAP D13)
  rounds_elided  rounds the occupancy compiler removed from the schedule
                 entirely (windowed/segment-bounded contig rings); these
                 never launched, unlike (rounds - rounds_live) which ran
                 fully masked

The split of labor per causal layout is visible directly: zigzag/striped
devices report near-equal `attn_pairs` (the load-balancing the layouts
exist for), a contig ring reports the raw triangle imbalance, and a
windowed contig ring shows the truncated round count.
"""

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = float("-inf")
_POS_INF = float("inf")


class DevStats(NamedTuple):
    """In-graph ring telemetry (see module docstring for field semantics).

    A pytree of device arrays: per-shard scalars inside shard_map, stacked
    to a leading `world` axis at the `burst_attn` boundary."""

    rounds: jnp.ndarray          # i32
    rounds_live: jnp.ndarray     # i32
    attn_pairs: jnp.ndarray      # f32
    total_pairs: jnp.ndarray     # f32
    flops: jnp.ndarray           # f32
    m_max: jnp.ndarray           # f32
    lse_min: jnp.ndarray         # f32
    lse_max: jnp.ndarray         # f32
    nonfinite_lse: jnp.ndarray   # i32
    nonfinite_acc: jnp.ndarray   # i32
    fused_rounds: jnp.ndarray    # i32
    # rounds the occupancy compiler ELIDED from the schedule (windowed /
    # length-bounded packed-segment contig rings): world minus the
    # compiled round count.  Executed-vs-live accounting: rounds +
    # rounds_elided == world on single-ring schedules, and an elided
    # round never launched, which is what distinguishes this counter from
    # (rounds - rounds_live), the rounds that RAN fully masked.
    rounds_elided: jnp.ndarray   # i32
    # finite-range gauge of the wire quantizer (cfg.wire_dtype): the
    # largest |value| the symmetric per-block quantization mapped to its
    # top code this dispatch.  0.0 on the dense wire.  A growing gauge
    # next to a fixed-range wire dtype means blocks are saturating —
    # observable here rather than silently clipped on the link.
    quant_absmax: jnp.ndarray      # f32

    def publish(self, registry=None, *, labels: Optional[dict] = None):
        """Fold concrete (post-step) stats into a host metrics registry.

        HOST-SIDE ONLY: forces the device arrays to numpy — call it after
        the step, never under a trace (the burstlint `obs-jit-safe` /
        `devstats-pure` pair keeps this honest).  Per-device gauges carry a
        `device` label (ring position); cross-device health extrema and the
        nonfinite counters are aggregated.  Returns the registry."""
        import numpy as np

        from .registry import default_registry

        reg = registry if registry is not None else default_registry()
        base = dict(labels or {})
        leaves = {f: np.asarray(getattr(self, f), dtype=np.float64)
                  for f in self._fields}
        if leaves["rounds"].ndim == 0:  # per-shard stats published directly
            leaves = {f: a[None, ...] for f, a in leaves.items()}
        world = leaves["rounds"].shape[0]

        for dev in range(world):
            lab = dict(base, device=dev)
            reg.gauge("devstats.rounds",
                      "executed ring rounds per device").set(
                leaves["rounds"][dev], **lab)
            reg.gauge("devstats.rounds_live",
                      "rounds with any attending pair").set(
                leaves["rounds_live"][dev], **lab)
            reg.gauge("devstats.rounds_elided",
                      "rounds the occupancy compiler removed from the "
                      "schedule (never launched)").set(
                leaves["rounds_elided"][dev], **lab)
            total = leaves["total_pairs"][dev]
            occ = leaves["attn_pairs"][dev] / total if total > 0 else 0.0
            reg.gauge("devstats.mask_occupancy",
                      "attended fraction of executed tile area").set(occ,
                                                                     **lab)
            reg.gauge("devstats.flops",
                      "attention flop estimate per device").set(
                leaves["flops"][dev], **lab)

        fl = leaves["flops"]
        mean = float(fl.mean())
        reg.gauge("devstats.flop_imbalance",
                  "max/mean per-device attention flops (1.0 = balanced)"
                  ).set(float(fl.max()) / mean if mean > 0 else 0.0, **base)
        reg.gauge("devstats.m_max",
                  "max running row-max across devices").set(
            float(leaves["m_max"].max()), **base)
        reg.gauge("devstats.lse_min").set(float(leaves["lse_min"].min()),
                                          **base)
        reg.gauge("devstats.lse_max").set(float(leaves["lse_max"].max()),
                                          **base)
        reg.counter("devstats.nonfinite",
                    "non-finite softmax-state entries seen, by array").inc(
            float(leaves["nonfinite_lse"].sum()), which="lse", **base)
        reg.counter("devstats.nonfinite").inc(
            float(leaves["nonfinite_acc"].sum()), which="acc", **base)
        reg.counter("devstats.fused_rounds",
                    "always 0 (no kernel counts here any more)").inc(
            float(leaves["fused_rounds"].sum()), **base)
        reg.gauge("devstats.quant_absmax",
                  "largest |value| the wire quantizer mapped to its top "
                  "code (0 = dense wire; watch for saturation)").set(
            float(leaves["quant_absmax"].max()), **base)
        reg.counter("devstats.publishes",
                    "DevStats pytrees folded into the registry").inc()
        return reg


def ring_stats(rounds, rounds_live, attn_pairs, total_pairs, head_dim,
               m, lse, acc, rounds_elided=0, quant_absmax=0.0) -> DevStats:
    """Assemble a per-shard DevStats from ring results (traced context).

    `acc` is the f32 accumulator: non-finite entries mean the softmax went
    wrong.  `lse` -inf entries are legal (fully-masked rows) and are
    excluded from the finite range but not counted as corruption."""
    i32 = jnp.int32
    f32 = jnp.float32
    attn_pairs = jnp.asarray(attn_pairs, f32)
    finite = jnp.isfinite(lse)
    stats = DevStats(
        rounds=jnp.asarray(rounds, i32),
        rounds_live=jnp.asarray(rounds_live, i32),
        attn_pairs=attn_pairs,
        total_pairs=jnp.asarray(total_pairs, f32),
        flops=attn_pairs * (4.0 * head_dim),
        m_max=jnp.max(m).astype(f32),
        lse_min=jnp.min(jnp.where(finite, lse, _POS_INF)).astype(f32),
        lse_max=jnp.max(jnp.where(finite, lse, _NEG_INF)).astype(f32),
        nonfinite_lse=jnp.sum(
            jnp.isnan(lse) | (lse == _POS_INF)).astype(i32),
        nonfinite_acc=jnp.sum(~jnp.isfinite(acc)).astype(i32),
        fused_rounds=jnp.asarray(0, i32),
        rounds_elided=jnp.asarray(rounds_elided, i32),
        quant_absmax=jnp.asarray(quant_absmax, f32),
    )
    # telemetry is non-differentiable by definition: zero the tangents here
    # so downstream cross_reduce/merge arithmetic never asks autodiff for
    # pmax/pmin rules and grads through the attention output stay untouched
    return jax.tree.map(lax.stop_gradient, stats)


# per-field cross-device reduction when extra (batch/head) mesh axes ride
# alongside the ring: counts sum, extrema max/min — so the published
# per-ring-position stats cover the whole shard group at that position
_REDUCE_MAX = ("m_max", "lse_max", "quant_absmax")
_REDUCE_MIN = ("lse_min",)


def cross_reduce(stats: DevStats, axes) -> DevStats:
    """Reduce per-shard stats over non-ring mesh axes (inside shard_map).

    `axes`: names of size>1 batch/head axes; empty = no-op.  Sums are the
    right unit for counters (total pairs across the replica group at one
    ring position), extrema for the health fields."""
    axes = tuple(axes)
    if not axes:
        return stats
    out = {}
    for f in stats._fields:
        v = getattr(stats, f)
        if f in _REDUCE_MAX:
            out[f] = lax.pmax(v, axes)
        elif f in _REDUCE_MIN:
            out[f] = lax.pmin(v, axes)
        else:
            out[f] = lax.psum(v, axes)
    return DevStats(**out)


def expand_device_axis(stats: DevStats) -> DevStats:
    """Per-shard scalars -> leading [1] axis, so a shard_map out_spec over
    the ring axis stacks them into per-device arrays of length `world`."""
    return jax.tree.map(lambda a: a[None, ...], stats)


def merge(a: DevStats, b: DevStats) -> DevStats:
    """Fold two DevStats (e.g. successive transformer layers): counts add,
    extrema max/min — same semantics as cross_reduce, host/trace agnostic."""
    out = {}
    for f in a._fields:
        va, vb = getattr(a, f), getattr(b, f)
        if f in _REDUCE_MAX:
            out[f] = jnp.maximum(va, vb)
        elif f in _REDUCE_MIN:
            out[f] = jnp.minimum(va, vb)
        else:
            out[f] = va + vb
    return DevStats(**out)
