"""obs-jit-safe + devstats-pure, jaxpr halves (burstlint family 1).

The AST half (astlint._check_obs_jit_safe) proves no obs BINDING is called
from a statically jit-marked function; this half closes the dynamic gap —
instrumentation smuggled into a compiled program through any indirection
(a helper module, `jax.debug.callback(REGISTRY.inc)`, a pure_callback
wrapper) shows up in the traced jaxpr as a host-callback primitive no
matter how it was spelled.  The hot attention programs are traced
abstractly (same harness as ringcheck) and must contain ZERO callback
primitives: the ring's value is overlap, and a host callback inside the
ring is a synchronous device<->host round trip per step, exactly the
regression this subsystem exists to catch.

`devstats-pure` extends the same proof to the device-side telemetry path
(obs/devstats.py — the one obs module the AST rule deliberately EXEMPTS
from the jit ban):

  1. the stats-enabled ring forward AND backward
     (`burst_attn_shard(..., collect_stats=True)` through
     `jax.value_and_grad`) trace to jaxprs with zero host-callback
     primitives — collecting telemetry in-graph must never smuggle a
     host hop into the ring;
  2. the stats-OFF trace is BIT-IDENTICAL (string-equal jaxpr) to the
     plain pre-devstats entry point (`_burst_attn_shard_plain`) — turning
     the feature off must cost nothing, byte for byte.

Flagged primitives: anything whose name contains "callback"
(pure_callback / io_callback / debug_callback across jax versions) plus
the legacy host_callback "outside_call".
"""

import inspect
from typing import List

from .core import Finding, rule
from .jaxpr_tools import iter_eqns

rule("devstats-pure", "jaxpr",
     "stats-enabled ring fwd/bwd carry zero host-callback primitives; "
     "stats-off trace bit-identical to the plain ring")(None)

rule("ckpt-jit-safe", "jaxpr",
     "traced serve-step programs (ragged_model_step / paged_decode_step) "
     "carry zero host-callback primitives — checkpoint/journal writes "
     "stay at the host dispatch boundary")(None)

rule("pipe-fused-pure", "jaxpr",
     "the fused multi-step decode scan (pipelined engine) traces with zero "
     "host-callback primitives and zero remote-DMA/collective primitives — "
     "K device steps per host dispatch, no hidden host or wire hops")(None)

rule("pipe-tick-identity", "jaxpr",
     "the K=1 pipelined tick traces string-identical to the synchronous "
     "engine tick (model step + sample) — pipelining moves WHEN readback "
     "happens, never WHAT is computed")(None)

_LEGACY_CALLBACK_PRIMS = ("outside_call",)


def _is_callback_prim(name: str) -> bool:
    return "callback" in name or name in _LEGACY_CALLBACK_PRIMS


def _anchor(fn):
    try:
        return inspect.getsourcefile(fn), inspect.getsourcelines(fn)[1]
    except (OSError, TypeError):
        return "<trace>", 0


def check_trace(closed_jaxpr, *, where: str, anchor,
                rule_name: str = "obs-jit-safe") -> List[Finding]:
    """Flag every host-callback primitive in one traced program."""
    findings: List[Finding] = []
    path, line = anchor
    for eqn in iter_eqns(closed_jaxpr):
        name = eqn.primitive.name
        if _is_callback_prim(name):
            findings.append(Finding(
                rule=rule_name, file=path, line=line,
                message=f"{where}: host-callback primitive `{name}` inside "
                        "the traced program — a synchronous device<->host "
                        "round trip per executed step; obs instrumentation "
                        "must stay at the host dispatch boundary"))
    return findings


# Substrings that mark a cross-device primitive: collectives (ppermute /
# psum / all_gather / all_to_all / pbroadcast), plus anything spelled as
# an explicit remote copy or DMA across jax versions.  A single-host
# decode scan must bind none of them — the fused launch's whole point is
# K steps with zero host AND zero wire traffic per dispatch.
_REMOTE_PRIM_MARKERS = ("ppermute", "psum", "pmax", "pmin", "pbroadcast",
                        "all_gather", "all_to_all", "collective",
                        "remote", "dma")


def check_remote_free(closed_jaxpr, *, where: str, anchor,
                      rule_name: str = "pipe-fused-pure") -> List[Finding]:
    """Flag every remote-DMA/collective primitive in one traced program."""
    findings: List[Finding] = []
    path, line = anchor
    for eqn in iter_eqns(closed_jaxpr):
        name = eqn.primitive.name
        if any(m in name for m in _REMOTE_PRIM_MARKERS):
            findings.append(Finding(
                rule=rule_name, file=path, line=line,
                message=f"{where}: remote/collective primitive `{name}` "
                        "inside the traced decode program — the fused "
                        "launch must be a purely local device program "
                        "(no wire traffic hidden inside the scan)"))
    return findings


_ADDR_RE = None


def _canon_jaxpr(closed_jaxpr) -> str:
    """Jaxpr pretty-print with run-dependent noise removed: custom_vjp
    params embed live function objects whose reprs carry heap addresses
    (`0x7f...`), which differ between two traces of the SAME program."""
    global _ADDR_RE
    if _ADDR_RE is None:
        import re

        _ADDR_RE = re.compile(r"0x[0-9a-f]+")
    return _ADDR_RE.sub("0x", str(closed_jaxpr))


def check_off_identity(jaxpr_off, jaxpr_plain, *, anchor) -> List[Finding]:
    """devstats-pure half 2: the collect_stats=False trace must be
    STRING-IDENTICAL (modulo heap addresses) to the plain (pre-devstats)
    ring program — the only acceptable cost of the telemetry feature when
    it is off is zero."""
    path, line = anchor
    if _canon_jaxpr(jaxpr_off) == _canon_jaxpr(jaxpr_plain):
        return []
    return [Finding(
        rule="devstats-pure", file=path, line=line,
        message="collect_stats=False ring trace diverged from the plain "
                "ring program — devstats machinery is leaking into the "
                "stats-off path (it must be bit-identical to a build "
                "without devstats)")]


def check_all() -> List[Finding]:
    """Trace the burst forward AND backward shard programs on a simulated
    flat ring and prove both are callback-free.  (The tile kernels
    and case-split branches are all inside these traces;
    ringcheck's topology matrix covers scheduling, this covers purity.)"""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from ..parallel import burst
    from jax import shard_map

    findings: List[Finding] = []
    devs = jax.devices()
    world = 4
    if len(devs) < world:
        raise RuntimeError(
            f"analysis needs {world} simulated devices "
            f"(XLA_FLAGS=--xla_force_host_platform_device_count=8); "
            f"have {len(devs)}")
    mesh = Mesh(np.asarray(devs[:world]), ("sp",))
    cfg = burst.BurstConfig(causal=True, layout="zigzag", intra_axis="sp",
                            backend="jnp")
    b, n, d, s_local = 1, 2, 8, 16
    S = jax.ShapeDtypeStruct
    q = S((b, n, s_local * world, d), jnp.bfloat16)
    lse = S((b, n, s_local * world), jnp.float32)
    spec4 = P(None, None, "sp", None)
    spec3 = P(None, None, "sp")

    fwd = shard_map(lambda q, k, v: burst._fwd_impl(q, k, v, cfg),
                    mesh=mesh, in_specs=(spec4,) * 3,
                    out_specs=(spec4, spec3), check_vma=False)
    findings += check_trace(jax.make_jaxpr(fwd)(q, q, q),
                            where="burst fwd", anchor=_anchor(burst._fwd_impl))
    bwd = shard_map(
        lambda q, k, v, o, lse, do: burst._bwd_impl(cfg, q, k, v, o, lse, do),
        mesh=mesh, in_specs=(spec4,) * 4 + (spec3, spec4),
        out_specs=(spec4,) * 3, check_vma=False)
    findings += check_trace(jax.make_jaxpr(bwd)(q, q, q, q, lse, q),
                            where="burst bwd", anchor=_anchor(burst._bwd_impl))

    # ---- devstats-pure: the telemetry path keeps both promises ----
    anchor_dev = _anchor(burst.burst_attn_shard)

    def stats_fwdbwd(q, k, v):
        # value_and_grad THROUGH the stats entry: fwd + bwd + every stats
        # equation land in one jaxpr; summing the stats leaves into the
        # output keeps them from being dead-code-eliminated
        def loss(q, k, v):
            o, st = burst.burst_attn_shard(q, k, v, cfg, collect_stats=True)
            return jnp.sum(o.astype(jnp.float32)), st

        (l, st), grads = jax.value_and_grad(loss, (0, 1, 2),
                                            has_aux=True)(q, k, v)
        st_sum = sum(jnp.sum(x.astype(jnp.float32))
                     for x in jax.tree.leaves(st))
        return l + st_sum, grads

    stats_prog = shard_map(stats_fwdbwd, mesh=mesh, in_specs=(spec4,) * 3,
                           out_specs=(P(), (spec4,) * 3), check_vma=False)
    findings += check_trace(jax.make_jaxpr(stats_prog)(q, q, q),
                            where="burst fwd+bwd (collect_stats=True)",
                            anchor=anchor_dev, rule_name="devstats-pure")

    off = shard_map(
        lambda q, k, v: burst.burst_attn_shard(q, k, v, cfg,
                                               collect_stats=False),
        mesh=mesh, in_specs=(spec4,) * 3, out_specs=spec4, check_vma=False)
    plain = shard_map(
        lambda q, k, v: burst._burst_attn_shard_plain(q, k, v, cfg),
        mesh=mesh, in_specs=(spec4,) * 3, out_specs=spec4, check_vma=False)
    findings += check_off_identity(jax.make_jaxpr(off)(q, q, q),
                                   jax.make_jaxpr(plain)(q, q, q),
                                   anchor=anchor_dev)

    # ---- ckpt-jit-safe: the serve-step programs the checkpoint layer
    # wraps.  Journal appends / snapshot saves live in the engines' host
    # loops; this proves none of them leaked INTO the traced step — a
    # journal hook spelled as `jax.debug.callback(journal.tokens, ...)`
    # would surface here as a callback primitive regardless of module.
    from ..models.paged_decode import init_paged_state, paged_decode_step
    from ..models.transformer import ModelConfig, init_params
    from ..serving import model as serving_model

    cfg_s = ModelConfig(vocab=97, d_model=16, n_layers=1, n_heads=2,
                        n_kv_heads=1, d_head=8, d_ff=32, attn_backend="jnp",
                        remat=False, dtype=jnp.float32, batch_axis=None,
                        head_axis=None)
    params = init_params(jax.random.PRNGKey(0), cfg_s)
    state, _pool = init_paged_state(cfg_s, slots=2, n_pages=4, page=128,
                                    max_pages_per_seq=2)
    toks2 = jnp.zeros((2, 8), jnp.int32)
    qlens = jnp.ones((2,), jnp.int32)
    for attn in ("dense", "ragged"):
        findings += check_trace(
            jax.make_jaxpr(
                lambda p, t, ql, st: serving_model.ragged_model_step(
                    p, t, ql, st, cfg_s, attn=attn)
            )(params, toks2, qlens, state),
            where=f"ragged_model_step (attn={attn})",
            anchor=_anchor(serving_model.ragged_model_step),
            rule_name="ckpt-jit-safe")
    findings += check_trace(
        jax.make_jaxpr(
            lambda p, t, st: paged_decode_step(p, t, st, cfg_s)
        )(params, jnp.zeros((2,), jnp.int32), state),
        where="paged_decode_step", anchor=_anchor(paged_decode_step),
        rule_name="ckpt-jit-safe")

    # ---- pipe-fused-pure: the pipelined engine's fused multi-step scan.
    # K decode steps execute per host dispatch; a callback primitive in
    # the scan body would fire K times per launch, and a collective/DMA
    # would put wire traffic inside what must be a purely local program.
    rng = jax.random.PRNGKey(0)
    first = jnp.zeros((2,), jnp.int32)
    anchor_ms = _anchor(serving_model.multi_step_decode)
    for attn in ("dense", "ragged"):
        jx = jax.make_jaxpr(
            lambda p, t, ql, st, r, attn=attn: serving_model.multi_step_decode(
                p, t, ql, st, r, cfg_s, k=4, attn=attn)
        )(params, first, qlens, state, rng)
        where = f"multi_step_decode (k=4, attn={attn})"
        findings += check_trace(jx, where=where, anchor=anchor_ms,
                                rule_name="pipe-fused-pure")
        findings += check_remote_free(jx, where=where, anchor=anchor_ms)

    # ---- pipe-tick-identity: the K=1 pipelined launch is the SAME
    # program as the synchronous engine's tick (model step + greedy
    # sample), proven at the jaxpr-string level — the token-exactness
    # argument for the pipelined engine rests on this identity.
    def _sync_tick(p, t, ql, st, key):
        logits, st2 = serving_model.ragged_model_step(p, t, ql, st, cfg_s,
                                                      attn="ragged")
        choice = serving_model.sample_logits(logits, key, temperature=0.0,
                                             top_k=None, top_p=None,
                                             nan_sentinel=True)
        return choice, st2

    toks1 = jnp.zeros((2, 1), jnp.int32)
    jx_pipe = jax.make_jaxpr(
        lambda p, t, ql, st, key: serving_model.pipelined_tick(
            p, t, ql, st, key, cfg_s, attn="ragged")
    )(params, toks1, qlens, state, rng)
    jx_sync = jax.make_jaxpr(_sync_tick)(params, toks1, qlens, state, rng)
    if _canon_jaxpr(jx_pipe) != _canon_jaxpr(jx_sync):
        path, line = _anchor(serving_model.pipelined_tick)
        findings.append(Finding(
            rule="pipe-tick-identity", file=path, line=line,
            message="K=1 pipelined tick trace diverged from the synchronous "
                    "engine tick — the pipelined engine is no longer "
                    "launching the same compiled program, so its "
                    "token-exactness guarantee is void"))
    return findings
