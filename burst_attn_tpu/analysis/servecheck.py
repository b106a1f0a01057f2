"""ragged-serve-safe: the serving kernel's static contract (burstlint).

The one-launch ragged kernel (ops/ragged_paged.py) is the serving hot
path: every engine tick is one launch, and the engine jit-compiles it
with TRACED per-slot token counts (q_lens) so admission, retirement, and
chunking never retrace.  This rule proves, from the traced jaxpr alone:

  jit-safety     the kernel wrapper traces abstractly at both engine
                 launch widths (decode qt=1, prefill chunk) with every
                 runtime input a tracer — any host concretization of
                 q_lens/kv_lens/page_table (an `int()` on a tracer, a
                 shape depending on a value) fails the trace and is a
                 finding, not a serving-time crash.
  callback-free  zero host-callback primitives inside the launch (the
                 obs-jit-safe contract, extended to serving: a callback
                 here is a device<->host round trip per engine tick).
  remote-DMA=0   a census of cross-chip DMA starts in the kernel body
                 must be ZERO.  This kernel serves the single-host pool;
                 cross-device traffic belongs to the ring subsystem
                 (parallel/burst.py) and the sequence-parallel
                 decode path (models/dist_decode.py) — a remote
                 `dma_start` appearing in THIS kernel means pool state
                 leaked into a collective.
  fp32-accum     every low-precision dot in the launch accumulates in
                 float32 (numerics family, same walker) — the online
                 softmax keeps the FlashAttention numerics contract in
                 serving too.

All checks are host-side jaxpr walks over `jax.make_jaxpr` traces — no
TPU, no execution — so they run in the tier-1 burstlint gate.
"""

import inspect
from typing import List

from .core import Finding, rule

rule("ragged-serve-safe", "jaxpr",
     "ragged serving kernel traces under jit with traced q_lens, carries "
     "zero host callbacks and zero remote DMA starts")(None)


def _anchor(fn):
    try:
        return inspect.getsourcefile(fn), inspect.getsourcelines(fn)[1]
    except (OSError, TypeError):
        return "<trace>", 0


def check_trace(closed_jaxpr, *, where: str, anchor) -> List[Finding]:
    """The three jaxpr-walk halves over one traced ragged launch."""
    from . import numerics, obscheck
    from .ringcheck import _remote_dma_starts

    findings = obscheck.check_trace(closed_jaxpr, where=where, anchor=anchor,
                                    rule_name="ragged-serve-safe")
    remote = _remote_dma_starts(closed_jaxpr)
    if remote:
        path, line = anchor
        findings.append(Finding(
            rule="ragged-serve-safe", file=path, line=line,
            message=f"{where}: {len(remote)} remote DMA start(s) in the "
                    "single-host serving kernel — cross-chip traffic "
                    "belongs to the ring/dist_decode paths, never this "
                    "launch (census must be zero)"))
    findings += numerics.check_trace(closed_jaxpr, where=where, anchor=anchor)
    return findings


def check_all() -> List[Finding]:
    """Trace the ragged launch at the engine's widths (decode 1, chunk 8;
    fp32 pool and int8+bf16 GQA pool) and walk every contract."""
    import jax
    import jax.numpy as jnp

    from ..ops import ragged_paged

    anchor = _anchor(ragged_paged.ragged_paged_attention)
    findings: List[Finding] = []
    S = jax.ShapeDtypeStruct
    slots, width, page, d = 4, 8, 128, 64

    cases = [
        # (label, n_q, n_kv, qt, dtype, quantized)
        ("decode fp32", 4, 4, 1, jnp.float32, False),
        ("chunk fp32", 4, 4, 8, jnp.float32, False),
        ("chunk bf16 GQA int8", 8, 2, 8, jnp.bfloat16, True),
    ]
    for label, n_q, n_kv, qt, dt, quant in cases:
        q = S((slots, n_q, qt, d), dt)
        kp = S((16, n_kv, page, d), jnp.int8 if quant else dt)
        table = S((slots, width), jnp.int32)
        lens = S((slots,), jnp.int32)
        sc = S((16, n_kv, page), jnp.float32) if quant else None

        def launch(q, kp, vp, table, q_lens, kv_lens, ks=None, vs=None):
            return ragged_paged.ragged_paged_attention(
                q, kp, vp, table, q_lens, kv_lens,
                k_scales=ks, v_scales=vs, interpret=True)

        try:
            if quant:
                jx = jax.make_jaxpr(launch)(q, kp, kp, table, lens, lens,
                                            sc, sc)
            else:
                jx = jax.make_jaxpr(launch)(q, kp, kp, table, lens, lens)
        except Exception as e:  # noqa: BLE001 — the failure IS the finding
            path, line = anchor
            findings.append(Finding(
                rule="ragged-serve-safe", file=path, line=line,
                message=f"ragged launch ({label}): abstract trace with "
                        f"traced q_lens/kv_lens failed — the kernel is not "
                        f"jit-safe for the serving engine "
                        f"({type(e).__name__}: {e})"))
            continue
        findings += check_trace(jx, where=f"ragged launch ({label})",
                                anchor=anchor)
    return findings
