"""Numerics verifiers (burstlint family 1, rules fp32-accum / lse-fp32).

Walks the jaxprs of the attention tile oracle (ops/tile.py) and the Pallas
flash kernels (ops/pallas_flash.py, traced through the pallas_call
equation's inner jaxpr — no TPU needed) on bf16 inputs and asserts the
FlashAttention numerics contract (arXiv 2205.14135; PAPER.md):

  fp32-accum  every dot_general with a low-precision (bf16/f16) operand
              accumulates in float32 (preferred_element_type) — an MXU dot
              that keeps a bf16 accumulator loses ~8 bits of mantissa per
              long-sequence softmax reduction.  Also: an int8/fp8 ring
              payload (cfg.wire_dtype) meets its per-block scale before it
              is accumulated (check_wire_trace, which ringcheck runs on
              the ring's shard programs at wire_dtype int8 and fp8).
  lse-fp32    the running-max / log-sum-exp statistics ([B, N, S] rank-3
              float32 tensors in every shard-level trace) are never
              downcast below fp32 mid-ring; only the final rank-4 output
              may cast back to the activation dtype.
"""

import inspect
from typing import List

from .core import Finding, rule
from .jaxpr_tools import iter_eqns

rule("fp32-accum", "jaxpr",
     "dot_general on bf16/f16 operands must accumulate in float32; a "
     "quantized ring payload meets its scale before any accumulation")(None)
rule("lse-fp32", "jaxpr",
     "rank-3 softmax stats (m/lse/delta) must never downcast below fp32")(None)

_LOW = ("bfloat16", "float16")


def _anchor(fn):
    try:
        return inspect.getsourcefile(fn), inspect.getsourcelines(fn)[1]
    except (OSError, TypeError):
        return "<trace>", 0


def check_trace(closed_jaxpr, *, where: str, anchor,
                stats_rank: int = 3) -> List[Finding]:
    """Run both numerics rules over one traced program."""
    findings: List[Finding] = []
    path, line = anchor
    for eqn in iter_eqns(closed_jaxpr):
        name = eqn.primitive.name
        if name == "dot_general":
            in_dtypes = {str(v.aval.dtype) for v in eqn.invars
                         if hasattr(v.aval, "dtype")}
            out_dtype = str(eqn.outvars[0].aval.dtype)
            if in_dtypes & set(_LOW) and out_dtype != "float32":
                findings.append(Finding(
                    rule="fp32-accum", file=path, line=line,
                    message=f"{where}: dot_general({'/'.join(sorted(in_dtypes))})"
                            f" accumulates in {out_dtype}, not float32 — "
                            "pass preferred_element_type=jnp.float32"))
        elif name == "convert_element_type":
            out = eqn.outvars[0].aval
            src = eqn.invars[0].aval
            if (str(getattr(src, "dtype", "")) == "float32"
                    and str(out.dtype) in _LOW
                    and len(getattr(src, "shape", ())) == stats_rank):
                findings.append(Finding(
                    rule="lse-fp32", file=path, line=line,
                    message=f"{where}: rank-{stats_rank} float32 stat tensor "
                            f"{tuple(src.shape)} downcast to {out.dtype} — "
                            "m/lse/delta must stay fp32 across ring rounds"))
    return findings


# ---------------------------------------------------------------------------
# wire-precision scale handling (reported under fp32-accum)

_QUANT = ("int8", "float8_e4m3fn", "float8_e5m2")
# prims a still-unscaled dequantized value may flow through: linear in the
# value, so the deferred per-block scale can still be applied after them
# (a scalar scale may multiply AFTER the QK/PV dot — distributivity)
_WIRE_PASS = {
    "convert_element_type", "reshape", "transpose", "broadcast_in_dim",
    "squeeze", "expand_dims", "slice", "dynamic_slice", "rev", "copy",
    "neg", "dot_general", "concatenate", "gather",
}


def _tainted_in(eqn, tainted):
    return any((v in tainted) for v in eqn.invars if not hasattr(v, "val"))


def _map_sub_taint(eqn, tainted):
    """(subjaxprs, per-sub tainted-invar sets) for control-flow prims whose
    operand->body mapping is positional; everything else recurses fresh."""
    name = eqn.primitive.name
    outs = []
    if name == "cond":
        ops = eqn.invars[1:]
        for br in eqn.params["branches"]:
            jx = br.jaxpr if hasattr(br, "jaxpr") else br
            sub_t = {sv for v, sv in zip(ops, jx.invars)
                     if not hasattr(v, "val") and v in tainted}
            outs.append((jx, sub_t))
        return outs
    for key in ("jaxpr", "call_jaxpr"):
        sub = eqn.params.get(key)
        if sub is None:
            continue
        jx = sub.jaxpr if hasattr(sub, "jaxpr") else sub
        if not hasattr(jx, "eqns"):
            continue
        if len(jx.invars) == len(eqn.invars):
            sub_t = {sv for v, sv in zip(eqn.invars, jx.invars)
                     if not hasattr(v, "val") and v in tainted}
        else:
            sub_t = set()
        outs.append((jx, sub_t))
    return outs


def _walk_wire(jaxpr, tainted, findings, where, path, line, seen):
    """Taint pass for the wire-rescale proof: a convert FROM a quantized
    dtype seeds taint; a `mul` clears it (the in-tile rescale); linear
    pass-through prims propagate it; anything else consuming a tainted
    value — an add into an accumulator, an exp2, a reduction — means a
    quantized payload reached accumulation without its scale."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            qin = {str(v.aval.dtype) for v in eqn.invars
                   if hasattr(v.aval, "dtype")} & set(_QUANT)
            if qin:
                findings.append(Finding(
                    rule="fp32-accum", file=path, line=line,
                    message=f"{where}: dot_general consumes a raw "
                            f"{'/'.join(sorted(qin))} operand — quantized "
                            "payloads must convert to f32 (and rescale) "
                            "around the MXU, never feed it directly"))
        tin = _tainted_in(eqn, tainted)
        if name == "convert_element_type":
            src = str(getattr(eqn.invars[0].aval, "dtype", ""))
            dst = str(eqn.outvars[0].aval.dtype)
            if src in _QUANT and dst.startswith(("float", "bfloat")):
                tainted.add(eqn.outvars[0])
                continue
            if tin:
                tainted.add(eqn.outvars[0])
            continue
        if name == "mul":
            continue  # the rescale: taint (if any) is discharged here
        subs = _map_sub_taint(eqn, tainted)
        if subs:
            for jx, sub_t in subs:
                key = id(jx)
                if key in seen and not sub_t:
                    continue
                seen.add(key)
                sub_out = _walk_wire(jx, sub_t, findings, where, path, line,
                                     seen)
                if hasattr(jx, "outvars") and len(jx.outvars) == \
                        len(eqn.outvars):
                    for ov, sov in zip(eqn.outvars, jx.outvars):
                        if not hasattr(sov, "val") and sov in sub_out:
                            tainted.add(ov)
            continue
        if not tin:
            continue
        if name in _WIRE_PASS:
            for ov in eqn.outvars:
                tainted.add(ov)
            continue
        findings.append(Finding(
            rule="fp32-accum", file=path, line=line,
            message=f"{where}: dequantized wire payload reaches `{name}` "
                    "without an in-tile rescale — every quantized send "
                    "needs a matching scale multiply before accumulation"))
    return tainted


def check_wire_trace(closed_jaxpr, *, where: str, anchor) -> List[Finding]:
    """Scale-handling proof over one traced program (reported under
    fp32-accum): every int8/fp8 -> float conversion must meet a `mul` (its
    per-block scale) before the value is accumulated or leaves the trace,
    and no dot_general may consume a quantized dtype directly.  Vacuous on
    dense traces (no quantized converts), so it runs unconditionally."""
    findings: List[Finding] = []
    path, line = anchor
    jaxpr = closed_jaxpr.jaxpr if hasattr(closed_jaxpr, "jaxpr") \
        else closed_jaxpr
    out_taint = _walk_wire(jaxpr, set(), findings, where, path, line, set())
    escaped = [v for v in jaxpr.outvars
               if not hasattr(v, "val") and v in out_taint]
    if escaped:
        findings.append(Finding(
            rule="fp32-accum", file=path, line=line,
            message=f"{where}: {len(escaped)} output(s) carry a dequantized "
                    "payload that never met its scale multiply"))
    return findings


def check_all() -> List[Finding]:
    import jax
    import jax.numpy as jnp

    from ..ops import tile
    from ..ops.masks import round_spec

    findings: List[Finding] = []
    b, n, s, d = 1, 2, 128, 64
    S = jax.ShapeDtypeStruct
    q4 = S((b, n, s, d), jnp.bfloat16)
    f3 = S((b, n, s), jnp.float32)
    f4 = S((b, n, s, d), jnp.float32)
    spec = round_spec(jnp.int32(0), jnp.int32(0), s, s, True, "contig")
    scale = d ** -0.5

    # ---- jnp tile oracle ----
    jx = jax.make_jaxpr(
        lambda q, k, v, m, lse, acc: tile.tile_fwd(
            q, k, v, m, lse, acc, scale, spec))(q4, q4, q4, f3, f3, f4)
    findings += check_trace(jx, where="tile_fwd",
                            anchor=_anchor(tile.tile_fwd))
    jx = jax.make_jaxpr(
        lambda do, q, k, v, delta, lse: tile.tile_bwd(
            do, q, k, v, delta, lse, scale, spec))(q4, q4, q4, q4, f3, f3)
    findings += check_trace(jx, where="tile_bwd",
                            anchor=_anchor(tile.tile_bwd))

    # ---- pallas flash kernels (inner jaxpr of the pallas_call eqn) ----
    try:
        from ..ops import pallas_flash
    except ImportError:
        return findings  # no pallas on this backend: the tile rules stand
    jx = jax.make_jaxpr(
        lambda q, k, v: pallas_flash.flash_fwd(
            q, k, v, None, None, None, scale, spec,
            block_q=64, block_kv=64))(q4, q4, q4)
    findings += check_trace(jx, where="flash_fwd kernel",
                            anchor=_anchor(pallas_flash.flash_fwd))
    jx = jax.make_jaxpr(
        lambda do, q, k, v, delta, lse: pallas_flash.flash_bwd(
            do, q, k, v, delta, lse, scale, spec,
            block_q=64, block_kv=64))(q4, q4, q4, q4, f3, f3)
    findings += check_trace(jx, where="flash_bwd kernel",
                            anchor=_anchor(pallas_flash.flash_bwd))
    return findings
