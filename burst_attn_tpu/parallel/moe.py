"""Mixture-of-Experts with expert parallelism over an "ep" mesh axis.

Absent from the reference (SURVEY.md §2.4 — DP/TP/PP/EP all delegated to
host frameworks); here it completes the framework's parallelism axes
(dp / sp ring / tp / pp / ep).

TPU-first formulation — the Shazeer dense-dispatch einsum form, which XLA
maps straight onto the MXU (no scatter/gather, no dynamic shapes, no
sorting):

  router logits  [T, E]  -> top-k gates + expert assignment
  dispatch       [T, E, C] one-hot (token t -> slot c of expert e)
  expert inputs  = einsum('tec,td->ecd', dispatch, x)
  expert outputs = per-expert MLP on [E, C, d]
  combined       = einsum('tec,ecd->td', combine, expert_out)

Capacity C bounds each expert's work (static shapes!); tokens routed past
an expert's capacity are DROPPED (their combine weight is zero) — the
standard GShard/Switch trade, surfaced in the aux metrics.

Expert parallelism = sharding the E axis of the expert MLP over "ep" inside
shard_map: each device dispatches its LOCAL tokens to all E experts, a
`lax.all_to_all` regroups [E, C, d] so each device holds its E/ep experts'
slots from every peer, the local expert MLPs run, and a second all_to_all
routes results home.  Combined with dp on the token axis this is exactly
the GShard data+expert layout.

Load balancing: the standard Switch aux loss (mean fraction of tokens per
expert x mean router prob per expert, scaled by E) is returned alongside
the output so the trainer can add `aux_weight * aux_loss`.
"""

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from jax import shard_map


class MoEParams(NamedTuple):
    router: jax.Array   # [d, E]
    w_gate: jax.Array   # [E, d, f]
    w_up: jax.Array     # [E, d, f]
    w_down: jax.Array   # [E, f, d]


def init_moe_params(key, d: int, d_ff: int, n_experts: int,
                    dtype=jnp.float32) -> MoEParams:
    kr, kg, ku, kd = jax.random.split(key, 4)
    init = jax.nn.initializers.normal(stddev=0.02)
    return MoEParams(
        router=init(kr, (d, n_experts), jnp.float32),  # router math in fp32
        w_gate=init(kg, (n_experts, d, d_ff), dtype),
        w_up=init(ku, (n_experts, d, d_ff), dtype),
        w_down=init(kd, (n_experts, d_ff, d), dtype),
    )


def _routing(x, router, top_k: int, capacity: int):
    """Dense dispatch/combine tensors for [T, d] tokens.

    Returns (dispatch [T, E, C] float, combine [T, E, C] float,
    aux_loss scalar, dropped fraction scalar).
    """
    t, _ = x.shape
    e = router.shape[1]
    logits = x.astype(jnp.float32) @ router          # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)

    # top-k expert choice per token; gates renormalized over the chosen k
    gate_vals, expert_idx = lax.top_k(probs, top_k)  # [T, K]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )

    # position of each (token, choice) in its expert's queue: priority by
    # token order within each k-level, k-levels sequential (Switch style)
    dispatch = jnp.zeros((t, e, capacity), jnp.float32)
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    fill = jnp.zeros((e,), jnp.int32)                # slots used per expert
    kept = jnp.zeros((), jnp.float32)
    for k in range(top_k):
        onehot = jax.nn.one_hot(expert_idx[:, k], e, dtype=jnp.int32)  # [T, E]
        pos = fill[None, :] + jnp.cumsum(onehot, axis=0) - onehot       # [T, E]
        pos_tok = jnp.sum(pos * onehot, axis=1)                         # [T]
        in_cap = pos_tok < capacity
        slot = jax.nn.one_hot(
            jnp.where(in_cap, pos_tok, capacity), capacity, dtype=jnp.float32
        )  # overflow -> all-zero row (one_hot of out-of-range)
        d_k = onehot.astype(jnp.float32)[:, :, None] * slot[:, None, :]
        dispatch = dispatch + d_k
        combine = combine + d_k * gate_vals[:, k][:, None, None]
        fill = fill + jnp.sum(onehot * in_cap[:, None].astype(jnp.int32), axis=0)
        kept = kept + jnp.sum(in_cap.astype(jnp.float32))

    # Switch aux loss: E * mean_e(frac_tokens_e * mean_prob_e), over the
    # TOP-1 assignment
    top1 = jax.nn.one_hot(expert_idx[:, 0], e, dtype=jnp.float32)
    aux = e * jnp.sum(jnp.mean(top1, axis=0) * jnp.mean(probs, axis=0))
    dropped = 1.0 - kept / (t * top_k)
    return dispatch, combine, aux, dropped


def _expert_mlp(p: MoEParams, h):
    """SwiGLU per expert: h [E, C, d] -> [E, C, d]."""
    g = jnp.einsum("ecd,edf->ecf", h, p.w_gate)
    u = jnp.einsum("ecd,edf->ecf", h, p.w_up)
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, p.w_down)


def moe_shard(p: MoEParams, x, *, top_k: int, capacity: int, axis: Optional[str]):
    """Per-shard MoE on [T_local, d] tokens — call inside shard_map.

    With `axis`, the expert dimension of p is already sliced to E/ep by
    shard_map; two all_to_alls move dispatched tokens to their experts'
    devices and back (GShard).  Without, plain dense MoE.
    """
    dispatch, combine, aux, dropped = _routing(x, p.router, top_k, capacity)
    h = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32)).astype(x.dtype)
    if axis is not None:
        # [E, C, d] -> exchange: split E over the ep group, concat peers'
        # slots along capacity -> [E/ep, C * ep, d]
        h = lax.all_to_all(h, axis, split_axis=0, concat_axis=1, tiled=True)
    out = _expert_mlp(p, h)
    if axis is not None:
        out = lax.all_to_all(out, axis, split_axis=1, concat_axis=0, tiled=True)
    y = jnp.einsum("tec,ecd->td", combine, out.astype(jnp.float32))
    # aux/dropped are per-shard means over local tokens; average over peers
    if axis is not None:
        aux = lax.pmean(aux, axis)
        dropped = lax.pmean(dropped, axis)
    return y.astype(x.dtype), aux, dropped


def capacity_for(tokens: int, experts: int, top_k: int,
                 capacity_factor: float) -> int:
    """Per-group expert slot count — the ONE place the capacity rounding
    policy lives (consumers: moe_apply, the LM's dense-path _mlp, and the
    pipeline-parallel _moe_block; a policy change must move all three in
    lockstep or pp-vs-regular MoE parity silently breaks)."""
    return max(1, int(capacity_factor * top_k * tokens / experts))


def moe_apply(p: MoEParams, x, *, mesh=None, axis: Optional[str] = "ep",
              top_k: int = 2, capacity_factor: float = 1.25):
    """MoE layer on [B, T, d] (or [T, d]) tokens.

    With mesh+axis: expert-parallel over `axis` — p's expert dimension must
    be sharded P(axis) and x replicated/sharded over the OTHER axes.  The
    token dim is flattened locally; capacity is per LOCAL token count.
    Returns (y, aux_loss, dropped_fraction).
    """
    squeeze = x.ndim == 2
    if squeeze:
        x = x[None]
    b, t, d = x.shape
    e = p.router.shape[1]

    if mesh is None or axis is None:
        cap = capacity_for(b * t, e, top_k, capacity_factor)
        y, aux, dropped = moe_shard(
            p, x.reshape(b * t, d), top_k=top_k, capacity=cap, axis=None
        )
        y = y.reshape(b, t, d)
        return (y[0] if squeeze else y), aux, dropped

    ep = mesh.shape.get(axis, 1)
    if e % ep:
        raise ValueError(f"experts {e} not divisible by ep axis size {ep}")
    if t % ep:
        raise ValueError(f"tokens {t} not divisible by ep axis size {ep}")
    cap = capacity_for(b * t // ep, e, top_k, capacity_factor)

    def body(p_shard, x_shard):
        bb, tt, _ = x_shard.shape
        y, aux, dropped = moe_shard(
            p_shard, x_shard.reshape(bb * tt, d), top_k=top_k, capacity=cap,
            axis=axis,
        )
        return y.reshape(bb, tt, d), aux, dropped

    pspec = MoEParams(P(), P(axis), P(axis), P(axis))
    y, aux, dropped = shard_map(
        body, mesh=mesh,
        in_specs=(pspec, P(None, axis, None)),
        out_specs=(P(None, axis, None), P(), P()),
        check_vma=False,
    )(p, x)
    return (y[0] if squeeze else y), aux, dropped
