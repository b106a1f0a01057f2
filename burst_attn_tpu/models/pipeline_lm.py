"""Pipeline-parallel forward for the flagship LM: pp x dp x sp composed.

The reference has no pipeline parallelism (SURVEY.md §2.4 — DP/TP/PP are
delegated to host frameworks); `parallel/pipeline.py` provides the generic
GPipe-over-`lax.scan` building block, and this module is its integration
with the transformer + burst sequence ring (round-1 verdict item 5).

Composition problem: the regular forward path (transformer.forward_with_aux)
is GSPMD-style — einsums under jit with sharding constraints — and
`burst_attn` internally opens its own `shard_map` over the sequence axis.
`shard_map` does not nest, so a pipeline wrapper around that path can't
work.  TPU-native answer: ONE `shard_map` over the FULL (pp, dp, sp) mesh
whose body is fully manual per-shard code —

  * GPipe tick loop: stage p holds layers [p*L/P, (p+1)*L/P); activations
    `lax.ppermute` one hop along `pp` per tick; stage 0 injects microbatch
    t, the last stage banks finished microbatches (same schedule as
    parallel/pipeline.py:pipeline_shard).
  * attention: `burst_attn_shard` — the shard-level custom_vjp ring — runs
    over `sp` inside each stage (double ring over ("inter","intra") seq
    axes works the same way).
  * dp needs no code: the batch dim is sharded by the outer shard_map and
    parameter cotangents are psum'd across replicated axes by shard_map's
    transpose.

The backward pipeline schedule is free: jax.grad of scan + ppermute IS the
reverse schedule (ppermute transposes to the reverse permutation).

Tensor parallelism composes too: the megatron collectives GSPMD would infer
for the regular path are hand-written in `_layer_fwd` (column-sliced
qkv/gate/up, row-sliced wo/down, one psum over `tp` after each of attention
and the MLP).  So does MoE: `moe_shard` is already a per-shard function, so
the pp body calls it directly with the expert dim sliced over `ep` by the
outer shard_map; per-stage aux losses accumulate over live ticks only
(bubble ticks compute garbage) and psum over pp.  Embeddings/lm_head stay
replicated in pp mode (vocab-dim sharding would need a masked-lookup + psum
in the manual body for marginal memory win).

Parameter layout: `layers` holds stacked leaves [n_layers, ...] (dim 0
sharded over `pp`), not the regular list-of-dicts — see
transformer.init_params / stack_layers.
"""

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from jax import shard_map
from jax.lax import axis_size

from ..parallel.burst import BurstConfig, burst_attn_shard, _resolve_backend
# the pure math MUST be shared with the regular path: a numerics change
# there must not silently break pp=1 vs pp=N parity (_mlp's dense path is
# per-shard pure math too — cfg=None selects it)
from .transformer import _attn_out, _mlp, _qkv_proj, _rms_norm, param_specs


def stack_layers(layers):
    """List-of-layer-dicts -> one pytree with a leading [n_layers, ...] axis
    (the layout the pp path shards over the `pp` mesh axis)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def unstack_layers(stacked, n_layers):
    """Inverse of stack_layers (e.g. to run a pp checkpoint without pp)."""
    return [jax.tree.map(lambda a: a[i], stacked) for i in range(n_layers)]


def _moe_block(p, x, cfg):
    """Per-shard routed MoE (training path): the same moe_shard call the
    regular path's _mlp makes inside ITS shard_map, minus the wrapper —
    here the outer pp shard_map has already sliced the expert dim over
    `ep`.  Routing groups are this stage's (microbatch x seq-shard) tokens.
    Returns (out, aux) with aux pmean'd over every token-sharding axis."""
    from ..parallel.moe import MoEParams, capacity_for, moe_shard

    h = _rms_norm(x, p["mlp_norm"])
    bb, ss, dd = h.shape
    tokens = bb * ss
    cap = capacity_for(tokens, cfg.n_experts, cfg.moe_top_k,
                       cfg.moe_capacity_factor)
    mp = MoEParams(p["router"], p["w_gate"], p["w_up"], p["w_down"])
    y, aux, _ = moe_shard(mp, h.reshape(tokens, dd), top_k=cfg.moe_top_k,
                          capacity=cap, axis=cfg.expert_axis)
    rest = tuple(a for a in (cfg.batch_axis, *cfg.seq_axes)
                 if a is not None and a != cfg.expert_axis)
    if rest:
        aux = lax.pmean(aux, rest)
    return y.reshape(bb, ss, dd), aux


def _layer_fwd(p, x, positions, cfg, bcfg: BurstConfig, seg=None):
    """One transformer block, per-shard (x [mb, s_local, d]) ->
    (x, aux_loss).

    Tensor parallelism is hand-written megatron: qkv/gate/up weights arrive
    column-sliced over `tp` (so the einsums run on the local head/ffn
    shard), wo/down row-sliced, and the two psums below reduce the partial
    outputs — exactly the collectives GSPMD infers for the regular path's
    param_specs, made explicit because this body is inside shard_map.
    MoE layers (cfg.n_experts) route per-stage token groups over `ep`;
    expert weights are replicated across tp (as in the regular path), so
    the MoE output needs no tp psum."""
    tp = cfg.head_axis
    q, k, v = _qkv_proj(p, x, positions, cfg)
    o = burst_attn_shard(q, k, v, bcfg, seg)
    attn = _attn_out(p, o)
    if tp is not None:
        attn = lax.psum(attn, tp)
    x = x + attn
    if cfg.n_experts:
        mlp_out, aux = _moe_block(p, x, cfg)
    else:
        mlp_out, aux = _mlp(p, x)[0], jnp.float32(0.0)
        if tp is not None:
            mlp_out = lax.psum(mlp_out, tp)
    return x + mlp_out, aux


def _pp_forward_shard(layers_p, embed, final_norm, lm_head, tokens, positions,
                      segments=None, *, cfg, bcfg: BurstConfig, m: int):
    """Per-shard body: embed -> GPipe ticks over `pp` -> head.

    layers_p: this stage's layers, leaves [L/P, ...]; tokens/positions
    [b_local, s_local] (dp x sp shard)."""
    pp = cfg.pp_axis
    n_stages = axis_size(pp)
    stage = lax.axis_index(pp)
    b_l, s_l = tokens.shape
    x = embed.astype(cfg.dtype)[tokens]
    d = x.shape[-1]
    mb = b_l // m
    x_mb = x.reshape(m, mb, s_l, d)
    pos_mb = positions.reshape(m, mb, s_l)
    seg_mb = (None if segments is None
              else segments.reshape(m, mb, s_l))

    def stage_fn(x, pos, seg):
        def body(carry, p):
            x, aux = carry
            x, aux_l = _layer_fwd(p, x, pos, cfg, bcfg, seg)
            return (x, aux + aux_l), None

        if cfg.remat:
            body = jax.checkpoint(body)
        (x, aux), _ = lax.scan(body, (x, jnp.float32(0.0)), layers_p)
        return x, aux

    ticks = m + n_stages - 1
    buf = jnp.zeros_like(x_mb[0])  # activation arriving from the left
    out = jnp.zeros_like(x_mb)     # banked results (last stage only)

    def tick(carry, t):
        buf, out, aux_acc = carry
        inject = lax.dynamic_index_in_dim(
            x_mb, jnp.minimum(t, m - 1), axis=0, keepdims=False)
        cur = jnp.where(stage == 0, inject, buf)
        # the activation at stage s on tick t is microbatch t - s; its
        # positions (rope) must travel with it.  Clamped: bubble ticks
        # compute garbage that is never banked.
        mb_id = t - stage
        pos = lax.dynamic_index_in_dim(
            pos_mb, jnp.clip(mb_id, 0, m - 1), axis=0, keepdims=False)
        seg = (None if seg_mb is None else lax.dynamic_index_in_dim(
            seg_mb, jnp.clip(mb_id, 0, m - 1), axis=0, keepdims=False))
        y, aux_t = stage_fn(cur, pos, seg)
        # MoE aux from bubble ticks (garbage activations) must not count
        live = (mb_id >= 0) & (mb_id < m)
        aux_acc = aux_acc + jnp.where(live, aux_t, 0.0)
        out_id = t - (n_stages - 1)
        bank = (stage == n_stages - 1) & (out_id >= 0)
        banked = lax.dynamic_update_index_in_dim(
            out, y, jnp.clip(out_id, 0, m - 1), axis=0)
        out = jnp.where(bank, banked, out)
        nxt = lax.ppermute(
            y, pp, [(i, (i + 1) % n_stages) for i in range(n_stages)])
        return (nxt, out, aux_acc), None

    (_, out, aux_acc), _ = lax.scan(
        tick, (buf, out, jnp.float32(0.0)), jnp.arange(ticks))
    # banked outputs live on the last stage; psum replicates them so every
    # pp shard computes the (cheap) head on its own dp x sp shard.  aux:
    # each stage holds its own layers' aux summed over its m live ticks —
    # psum over pp completes the layer sum, / m averages microbatches
    # (identical to the regular path when m == 1).
    aux = lax.psum(aux_acc, pp) / m
    xf = lax.psum(out, pp).reshape(b_l, s_l, d)
    xf = _rms_norm(xf, final_norm)
    logits = jnp.einsum("bsd,vd->bsv", xf, lm_head,
                        preferred_element_type=jnp.float32)
    return logits, aux


def pp_forward_with_aux(params, tokens, positions, cfg, mesh,
                        segment_ids=None):
    """Pipeline-parallel forward_with_aux: fp32 logits [B, S, vocab] + the
    MoE aux loss (0 for dense models).

    Same contract as transformer.forward_with_aux; dispatched from there
    when cfg.pp_axis is set.  With pp_microbatches > 1 the MoE aux (and
    routing groups) are per-microbatch — the mean over microbatches, which
    differs from the regular path's full-batch routing exactly the way
    grad-accumulation microbatching does; m == 1 matches it exactly."""
    if cfg.head_axis is not None:
        if cfg.head_axis not in mesh.shape:
            raise ValueError(
                f"head_axis {cfg.head_axis!r} is not an axis of the mesh "
                f"{dict(mesh.shape)}; set head_axis=None (ModelConfig "
                "defaults it to 'tp') or add the axis to the mesh")
        tp_size = mesh.shape.get(cfg.head_axis, 1)
        if cfg.n_heads % tp_size or cfg.n_kv_heads % tp_size:
            raise ValueError(
                f"n_heads {cfg.n_heads} / n_kv_heads {cfg.n_kv_heads} not "
                f"divisible by {cfg.head_axis!r} mesh size {tp_size}")
        if not cfg.n_experts and cfg.d_ff % tp_size:
            raise ValueError(
                f"d_ff {cfg.d_ff} not divisible by {cfg.head_axis!r} mesh "
                f"size {tp_size} (the dense MLP weights are column-sliced "
                "over tp)")
    if cfg.n_experts and cfg.expert_axis is not None:
        if cfg.expert_axis not in mesh.shape:
            raise ValueError(
                f"expert_axis {cfg.expert_axis!r} is not an axis of the "
                f"mesh {dict(mesh.shape)}")
        ep_size = mesh.shape.get(cfg.expert_axis, 1)
        if cfg.n_experts % ep_size:
            raise ValueError(
                f"n_experts {cfg.n_experts} not divisible by "
                f"expert_axis {cfg.expert_axis!r} size {ep_size}")
    if cfg.attn_strategy != "burst":
        raise ValueError("pp path supports attn_strategy='burst' only")
    if cfg.pp_axis not in mesh.shape:
        raise ValueError(
            f"pp_axis {cfg.pp_axis!r} is not an axis of the mesh "
            f"{dict(mesh.shape)}")
    if cfg.batch_axis is not None and cfg.batch_axis not in mesh.shape:
        raise ValueError(
            f"batch_axis {cfg.batch_axis!r} is not an axis of the mesh "
            f"{dict(mesh.shape)}; set batch_axis=None or add a dp axis")
    n_stages = mesh.shape.get(cfg.pp_axis, 1)
    if cfg.n_layers % n_stages:
        raise ValueError(
            f"n_layers {cfg.n_layers} not divisible by pp={n_stages}")
    m = cfg.pp_microbatches
    dp = mesh.shape.get(cfg.batch_axis, 1) if cfg.batch_axis else 1
    b_local = tokens.shape[0] // dp
    if b_local % m:
        raise ValueError(
            f"per-dp-shard batch {b_local} not divisible by "
            f"pp_microbatches {m}")

    if len(cfg.seq_axes) == 1:
        inter_axis, intra_axis = None, cfg.seq_axes[0]
    else:
        inter_axis, intra_axis = cfg.seq_axes
    bcfg = BurstConfig(
        causal=cfg.causal,
        layout=cfg.layout,
        intra_axis=intra_axis,
        inter_axis=inter_axis,
        backend=_resolve_backend(cfg.attn_backend),
        block_q=cfg.block_q,
        block_kv=cfg.block_kv,
        window=cfg.window,
    )
    seq_spec = cfg.seq_axes if len(cfg.seq_axes) > 1 else cfg.seq_axes[0]
    tok_spec = P(cfg.batch_axis, seq_spec)
    # full per-leaf specs, not a P(pp) prefix: with tp the qkv/gate/up/wo/
    # down leaves are column/row-sliced over head_axis too, and a prefix
    # spec would hand every tp shard the full weights (double-counted after
    # the body's psums)
    layer_specs = param_specs(cfg)["layers"]
    in_specs = [layer_specs, P(), P(), P(), tok_spec, tok_spec]
    args = [params["layers"], params["embed"], params["final_norm"],
            params["lm_head"], tokens, positions]
    if segment_ids is not None:
        in_specs.append(tok_spec)
        args.append(jnp.asarray(segment_ids, jnp.int32))
    fn = shard_map(
        partial(_pp_forward_shard, cfg=cfg, bcfg=bcfg, m=m),
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(cfg.batch_axis, seq_spec, None), P()),
        check_vma=False,
    )
    logits, aux = fn(*args)
    return logits, aux
