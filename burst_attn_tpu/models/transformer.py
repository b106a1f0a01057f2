"""Flagship model: a decoder-only transformer LM on burst (ring) attention.

The reference is an op library whose integration story is "plug
burst_attn_func into your training framework" (reference README.md:36-38,
CPM-Live/BMTrain integration).  Here the model layer is first-class and
TPU-native: pure-functional pytree parameters with an explicit
PartitionSpec tree, so one `jit` with sharding constraints expresses
DP x TP x SP (sequence ring) over a named mesh — XLA inserts the
collectives (megatron-style TP from the param specs; the sequence ring
from burst_attn's shard_map).

Layout contract: `tokens` / `positions` fed to `forward` are in LAYOUT
order (parallel/layouts.to_layout) when causal load balancing is on;
`positions` carries the true global position of each token so rotary
embeddings are exact under any permutation (parallel/layouts.position_ids).

Design choices (TPU-first):
  * bf16 activations/params, fp32 rotary and norm accumulation, fp32 logits
    for a stable softmax cross-entropy.
  * RMSNorm + SwiGLU + rotary: the modern decoder block; all matmuls are
    [.., D] x [D, ..] einsums that XLA tiles onto the MXU.
  * GQA: n_kv_heads <= n_heads, both divisible by the tp axis size.
"""

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from ..parallel.burst import burst_attn


@dataclass(frozen=True)
class ModelConfig:
    vocab: int = 32768
    d_model: int = 1024
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 8
    d_head: int = 128
    d_ff: int = 2816  # ~8/3 * d_model rounded to 256
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    # attention / parallelism
    causal: bool = True
    attn_strategy: str = "burst"  # "burst" (ring) | "ulysses" (all-to-all)
    layout: str = "zigzag"  # ring layouts; ulysses uses natural order
    attn_backend: str = "auto"
    # sliding-window causal attention (tokens each query may see, incl.
    # itself); requires layout="contig" — see parallel/burst.py
    window: Optional[int] = None
    seq_axes: Tuple[str, ...] = ("sp",)
    batch_axis: Optional[str] = "dp"
    head_axis: Optional[str] = "tp"
    # kernel blocks; None = per-TPU-generation defaults (ops/tuning.py),
    # clamped down for short shards
    block_q: Optional[int] = None
    block_kv: Optional[int] = None
    remat: bool = True  # jax.checkpoint each block: FLOPs for HBM
    # MoE (parallel/moe.py): n_experts=0 -> dense SwiGLU MLP.  With experts,
    # every layer's MLP becomes a top-k routed MoE; expert_axis names the
    # mesh axis experts shard over (GSPMD inserts the dispatch collectives)
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    expert_axis: Optional[str] = None
    # Pipeline parallelism (models/pipeline_lm.py): pp_axis names the mesh
    # axis stages shard over; layers are then stored STACKED [n_layers, ...]
    # (dim 0 sharded over pp) and the forward runs the GPipe schedule.
    # pp_microbatches must divide the per-dp-shard batch.
    pp_axis: Optional[str] = None
    pp_microbatches: int = 1


Params = Dict[str, Any]


def _split(key, n):
    return list(jax.random.split(key, n))


def init_params(key, cfg: ModelConfig) -> Params:
    """Initialize the parameter pytree (all leaves cfg.dtype except norms)."""
    d, nh, nkv, hd, f = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head, cfg.d_ff
    init = jax.nn.initializers.normal(stddev=0.02)

    def dense(k, shape):
        return init(k, shape, cfg.dtype)

    keys = _split(key, cfg.n_layers + 2)
    layers = []
    for lk in keys[: cfg.n_layers]:
        ks = _split(lk, 6)
        layer = {
            "attn_norm": jnp.ones((d,), jnp.float32),
            "wq": dense(ks[0], (d, nh, hd)),
            "wk": dense(ks[1], (d, nkv, hd)),
            "wv": dense(ks[2], (d, nkv, hd)),
            "wo": dense(ks[3], (nh, hd, d)),
            "mlp_norm": jnp.ones((d,), jnp.float32),
        }
        if cfg.n_experts:
            from ..parallel.moe import init_moe_params

            layer.update(
                **init_moe_params(ks[4], d, f, cfg.n_experts,
                                  dtype=cfg.dtype)._asdict()
            )
        else:
            layer.update(
                w_gate=dense(ks[4], (d, f)),
                w_up=dense(ks[5], (d, f)),
                w_down=dense(_split(ks[5], 2)[1], (f, d)),
            )
        layers.append(layer)
    if cfg.pp_axis is not None:
        from .pipeline_lm import stack_layers

        layers = stack_layers(layers)
    return {
        "embed": init(keys[-2], (cfg.vocab, d), cfg.dtype),
        "layers": layers,
        "final_norm": jnp.ones((d,), jnp.float32),
        "lm_head": init(keys[-1], (cfg.vocab, d), cfg.dtype),
    }


def param_specs(cfg: ModelConfig) -> Params:
    """PartitionSpec tree matching init_params: megatron TP over `head_axis`.

    qkv projections are column-parallel (heads sharded), the output
    projection row-parallel, the MLP gate/up column- and down row-parallel;
    embeddings/lm_head shard the vocab dim.  Norm scales are replicated.
    """
    tp = cfg.head_axis
    layer = {
        "attn_norm": P(None),
        "wq": P(None, tp, None),
        "wk": P(None, tp, None),
        "wv": P(None, tp, None),
        "wo": P(tp, None, None),
        "mlp_norm": P(None),
    }
    if cfg.n_experts:
        # experts shard over expert_axis ONLY (the _mlp shard_map slices the
        # same way); sharding their ffn dim over tp as well would need a
        # row-parallel psum inside the expert MLP — replication across tp is
        # the simpler trade at these expert sizes
        ep = cfg.expert_axis
        layer.update(
            router=P(None, None),
            w_gate=P(ep, None, None),
            w_up=P(ep, None, None),
            w_down=P(ep, None, None),
        )
    else:
        layer.update(
            w_gate=P(None, tp),
            w_up=P(None, tp),
            w_down=P(tp, None),
        )
    if cfg.pp_axis is not None:
        # stacked layout: leading stage/layer dim sharded over pp, with the
        # per-leaf tp axes PRESERVED in the trailing dims — pipeline_lm
        # passes these specs as shard_map in_specs, and its hand-written
        # megatron psums assume column/row-sliced weights (replicating them
        # here would double-count after the psums)
        layer = {k: P(cfg.pp_axis, *s) for k, s in layer.items()}
        return {
            "embed": P(None, None),
            "layers": layer,
            "final_norm": P(None),
            "lm_head": P(None, None),
        }
    return {
        "embed": P(tp, None),
        "layers": [layer] * cfg.n_layers,
        "final_norm": P(None),
        "lm_head": P(tp, None),
    }


def _rms_norm(x, scale, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def _rope(x, positions, theta):
    """Rotary embedding. x [B, N, S, H], positions [B, S] (global token ids)."""
    h = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, h, 2, dtype=jnp.float32) / h))
    angles = positions[:, None, :, None].astype(jnp.float32) * freqs  # [B,1,S,H/2]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _qkv_proj(p, x, positions, cfg: ModelConfig):
    """Norm + qkv projections + rotary — shared by the regular and
    pipeline-parallel paths (a numerics change here must hit both, or the
    pp-vs-regular parity tests break)."""
    h = _rms_norm(x, p["attn_norm"])
    q = jnp.einsum("bsd,dnh->bnsh", h, p["wq"])
    k = jnp.einsum("bsd,dnh->bnsh", h, p["wk"])
    v = jnp.einsum("bsd,dnh->bnsh", h, p["wv"])
    return (_rope(q, positions, cfg.rope_theta),
            _rope(k, positions, cfg.rope_theta), v)


def _attn_out(p, o):
    """Output projection (row-parallel under tp) — shared like _qkv_proj."""
    return jnp.einsum("bnsh,nhd->bsd", o, p["wo"])


def _attention(p, x, positions, cfg: ModelConfig, mesh, segment_ids=None,
               collect_stats=False):
    """One attention sublayer.  `collect_stats` (static) additionally
    returns the ring's in-graph DevStats (burst strategy only — ulysses has
    no ring to instrument): `(out, DevStats)` instead of `out`."""
    q, k, v = _qkv_proj(p, x, positions, cfg)
    if collect_stats and cfg.attn_strategy != "burst":
        raise ValueError(
            "collect_stats requires attn_strategy='burst' (devstats "
            f"instruments the ring); got {cfg.attn_strategy!r}")
    if cfg.attn_strategy == "ulysses":
        if len(cfg.seq_axes) != 1:
            raise ValueError("ulysses supports a single sequence axis")
        if cfg.layout != "contig":
            # ulysses attends in array order with a plain causal mask; a ring
            # layout permutation would silently scramble causality
            raise ValueError(
                "attn_strategy='ulysses' requires layout='contig' (natural "
                f"token order); got layout={cfg.layout!r}"
            )
        from ..parallel.ulysses import ulysses_attn

        o = ulysses_attn(
            q, k, v, mesh=mesh, seq_axis=cfg.seq_axes[0], causal=cfg.causal,
            backend=cfg.attn_backend, block_q=cfg.block_q,
            block_kv=cfg.block_kv, batch_axes=cfg.batch_axis,
            head_axes=cfg.head_axis, window=cfg.window,
            segment_ids=segment_ids,
        )
    elif cfg.attn_strategy == "burst":
        o = burst_attn(
            q,
            k,
            v,
            mesh=mesh,
            seq_axes=cfg.seq_axes,
            causal=cfg.causal,
            layout=cfg.layout,
            backend=cfg.attn_backend,
            block_q=cfg.block_q,
            block_kv=cfg.block_kv,
            batch_axes=cfg.batch_axis,
            head_axes=cfg.head_axis,
            window=cfg.window,
            segment_ids=segment_ids,
            collect_stats=collect_stats,
        )
        if collect_stats:
            o, stats = o
            return _attn_out(p, o), stats
    else:
        raise ValueError(
            f"unknown attn_strategy {cfg.attn_strategy!r}; "
            "expected 'burst' or 'ulysses'"
        )
    return _attn_out(p, o)


def _mlp(p, x, cfg: Optional[ModelConfig] = None, mesh=None, inference=False):
    """Dense SwiGLU, or (cfg.n_experts > 0) a routed MoE.  Returns
    (out, aux_loss) — aux is 0 for the dense path so callers are uniform.

    MoE routing is PER SHARD (GShard): tokens route within their
    (batch, seq)-shard's group, so the [T, E, C] dispatch tensors stay
    O(local_tokens^2) instead of O(global_tokens^2) — routing the global
    token set as one group is quadratically infeasible at long sequence.
    `inference=True` sizes capacity drop-free (tokens x top_k): silently
    zeroing a token's MLP output is a training-time trade, not an
    inference-time one.
    """
    h = _rms_norm(x, p["mlp_norm"])
    if cfg is not None and cfg.n_experts:
        from ..parallel.moe import MoEParams, moe_shard

        mp = MoEParams(p["router"], p["w_gate"], p["w_up"], p["w_down"])
        token_axes = tuple(
            a for a in (cfg.batch_axis, *cfg.seq_axes) if a is not None
        )
        # single-program callers (decode) have no mesh: no expert axis, no
        # cross-shard aux reduction
        ep_axis = cfg.expert_axis if mesh is not None else None
        # Drop-free inference routes in CHUNKS: capacity == chunk size is
        # drop-free (a token contributes at most one slot per expert), and
        # chunking keeps the [chunk, E, chunk] dispatch tensors O(chunk^2)
        # instead of O(T^2) on long prefills.  Chunking is exact when
        # nothing drops — routing is per-token.
        chunk = 512

        def route(mp, h2, cap):
            y, aux, _ = moe_shard(
                mp, h2, top_k=cfg.moe_top_k, capacity=cap, axis=ep_axis
            )
            return y, aux

        def group(mp, h):
            bb, ss, dd = h.shape
            tokens = bb * ss
            h2 = h.reshape(tokens, dd)
            if inference:
                c = min(chunk, tokens)
                if tokens % c or ep_axis is not None:
                    # ragged, or collectives in route (vmap of all_to_all is
                    # not supported): one drop-free group
                    y, aux = route(mp, h2, tokens)
                else:
                    yc, aux = jax.vmap(lambda hc: route(mp, hc, c))(
                        h2.reshape(tokens // c, c, dd)
                    )
                    y, aux = yc.reshape(tokens, dd), jnp.mean(aux)
            else:
                from ..parallel.moe import capacity_for

                cap = capacity_for(tokens, cfg.n_experts, cfg.moe_top_k,
                                   cfg.moe_capacity_factor)
                y, aux = route(mp, h2, cap)
            # moe_shard pmeans over the expert axis; average the remaining
            # token-sharding axes so aux is replicated
            rest = tuple(a for a in token_axes if a != ep_axis)
            if mesh is not None and rest:
                aux = jax.lax.pmean(aux, rest)
            return y.reshape(bb, ss, dd), aux

        if mesh is None:  # single-program path (e.g. decode off-mesh)
            y, aux = group(mp, h)
            return y, aux

        seq_spec = cfg.seq_axes if len(cfg.seq_axes) > 1 else cfg.seq_axes[0]
        ep = cfg.expert_axis
        if ep is not None:
            ep_size = mesh.shape.get(ep, 1)
            if cfg.n_experts % ep_size:
                raise ValueError(
                    f"n_experts {cfg.n_experts} not divisible by "
                    f"expert_axis {ep!r} size {ep_size}")
        pspec = MoEParams(P(None, None), P(ep, None, None),
                          P(ep, None, None), P(ep, None, None))
        y, aux = shard_map(
            group, mesh=mesh,
            in_specs=(pspec, P(cfg.batch_axis, seq_spec, None)),
            out_specs=(P(cfg.batch_axis, seq_spec, None), P()),
            check_vma=False,
        )(mp, h)
        return y, aux
    gate = jnp.einsum("bsd,df->bsf", h, p["w_gate"])
    up = jnp.einsum("bsd,df->bsf", h, p["w_up"])
    out = jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up, p["w_down"])
    return out, jnp.float32(0.0)


def forward(params: Params, tokens, positions, cfg: ModelConfig, mesh,
            segment_ids=None) -> jax.Array:
    """tokens, positions: [B, S] int32 (layout order). Returns fp32 logits
    [B, S, vocab].  segment_ids [B, S]: packed-sequence ids in layout order
    (attention never crosses document boundaries)."""
    logits, _ = forward_with_aux(params, tokens, positions, cfg, mesh,
                                 segment_ids=segment_ids)
    return logits


def forward_with_aux(params: Params, tokens, positions, cfg: ModelConfig, mesh,
                     segment_ids=None, collect_stats=False):
    """forward + the summed MoE auxiliary load-balancing loss (0 for dense
    models); the trainer adds `moe_aux_weight * aux` to the objective.

    `collect_stats` (static): additionally return the per-device ring
    telemetry folded across layers (obs.devstats.merge — counts add,
    extrema max/min) as a third element: `(logits, aux, DevStats)`.  Burst
    attention only; the pipeline-parallel path keeps its own schedule and
    does not thread stats."""
    if cfg.pp_axis is not None:
        if collect_stats:
            raise ValueError(
                "collect_stats is not supported on the pipeline-parallel "
                "path (pp_axis set) — the pp schedule slices layers across "
                "stages and has no single ring to instrument")
        from .pipeline_lm import pp_forward_with_aux

        return pp_forward_with_aux(params, tokens, positions, cfg, mesh,
                                   segment_ids=segment_ids)
    from jax.sharding import NamedSharding

    seq_spec = cfg.seq_axes if len(cfg.seq_axes) > 1 else cfg.seq_axes[0]
    act_spec = NamedSharding(mesh, P(cfg.batch_axis, seq_spec, None))
    logit_spec = NamedSharding(mesh, P(cfg.batch_axis, seq_spec, cfg.head_axis))

    # obs.model.* named scopes: the module half of an op's `op_name`, which
    # obs.spans.phase_of reads back from a device trace (metadata only, no
    # equations; docs/observability.md)
    with jax.named_scope("obs.model.embed"):
        x = params["embed"].astype(cfg.dtype)[tokens]
        x = jax.lax.with_sharding_constraint(x, act_spec)

    def block(carry, p):
        if collect_stats:
            from ..obs import devstats

            x, aux, stats = carry
            with jax.named_scope("obs.model.attn"):
                a, st = _attention(p, x, positions, cfg, mesh,
                                   segment_ids=segment_ids,
                                   collect_stats=True)
                x = x + a
            stats = st if stats is None else devstats.merge(stats, st)
        else:
            x, aux = carry
            with jax.named_scope("obs.model.attn"):
                x = x + _attention(p, x, positions, cfg, mesh,
                                   segment_ids=segment_ids)
        with jax.named_scope("obs.model.mlp"):
            m, aux_l = _mlp(p, x, cfg, mesh)
            x = jax.lax.with_sharding_constraint(x + m, act_spec)
        if collect_stats:
            return x, aux + aux_l, stats
        return x, aux + aux_l

    carry = ((x, jnp.float32(0.0), None) if collect_stats
             else (x, jnp.float32(0.0)))
    for p in params["layers"]:
        if cfg.remat:
            carry = jax.checkpoint(block)(carry, p)
        else:
            carry = block(carry, p)
    if collect_stats:
        x, aux, stats = carry
    else:
        x, aux = carry

    with jax.named_scope("obs.model.loss_head"):
        x = _rms_norm(x, params["final_norm"])
        logits = jnp.einsum(
            "bsd,vd->bsv", x, params["lm_head"],
            preferred_element_type=jnp.float32
        )
        logits = jax.lax.with_sharding_constraint(logits, logit_spec)
    if collect_stats:
        return logits, aux, stats
    return logits, aux
