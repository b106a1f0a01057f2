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
from typing import Any, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from ..ops import mhc as mhc_ops
from ..ops.polynorm import PolyNorm, init_weights as _poly_init, poly_norm
from ..parallel.burst import burst_attn


# ---------------------------------------------------------------------------
# the layer pattern (ROADMAP D11, its first step): what ONE layer is, for the
# kinds the scalar knobs below cannot say.  Readers: init_params, param_specs,
# forward_with_aux (and a reference of its own, outside this package).  The
# pipeline path, decode and serving read the scalar knobs and refuse a pattern.


@dataclass(frozen=True)
class LatentAttn:
    """Latent attention's head geometry (the deepseek_v3 block; q at full
    rank).  Per head, q and k are `qk_nope + qk_rope` wide and v, o `v_head`:
    k_nope and v come up from one `kv_latent`-wide RMS-normed latent a token,
    and the `qk_rope` rotary channels of k are ONE key a token, shared by all
    heads.  The rotary pairing is the interleaved one: channels (2i, 2i+1)
    rotate together (not the half-split pairing of the GQA block).  Heads,
    theta and dtype are the model's (ModelConfig.n_heads, rope_theta)."""

    kv_latent: int
    qk_nope: int
    qk_rope: int
    v_head: int


@dataclass(frozen=True)
class GDLAttn(LatentAttn):
    """Grouped differential latent attention: LatentAttn's geometry with q
    from a `q_latent`-wide RMS-normed latent a token, and k_nope, v for
    `kv_heads` KV heads (not one a query head).  The ModelConfig.n_heads
    query heads fall in `kv_heads` groups of n_heads / kv_heads, group g
    reading KV head g; the LAST head of each group is its noise head, so
    `noise_heads` == `kv_heads`, and the others are signal heads.  Each
    signal head i outputs o_i - lambda_i * o_noise(group of i), lambda =
    sigmoid(h W_lambda) one a signal head and token, times an elementwise
    gate sigmoid(h W_gate) of the same width (h: the layer's normed input),
    and the output projection reads the signal heads only."""

    q_latent: int
    kv_heads: int
    noise_heads: int


@dataclass(frozen=True)
class DenseMLP:
    """One SwiGLU of width `d_ff` every token takes."""

    d_ff: int


@dataclass(frozen=True)
class ExpertMLP:
    """Routed experts of width `d_ff` (parallel/moe.py): `n_experts` router
    outputs, `top_k` choices a token, weights for the experts `held` = (lo,
    hi) here (None: all).  `score`, `choice_bias` (a float32 [n_experts]
    leaf `router_bias` that enters the choice and not the gate: model STATE,
    no gradient, no weight decay, see STATE_LEAVES) and `gate_scale` are
    moe.route's; `shared_ff` > 0 adds the shared experts, one SwiGLU of that
    width every token takes (moe.moe_held's `shared`)."""

    d_ff: int
    n_experts: int
    top_k: int
    held: Optional[Tuple[int, int]] = None
    score: str = "softmax"
    choice_bias: bool = False
    gate_scale: float = 1.0
    shared_ff: int = 0


@dataclass(frozen=True)
class LayerSpec:
    """One layer: its MLP kind, its attention kind where that is not the GQA
    block of ModelConfig's scalar knobs (None), its attention window where
    that is not ModelConfig.window (None: the model's; needs layout
    "contig", as any window does), and its MLPs' activation of the gate
    product (None: SiLU; a PolyNorm: that, with the layer's own weights,
    in the dense MLP, the shared and every routed expert)."""

    mlp: Union[DenseMLP, ExpertMLP]
    attn: Optional[LatentAttn] = None
    window: Optional[int] = None
    act: Optional[PolyNorm] = None


# Parameter leaves that are model state, not trained: the forward reads them,
# no gradient reaches them, and the train step hands them on as they came
# (models/train.py: no update, no weight decay).
STATE_LEAVES = ("router_bias",)


@dataclass(frozen=True)
class MHC:
    """The residual path as `streams` streams, side by side [B, S, n d]
    (mHC, arXiv 2512.24880): _mhc_pre / _mhc_post around each sublayer (on
    the TPU ops/mhc.py's kernels: _mhc_passes), the maps' mix
    doubly stochastic by `sinkhorn_iters` Sinkhorn-Knopp rounds; the
    embedding copied to every stream, the streams summed before the final
    norm; the streams clipped to +-`clamp` after each sublayer (None:
    not)."""

    streams: int
    sinkhorn_iters: int
    clamp: Optional[float] = None


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
    # per-head RMSNorm on q and k (own scales [d_head]) before the rotary
    # embedding, as the Qwen3 lineage has it
    qk_norm: bool = False
    # Block-diffusion training (ops/masks.bd_quadrants): the sequence a layer
    # sees is the stream [noised; clean] of one document and this is the
    # block length of its attention mask, which replaces `causal` / `window`
    # (models/train.py builds the stream).  One sequence shard only.
    block_diffusion: Optional[int] = None
    # MoE (parallel/moe.py): n_experts=0 -> dense SwiGLU MLP.  With experts,
    # every layer's MLP becomes a top-k routed MoE, and which layer serves
    # depends on the caller (_moe_group):
    #   * the trainer with expert_axis=None: moe.moe_held, drop-free, over
    #     the experts held here.  `experts_held` = (lo, hi) says which of the
    #     n_experts have weights on this chip (None: all): the router keeps
    #     n_experts outputs and moe_top_k choices, the absent experts' part
    #     of the sum is left out.  moe_capacity_factor does not apply.
    #   * the trainer with expert_axis named (the mesh axis experts shard
    #     over), and inference everywhere: moe.moe_shard, dense dispatch with
    #     capacity (inference sizes it so nothing drops) and the ep exchange
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    expert_axis: Optional[str] = None
    experts_held: Optional[Tuple[int, int]] = None
    # Pipeline parallelism (models/pipeline_lm.py): pp_axis names the mesh
    # axis stages shard over; layers are then stored STACKED [n_layers, ...]
    # (dim 0 sharded over pp) and the forward runs the GPipe schedule.
    # pp_microbatches must divide the per-dp-shard batch.
    pp_axis: Optional[str] = None
    pp_microbatches: int = 1
    # One LayerSpec a layer (len == n_layers), for stacks whose layers differ
    # or whose kinds the knobs above cannot say (latent attention, a leading
    # dense layer before sparse ones, a sigmoid router, shared experts).
    # None: every layer is the block the scalar knobs describe (layer_specs).
    # With a pattern, d_head / n_kv_heads / d_ff / n_experts / moe_top_k /
    # experts_held are not read for the kinds it names.  The trainer's
    # forward only: pp_axis, decode and serving refuse it.
    pattern: Optional[Tuple[LayerSpec, ...]] = None
    # The residual path as mHC's streams (MHC; None: the one stream x).
    # The trainer's forward only, as a pattern.
    mhc: Optional["MHC"] = None
    # epsilon of every RMSNorm of the trainer's forward
    norm_eps: float = 1e-6


Params = Dict[str, Any]


def _knob_mlp(cfg: ModelConfig):
    """The MLP kind the scalar knobs describe."""
    if cfg.n_experts:
        return ExpertMLP(cfg.d_ff, cfg.n_experts, cfg.moe_top_k,
                         cfg.experts_held)
    return DenseMLP(cfg.d_ff)


def layer_specs(cfg: ModelConfig) -> Tuple[LayerSpec, ...]:
    """One LayerSpec a layer: the pattern, or the scalar knobs' block
    n_layers times."""
    if cfg.pattern is None:
        return (LayerSpec(_knob_mlp(cfg)),) * cfg.n_layers
    if len(cfg.pattern) != cfg.n_layers:
        raise ValueError(f"pattern describes {len(cfg.pattern)} layers, "
                         f"n_layers is {cfg.n_layers}")
    return tuple(cfg.pattern)


def has_experts(cfg: ModelConfig) -> bool:
    """Whether any layer routes over experts."""
    return any(isinstance(sp.mlp, ExpertMLP) for sp in layer_specs(cfg))


def _trainer_only_kinds(cfg: ModelConfig):
    """What of `cfg` the trainer's forward_with_aux alone computes, by name
    (empty where nothing)."""
    met = []
    if cfg.pattern is not None:
        specs = cfg.pattern
        attns = {type(sp.attn) for sp in specs if sp.attn is not None}
        met += [name for kind, name in (
            (LatentAttn, "latent attention"),
            (GDLAttn, "grouped differential latent attention")) if kind in attns]
        if any(sp.window is not None for sp in specs):
            met.append("a window a layer")
        if any(sp.act is not None for sp in specs):
            met.append("PolyNorm MLPs")
        if len({type(sp.mlp) for sp in specs}) > 1:
            met.append("per-layer MLP kinds")
        if not met:
            met.append("a layer pattern")
    if cfg.mhc is not None:
        met.append(f"the mHC residual of {cfg.mhc.streams} streams")
    if cfg.norm_eps != ModelConfig.norm_eps:
        met.append(f"a norm epsilon of {cfg.norm_eps}")
    return met


def _refuse_pattern(cfg, who):
    met = [] if cfg is None else _trainer_only_kinds(cfg)
    if met:
        raise ValueError(
            f"{who} reads ModelConfig's scalar knobs (one block for the "
            "whole stack); a layer pattern and the knobs beside it "
            "(ModelConfig.pattern: latent or grouped differential latent "
            "attention, a window a layer, PolyNorm MLPs, per-layer MLP "
            "kinds; mhc, norm_eps) run through the trainer's "
            f"forward_with_aux only, and this configuration has "
            f"{', '.join(met)}")


def _split(key, n):
    return list(jax.random.split(key, n))


MHC_SUBLAYERS = ("attn", "mlp")


def _init_mhc(key, cfg: ModelConfig):
    """A layer's mHC leaves, for each sublayer s: `mhc_<s>_phi` [n d, 2n +
    n^2] float32 (the three maps' projections of the normed streams, side by
    side: pre, post, res), `mhc_<s>_alpha` [3] (their gains, 0.01 at
    first) and `mhc_<s>_bias` [2n + n^2] (seeded, not the identity)."""
    n, d = cfg.mhc.streams, cfg.d_model
    width = 2 * n + n * n
    out = {}
    for s, k in zip(MHC_SUBLAYERS, _split(key, len(MHC_SUBLAYERS))):
        kp, kb = _split(k, 2)
        out[f"mhc_{s}_phi"] = 0.02 * jax.random.normal(kp, (n * d, width),
                                                       jnp.float32)
        out[f"mhc_{s}_alpha"] = jnp.full((3,), 0.01, jnp.float32)
        out[f"mhc_{s}_bias"] = 0.1 * jax.random.normal(kb, (width,),
                                                       jnp.float32)
    return out


def _init_poly(key, spec: LayerSpec):
    """A layer's PolyNorm weights: `poly` [4] (the dense MLP), or
    `expert_poly` [held, 4] and, with shared experts, `shared_poly` [4]."""
    mlp = spec.mlp
    if not isinstance(mlp, ExpertMLP):
        return {"poly": _poly_init(key)}
    held = mlp.n_experts if mlp.held is None else mlp.held[1] - mlp.held[0]
    ke, ks = _split(key, 2)
    out = {"expert_poly": _poly_init(ke, (held,))}
    if mlp.shared_ff:
        out["shared_poly"] = _poly_init(ks)
    return out


def init_params(key, cfg: ModelConfig) -> Params:
    """Initialize the parameter pytree (all leaves cfg.dtype except norms)."""
    d, nh, nkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    init = jax.nn.initializers.normal(stddev=0.02)

    def dense(k, shape):
        return init(k, shape, cfg.dtype)

    keys = _split(key, cfg.n_layers + 2)
    layers = []
    for lk, spec in zip(keys[: cfg.n_layers], layer_specs(cfg)):
        ks = _split(lk, 6)
        layer = {"attn_norm": jnp.ones((d,), jnp.float32),
                 "mlp_norm": jnp.ones((d,), jnp.float32)}
        if spec.attn is None:
            layer.update(
                wq=dense(ks[0], (d, nh, hd)),
                wk=dense(ks[1], (d, nkv, hd)),
                wv=dense(ks[2], (d, nkv, hd)),
                wo=dense(ks[3], (nh, hd, d)),
            )
            if cfg.qk_norm:
                layer.update(q_norm=jnp.ones((hd,), jnp.float32),
                             k_norm=jnp.ones((hd,), jnp.float32))
        elif isinstance(spec.attn, GDLAttn):
            a = spec.attn
            ka = _split(ks[3], 4)
            signal = nh - a.noise_heads
            layer.update(
                wq_a=dense(ks[0], (d, a.q_latent)),
                q_a_norm=jnp.ones((a.q_latent,), jnp.float32),
                wq_b=dense(ka[0], (a.q_latent, nh, a.qk_nope + a.qk_rope)),
                wkv_a=dense(ks[1], (d, a.kv_latent + a.qk_rope)),
                kv_norm=jnp.ones((a.kv_latent,), jnp.float32),
                wkv_b=dense(ks[2], (a.kv_latent, a.kv_heads,
                                    a.qk_nope + a.v_head)),
                w_lambda=dense(ka[1], (d, signal)),
                w_attn_gate=dense(ka[2], (d, signal, a.v_head)),
                wo=dense(ka[3], (signal, a.v_head, d)),
            )
        else:
            a = spec.attn
            layer.update(
                wq=dense(ks[0], (d, nh, a.qk_nope + a.qk_rope)),
                wkv_a=dense(ks[1], (d, a.kv_latent + a.qk_rope)),
                kv_norm=jnp.ones((a.kv_latent,), jnp.float32),
                wkv_b=dense(ks[2], (a.kv_latent, nh, a.qk_nope + a.v_head)),
                wo=dense(ks[3], (nh, a.v_head, d)),
            )
        if cfg.mhc is not None:
            layer.update(_init_mhc(jax.random.fold_in(lk, 1), cfg))
        if spec.act is not None:
            layer.update(_init_poly(jax.random.fold_in(lk, 2), spec))
        mlp = spec.mlp
        if isinstance(mlp, ExpertMLP):
            from ..parallel.moe import init_moe_params

            held = mlp.held
            layer.update(
                **init_moe_params(
                    ks[4], d, mlp.d_ff, mlp.n_experts, dtype=cfg.dtype,
                    n_held=None if held is None else held[1] - held[0],
                )._asdict()
            )
            kb, *kshared = _split(ks[5], 4)
            if mlp.choice_bias:
                # drawn, not zero: a zero bias would let "bias in the gate"
                # pass for "bias in the choice" (no load-driven update here:
                # the leaf is state, STATE_LEAVES)
                layer["router_bias"] = 0.1 * jax.random.normal(
                    kb, (mlp.n_experts,), jnp.float32)
            if mlp.shared_ff:
                layer.update(
                    shared_gate=dense(kshared[0], (d, mlp.shared_ff)),
                    shared_up=dense(kshared[1], (d, mlp.shared_ff)),
                    shared_down=dense(kshared[2], (mlp.shared_ff, d)),
                )
        else:
            layer.update(
                w_gate=dense(ks[4], (d, mlp.d_ff)),
                w_up=dense(ks[5], (d, mlp.d_ff)),
                w_down=dense(_split(ks[5], 2)[1], (mlp.d_ff, d)),
            )
        layers.append(layer)
    if cfg.pp_axis is not None:
        _refuse_pattern(cfg, "the pipeline-parallel stack")
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

    def layer_of(spec: LayerSpec):
        layer = {"attn_norm": P(None), "mlp_norm": P(None),
                 "wo": P(tp, None, None)}
        if isinstance(spec.attn, GDLAttn):
            # the latents and their norms serve every head: replicated; a
            # head group's signal heads sit on the chip of its KV head
            layer.update(wq_a=P(None, None), q_a_norm=P(None),
                         wq_b=P(None, tp, None), w_lambda=P(None, tp),
                         w_attn_gate=P(None, tp, None))
        else:
            layer["wq"] = P(None, tp, None)
        if spec.attn is None:
            layer.update(wk=P(None, tp, None), wv=P(None, tp, None))
            if cfg.qk_norm:
                layer.update(q_norm=P(None), k_norm=P(None))
        else:
            # the down-projection and its norm serve every head: replicated
            layer.update(wkv_a=P(None, None), kv_norm=P(None),
                         wkv_b=P(None, tp, None))
        if cfg.mhc is not None:
            layer.update({f"mhc_{s}_{leaf}": P(*(None,) * rank)
                          for s in MHC_SUBLAYERS
                          for leaf, rank in (("phi", 2), ("alpha", 1),
                                             ("bias", 1))})
        if spec.act is not None:
            if isinstance(spec.mlp, ExpertMLP):
                layer["expert_poly"] = P(cfg.expert_axis, None)
                if spec.mlp.shared_ff:
                    layer["shared_poly"] = P(None)
            else:
                layer["poly"] = P(None)
        if isinstance(spec.mlp, ExpertMLP):
            # experts shard over expert_axis ONLY (the _mlp shard_map slices
            # the same way); sharding their ffn dim over tp as well would
            # need a row-parallel psum inside the expert MLP — replication
            # across tp is the simpler trade at these expert sizes
            ep = cfg.expert_axis
            layer.update(
                router=P(None, None),
                w_gate=P(ep, None, None),
                w_up=P(ep, None, None),
                w_down=P(ep, None, None),
            )
            if spec.mlp.choice_bias:
                layer["router_bias"] = P(None)
            if spec.mlp.shared_ff:
                # inside the expert layer's shard_map, which has no tp psum
                layer.update(shared_gate=P(None, None),
                             shared_up=P(None, None),
                             shared_down=P(None, None))
        else:
            layer.update(
                w_gate=P(None, tp),
                w_up=P(None, tp),
                w_down=P(tp, None),
            )
        return layer

    specs = layer_specs(cfg)
    layer = layer_of(specs[0])
    if cfg.pp_axis is not None:
        _refuse_pattern(cfg, "the pipeline-parallel stack")
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
        "layers": ([layer] * cfg.n_layers if cfg.pattern is None
                   else [layer_of(spec) for spec in specs]),
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


def _rope_interleaved(x, positions, theta):
    """Rotary embedding with the INTERLEAVED pairing: channels (2i, 2i+1) of
    x [B, N, S, H] are one complex number, rotated by positions * theta^(-2i/H)
    (positions [B, S]); the pairs stay where they are."""
    h = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, h, 2, dtype=jnp.float32) / h))
    angles = positions[:, None, :, None].astype(jnp.float32) * freqs  # [B,1,S,H/2]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], h // 2, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _latent_qkv(p, h, positions, cfg: ModelConfig, a: LatentAttn):
    """Latent attention's q [B, N, S, qk_nope + qk_rope], k of the same
    width and v [B, N, S, v_head] of the layer's normed input `h`.  q is two
    products over column ranges of `wq`, not one product cut in two
    afterwards: the cut is then of the (small) weight, and no second [B, N,
    S, 192] array of activations exists to be sliced.  Scopes
    obs.model.mla.q / .kv_down / .kv_up (docs/observability.md)."""
    proj = partial(jnp.einsum, "bsd,dnh->bnsh")
    with jax.named_scope("obs.model.mla.q"):
        q = jnp.concatenate(
            [proj(h, p["wq"][..., :a.qk_nope]),
             _rope_interleaved(proj(h, p["wq"][..., a.qk_nope:]), positions,
                               cfg.rope_theta)], axis=-1)
    return (q, *_latent_kv(p, h, positions, cfg, a))


def _gdla_qkv(p, h, positions, cfg: ModelConfig, a: GDLAttn):
    """GDLA's q [B, N, S, qk_nope + qk_rope] from its normed q latent, and
    _latent_kv's k, v for its `kv_heads`.  q is ONE product cut afterwards:
    two column-range products of `wq_b` compile the rotary range's weight
    gradient to a convolution windowed over all 192 columns (68 ms a layer
    at 4,096 tokens on a v5e: PERF.md section 6).  The scopes are
    _latent_qkv's."""
    with jax.named_scope("obs.model.mla.q"):
        q = jnp.einsum("bsc,cnh->bnsh", _rms_norm(
            jnp.einsum("bsd,dc->bsc", h, p["wq_a"]), p["q_a_norm"],
            cfg.norm_eps), p["wq_b"])
        q = jnp.concatenate(
            [q[..., :a.qk_nope],
             _rope_interleaved(q[..., a.qk_nope:], positions,
                               cfg.rope_theta)], axis=-1)
    return (q, *_latent_kv(p, h, positions, cfg, a))


def _latent_kv(p, h, positions, cfg: ModelConfig, a: LatentAttn):
    """Latent attention's k [B, N_kv, S, qk_nope + qk_rope] and v [B, N_kv,
    S, v_head] of the layer's normed input `h` (N_kv: a LatentAttn's n_heads,
    a GDLAttn's kv_heads, read off `wkv_b`).  k is materialised at full
    width a head, as the published code does, its rotary part the one key a
    token; each product is over a column range of `wkv_b`, as _latent_qkv's
    q.  Scopes obs.model.mla.kv_down / .kv_up."""
    proj = partial(jnp.einsum, "bsd,dnh->bnsh")
    with jax.named_scope("obs.model.mla.kv_down"):
        down = jnp.einsum("bsd,dc->bsc", h, p["wkv_a"])
        latent = _rms_norm(down[..., :a.kv_latent], p["kv_norm"],
                           cfg.norm_eps)
        # the one rotary key a token, every head's
        k_rope = _rope_interleaved(down[:, None, :, a.kv_latent:], positions,
                                   cfg.rope_theta)
    with jax.named_scope("obs.model.mla.kv_up"):
        k_nope = proj(latent, p["wkv_b"][..., :a.qk_nope])
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (*k_nope.shape[:3],
                                               a.qk_rope))], axis=-1)
        v = proj(latent, p["wkv_b"][..., a.qk_nope:])
    return k, v


def _qkv_proj(p, x, positions, cfg: ModelConfig):
    """Norm + qkv projections + rotary — shared by the regular and
    pipeline-parallel paths (a numerics change here must hit both, or the
    pp-vs-regular parity tests break)."""
    h = _rms_norm(x, p["attn_norm"], cfg.norm_eps)
    q = jnp.einsum("bsd,dnh->bnsh", h, p["wq"])
    k = jnp.einsum("bsd,dnh->bnsh", h, p["wk"])
    v = jnp.einsum("bsd,dnh->bnsh", h, p["wv"])
    if cfg.qk_norm:
        q, k = (_rms_norm(q, p["q_norm"], cfg.norm_eps),
                _rms_norm(k, p["k_norm"], cfg.norm_eps))
    return (_rope(q, positions, cfg.rope_theta),
            _rope(k, positions, cfg.rope_theta), v)


def _attn_out(p, o):
    """Output projection (row-parallel under tp) — shared like _qkv_proj."""
    return jnp.einsum("bnsh,nhd->bsd", o, p["wo"])


def _gdla_out(p, h, o, a: GDLAttn):
    """GDLA's output [B, S, D] from the attention's `o` [B, N, S, v_head]
    (heads in KV-group order, each group's last the noise head) and the
    layer's normed input `h`: each signal head minus lambda times its
    group's noise head, times the elementwise gate, through wo.  The
    difference and the gate in float32; scope obs.model.gdla.diff."""
    b, n, s, dv = o.shape
    per = n // a.kv_heads
    with jax.named_scope("obs.model.gdla.diff"):
        lam = jax.nn.sigmoid(jnp.einsum(
            "bsd,dn->bns", h, p["w_lambda"]).astype(jnp.float32))
        gate = jax.nn.sigmoid(jnp.einsum(
            "bsd,dnh->bnsh", h, p["w_attn_gate"]).astype(jnp.float32))
        o = o.astype(jnp.float32).reshape(b, a.kv_heads, per, s, dv)
        lam = lam.reshape(b, a.kv_heads, per - 1, s, 1)
        diff = (o[:, :, :-1] - lam * o[:, :, -1:]).reshape(b, -1, s, dv)
        out = (gate * diff).astype(h.dtype)
    return _attn_out(p, out)


def _attention(p, x, positions, cfg: ModelConfig, mesh, segment_ids=None,
               collect_stats=False, kind: Optional[LatentAttn] = None,
               window: Optional[int] = None):
    """One attention sublayer.  `collect_stats` (static) additionally
    returns the ring's in-graph DevStats (burst strategy only — ulysses has
    no ring to instrument): `(out, DevStats)` instead of `out`.  `kind`: the
    layer's LayerSpec.attn (None: the GQA block of the scalar knobs);
    `window`: the layer's LayerSpec.window (None: cfg.window)."""
    window = cfg.window if window is None else window
    if kind is None:
        q, k, v = _qkv_proj(p, x, positions, cfg)
    else:
        h = _rms_norm(x, p["attn_norm"], cfg.norm_eps)
        q, k, v = (_gdla_qkv if isinstance(kind, GDLAttn) else _latent_qkv)(
            p, h, positions, cfg, kind)
    if isinstance(kind, GDLAttn):
        out = partial(_gdla_out, p, h, a=kind)
    else:
        out = partial(_attn_out, p)
    if collect_stats and cfg.attn_strategy != "burst":
        raise ValueError(
            "collect_stats requires attn_strategy='burst' (devstats "
            f"instruments the ring); got {cfg.attn_strategy!r}")
    if cfg.attn_strategy == "ulysses":
        if cfg.block_diffusion is not None:
            raise ValueError("block_diffusion needs attn_strategy='burst' "
                             "(the mask lives in burst_attn's tiles)")
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
            head_axes=cfg.head_axis, window=window,
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
            window=window,
            segment_ids=segment_ids,
            collect_stats=collect_stats,
            block_diffusion=cfg.block_diffusion,
        )
        if collect_stats:
            o, stats = o
            return out(o), stats
    else:
        raise ValueError(
            f"unknown attn_strategy {cfg.attn_strategy!r}; "
            "expected 'burst' or 'ulysses'"
        )
    return out(o)


def _sinkhorn(logits, iters: int):
    """Sinkhorn-Knopp on exp(logits) [..., n, n], float32: `iters` rounds
    of a row then a column normalisation (doubly stochastic in the limit;
    exactly column-stochastic after the last round).  A loop, not `iters`
    copies: unrolled, the rounds were a quarter of the train step's
    executable."""
    def normalise(_, m):
        m = m / jnp.sum(m, axis=-1, keepdims=True)
        return m / jnp.sum(m, axis=-2, keepdims=True)

    m = jnp.exp(logits - jnp.max(logits, axis=(-2, -1), keepdims=True))
    return jax.lax.fori_loop(0, iters, normalise, m)


def mhc_maps(p, x, sub: str, cfg: ModelConfig):
    """mHC's three maps of the streams x [B, S, n, D] for sublayer `sub`
    (MHC_SUBLAYERS), float32: pre [B, S, n] = sigmoid, post [B, S, n] = 2
    sigmoid, res [B, S, n, n] = Sinkhorn-Knopp of exp, each of its gain
    times its columns of RMSNorm(vec x) phi, plus its bias."""
    b, s, n, d = x.shape
    flat = x.reshape(b, s, n * d).astype(jnp.float32)
    flat = flat * jax.lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                                + cfg.norm_eps)
    alpha, bias = p[f"mhc_{sub}_alpha"], p[f"mhc_{sub}_bias"]
    proj = jnp.einsum("bsc,cm->bsm", flat, p[f"mhc_{sub}_phi"])
    pre = jax.nn.sigmoid(alpha[0] * proj[..., :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * proj[..., n:2 * n]
                                + bias[n:2 * n])
    res = (alpha[2] * proj[..., 2 * n:] + bias[2 * n:]).reshape(b, s, n, n)
    return pre, post, _sinkhorn(res, cfg.mhc.sinkhorn_iters)


def _mhc_pre(p, x, sub: str, cfg: ModelConfig):
    """(the sublayer's input [B, S, D] = sum_i pre_i x_i, the maps)."""
    pre, post, res = maps = mhc_maps(p, x, sub, cfg)
    u = jnp.einsum("bsn,bsnd->bsd", pre, x.astype(jnp.float32))
    return u.astype(x.dtype), maps


def _mhc_post(x, maps, f, cfg: ModelConfig):
    """The streams after a sublayer: res x + post^T f(.), f [B, S, D] the
    sublayer's output, clipped to +-cfg.mhc.clamp where it is set."""
    _, post, res = maps
    y = (jnp.einsum("bsij,bsjd->bsid", res, x.astype(jnp.float32))
         + post[..., None] * f.astype(jnp.float32)[:, :, None])
    if cfg.mhc.clamp is not None:
        y = jnp.clip(y, -cfg.mhc.clamp, cfg.mhc.clamp)
    return y.astype(x.dtype)


def _mhc_passes(cfg: ModelConfig, mesh, seq: int):
    """(pre, post) of a sublayer pass on the streams flat [B, S, n D]:
    pre(p, x, sub) -> (u, maps, x), post(x, maps, f) -> the streams after.
    ops/mhc.py's kernels on the TPU (the rule of burst_attn's
    backend="auto") where the streams tile and one device holds every token
    (the launches are not partitioned); _mhc_pre / _mhc_post elsewhere."""
    n, d, mc = cfg.mhc.streams, cfg.d_model, cfg.mhc
    token_shards = 1
    for a in (cfg.batch_axis, *cfg.seq_axes):
        token_shards *= mesh.shape.get(a, 1) if a is not None else 1
    if (mhc_ops.engaged() and mhc_ops.tiles(seq, d, n)
            and token_shards == 1):
        def pre(p, x, sub):
            return mhc_ops.mhc_pre(
                x, p[f"mhc_{sub}_phi"], p[f"mhc_{sub}_alpha"],
                p[f"mhc_{sub}_bias"], streams=n, eps=cfg.norm_eps,
                iters=mc.sinkhorn_iters)

        def post(x, maps, f):
            return mhc_ops.mhc_post(x, maps, f, streams=n, clamp=mc.clamp)

        return pre, post
    split = lambda x: x.reshape(*x.shape[:2], n, d)

    def pre(p, x, sub):
        return (*_mhc_pre(p, split(x), sub, cfg), x)

    def post(x, maps, f):
        return _mhc_post(split(x), maps, f, cfg).reshape(x.shape)

    return pre, post


def _moe_group(mp, h2, cfg: ModelConfig, ep_axis, inference=False,
               kind: Optional[ExpertMLP] = None, extra=None, act=None):
    """One routing group's MoE: [tokens, d] -> (y, aux, MoEStats or None).
    `kind`: the layer's ExpertMLP (None: the scalar knobs'); `extra`: its
    leaves beside MoEParams (_MOE_EXTRA, those it has); `act`: the layer's
    LayerSpec.act (None: SiLU).
    The ONE place that picks the layer (see ModelConfig.n_experts): the
    regular path's _mlp and the pipeline's _moe_block both call it, inside
    their shard_maps.

    The dense-dispatch side routes PER SHARD (GShard): tokens route within
    their (batch, seq)-shard's group, so the [T, E, C] dispatch tensors stay
    O(local_tokens^2).  `inference=True` sizes capacity drop-free (tokens x
    top_k), in chunks: capacity == chunk size is drop-free (a token
    contributes at most one slot per expert), and chunking keeps the
    [chunk, E, chunk] dispatch tensors O(chunk^2) on long prefills; it is
    exact when nothing drops, since routing is per-token."""
    from ..parallel.moe import capacity_for, moe_held, moe_shard

    kind = _knob_mlp(cfg) if kind is None else kind
    extra = extra or {}
    if ep_axis is None and not inference:
        shared = ("shared_gate", "shared_up", "shared_down") + (
            () if act is None else ("shared_poly",))
        return moe_held(
            mp, h2, top_k=kind.top_k, held=kind.held, score=kind.score,
            bias=extra.get("router_bias"), gate_scale=kind.gate_scale,
            shared=(tuple(extra[k] for k in shared)
                    if kind.shared_ff else None),
            **({} if act is None else dict(
                act=partial(poly_norm, spec=act),
                act_weights=extra["expert_poly"])))
    if (kind.score, kind.choice_bias, kind.gate_scale, kind.shared_ff,
            act) != ("softmax", False, 1.0, 0, None):
        raise ValueError(
            "a sigmoid router, a choice bias, a gate scale, shared experts "
            "and PolyNorm experts are the drop-free trainer layer's "
            "(moe.moe_held); inference and the expert_axis exchange run the "
            "dense-dispatch layer, which has none of them")
    if kind.held is not None:
        raise ValueError(
            "experts_held is the drop-free trainer layer's (moe.moe_held); "
            "inference and the expert_axis exchange run the dense-dispatch "
            "layer, which holds every expert")
    tokens, dd = h2.shape

    def route(hc, cap):
        y, aux, _ = moe_shard(mp, hc, top_k=kind.top_k, capacity=cap,
                              axis=ep_axis)
        return y, aux

    if not inference:
        y, aux = route(h2, capacity_for(tokens, kind.n_experts, kind.top_k,
                                        cfg.moe_capacity_factor))
        return y, aux, None
    c = min(512, tokens)
    if tokens % c or ep_axis is not None:
        # ragged, or collectives in route (vmap of all_to_all is not
        # supported): one drop-free group
        y, aux = route(h2, tokens)
        return y, aux, None
    yc, aux = jax.vmap(lambda hc: route(hc, c))(
        h2.reshape(tokens // c, c, dd))
    return yc.reshape(tokens, dd), jnp.mean(aux), None


def _mlp(p, x, cfg: Optional[ModelConfig] = None, mesh=None, inference=False):
    """Dense SwiGLU, or (cfg.n_experts > 0) a routed MoE (_moe_group says
    which).  Returns (out, aux_loss) — aux is 0 for the dense path so
    callers are uniform."""
    _refuse_pattern(cfg, "this caller of _mlp (decode, serving, pipeline)")
    return _mlp_stats(p, x, cfg, mesh, inference)[:2]


_MOE_EXTRA = ("router_bias", "shared_gate", "shared_up", "shared_down",
              "expert_poly", "shared_poly")


def _mlp_stats(p, x, cfg: Optional[ModelConfig] = None, mesh=None,
               inference=False, kind=None, act=None):
    """_mlp's (out, aux_loss) and, third, what the drop-free expert layer
    did: its moe.MoEStats over the token shards (slots summed, the load
    ratio's worst shard, the choices [B, S, k] sharded like the tokens);
    None for the dense MLP and the dense-dispatch layer.  `kind`: the
    layer's LayerSpec.mlp (None: the scalar knobs'); `act`: its
    LayerSpec.act, the activation of every gate product (None: SiLU)."""
    h = _rms_norm(x, p["mlp_norm"],
                  ModelConfig.norm_eps if cfg is None else cfg.norm_eps)
    if kind is None and cfg is not None:
        kind = _knob_mlp(cfg)
    if isinstance(kind, ExpertMLP):
        from ..parallel.moe import MoEParams, MoEStats

        mp = MoEParams(p["router"], p["w_gate"], p["w_up"], p["w_down"])
        extra = {k: p[k] for k in _MOE_EXTRA if k in p}
        token_axes = tuple(
            a for a in (cfg.batch_axis, *cfg.seq_axes) if a is not None
        )
        # single-program callers (decode) have no mesh: no expert axis, no
        # cross-shard aux reduction
        ep_axis = cfg.expert_axis if mesh is not None else None

        def group(mp, h, extra):
            bb, ss, dd = h.shape
            y, aux, stats = _moe_group(mp, h.reshape(bb * ss, dd), cfg,
                                       ep_axis, inference, kind, extra, act)
            # moe_shard pmeans over the expert axis; average the remaining
            # token-sharding axes so aux is replicated
            rest = tuple(a for a in token_axes if a != ep_axis)
            if mesh is not None and rest:
                aux = jax.lax.pmean(aux, rest)
            if stats is not None:
                slots, load = stats.slots_here, stats.load_max_over_mean
                if mesh is not None and rest:
                    slots = jax.lax.psum(slots, rest)
                    load = jax.lax.pmax(load, rest)
                stats = MoEStats(slots, load, stats.choice.reshape(bb, ss, -1))
            return y.reshape(bb, ss, dd), aux, stats

        if mesh is None:  # single-program path (e.g. decode off-mesh)
            return group(mp, h, extra)

        seq_spec = cfg.seq_axes if len(cfg.seq_axes) > 1 else cfg.seq_axes[0]
        ep = cfg.expert_axis
        if ep is not None:
            ep_size = mesh.shape.get(ep, 1)
            if kind.n_experts % ep_size:
                raise ValueError(
                    f"n_experts {kind.n_experts} not divisible by "
                    f"expert_axis {ep!r} size {ep_size}")
        pspec = MoEParams(P(None, None), P(ep, None, None),
                          P(ep, None, None), P(ep, None, None))
        tokens_spec = P(cfg.batch_axis, seq_spec, None)
        # _moe_group's rule: the drop-free layer, which has stats, serves
        # where no expert axis is named
        stats_spec = (MoEStats(P(), P(), tokens_spec)
                      if ep is None and not inference else None)
        return shard_map(
            group, mesh=mesh,
            in_specs=(pspec, tokens_spec, {k: P() for k in extra}),
            out_specs=(tokens_spec, P(), stats_spec),
            check_vma=False,
        )(mp, h, extra)
    gate = jnp.einsum("bsd,df->bsf", h, p["w_gate"])
    up = jnp.einsum("bsd,df->bsf", h, p["w_up"])
    gate = (jax.nn.silu(gate) if act is None
            else poly_norm(gate, p["poly"], act))
    out = jnp.einsum("bsf,fd->bsd", gate * up, p["w_down"])
    return out, jnp.float32(0.0), None


def forward(params: Params, tokens, positions, cfg: ModelConfig, mesh,
            segment_ids=None) -> jax.Array:
    """tokens, positions: [B, S] int32 (layout order). Returns fp32 logits
    [B, S, vocab].  segment_ids [B, S]: packed-sequence ids in layout order
    (attention never crosses document boundaries)."""
    logits, _ = forward_with_aux(params, tokens, positions, cfg, mesh,
                                 segment_ids=segment_ids)
    return logits


def forward_with_aux(params: Params, tokens, positions, cfg: ModelConfig, mesh,
                     segment_ids=None, collect_stats=False, moe_stats=False,
                     head_rows=None):
    """forward + the summed MoE auxiliary load-balancing loss (0 for dense
    models); the trainer adds `moe_aux_weight * aux` to the objective.

    `collect_stats` (static): additionally return the per-device ring
    telemetry folded across layers (obs.devstats.merge — counts add,
    extrema max/min) as a third element: `(logits, aux, DevStats)`.  Burst
    attention only; the pipeline-parallel path keeps its own schedule and
    does not thread stats.

    `moe_stats` (static): `aux` comes back as `(aux, MoEStats)`, what the
    drop-free expert layer (see _moe_group) did, folded across layers
    (moe.fold_stats: slots add, the load ratio takes the maximum, the
    choices stack to [layers, B, S, k]); `(aux, None)` for a model without
    that layer.  `head_rows` (static (lo, hi)): the final norm and the
    output head run on those sequence rows only, and the logits are
    [B, hi - lo, vocab] (block diffusion reads the noised half of its
    stream)."""
    if cfg.pp_axis is not None:
        _refuse_pattern(cfg, "the pipeline-parallel path")
        if moe_stats or head_rows is not None or cfg.block_diffusion:
            raise ValueError(
                "moe_stats, head_rows and block_diffusion are not threaded "
                "through the pipeline-parallel path (pp_axis set)")
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
    n_mhc = 0 if cfg.mhc is None else cfg.mhc.streams
    if n_mhc:
        # the carry between blocks is the streams, side by side [B, S, n D]
        stream_spec = NamedSharding(mesh, P(cfg.batch_axis, seq_spec, None))
        mhc_pre, mhc_post = _mhc_passes(cfg, mesh, tokens.shape[1])
        with jax.named_scope("obs.model.mhc"):
            x = jax.lax.with_sharding_constraint(
                jnp.concatenate([x] * n_mhc, axis=-1), stream_spec)

    def block(carry, p, spec=None):
        spec = LayerSpec(_knob_mlp(cfg)) if spec is None else spec
        attend = partial(_attention, p, positions=positions, cfg=cfg,
                         mesh=mesh, segment_ids=segment_ids, kind=spec.attn,
                         window=spec.window)
        if collect_stats:
            x, aux, stats = carry
        else:
            x, aux = carry
        if n_mhc:
            with jax.named_scope("obs.model.mhc"):
                u, maps, x = mhc_pre(p, x, "attn")
        else:
            u = x
        if collect_stats:
            from ..obs import devstats

            with jax.named_scope("obs.model.attn"):
                a, st = attend(u, collect_stats=True)
                if not n_mhc:
                    x = x + a
            stats = st if stats is None else devstats.merge(stats, st)
        else:
            with jax.named_scope("obs.model.attn"):
                a = attend(u)
                if not n_mhc:
                    x = x + a
        if n_mhc:
            with jax.named_scope("obs.model.mhc"):
                x = mhc_post(x, maps, a)
                u, maps, x = mhc_pre(p, x, "mlp")
        else:
            u = x
        with jax.named_scope("obs.model.mlp"):
            m, aux_l, moe_l = _mlp_stats(p, u, cfg, mesh, kind=spec.mlp,
                                         act=spec.act)
            if not n_mhc:
                x = jax.lax.with_sharding_constraint(x + m, act_spec)
        if n_mhc:
            with jax.named_scope("obs.model.mhc"):
                x = jax.lax.with_sharding_constraint(
                    mhc_post(x, maps, m), stream_spec)
        if collect_stats:
            return (x, aux + aux_l, stats), moe_l
        return (x, aux + aux_l), moe_l

    carry = ((x, jnp.float32(0.0), None) if collect_stats
             else (x, jnp.float32(0.0)))
    moe_layers = []  # each layer's MoEStats (None: no drop-free layer)
    # one function object a DISTINCT spec (a stack without a pattern: the one
    # it always ran), so that layers of one kind share jax.checkpoint's trace
    kinds = {None: block}
    for p, spec in zip(params["layers"], layer_specs(cfg)):
        key = None if cfg.pattern is None else spec
        layer = kinds.setdefault(key, partial(block, spec=spec))
        carry, moe_l = (jax.checkpoint(layer) if cfg.remat else layer)(
            carry, p)
        moe_layers.append(moe_l)
    if collect_stats:
        x, aux, stats = carry
    else:
        x, aux = carry
    if moe_stats:
        from ..parallel.moe import fold_stats

        aux = (aux, fold_stats(moe_layers))

    if n_mhc:
        with jax.named_scope("obs.model.mhc"):
            d = x.shape[-1] // n_mhc
            x = sum(x[..., i * d:(i + 1) * d].astype(jnp.float32)
                    for i in range(n_mhc)).astype(cfg.dtype)
    with jax.named_scope("obs.model.loss_head"):
        if head_rows is not None:
            x = jax.lax.slice_in_dim(x, *head_rows, axis=1)
        x = _rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum(
            "bsd,vd->bsv", x, params["lm_head"],
            preferred_element_type=jnp.float32
        )
        logits = jax.lax.with_sharding_constraint(logits, logit_spec)
    if collect_stats:
        return logits, aux, stats
    return logits, aux
