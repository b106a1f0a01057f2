"""Distributed long-context inference: ring prefill with a SEQUENCE-SHARDED
KV cache, then LSE-merged decode across the shards.

models/decode.py keeps the whole cache on one replica — fine up to the HBM
of a single chip, but this framework's point is sequences that need the
ring.  Here the prompt's KV cache never leaves its sequence shards:

  * prefill: the training forward (burst ring attention over `sp`, any
    layout) runs once over the prompt, capturing each layer's rope'd K/V.
    The cache stays sharded [B, Nkv, S/W, D] per device, in LAYOUT order —
    decode never needs the order: a new token attends ALL cached tokens, and
    attention is permutation-invariant when everything is visible.
  * decode: per layer, the new token's q computes a PARTIAL online-softmax
    against the local cache shard; the partials merge across the `sp` axis
    in log space (pmax of the row max, psum of the rescaled sum/accumulator
    — the same merge the ring uses, ops/tile.py), then merge once more with
    a small REPLICATED buffer holding the tokens generated so far.  New
    tokens append to that replicated buffer: O(steps) memory, no shard
    surgery, exact attention.

Single-axis sp mesh (pass the same mesh used for prefill). Generated-token
budget = the replicated buffer size = `steps`.
"""

from functools import partial
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from .transformer import ModelConfig, _attn_out, _mlp, _qkv_proj, _rms_norm
from ..parallel import layouts
from ..parallel.burst import burst_attn


class DistCache(NamedTuple):
    # per layer, sequence-sharded over sp (layout order), dtype = cfg.dtype
    k_shard: Tuple[jax.Array, ...]   # each [B, Nkv, S, D]
    v_shard: Tuple[jax.Array, ...]
    # per layer, replicated recent-token buffers
    k_new: Tuple[jax.Array, ...]     # each [B, Nkv, R, D]
    v_new: Tuple[jax.Array, ...]
    n_new: jax.Array                 # scalar int32: valid positions in *_new


def dist_prefill(params, tokens, cfg: ModelConfig, mesh, *, gen_budget: int):
    """Absorb a [B, S] prompt (natural order) with the sharded forward.

    Returns (last_logits [B, vocab] fp32, DistCache).  S must divide by the
    sp world; gen_budget sizes the replicated recent-KV buffers.
    """
    b, s = tokens.shape
    world = 1
    for a in cfg.seq_axes:
        world *= mesh.shape.get(a, 1)
    perm = layouts.seq_permutation(cfg.layout, s, world)
    pos = jnp.broadcast_to(jnp.asarray(perm, jnp.int32)[None, :], (b, s))
    tokens_l = jnp.take(tokens, jnp.asarray(perm), axis=1)

    seq_spec = cfg.seq_axes if len(cfg.seq_axes) > 1 else cfg.seq_axes[0]
    act_spec = NamedSharding(mesh, P(cfg.batch_axis, seq_spec, None))
    kv_spec = NamedSharding(mesh, P(cfg.batch_axis, None, seq_spec, None))

    x = params["embed"].astype(cfg.dtype)[tokens_l]
    x = lax.with_sharding_constraint(x, act_spec)
    ks, vs = [], []
    for p in params["layers"]:
        q, k, v = _qkv_proj(p, x, pos, cfg)
        k = lax.with_sharding_constraint(k.astype(cfg.dtype), kv_spec)
        v = lax.with_sharding_constraint(v.astype(cfg.dtype), kv_spec)
        ks.append(k)
        vs.append(v)
        o = burst_attn(
            q, k, v, mesh=mesh, seq_axes=cfg.seq_axes, causal=cfg.causal,
            layout=cfg.layout, backend=cfg.attn_backend,
            block_q=cfg.block_q, block_kv=cfg.block_kv,
            batch_axes=cfg.batch_axis, head_axes=cfg.head_axis,
            window=cfg.window,
        )
        x = x + _attn_out(p, o)
        # inference=True: drop-free MoE routing, matching decode.py's prefill
        m, _ = _mlp(p, x, cfg, mesh, inference=True)
        x = lax.with_sharding_constraint(x + m, act_spec)

    xf = _rms_norm(x, params["final_norm"])
    # only ONE position feeds decoding; the full [B, S, vocab] fp32 logits
    # would be GBs at the contexts this module exists for.  The LAST token
    # in natural order sits at layout position inv_perm[s-1] — a host-side
    # numpy scalar (perm is a layout table, never traced), so it indexes xf
    # as a static constant under jit with no int() coercion needed.
    last_pos = layouts.inverse_permutation(perm)[s - 1]
    last_logits = jnp.einsum("bd,vd->bv", xf[:, last_pos], params["lm_head"],
                             preferred_element_type=jnp.float32)

    shape_new = (b, cfg.n_kv_heads, gen_budget, cfg.d_head)
    zeros_new = tuple(jnp.zeros(shape_new, cfg.dtype)
                      for _ in range(cfg.n_layers))
    cache = DistCache(tuple(ks), tuple(vs), zeros_new,
                      tuple(jnp.zeros(shape_new, cfg.dtype)
                            for _ in range(cfg.n_layers)),
                      jnp.int32(0))
    return last_logits, cache


def _merge(parts):
    """Log-space merge of [(m, l, acc)] partials (m [B,N,1], l [B,N,1],
    acc [B,N,1,D] unnormalized)."""
    m_g = parts[0][0]
    for m, _, _ in parts[1:]:
        m_g = jnp.maximum(m_g, m)
    l_g = sum(l * jnp.exp(m - m_g) for m, l, _ in parts)
    acc_g = sum(acc * jnp.exp(m - m_g)[..., None] for m, _, acc in parts)
    return acc_g / jnp.maximum(l_g, 1e-30)[..., None]


def _partial_attn(q, k, v, scale, n_valid=None, col_lo=None):
    """Unnormalized online-softmax partial of q [B,N,1,D] against k/v
    [B,Nk,T,D]; positions >= n_valid masked, positions < col_lo masked
    (the sliding-window lower bound in this buffer's local coordinates).
    Returns (m, l, acc) with leading [B, N, 1] shape.  GQA via a grouped
    query axis — the dominant cache buffers are never repeated (decode.py's
    convention)."""
    b, n, _, d = q.shape
    nk, t = k.shape[1], k.shape[2]
    qg = q.reshape(b, nk, n // nk, 1, d)
    s = jnp.einsum("bngid,bnjd->bngij", qg, k,
                   preferred_element_type=jnp.float32) * scale
    cols = jnp.arange(t, dtype=jnp.int32)[None, None, None, None, :]
    if n_valid is not None:
        s = jnp.where(cols < n_valid, s, -jnp.inf)
    if col_lo is not None:
        s = jnp.where(cols >= col_lo, s, -jnp.inf)
    m = jnp.max(s, axis=-1)
    # fully-masked partial (empty recent buffer): exp(-inf - -inf) guard
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bngij,bnjd->bngid", p, v.astype(jnp.float32))
    m = jnp.where(jnp.isfinite(m), m, -1e30)  # neutral under max-merge
    return (m.reshape(b, n, 1), l.reshape(b, n, 1),
            acc.reshape(b, n, 1, d))


def dist_decode_step(params, token, position, cache: DistCache,
                     cfg: ModelConfig, mesh):
    """One token: [B] int32 -> (fp32 logits [B, vocab], updated cache)."""
    sp_axes = cfg.seq_axes
    scale = cfg.d_head**-0.5

    x = params["embed"].astype(cfg.dtype)[token][:, None, :]  # [B,1,d]
    pos = jnp.broadcast_to(position[None, None], (x.shape[0], 1)).astype(jnp.int32)

    k_new, v_new = [], []
    for li, p in enumerate(params["layers"]):
        q, k, v = _qkv_proj(p, x, pos, cfg)

        def shard_partial(q, kc, vc):
            col_lo = None
            if cfg.window is not None:
                # contig layout (enforced for windowed models): this shard's
                # first token is globally at part * s_local, so the band's
                # global lower bound position - window + 1 lands at local
                # column (position - window + 1) - part * s_local
                from ..parallel.ring import my_partition

                intra = sp_axes[-1]
                inter = sp_axes[0] if len(sp_axes) > 1 else None
                part = my_partition(intra, inter)
                col_lo = position - cfg.window + 1 - part * kc.shape[2]
            m, l, acc = _partial_attn(q, kc, vc, scale, col_lo=col_lo)
            # merge across the sequence shards in log space
            m_g = lax.pmax(m, sp_axes)
            w = jnp.exp(m - m_g)
            l_g = lax.psum(l * w, sp_axes)
            acc_g = lax.psum(acc * w[..., None], sp_axes)
            return m_g, l_g, acc_g

        seq_spec = sp_axes if len(sp_axes) > 1 else sp_axes[0]
        m_c, l_c, acc_c = shard_map(
            shard_partial, mesh=mesh,
            in_specs=(P(cfg.batch_axis, None, None, None),
                      P(cfg.batch_axis, None, seq_spec, None),
                      P(cfg.batch_axis, None, seq_spec, None)),
            out_specs=(P(cfg.batch_axis, None, None),
                       P(cfg.batch_axis, None, None),
                       P(cfg.batch_axis, None, None, None)),
            check_vma=False,
        )(q, cache.k_shard[li], cache.v_shard[li])

        # recent generated tokens (replicated) + the token being computed
        kr = lax.dynamic_update_slice(
            cache.k_new[li], k.astype(cfg.dtype), (0, 0, cache.n_new, 0))
        vr = lax.dynamic_update_slice(
            cache.v_new[li], v.astype(cfg.dtype), (0, 0, cache.n_new, 0))
        k_new.append(kr)
        v_new.append(vr)
        # recent buffer slot j holds global position (position - n_new) + j,
        # so the band's lower bound lands at slot n_new - window + 1
        rec_lo = (cache.n_new - cfg.window + 1
                  if cfg.window is not None else None)
        m_r, l_r, acc_r = _partial_attn(q, kr, vr, scale,
                                        n_valid=cache.n_new + 1,
                                        col_lo=rec_lo)
        o = _merge([(m_c, l_c, acc_c), (m_r, l_r, acc_r)]).astype(cfg.dtype)
        x = x + _attn_out(p, o)
        m_out, _ = _mlp(p, x, cfg, inference=True)
        x = x + m_out

    xf = _rms_norm(x, params["final_norm"])
    logits = jnp.einsum("bsd,vd->bsv", xf, params["lm_head"],
                        preferred_element_type=jnp.float32)[:, 0]
    cache = DistCache(cache.k_shard, cache.v_shard, tuple(k_new),
                      tuple(v_new), cache.n_new + 1)
    return logits, cache


def _page_partition(sp_axes):
    """Linear shard index over the (possibly nested) sequence axes — the
    same coordinate my_partition gives the ring."""
    from ..parallel.ring import my_partition

    intra = sp_axes[-1]
    inter = sp_axes[0] if len(sp_axes) > 1 else None
    return my_partition(intra, inter)


@partial(jax.jit, static_argnames=("cfg", "mesh"), donate_argnums=(2,))
def dist_paged_decode_step(params, tokens, state, cfg: ModelConfig, mesh):
    """One decode step against a PAGE-SHARDED pool: the pools split over
    the sequence axes along the page dimension (shard w owns global pages
    [w·P/W, (w+1)·P/W)), each shard computes an online-softmax partial
    over the table entries it owns, and the partials LSE-merge across the
    axes — dist_decode_step's merge, reading serving pages instead of a
    dense cache shard.

    This is the decode half of the million-token handoff
    (serving/handoff.py): ring prefill lands its K/V in pool pages in
    LAYOUT order with no re-layout copy, which is correct here because a
    decode token attends EVERY cached position (validity is "is this
    table entry a real token", not an ordering) and full-visibility
    attention is permutation-invariant.  cfg.window must be None for
    exactly that reason.  The append itself is a global scatter (GSPMD
    splits it along the pools' sharding); table/lengths ride replicated.

    tokens [slots] int32 -> (fp32 logits [slots, vocab], new state).
    n_pages must divide by the sequence-axis world size.
    """
    from .paged_decode import PagedState
    from ..ops.paged_attention import quantize_tokens as _quant

    if cfg.window is not None:
        raise ValueError(
            "dist_paged_decode_step requires cfg.window=None: pages hold "
            "layout-order tokens, and a windowed band over page order "
            "would not be the band over natural positions")
    sp_axes = cfg.seq_axes
    world = 1
    for a in sp_axes:
        world *= mesh.shape.get(a, 1)
    slots = tokens.shape[0]
    page = state.k_pages[0].shape[2]
    n_pages = state.k_pages[0].shape[0]
    if n_pages % world:
        raise ValueError(f"n_pages {n_pages} must divide by the sequence "
                         f"world {world} to shard the pool page dim")
    scale = cfg.d_head**-0.5
    group = cfg.n_heads // cfg.n_kv_heads
    live = state.lengths > 0
    pos = jnp.where(live, state.lengths, 0)
    x = params["embed"].astype(cfg.dtype)[tokens[:, None]]
    slot_page = state.lengths // page
    offset = state.lengths % page
    page_id = jnp.take_along_axis(state.page_table, slot_page[:, None],
                                  axis=1)[:, 0]
    boundary_unassigned = live & (page_id == 0)
    page_id = jnp.where(live, page_id, 0)
    lengths_new = state.lengths + live.astype(jnp.int32)
    quant = state.k_scales is not None
    seq_spec = sp_axes if len(sp_axes) > 1 else sp_axes[0]
    pool_spec = P(seq_spec, None, None, None)
    scale_spec = P(seq_spec, None, None)

    def shard_partial(qg, kp_l, vp_l, ks_l, vs_l, table, lens):
        part = _page_partition(sp_axes)
        p_loc = kp_l.shape[0]
        lo = part * p_loc
        owned = (table >= lo) & (table < lo + p_loc) & (table != 0)
        lp = jnp.clip(table - lo, 0, p_loc - 1)
        k_loc = kp_l[lp]                     # [slots, cols, Nkv, page, D]
        v_loc = vp_l[lp]
        if quant:
            k_loc = k_loc.astype(jnp.float32) * ks_l[lp][..., None]
            v_loc = v_loc.astype(jnp.float32) * vs_l[lp][..., None]
        cols = table.shape[1]
        k_loc = jnp.moveaxis(k_loc, 2, 1).reshape(
            slots, cfg.n_kv_heads, cols * page, cfg.d_head)
        v_loc = jnp.moveaxis(v_loc, 2, 1).reshape(
            slots, cfg.n_kv_heads, cols * page, cfg.d_head)
        col_pos = jnp.arange(cols * page, dtype=jnp.int32)[None, :]
        valid = (col_pos < lens[:, None]) \
            & jnp.repeat(owned, page, axis=1)
        s = jnp.einsum("bngd,bnjd->bngj", qg.astype(jnp.float32),
                       k_loc.astype(jnp.float32)) * scale
        s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
        m = jnp.max(s, axis=-1)
        m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        l = jnp.sum(p, axis=-1)
        acc = jnp.einsum("bngj,bnjd->bngd", p, v_loc.astype(jnp.float32))
        m = jnp.where(jnp.isfinite(m), m, -1e30)  # neutral under pmax
        m_g = lax.pmax(m, sp_axes)
        w = jnp.exp(m - m_g)
        l_g = lax.psum(l * w, sp_axes)
        acc_g = lax.psum(acc * w[..., None], sp_axes)
        return acc_g / jnp.maximum(l_g, 1e-30)[..., None]

    k_pools, v_pools, k_scs, v_scs = [], [], [], []
    for li, (p, kp, vp) in enumerate(zip(params["layers"], state.k_pages,
                                         state.v_pages)):
        q, k, v = _qkv_proj(p, x, pos[:, None], cfg)
        k_row, v_row = k[:, :, 0], v[:, :, 0]
        ks = vs = None
        if quant:
            k8, k_s = _quant(k_row)
            v8, v_s = _quant(v_row)
            kp = kp.at[page_id, :, offset].set(k8)
            vp = vp.at[page_id, :, offset].set(v8)
            ks = state.k_scales[li].at[page_id, :, offset].set(k_s)
            vs = state.v_scales[li].at[page_id, :, offset].set(v_s)
        else:
            kp = kp.at[page_id, :, offset].set(k_row.astype(kp.dtype))
            vp = vp.at[page_id, :, offset].set(v_row.astype(vp.dtype))
        qg = q.reshape(slots, cfg.n_kv_heads, group, cfg.d_head)
        in_specs = [P(None, None, None, None), pool_spec, pool_spec,
                    scale_spec if quant else P(),
                    scale_spec if quant else P(),
                    P(None, None), P(None)]
        o = shard_map(
            shard_partial, mesh=mesh, in_specs=tuple(in_specs),
            out_specs=P(None, None, None, None), check_vma=False,
        )(qg, kp, vp,
          ks if quant else jnp.zeros((), cfg.dtype),
          vs if quant else jnp.zeros((), cfg.dtype),
          state.page_table, lengths_new)
        o = o.reshape(slots, cfg.n_heads, 1, cfg.d_head).astype(cfg.dtype)
        x = x + _attn_out(p, o)
        m_out, _ = _mlp(p, x, cfg, inference=True)
        x = x + m_out
        k_pools.append(kp)
        v_pools.append(vp)
        k_scs.append(ks)
        v_scs.append(vs)
    x = _rms_norm(x, params["final_norm"])
    logits = jnp.einsum("bsd,vd->bsv", x, params["lm_head"],
                        preferred_element_type=jnp.float32)[:, 0]
    logits = jnp.where(boundary_unassigned[:, None], jnp.nan, logits)
    return logits, PagedState(
        tuple(k_pools), tuple(v_pools), state.page_table, lengths_new,
        tuple(k_scs) if quant else None, tuple(v_scs) if quant else None)


def dist_generate(params, prompt, cfg: ModelConfig, mesh, *, steps: int,
                  temperature: float = 0.0, top_k=None, top_p=None, rng=None):
    """Greedy/sampled generation with the sequence-sharded prompt cache.

    prompt [B, S] natural order; returns [B, steps] tokens.  The decode loop
    is a python loop over jitted steps (the cache pytree's shardings are
    stable, so each step reuses one compiled program).  Sampling semantics
    (temperature / top-k / top-p) are decode.sample_logits's.
    """
    from .decode import sample_logits

    b, s = prompt.shape
    last_logits, cache = jax.jit(
        partial(dist_prefill, cfg=cfg, mesh=mesh, gen_budget=steps)
    )(params, prompt)
    rng = jax.random.PRNGKey(0) if rng is None else rng

    # jitted with the sampling config closed over (Python constants): the
    # per-token path must stay one cached program per step, not ~8 eager
    # full-vocab dispatches
    @jax.jit
    def pick(logits, key):
        return sample_logits(logits, key, temperature=temperature,
                             top_k=top_k, top_p=top_p)

    step_fn = jax.jit(partial(dist_decode_step, cfg=cfg, mesh=mesh))
    keys = jax.random.split(rng, steps + 1)
    token = pick(last_logits, keys[0])
    out = [token]
    for i in range(steps - 1):
        logits, cache = step_fn(params, token, jnp.int32(s + i), cache)
        token = pick(logits, keys[i + 1])
        out.append(token)
    return jnp.stack(out, axis=1)
