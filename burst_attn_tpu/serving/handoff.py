"""The million-token handoff: ring-sharded prefill whose K/V lands
DIRECTLY in pool pages, feeding sequence-parallel paged decode.

The long-context serving story has three acts (ROADMAP items 3/4):

  1. PREFILL at ring scale: the training forward (burst ring attention
     over the `sp` axes; cfg.attn_backend picks the tile, exactly as in
     training) absorbs the prompt.
  2. HANDOFF: each layer's rope'd K/V is scattered straight from the
     ring-sharded activations into pool pages — in LAYOUT order, with NO
     re-layout copy.  Page p simply holds layout positions
     [p·page, (p+1)·page); the page table records which pool page that
     is.  A million-token prompt never materializes a natural-order
     cache.
  3. DECODE sequence-parallel: models/dist_decode.dist_paged_decode_step
     shards the POOL's page dim over the same axes; each device attends
     the table entries whose pages it owns and the partials LSE-merge.

Skipping the re-layout is correct because decode attends EVERY cached
position — validity is "is this table entry a real token", never an
ordering — and full-visibility attention is permutation-invariant.  That
argument needs cfg.window=None (a sliding window IS an ordering), which
both ends enforce.

The single-host engine (RaggedServeEngine) and this path share the same
PagedState/PagePool, so a handed-off slot can also be decoded by the
plain paged kernels when the pool lives on one chip (tested both ways).
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models.dist_decode import dist_paged_decode_step
from ..models.paged_decode import (
    PagedState, PagePool, _scatter_pages, _write_table_row,
    provision_capacity,
)
from ..models.transformer import (
    ModelConfig, _attn_out, _mlp, _qkv_proj, _rms_norm,
)
from ..parallel import layouts
from ..parallel.burst import burst_attn


def check_handoff_preconditions(state: PagedState, pool: PagePool,
                                slot: int, n_tokens: int,
                                cfg: ModelConfig, *, steps: int = 0) -> int:
    """Validate EVERY admission precondition for a handoff — prompt
    shape, window mode, slot state, table width, and pool availability
    for prefill pages PLUS the decode budget (`steps`) — before a single
    page is acquired or a single state field mutated.

    Callers rely on the zero-mutation guarantee: any raise here leaves
    pool occupancy and state byte-for-byte unchanged, so a rejected
    request can be retried or re-routed with nothing to clean up.
    Returns the number of prefill pages the prompt needs."""
    page = int(state.k_pages[0].shape[2])
    if cfg.window is not None:
        raise ValueError("ring_prefill_to_pages requires cfg.window=None "
                         "(layout-order pages; see module docstring)")
    if n_tokens <= 0:
        raise ValueError(f"empty prompt (n_tokens={n_tokens})")
    if n_tokens % page:
        raise ValueError(f"prompt length {n_tokens} must be a multiple of "
                         f"the page size {page} for the direct-scatter "
                         f"handoff")
    if steps < 0:
        raise ValueError(f"negative decode budget ({steps})")
    if not 0 <= slot < state.lengths.shape[0]:
        raise ValueError(f"slot {slot} out of range "
                         f"[0, {state.lengths.shape[0]})")
    n_prefill = n_tokens // page
    n_total = -(-(n_tokens + steps) // page)
    if n_total > state.page_table.shape[1]:
        raise ValueError(f"request needs {n_total} pages (prompt "
                         f"{n_prefill} + decode budget {steps} tokens) > "
                         f"table width {state.page_table.shape[1]}")
    if int(state.lengths[slot]) != 0:
        raise RuntimeError(f"slot {slot} is still live; retire it first")
    if pool.available < n_total:
        raise RuntimeError(f"page pool exhausted: want {n_total}, have "
                           f"{pool.available}")
    return n_prefill


def ring_prefill_to_pages(params, tokens, state: PagedState, pool: PagePool,
                          slot: int, cfg: ModelConfig, mesh):
    """Absorb a [S] prompt into batch slot `slot` with the ring-sharded
    forward, landing each layer's K/V directly in pool pages.

    Host wrapper: acquires S/page pages, runs the jitted ring pass
    (burst_attn prefill + paged scatter in layout order), rewrites the
    slot's table row.  Returns (last-token logits [vocab] fp32, state).
    S must be a page multiple (ring shards are page-aligned by
    construction: S divides by the sp world and page | S/world in any
    deployment this path targets) and cfg.window must be None (see the
    module docstring's permutation-invariance argument).  All
    preconditions are checked up-front (`check_handoff_preconditions`);
    any rejection leaves the pool untouched."""
    t = int(tokens.shape[0])
    n_need = check_handoff_preconditions(state, pool, slot, t, cfg)
    ids = pool.acquire(n_need)
    try:
        logits, state = _ring_prefill_jit(
            params, jnp.asarray(tokens)[None, :], state,
            jnp.asarray(ids, jnp.int32), jnp.int32(slot), cfg, mesh)
    except Exception:
        pool.release(ids)
        raise
    return logits[0], state


@partial(jax.jit, static_argnames=("cfg", "mesh"), donate_argnums=(2,))
def _ring_prefill_jit(params, tokens, state: PagedState, page_ids, slot,
                      cfg: ModelConfig, mesh):
    """dist_prefill's forward with the cache capture replaced by a paged
    scatter: K/V stays in layout order end to end — the pages ARE the
    sharded cache."""
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
    quant = state.k_scales is not None

    x = params["embed"].astype(cfg.dtype)[tokens_l]
    x = lax.with_sharding_constraint(x, act_spec)
    k_pools, v_pools, k_scs, v_scs = [], [], [], []
    for li, (p, kp, vp) in enumerate(zip(params["layers"], state.k_pages,
                                         state.v_pages)):
        q, k, v = _qkv_proj(p, x, pos, cfg)
        k = lax.with_sharding_constraint(k.astype(cfg.dtype), kv_spec)
        v = lax.with_sharding_constraint(v.astype(cfg.dtype), kv_spec)
        o = burst_attn(
            q, k, v, mesh=mesh, seq_axes=cfg.seq_axes, causal=cfg.causal,
            layout=cfg.layout, backend=cfg.attn_backend,
            block_q=cfg.block_q, block_kv=cfg.block_kv,
            batch_axes=cfg.batch_axis, head_axes=cfg.head_axis,
            window=cfg.window,
        )
        # THE handoff: layout-order K/V -> pool pages, no re-layout copy
        kp2, ks2 = _scatter_pages(kp, k, page_ids,
                                  state.k_scales[li] if quant else None)
        vp2, vs2 = _scatter_pages(vp, v, page_ids,
                                  state.v_scales[li] if quant else None)
        k_pools.append(kp2)
        v_pools.append(vp2)
        k_scs.append(ks2)
        v_scs.append(vs2)
        x = x + _attn_out(p, o)
        m, _ = _mlp(p, x, cfg, mesh, inference=True)
        x = lax.with_sharding_constraint(x + m, act_spec)

    xf = _rms_norm(x, params["final_norm"])
    # the last NATURAL token sits at layout position inv_perm[s-1] — a
    # host-side constant (perm is a layout table, never traced)
    last_pos = layouts.inverse_permutation(perm)[s - 1]
    logits = jnp.einsum("bd,vd->bv", xf[:, last_pos], params["lm_head"],
                        preferred_element_type=jnp.float32)
    table = _write_table_row(state, slot, page_ids)
    lengths = state.lengths.at[slot].set(s)
    return logits, PagedState(
        tuple(k_pools), tuple(v_pools), table, lengths,
        tuple(k_scs) if quant else None, tuple(v_scs) if quant else None)


def handoff_generate(params, prompt, state: PagedState, pool: PagePool,
                     cfg: ModelConfig, mesh, *, steps: int, slot: int = 0,
                     temperature: float = 0.0, top_k=None, top_p=None,
                     rng=None):
    """End-to-end million-token path on one slot: ring prefill into pool
    pages, provision the decode budget, then `steps` sequence-parallel
    paged decode steps.  Returns ([steps] tokens, final state).

    Greedy/sampled semantics are decode.sample_logits's; the decode loop
    is a python loop over one jitted step (static shapes — no retrace).

    Admission is all-or-nothing: the decode budget is validated together
    with the prefill's page needs BEFORE the ring pass runs, so a
    request whose budget cannot fit (table width or pool availability)
    rejects with zero pool mutation — previously the provision ran after
    prefill had already acquired pages and made the slot live, leaking
    them on rejection."""
    from ..models.decode import sample_logits

    if steps < 1:
        raise ValueError(f"steps must be >= 1, got {steps}")
    check_handoff_preconditions(state, pool, slot, int(prompt.shape[0]),
                                cfg, steps=steps)
    rng = jax.random.PRNGKey(0) if rng is None else rng
    last_logits, state = ring_prefill_to_pages(
        params, prompt, state, pool, slot, cfg, mesh)
    state = provision_capacity(state, pool, slot, steps)

    @jax.jit
    def pick(logits, key):
        return sample_logits(logits, key, temperature=temperature,
                             top_k=top_k, top_p=top_p, nan_sentinel=True)

    slots = state.lengths.shape[0]
    keys = jax.random.split(rng, steps + 1)
    tok = int(np.asarray(pick(last_logits[None, :], keys[0]))[0])
    if tok < 0:
        raise RuntimeError("handoff prefill logits are NaN-poisoned")
    out = [tok]
    feed = np.zeros((slots,), np.int32)
    for i in range(steps - 1):
        feed[slot] = out[-1]
        logits, state = dist_paged_decode_step(
            params, jnp.asarray(feed), state, cfg, mesh)
        tok = int(np.asarray(pick(logits[slot][None, :], keys[i + 1]))[0])
        if tok < 0:
            raise RuntimeError(
                f"handoff decode step {i} logits are NaN-poisoned")
        out.append(tok)
    return out, state


def handoff_decode(params, state: PagedState, cfg: ModelConfig, mesh, *,
                   slot: int, last_token: int, n_steps: int, journal=None,
                   rid: int = 0):
    """Resumable greedy decode on an already-provisioned handoff slot:
    `n_steps` sequence-parallel paged steps continuing from `last_token`
    (the newest token already in the stream — prefill-sampled or
    journal-recovered).  Returns ([n_steps] tokens, final state).

    This is the crash-consistency surface for the million-token path:
    handoff_generate fused prefill+decode in one call, so a fault left
    nothing to resume FROM.  Here the caller owns the split — after
    `ring_prefill_to_pages` + `provision_capacity` (or after
    `load_paged_snapshot` rebuilt the state from a checkpoint), decode
    proceeds in restartable strides, and each emitted token can be
    journaled write-ahead (`journal.tokens(rid, [tok])` + sync per step)
    so a killed decode resumes from its last durable token instead of
    re-burning the ring prefill.  Greedy only (argmax == sample_logits
    at temperature 0): a resumed stream must be the continuation the
    dead decode would have produced."""
    slots = state.lengths.shape[0]
    feed = np.zeros((slots,), np.int32)
    cur = int(last_token)
    out = []
    for i in range(n_steps):
        feed[slot] = cur
        logits, state = dist_paged_decode_step(
            params, jnp.asarray(feed), state, cfg, mesh)
        row = np.asarray(logits[slot])
        if np.isnan(row).any():
            raise RuntimeError(
                f"handoff decode step {i} logits are NaN-poisoned: slot "
                f"{slot} stepped without provisioned capacity")
        cur = int(row.argmax())
        out.append(cur)
        if journal is not None:
            journal.tokens(rid, [cur])
            journal.sync()
    return out, state
