"""Training loop machinery for the flagship LM: mesh building, sharded state
init, and a jitted train step over a (dp, sp[, inter], tp) mesh.

This is the end-to-end integration layer the reference delegates to host
frameworks (BMTrain; reference README.md:36-38) — here it is in-framework and
TPU-native: one `jax.jit` whose input/output shardings come from the model's
PartitionSpec tree; XLA inserts the DP grad psums and megatron TP collectives,
while burst_attn's shard_map runs the sequence ring over `sp` (and the
hierarchical double ring when an `inter` axis is present).

Two objectives.  Next-token cross entropy is the default.  With
`ModelConfig.block_diffusion = B` the step trains by diffusion over blocks of
B tokens instead (bd_stream / bd_loss_parts below): it builds the stream
[noised; clean] on the device from the loader's tokens and a key folded from
`TrainConfig.bd_seed` and the optimizer's step count, runs the stack once on
2L tokens under the block-diffusion mask, and takes the head and the
weighted loss on the noised half.

Next-token convention.  `tokens` and `labels` arrive
already layout-permuted (parallel/layouts.to_layout on axis=1) with `labels`
shifted BEFORE the permutation — shifting after would cross shard boundaries.
`positions` carries true global positions for rotary (layouts.position_ids).
"""

import gc
import itertools
import time
from dataclasses import dataclass
from functools import partial
from typing import Any, NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import obs

logger = obs.get_logger(__name__)

# -- train-loop metrics (host boundary: updated by guarded_step's wrapper,
# never inside the jitted step — burstlint `obs-jit-safe`).  Step time is
# measured dispatch-to-dispatch: the jitted step is async, so wall time
# between consecutive dispatches equals steady-state step time once the
# pipeline fills, WITHOUT inserting a device sync that would serialize the
# host-to-device prefetch against the running step (use
# obs.StepTimer/runner for blocking per-step times).  Each interval is also
# one `train.step` span in the ring (make_train_step), which carries what
# the loop's thread did inside it and why it may have been late.
_M_STEPS = obs.counter("train.steps")
_M_EVENTS = obs.counter(
    "train.events", "exceptional train-loop events by kind "
                    "(devstats_publish_failure; loss-scale kinds reserved "
                    "for a mixed-precision scaler)")
_M_STEP_S = obs.histogram("train.step_interval_s")
_M_COMPILES = obs.counter(
    "train.backend_compiles", "XLA backend compiles (persistent-cache reads "
                              "included) since the first make_train_step")
_M_MOE_SLOTS = obs.counter(
    "moe.slots_here", "(token, expert) pairs the drop-free expert layer "
                      "computed on this chip, all layers (moe.moe_held)")
_M_MOE_LOAD = obs.gauge(
    "moe.load_max_over_mean", "fullest held expert over the mean held "
                              "expert, the last step's worst layer")
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_listener = []  # [listener] once registered: one per process

try:
    import resource
except ImportError:  # no such module off POSIX: the attr is then absent
    resource = None

from .transformer import (STATE_LEAVES, ModelConfig, forward,
                          forward_with_aux, has_experts, init_params,
                          param_specs)
from ..parallel import layouts


@dataclass(frozen=True)
class TrainConfig:
    lr: float = 3e-4
    weight_decay: float = 0.01
    b1: float = 0.9
    b2: float = 0.95
    grad_clip: float = 1.0
    moe_aux_weight: float = 0.01  # weight of the MoE load-balancing loss
    grad_accum: int = 1  # microbatches per optimizer step (scan inside jit)
    # Collect device-side ring telemetry (obs.devstats) every step: the
    # forward accumulates a DevStats pytree IN-GRAPH and guarded_step
    # publishes it into the obs registry after dispatch.  Diagnostic knob:
    # publishing reads the (tiny) stats arrays back each step, which
    # synchronizes the host with the step stream — leave off for
    # steady-state throughput runs (the train.step_interval_s
    # dispatch-interval histogram stays meaningful either way, the sync
    # happens after the interval is measured).
    collect_devstats: bool = False
    # Block diffusion (ModelConfig.block_diffusion): the noise of step n is
    # drawn from fold_in(PRNGKey(bd_seed), n); masked tokens become
    # `bd_mask_id` (None: the vocabulary's last id)
    bd_seed: int = 0
    bd_mask_id: Optional[int] = None


def make_mesh(axis_sizes: dict, devices=None) -> Mesh:
    """Build a Mesh from {"dp": 2, "sp": 2, "tp": 2}-style sizes (order is
    significant: last axis is innermost = most ICI-local)."""
    names = tuple(axis_sizes)
    sizes = tuple(axis_sizes[n] for n in names)
    devices = devices if devices is not None else jax.devices()
    n = int(np.prod(sizes))
    if n > len(devices):
        raise ValueError(f"mesh {axis_sizes} needs {n} devices, have {len(devices)}")
    return Mesh(np.asarray(devices[:n]).reshape(sizes), names)


def _optimizer(tcfg: TrainConfig):
    return optax.chain(
        optax.clip_by_global_norm(tcfg.grad_clip),
        optax.adamw(tcfg.lr, b1=tcfg.b1, b2=tcfg.b2, weight_decay=tcfg.weight_decay),
    )


def state_specs(cfg: ModelConfig, tcfg: TrainConfig, params_shape):
    """PartitionSpec pytree for (params, opt_state): optimizer moments shard
    like their parameters.

    Matching is by TREE PATH, not array shape: optax state leaves embed the
    parameter tree, so an optimizer leaf whose path ends with a parameter's
    path (e.g. `.0.mu.layers[0].wq` vs `.layers[0].wq`) is that parameter's
    moment.  Shape-keyed matching would silently transpose specs whenever two
    differently-sharded parameters share a shape (w_gate/w_down at
    d_ff == d_model).  `params_shape` may be abstract (ShapeDtypeStructs).
    """
    pspecs = param_specs(cfg)
    opt = _optimizer(tcfg)
    opt_shape = jax.eval_shape(opt.init, params_shape)

    path_to_spec = {
        jax.tree_util.keystr(kp): spec
        for kp, spec in jax.tree_util.tree_flatten_with_path(
            pspecs, is_leaf=lambda x: isinstance(x, P)
        )[0]
    }

    def spec_of(kp, leaf):
        s = jax.tree_util.keystr(kp)
        for p, spec in path_to_spec.items():
            if s.endswith(p):
                return spec
        return P()  # scalars / step counts

    opt_specs = jax.tree_util.tree_map_with_path(spec_of, opt_shape)
    return pspecs, opt_specs


def init_train_state(key, cfg: ModelConfig, tcfg: TrainConfig, mesh: Mesh):
    """Initialize (params, opt_state) sharded over `mesh` per param_specs."""
    opt = _optimizer(tcfg)
    pspecs = param_specs(cfg)

    def init_fn(key):
        params = init_params(key, cfg)
        return params, opt.init(params)

    params_shape, opt_shape = jax.eval_shape(init_fn, key)
    _, opt_specs = state_specs(cfg, tcfg, params_shape)
    out_shardings = (
        jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                     is_leaf=lambda x: isinstance(x, P)),
        jax.tree.map(lambda s: NamedSharding(mesh, s), opt_specs,
                     is_leaf=lambda x: isinstance(x, P)),
    )
    return jax.jit(init_fn, out_shardings=out_shardings)(key)


def _loss_parts(params, tokens, positions, labels, cfg: ModelConfig, mesh,
                segment_ids=None, collect_stats=False, moe_stats=False):
    """(sum of masked nll, MoE aux[, DevStats]) — the linear pieces of the
    objective; `collect_stats` (static) appends the ring telemetry pytree,
    `moe_stats` (static) makes aux `(aux, moe.MoEStats or None)`."""
    out = forward_with_aux(params, tokens, positions, cfg, mesh,
                           segment_ids=segment_ids,
                           collect_stats=collect_stats, moe_stats=moe_stats)
    if collect_stats:
        logits, aux, stats = out
    else:
        logits, aux = out
    nll_sum = masked_nll_sum(logits, labels)
    if collect_stats:
        return nll_sum, aux, stats
    return nll_sum, aux


def masked_nll_sum(logits, labels):
    """Sum of the next-token cross entropy of fp32 `logits` [B, S, V] at the
    positions whose `labels` [B, S] are not negative (the objective's
    numerator: _loss_parts', and a caller's that holds its own logits)."""
    with jax.named_scope("obs.train.loss"):
        valid = labels >= 0
        labels_safe = jnp.where(valid, labels, 0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, labels_safe[..., None],
                                   axis=-1)[..., 0]
        return jnp.sum(jnp.where(valid, nll, 0.0))


def loss_fn(params, tokens, positions, labels, cfg: ModelConfig, mesh,
            moe_aux_weight: float = 0.0, segment_ids=None):
    """Mean next-token cross entropy (fp32) + weighted MoE aux loss.
    labels < 0 are masked out."""
    nll_sum, aux = _loss_parts(params, tokens, positions, labels, cfg, mesh,
                               segment_ids=segment_ids)
    ce = nll_sum / jnp.maximum(jnp.sum(labels >= 0), 1)
    return ce + moe_aux_weight * aux


def bd_stream(tokens, key, block: int, mask_id: int):
    """The block-diffusion stream of clean documents `tokens` [B, L]: each
    block of `block` tokens draws a rate t uniform in (0, 1] and each of its
    tokens becomes `mask_id` with probability t.  Returns
    (stream [B, 2L] = [noised; clean], positions [B, 2L] = 0..L-1 twice,
    weight [B, L] float32 = 1/t at masked positions, 0 elsewhere)."""
    b, length = tokens.shape
    if length % block:
        raise ValueError(f"document length {length} is not a multiple of "
                         f"the block length {block}")
    k_rate, k_mask = jax.random.split(key)
    rate = 1.0 - jax.random.uniform(k_rate, (b, length // block))
    rate = jnp.repeat(rate, block, axis=1)
    masked = jax.random.uniform(k_mask, (b, length)) < rate
    noised = jnp.where(masked, jnp.asarray(mask_id, tokens.dtype), tokens)
    pos = jnp.broadcast_to(jnp.arange(length, dtype=jnp.int32)[None],
                           (b, length))
    return (jnp.concatenate([noised, tokens], axis=1),
            jnp.concatenate([pos, pos], axis=1),
            jnp.where(masked, 1.0 / rate, 0.0))


def bd_mask_id(cfg: ModelConfig, tcfg: TrainConfig) -> int:
    return cfg.vocab - 1 if tcfg.bd_mask_id is None else tcfg.bd_mask_id


class BdExtras(NamedTuple):
    """What bd_loss_parts computed besides the objective's two terms."""
    logits: jax.Array      # [B, L, V], the noised half's
    nll_masked: jax.Array  # mean UNWEIGHTED nll over the masked positions
    moe: object            # moe.MoEStats folded over layers, or None


def bd_loss_parts(params, stream, positions, weight, cfg: ModelConfig, mesh):
    """(weighted nll summed over the noised half, MoE aux, BdExtras): the
    clean token's cross entropy at each masked position times its block's
    1/t.  The mean objective divides by B * L.  `nll_masked` is the same
    cross entropy without the weights: the 1/t weights make the objective
    of one step a heavy-tailed draw (a block at t near 0 counts 1/t times),
    the unweighted mean is what a loop can watch fall."""
    length = weight.shape[1]
    logits, (aux, moe_stats) = forward_with_aux(
        params, stream, positions, cfg, mesh, moe_stats=True,
        head_rows=(0, length))
    with jax.named_scope("obs.train.loss"):
        clean = stream[:, length:]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, clean[..., None], axis=-1)[..., 0]
        nll_sum = jnp.sum(weight * nll)
        masked = weight > 0
        nll_masked = (jnp.sum(jnp.where(masked, nll, 0.0))
                      / jnp.maximum(jnp.sum(masked), 1))
    return nll_sum, aux, BdExtras(logits, nll_masked, moe_stats)


def packed_fields(tokens, eos_id: int):
    """Derive packed-training fields from a [B, S] token stream in NATURAL
    order, where documents are delimited by `eos_id` (the EOS token belongs
    to the document it ends — the usual packing convention):

      segment_ids [B, S]  document index per token (monotone from 0)
      positions   [B, S]  rotary positions restarting at each document
      labels      [B, S]  next-token targets, -1 at document ends (the EOS
                          token never predicts the next document's first
                          token) and at the final position

    Feed tokens/labels/segment_ids through layouts.to_layout(axis=1) before
    a zigzag/striped ring; positions are already true positions and ride
    the same permutation."""
    b, s = tokens.shape
    is_eos = tokens == eos_id
    # token t's segment = number of EOS strictly before t
    seg = jnp.cumsum(is_eos.astype(jnp.int32), axis=1) - is_eos.astype(jnp.int32)
    idx = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    is_start = jnp.concatenate(
        [jnp.ones((b, 1), bool), seg[:, 1:] != seg[:, :-1]], axis=1)
    seg_start = lax.associative_scan(jnp.maximum,
                                     jnp.where(is_start, idx, 0), axis=1)
    positions = idx - seg_start
    nxt_same = jnp.concatenate(
        [seg[:, 1:] == seg[:, :-1], jnp.zeros((b, 1), bool)], axis=1)
    labels = jnp.where(
        nxt_same,
        jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1),
        -1,
    )
    return seg, positions, labels


def packed_fields_np(tokens, eos_id: int):
    """numpy twin of packed_fields for the HOST prefetch path: the loader
    thread derives packed fields without touching the device (an eager jax
    derivation would block on a device round-trip per batch, serializing
    against the in-flight train step)."""
    tokens = np.asarray(tokens)
    b, s = tokens.shape
    is_eos = tokens == eos_id
    seg = (np.cumsum(is_eos, axis=1) - is_eos).astype(np.int32)
    idx = np.broadcast_to(np.arange(s, dtype=np.int32)[None], (b, s))
    is_start = np.concatenate(
        [np.ones((b, 1), bool), seg[:, 1:] != seg[:, :-1]], axis=1)
    seg_start = np.maximum.accumulate(np.where(is_start, idx, 0), axis=1)
    positions = (idx - seg_start).astype(np.int32)
    nxt_same = np.concatenate(
        [seg[:, 1:] == seg[:, :-1], np.zeros((b, 1), bool)], axis=1)
    labels = np.where(
        nxt_same,
        np.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1),
        -1,
    ).astype(np.int32)
    return seg, positions, labels


def _hold_state_leaves(new, old):
    """`new` with every transformer.STATE_LEAVES leaf taken from `old`: model
    state rides the parameter tree (placement, checkpoints) and is no
    trained leaf.  No gradient reaches one (the forward reads it into a
    choice), and this keeps the weight decay off it too.  A tree without
    such leaves comes back as it is."""
    def hold(path, n, o):
        name = getattr(path[-1], "key", None)
        return o if name in STATE_LEAVES else n

    return jax.tree_util.tree_map_with_path(hold, new, old)


def jit_train_step(cfg: ModelConfig, tcfg: TrainConfig, mesh: Mesh):
    """The jitted step((params, opt_state), batch) -> (state, metrics)
    itself (state donated), for callers that lower or compile it.

    batch = dict(tokens, positions, labels), each [B, S] in layout order,
    sharded (dp, sp).  Under block diffusion `tokens` are the clean
    documents and the step makes positions and targets itself.

    Where the drop-free expert layer runs (transformer._moe_group), at
    grad_accum=1 without collect_devstats, metrics also hold
    `moe_slots_here` (the (token, expert) pairs this chip computed, all
    layers) and `moe_load_max_over_mean` (the fullest held expert over the
    mean one, worst layer); under block diffusion also `nll_masked`
    (bd_loss_parts).
    """
    opt = _optimizer(tcfg)
    aux_w = tcfg.moe_aux_weight if has_experts(cfg) else 0.0
    accum = tcfg.grad_accum
    collect = tcfg.collect_devstats
    if collect and accum != 1:
        raise ValueError(
            "collect_devstats supports grad_accum=1 only (per-microbatch "
            "stats inside the accumulation scan would need a scan-carried "
            "merge; fold it in when a run needs both)")

    def grad_of(params, batch):
        return jax.value_and_grad(loss_fn)(
            params, batch["tokens"], batch["positions"], batch["labels"], cfg,
            mesh, moe_aux_weight=aux_w,
            segment_ids=batch.get("segment_ids"),
        )

    def grad_of_stats(params, batch):
        # loss_fn's objective with the ring telemetry riding as has_aux;
        # gradients are bit-identical to grad_of (the stats custom_vjp
        # reuses the plain backward — burstlint devstats-pure)
        def scalar(params):
            nll_sum, aux, stats = _loss_parts(
                params, batch["tokens"], batch["positions"], batch["labels"],
                cfg, mesh, segment_ids=batch.get("segment_ids"),
                collect_stats=True)
            ce = nll_sum / jnp.maximum(jnp.sum(batch["labels"] >= 0), 1)
            return ce + aux_w * aux, stats

        (loss, stats), grads = jax.value_and_grad(scalar, has_aux=True)(params)
        return loss, stats, grads

    bd = cfg.block_diffusion
    if bd is not None and (collect or accum != 1):
        raise ValueError(
            "block diffusion supports grad_accum=1 without collect_devstats")
    # The drop-free expert layer's stats ride out of the plain step only:
    # under grad_accum > 1 or collect_devstats the step trains as before
    # and its metrics have no moe_* entries.
    held_moe = has_experts(cfg) and cfg.expert_axis is None \
        and cfg.pp_axis is None
    with_extras = bd is not None or (held_moe and accum == 1 and not collect)

    def grad_of_extras(params, batch, count):
        """The block-diffusion objective, or next-token loss on the
        drop-free expert layer, with {metric: value} riding out as
        has_aux."""
        def scalar(params):
            if bd is not None:
                key = jax.random.fold_in(jax.random.PRNGKey(tcfg.bd_seed),
                                         count)
                stream, positions, weight = bd_stream(
                    batch["tokens"], key, bd, bd_mask_id(cfg, tcfg))
                nll_sum, aux, out = bd_loss_parts(
                    params, stream, positions, weight, cfg, mesh)
                denom, moe_out = weight.size, out.moe
                extras = {"nll_masked": out.nll_masked}
            else:
                nll_sum, (aux, moe_out) = _loss_parts(
                    params, batch["tokens"], batch["positions"],
                    batch["labels"], cfg, mesh,
                    segment_ids=batch.get("segment_ids"), moe_stats=True)
                denom = jnp.maximum(jnp.sum(batch["labels"] >= 0), 1)
                extras = {}
            if moe_out is not None:
                extras.update(
                    moe_slots_here=moe_out.slots_here,
                    moe_load_max_over_mean=moe_out.load_max_over_mean)
            return nll_sum / denom + aux_w * aux, extras

        return jax.value_and_grad(scalar, has_aux=True)(params)

    def step(state, batch):
        params, opt_state = state
        extras = {}
        if with_extras:
            (loss, extras), grads = grad_of_extras(
                params, batch, optax.tree_utils.tree_get(opt_state, "count"))
        elif collect:
            loss, devstats_out, grads = grad_of_stats(params, batch)
        elif accum == 1:
            loss, grads = grad_of(params, batch)
        else:
            b0 = batch["tokens"].shape[0]
            if b0 % accum:
                raise ValueError(f"batch {b0} not divisible by grad_accum {accum}")
            if cfg.batch_axis is not None:
                dp = mesh.shape.get(cfg.batch_axis, 1)
                if (b0 // accum) % dp:
                    raise ValueError(
                        f"microbatch {b0 // accum} (batch {b0} / grad_accum "
                        f"{accum}) not divisible by {cfg.batch_axis!r} mesh "
                        f"size {dp}")
            # split the batch dim into `accum` microbatches inside ONE jit —
            # large effective batch, constant memory.  The masked mean is
            # normalized by the GLOBAL valid count (known upfront from the
            # labels alone), so uneven masking across microbatches yields
            # exactly the full-batch objective: the aux term is folded into
            # each microbatch scalar with weight v_total/accum so one grad
            # accumulation covers both pieces.
            v_total = jnp.maximum(
                jnp.sum(batch["labels"] >= 0).astype(jnp.float32), 1.0)
            mb = jax.tree.map(
                lambda a: a.reshape(accum, a.shape[0] // accum, *a.shape[1:]),
                batch,
            )

            def micro_scalar(params, micro):
                nll_sum, aux = _loss_parts(
                    params, micro["tokens"], micro["positions"],
                    micro["labels"], cfg, mesh,
                    segment_ids=micro.get("segment_ids"))
                return nll_sum + aux_w * aux * (v_total / accum)

            def body(carry, micro):
                s_c, grads_c = carry
                s, grads = jax.value_and_grad(micro_scalar)(params, micro)
                return (s_c + s, jax.tree.map(jnp.add, grads_c, grads)), None

            zeros = jax.tree.map(jnp.zeros_like, params)
            (s_sum, grads), _ = jax.lax.scan(body, (jnp.float32(0.0), zeros), mb)
            loss = s_sum / v_total
            grads = jax.tree.map(lambda g: g / v_total, grads)
        with jax.named_scope("obs.train.optimizer"):
            updates, opt_state = opt.update(grads, opt_state, params)
            params = _hold_state_leaves(
                optax.apply_updates(params, updates), params)
            gnorm = optax.global_norm(grads)
        metrics = {"loss": loss, "grad_norm": gnorm, **extras}
        if collect:
            metrics["devstats"] = devstats_out
        return (params, opt_state), metrics

    return jax.jit(step, donate_argnums=(0,))


def _count_compiles():
    """Register, once a process, the jax.monitoring listener behind
    `train.backend_compiles` (jax keeps listeners for the process's life)."""
    if _compile_listener:
        return

    def on_duration(event, seconds, **_):
        if event == _COMPILE_EVENT:
            _M_COMPILES.inc()

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    _compile_listener.append(on_duration)


def _nivcsw():
    """Involuntary context switches of the calling thread so far; None
    where the platform does not count them per thread.  One syscall."""
    if resource is None or not hasattr(resource, "RUSAGE_THREAD"):
        return None
    return resource.getrusage(resource.RUSAGE_THREAD).ru_nivcsw


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig, mesh: Mesh,
                    keep_executable: bool = False):
    """jit_train_step behind the host-side train-loop metrics: returns
    step((params, opt_state), batch) -> (state, metrics).

    `keep_executable`: the first call lowers and compiles the step for its
    arguments' shapes and shardings, every call runs that executable, and
    `step.executable()` returns it (None before the first call): its
    `memory_analysis()` and `as_text()` are then those of the program that
    ran, with no second compile (a step too large for the compile cache,
    ROADMAP S13, otherwise compiles once for each).  Later calls must keep
    the first call's shapes.

    Each call opens one `train.step` span (obs.begin) and closes the one the
    previous call opened, so a step's span runs dispatch to dispatch and is
    the parent of what the loop's thread does in between: `train.dispatch`
    (the jitted call), the `train.loader_wait` / `train.h2d` of the next
    `next(batches)`, the runner's log / eval / checkpoint spans.  Its self
    time is the wait for the device plus the caller's own code.  Closed, it
    carries `seq`, `wall_ns`, `loader_wait_s` / `h2d_s` / `dispatch_s` (sums
    of those children), `compiles`, `gc2`, and where the platform counts it
    `nivcsw` of the loop's thread (docs/observability.md "How to read a
    stall"), and where the drop-free expert layer ran, `moe.slots_here` and
    `moe.load_max_over_mean` from the step's metrics (also the counter and
    the gauge of those names).  Those two are read only if the step is done
    when its span closes: the loop is never made to wait for them.
    `train.step_interval_s` stays what it was, the time from one dispatch to
    the next, and leaves out the first, which holds the compile.

    `step.close()`, on the loop's thread, ends the open span and returns it
    (the runner does, after each step).  The last step's span is open as
    long as the step function lives, and spans entered on that thread
    meanwhile nest under it: close it when the loop is done, or drop the
    function, which takes the span with it unrecorded."""
    jit_step = jit_train_step(cfg, tcfg, mesh)
    kept = []  # [the step's executable] once keep_executable compiled it
    collect = tcfg.collect_devstats
    _count_compiles()
    open_step = []  # [live span, counters at its dispatch] while one is open
    moe_metrics = []  # the open step's [slots_here, load_max_over_mean]
    dispatched = []  # [perf_counter at the last dispatch]
    seq = itertools.count()
    # a platform counts these or it does not: asked once, not a step
    read_nivcsw = _nivcsw if _nivcsw() is not None else lambda: None

    def counters():
        return (_M_COMPILES.get(), gc.get_stats()[2]["collections"],
                read_nivcsw())

    def close(now=None):
        """End the open `train.step` span; its obs.Span, None if none."""
        if not open_step:
            return None
        live, (compiles0, gc0, nivcsw0) = open_step
        del open_step[:]
        compiles, gc2, nivcsw = now or counters()
        for attr, child in (("loader_wait_s", "train.loader_wait"),
                            ("h2d_s", "train.h2d"),
                            ("dispatch_s", "train.dispatch")):
            live.set(attr, live.child_s.get(child, 0.0))
        live.set("compiles", int(compiles - compiles0))
        live.set("gc2", gc2 - gc0)
        if nivcsw is not None:
            live.set("nivcsw", nivcsw - nivcsw0)
        if moe_metrics and all(x.is_ready() for x in moe_metrics):
            slots, load = (float(x) for x in jax.device_get(moe_metrics))
            live.set("moe.slots_here", slots)
            live.set("moe.load_max_over_mean", load)
            _M_MOE_SLOTS.inc(slots)
            _M_MOE_LOAD.set(load)
        del moe_metrics[:]
        # train.step_interval_s is the interval's one histogram
        return obs.end(live, observe=False)

    def guarded_step(state, batch):
        now = counters()  # one reading ends the last span and starts this
        close(now)
        n, t = next(seq), time.perf_counter()
        if n > 1:  # the first interval holds the compile
            _M_STEP_S.observe(t - dispatched[0])
        dispatched[:] = [t]
        open_step[:] = [obs.begin("train.step", seq=n,
                                  wall_ns=time.time_ns()), now]
        with obs.span("train.dispatch"):
            if keep_executable:
                if not kept:
                    kept.append(jit_step.lower(state, batch).compile())
                out = kept[0](state, batch)
            else:
                out = jit_step(state, batch)
        _M_STEPS.inc()
        if "moe_slots_here" in out[1]:
            moe_metrics[:] = [out[1]["moe_slots_here"],
                              out[1]["moe_load_max_over_mean"]]
        if collect:
            # fold the (tiny) device stats into the host registry AFTER the
            # dispatch; publish reads the arrays back, so this is the one
            # host<->device sync the knob buys.  Best effort: telemetry
            # must never be able to fail a train step.
            new_state, metrics = out
            stats = metrics.pop("devstats")
            try:
                stats.publish(labels={"source": "train"})
            except Exception as e:  # noqa: BLE001
                _M_EVENTS.inc(kind="devstats_publish_failure")
                logger.warning("devstats publish failed (%s: %s); step "
                               "continues without telemetry",
                               type(e).__name__, e)
            out = (new_state, metrics)
        return out

    guarded_step.close = close
    guarded_step.executable = lambda: kept[0] if kept else None
    return guarded_step


def train_step(state, batch, cfg: ModelConfig, tcfg: TrainConfig, mesh: Mesh):
    """Convenience one-shot (compiles per call; prefer make_train_step)."""
    step = make_train_step(cfg, tcfg, mesh)
    try:
        return step(state, batch)
    finally:
        step.close()


def batch_from_host(tokens, labels, cfg: ModelConfig, mesh: Mesh,
                    packed_eos_id=None):
    """Turn a host batch (e.g. from data.DataLoader: inputs/targets
    [B, S] int32 numpy, natural order) into the sharded, layout-permuted
    batch dict `make_train_step` consumes.

    Labels are shifted by the LOADER (targets = window[1:]), so here they
    only get the same layout permutation as tokens.

    `packed_eos_id`: treat the stream as EOS-delimited packed documents —
    positions restart per document, labels are re-derived with boundary
    masking, and segment_ids join the batch (attention isolation via
    forward(..., segment_ids)).  The loader's shifted labels are superseded
    in this mode (packed_fields recomputes them from tokens alone).

    Multi-process: `tokens`/`labels` are each process's LOCAL batch (e.g.
    its shard of the DataLoader stream); the global batch is assembled
    across processes, so the global batch size is local_B x the number of
    batch-sharding processes.  A plain device_put of local data against a
    cross-host sharding would silently drop most loaded rows.
    """
    tokens = np.asarray(tokens)
    labels = np.asarray(labels)
    b, s = tokens.shape
    world = int(np.prod([mesh.shape.get(a, 1) for a in cfg.seq_axes]))
    perm = layouts.seq_permutation(cfg.layout, s, world)
    seq_spec = cfg.seq_axes if len(cfg.seq_axes) > 1 else cfg.seq_axes[0]
    sharding = NamedSharding(mesh, P(cfg.batch_axis, seq_spec))
    if jax.process_count() > 1:
        put = partial(jax.make_array_from_process_local_data, sharding)
    else:
        put = partial(jax.device_put, device=sharding)
    if packed_eos_id is not None:
        seg, pos_packed, labels_packed = packed_fields_np(tokens, packed_eos_id)
        return {
            "tokens": put(np.ascontiguousarray(tokens[:, perm])),
            "positions": put(np.ascontiguousarray(pos_packed[:, perm])),
            "labels": put(np.ascontiguousarray(labels_packed[:, perm])),
            "segment_ids": put(np.ascontiguousarray(seg[:, perm])),
        }
    pos = np.ascontiguousarray(
        np.broadcast_to(np.asarray(perm, np.int32)[None, :], (b, s)))
    return {
        "tokens": put(np.ascontiguousarray(tokens[:, perm])),
        "positions": put(pos),
        "labels": put(np.ascontiguousarray(labels[:, perm])),
    }


def prefetch_batches(dl, cfg: ModelConfig, mesh: Mesh, depth: int = 2,
                     packed_eos_id=None):
    """Generator keeping `depth` device batches in flight: host->device
    transfer of batch N+1..N+depth overlaps the step running on batch N
    (device_put is async; the loader's worker threads fill the windows).
    `dl` is a data.DataLoader (or any (inputs, targets) iterator).
    `packed_eos_id`: see batch_from_host — packed-document training."""
    from collections import deque

    q = deque()
    it = iter(dl)
    mk = partial(batch_from_host, cfg=cfg, mesh=mesh,
                 packed_eos_id=packed_eos_id)

    def fetch():
        """Queue the next device batch, its two host phases each in a span
        (children of the open `train.step` when the loop's thread asks)."""
        with obs.span("train.loader_wait"):
            x, y = next(it)
        with obs.span("train.h2d"):
            q.append(mk(x, y))

    try:
        for _ in range(depth):
            fetch()
        while True:
            fetch()
            yield q.popleft()
    except StopIteration:
        pass  # the source ran out, perhaps before the queue was full
    while q:  # finite iterator: drain what is already in flight
        yield q.popleft()


def make_packed_batch(key, cfg: ModelConfig, mesh: Mesh, batch: int, seq: int,
                      eos_id: int = 0):
    """Synthetic PACKED LM batch: random tokens with EOS delimiters sprinkled
    in, fields derived by packed_fields, everything permuted into layout
    order and placed with (dp, sp) sharding."""
    world = int(np.prod([mesh.shape.get(a, 1) for a in cfg.seq_axes]))
    k1, k2 = jax.random.split(key)
    tokens = jax.random.randint(k1, (batch, seq), 0, cfg.vocab, dtype=jnp.int32)
    # ~4 documents per row on average
    eos_mask = jax.random.bernoulli(k2, 4.0 / seq, (batch, seq))
    tokens = jnp.where(eos_mask, eos_id, jnp.maximum(tokens, 1))
    seg, positions, labels = packed_fields(tokens, eos_id)
    to_l = lambda a: layouts.to_layout(a, cfg.layout, world, axis=1)
    seq_spec = cfg.seq_axes if len(cfg.seq_axes) > 1 else cfg.seq_axes[0]
    sharding = NamedSharding(mesh, P(cfg.batch_axis, seq_spec))
    return {
        "tokens": jax.device_put(to_l(tokens), sharding),
        "positions": jax.device_put(to_l(positions), sharding),
        "labels": jax.device_put(to_l(labels), sharding),
        "segment_ids": jax.device_put(to_l(seg), sharding),
    }


def make_batch(key, cfg: ModelConfig, mesh: Mesh, batch: int, seq: int):
    """Synthetic LM batch in layout order, placed with (dp, sp) sharding."""
    world = int(np.prod([mesh.shape.get(a, 1) for a in cfg.seq_axes]))
    tokens = jax.random.randint(key, (batch, seq), 0, cfg.vocab, dtype=jnp.int32)
    labels = jnp.concatenate(
        [tokens[:, 1:], jnp.full((batch, 1), -1, jnp.int32)], axis=1
    )
    pos = jnp.asarray(layouts.seq_permutation(cfg.layout, seq, world), jnp.int32)
    positions = jnp.broadcast_to(pos[None, :], (batch, seq))
    tokens_l = layouts.to_layout(tokens, cfg.layout, world, axis=1)
    labels_l = layouts.to_layout(labels, cfg.layout, world, axis=1)
    seq_spec = cfg.seq_axes if len(cfg.seq_axes) > 1 else cfg.seq_axes[0]
    sharding = NamedSharding(mesh, P(cfg.batch_axis, seq_spec))
    return {
        "tokens": jax.device_put(tokens_l, sharding),
        "positions": jax.device_put(positions, sharding),
        "labels": jax.device_put(labels_l, sharding),
    }
