"""Training loop machinery for the flagship LM: mesh building, sharded state
init, and a jitted train step over a (dp, sp[, inter], tp) mesh.

This is the end-to-end integration layer the reference delegates to host
frameworks (BMTrain; reference README.md:36-38) — here it is in-framework and
TPU-native: one `jax.jit` whose input/output shardings come from the model's
PartitionSpec tree; XLA inserts the DP grad psums and megatron TP collectives,
while burst_attn's shard_map runs the sequence ring over `sp` (and the
hierarchical double ring when an `inter` axis is present).

Loss convention: next-token cross entropy.  `tokens` and `labels` arrive
already layout-permuted (parallel/layouts.to_layout on axis=1) with `labels`
shifted BEFORE the permutation — shifting after would cross shard boundaries.
`positions` carries true global positions for rotary (layouts.position_ids).
"""

import gc
import itertools
import time
from dataclasses import dataclass
from functools import partial
from typing import Any, Optional, Tuple

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
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_listener = []  # [listener] once registered: one per process

try:
    import resource
except ImportError:  # no such module off POSIX: the attr is then absent
    resource = None

from .transformer import ModelConfig, forward, forward_with_aux, init_params, param_specs
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
                segment_ids=None, collect_stats=False):
    """(sum of masked nll, MoE aux[, DevStats]) — the linear pieces of the
    objective; `collect_stats` (static) appends the ring telemetry pytree."""
    out = forward_with_aux(params, tokens, positions, cfg, mesh,
                           segment_ids=segment_ids,
                           collect_stats=collect_stats)
    if collect_stats:
        logits, aux, stats = out
    else:
        logits, aux = out
    with jax.named_scope("obs.train.loss"):
        valid = labels >= 0
        labels_safe = jnp.where(valid, labels, 0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, labels_safe[..., None],
                                   axis=-1)[..., 0]
        nll_sum = jnp.sum(jnp.where(valid, nll, 0.0))
    if collect_stats:
        return nll_sum, aux, stats
    return nll_sum, aux


def loss_fn(params, tokens, positions, labels, cfg: ModelConfig, mesh,
            moe_aux_weight: float = 0.0, segment_ids=None):
    """Mean next-token cross entropy (fp32) + weighted MoE aux loss.
    labels < 0 are masked out."""
    nll_sum, aux = _loss_parts(params, tokens, positions, labels, cfg, mesh,
                               segment_ids=segment_ids)
    ce = nll_sum / jnp.maximum(jnp.sum(labels >= 0), 1)
    return ce + moe_aux_weight * aux


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


def jit_train_step(cfg: ModelConfig, tcfg: TrainConfig, mesh: Mesh):
    """The jitted step((params, opt_state), batch) -> (state, metrics)
    itself (state donated), for callers that lower or compile it.

    batch = dict(tokens, positions, labels), each [B, S] in layout order,
    sharded (dp, sp).
    """
    opt = _optimizer(tcfg)
    aux_w = tcfg.moe_aux_weight if cfg.n_experts else 0.0
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

    def step(state, batch):
        params, opt_state = state
        if collect:
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
            params = optax.apply_updates(params, updates)
            gnorm = optax.global_norm(grads)
        metrics = {"loss": loss, "grad_norm": gnorm}
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


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig, mesh: Mesh):
    """jit_train_step behind the host-side train-loop metrics: returns
    step((params, opt_state), batch) -> (state, metrics).

    Each call opens one `train.step` span (obs.begin) and closes the one the
    previous call opened, so a step's span runs dispatch to dispatch and is
    the parent of what the loop's thread does in between: `train.dispatch`
    (the jitted call), the `train.loader_wait` / `train.h2d` of the next
    `next(batches)`, the runner's log / eval / checkpoint spans.  Its self
    time is the wait for the device plus the caller's own code.  Closed, it
    carries `seq`, `wall_ns`, `loader_wait_s` / `h2d_s` / `dispatch_s` (sums
    of those children), `compiles`, `gc2`, and where the platform counts it
    `nivcsw` of the loop's thread (docs/observability.md "How to read a
    stall").  `train.step_interval_s` stays what it was, the time from
    one dispatch to the next, and leaves out the first, which holds the
    compile.

    `step.close()`, on the loop's thread, ends the open span and returns it
    (the runner does, after each step).  The last step's span is open as
    long as the step function lives, and spans entered on that thread
    meanwhile nest under it: close it when the loop is done, or drop the
    function, which takes the span with it unrecorded."""
    jit_step = jit_train_step(cfg, tcfg, mesh)
    collect = tcfg.collect_devstats
    _count_compiles()
    open_step = []  # [live span, counters at its dispatch] while one is open
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
            out = jit_step(state, batch)
        _M_STEPS.inc()
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
