"""End-to-end training runner + CLI: the glue that makes the framework a
trainer, not an op library.

Ties together the subsystems the reference delegates to host frameworks
(reference README.md:36-38): the native data loader (data/loader.py), the
sharded train step (models/train.py), orbax checkpointing
(utils/checkpoint.py), step timing + metrics (burst_attn_tpu.obs), and
rank-0 logging (utils/log_helper.py; handlers via the obs logger).  Resume is exact: the checkpoint step repositions the
deterministic loader with `seek(step)`, so the token stream continues as if
the run never stopped.

CLI:
    python -m burst_attn_tpu.models.runner --data tokens.batd --steps 100 \
        --mesh dp=2,sp=2,tp=2 --d-model 256 --n-layers 2 --seq-len 1024
"""

import argparse
import json
import time
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import numpy as np

from .train import (
    TrainConfig, batch_from_host, init_train_state, make_mesh, make_train_step,
    prefetch_batches,
)
from .transformer import ModelConfig
from .. import obs
from ..data import DataLoader
from ..obs import get_logger
from ..utils import log_helper
from ..utils.compile_cache import place_compile_cache


@dataclass(frozen=True)
class RunConfig:
    """One training run: data, duration, checkpointing cadence."""

    data_path: str
    steps: int
    batch: int
    seq_len: int
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 500
    log_every: int = 10
    seed: int = 0
    loader_threads: int = 2
    eval_data_path: Optional[str] = None
    eval_every: int = 500
    eval_batches: int = 16
    # packed-document training: EOS token id delimiting documents in the
    # token stream (None = plain contiguous LM crops)
    packed_eos_id: Optional[int] = None


def fit(cfg: ModelConfig, tcfg: TrainConfig, run: RunConfig, mesh,
        on_step: Optional[Callable[[dict], Optional[bool]]] = None):
    """Train for run.steps, checkpointing and resuming as configured.

    Returns (state, history) where history is a list of {step, loss, ...}
    dicts (rank-0 view).

    `on_step(record)` is called after every step with that step's closed
    `train.step` span as a dict (obs.Span.record(): `duration_s`, and under
    `attrs` what make_train_step documents) plus `step` (1-based, as in
    history) and `blocked_s` (seconds from the step's dispatch until its
    outputs were ready; fit is what blocks).  Returning False ends the run
    after that step, so a caller can bound a run by the clock.
    """
    log = get_logger("runner")
    primary = log_helper.is_primary()
    ckpt = None
    state, start_step = None, 0
    if run.ckpt_dir:
        from ..utils.checkpoint import Checkpointer

        ckpt = Checkpointer(run.ckpt_dir)
        state, restored = ckpt.restore_latest(cfg, tcfg, mesh)
        if restored is not None:
            start_step = restored
            if primary:
                log.info("resumed from step %d", start_step)
    if state is None:
        state = init_train_state(jax.random.PRNGKey(run.seed), cfg, tcfg, mesh)

    step_fn = make_train_step(cfg, tcfg, mesh)
    blocked = []  # seconds, one a step
    history = []

    evaluator = None
    if run.eval_data_path:
        from .evaluate import Evaluator

        evaluator = Evaluator(
            cfg, mesh, run.eval_data_path, batch=run.batch,
            seq_len=run.seq_len, max_batches=run.eval_batches,
            packed_eos_id=run.packed_eos_id,
        )

    def maybe_eval(step):
        if evaluator is None:
            return
        if (step + 1) % run.eval_every and step + 1 != run.steps:
            return
        with obs.span("train.eval", step=step + 1):
            metrics = evaluator(state[0])
        row = {"step": step + 1, **{k: round(v, 4) for k, v in metrics.items()}}
        history.append(row)
        if primary:
            log.info("%s", json.dumps(row))
    try:
        with DataLoader(
            run.data_path, run.batch, run.seq_len,
            shard_id=jax.process_index(), num_shards=jax.process_count(),
            seed=run.seed, num_threads=run.loader_threads,
        ) as dl:
            if start_step:
                dl.seek(start_step)
            batches = prefetch_batches(dl, cfg, mesh,
                                       packed_eos_id=run.packed_eos_id)
            # the batch of step N+1 is asked for inside step N's span, so
            # that its loader wait and transfer are that span's children
            batch = next(batches) if start_step < run.steps else None
            for step in range(start_step, run.steps):
                last = step + 1 == run.steps
                t0 = time.perf_counter()
                state, metrics = step_fn(state, batch)
                jax.block_until_ready(state)
                blocked.append(time.perf_counter() - t0)
                if (step + 1) % run.log_every == 0 or last:
                    with obs.span("train.log", step=step + 1):
                        row = {
                            "step": step + 1,
                            "loss": float(metrics["loss"]),
                            "grad_norm": float(metrics["grad_norm"]),
                            "step_s": blocked[-1],
                        }
                        history.append(row)
                        if primary:
                            log.info("%s", json.dumps(row))
                maybe_eval(step)
                if ckpt and ((step + 1) % run.ckpt_every == 0 or last):
                    with obs.span("train.ckpt_save", step=step + 1):
                        ckpt.save(step + 1, state)
                if not last:
                    batch = next(batches)
                record = step_fn.close().record()
                if on_step is not None and on_step(
                        {**record, "step": step + 1,
                         "blocked_s": blocked[-1]}) is False:
                    break
    finally:
        step_fn.close()  # a step an exception cut short still gets its span
        # flush the async orbax save even on an exception mid-run — the
        # crash case is exactly when the newest checkpoint matters
        if ckpt:
            ckpt.close()
        if evaluator is not None:
            evaluator.close()
    steady = blocked[1:] or blocked  # the first step compiles
    if steady and primary:
        log.info("done: %d steps, mean %.3fs/step", len(steady),
                 sum(steady) / len(steady))
    # BURST_OBS_EXPORT=<path>: drop the run's full metric/span state as an
    # obs JSONL export (readable with `python -m burst_attn_tpu.obs`)
    import os

    export_path = os.environ.get("BURST_OBS_EXPORT")
    if export_path:
        obs.export_jsonl(export_path)
        if primary:
            log.info("obs export written to %s", export_path)
    return state, history


def _parse_mesh(spec: str) -> dict:
    """"dp=2,sp=2,tp=2" -> {"dp": 2, "sp": 2, "tp": 2} (order preserved)."""
    out = {}
    for part in spec.split(","):
        name, _, size = part.partition("=")
        if not size:
            raise ValueError(f"bad mesh spec {spec!r}; want e.g. dp=2,sp=4")
        out[name.strip()] = int(size)
    return out


def parse_args(argv=None):
    """The CLI's arguments as fit()'s (cfg, tcfg, run, mesh)."""
    p = argparse.ArgumentParser(description="Train the flagship LM on a token file.")
    p.add_argument("--data", required=True, help="BATD token file (data.write_token_file)")
    p.add_argument("--steps", type=int, required=True)
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--seq-len", type=int, default=4096)
    p.add_argument("--mesh", default="sp=1", help="e.g. dp=2,sp=2,tp=2")
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=500)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--eval-data", default=None,
                   help="held-out BATD token file (perplexity eval)")
    p.add_argument("--eval-every", type=int, default=500)
    p.add_argument("--eval-batches", type=int, default=16)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--grad-accum", type=int, default=1)
    p.add_argument("--vocab", type=int, default=32768)
    p.add_argument("--d-model", type=int, default=1024)
    p.add_argument("--n-layers", type=int, default=8)
    p.add_argument("--n-heads", type=int, default=8)
    p.add_argument("--n-kv-heads", type=int, default=None)
    p.add_argument("--d-ff", type=int, default=None)
    p.add_argument("--layout", default="zigzag")
    p.add_argument("--n-experts", type=int, default=0,
                   help="MoE experts per layer (0 = dense MLP)")
    p.add_argument("--microbatches", type=int, default=None,
                   help="GPipe microbatches for a pp= mesh (default: pp size)")
    p.add_argument("--no-remat", action="store_true")
    p.add_argument("--packed-eos", type=int, default=None,
                   help="EOS token id delimiting packed documents: positions "
                        "restart per document, loss masks boundaries, and "
                        "attention never crosses them (segment_ids)")
    p.add_argument("--multihost", action="store_true",
                   help="call multihost.initialize() before touching jax")
    args = p.parse_args(argv)

    if args.multihost:
        from ..utils import multihost

        multihost.initialize()

    mesh_axes = _parse_mesh(args.mesh)
    # a double-ring mesh (inter, intra) maps straight onto seq_axes; any
    # other mesh uses a (possibly trivial) "sp" ring — auto-append sp=1 so
    # e.g. --mesh dp=8 works instead of dying on a missing axis
    if "inter" in mesh_axes and "intra" in mesh_axes:
        seq_axes = ("inter", "intra")
    else:
        seq_axes = ("sp",)
        mesh_axes.setdefault("sp", 1)
    mesh = make_mesh(mesh_axes)
    n_heads = args.n_heads
    # experts shard over a dedicated "ep" axis when the mesh has one, else
    # ride the dp axis (the classic GShard data+expert layout)
    expert_axis = None
    if args.n_experts:
        expert_axis = "ep" if "ep" in mesh_axes else (
            "dp" if "dp" in mesh_axes else None)
    # a pp= axis turns on the pipeline-parallel forward (pipeline_lm.py);
    # microbatches default to the stage count (the GPipe sweet spot floor)
    pp_axis = "pp" if "pp" in mesh_axes else None
    if args.microbatches and not pp_axis:
        raise SystemExit("--microbatches requires a pp= axis in --mesh")
    cfg = ModelConfig(
        seq_axes=seq_axes,
        batch_axis="dp" if "dp" in mesh_axes else None,
        head_axis="tp" if "tp" in mesh_axes else None,
        pp_axis=pp_axis,
        pp_microbatches=(args.microbatches or mesh_axes.get("pp", 1))
        if pp_axis else 1,
        n_experts=args.n_experts,
        expert_axis=expert_axis,
        vocab=args.vocab,
        d_model=args.d_model,
        n_layers=args.n_layers,
        n_heads=n_heads,
        n_kv_heads=args.n_kv_heads or n_heads,
        d_head=args.d_model // n_heads,
        d_ff=args.d_ff or 4 * args.d_model,
        layout=args.layout,
        remat=not args.no_remat,
    )
    tcfg = TrainConfig(lr=args.lr, grad_accum=args.grad_accum)
    run = RunConfig(
        data_path=args.data, steps=args.steps, batch=args.batch,
        seq_len=args.seq_len, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, log_every=args.log_every, seed=args.seed,
        eval_data_path=args.eval_data, eval_every=args.eval_every,
        eval_batches=args.eval_batches, packed_eos_id=args.packed_eos,
    )
    return cfg, tcfg, run, mesh


def main(argv=None):
    """Train as the CLI's arguments say; returns fit()'s (state, history)."""
    place_compile_cache()
    return fit(*parse_args(argv))


if __name__ == "__main__":
    main()
