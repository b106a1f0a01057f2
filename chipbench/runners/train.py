"""The trainer cells: the calls `models/runner.fit` makes, in its order
(`init_train_state`, `make_train_step`, `DataLoader`, `prefetch_batches`,
block on the new state each step), in a loop that stops on the clock.
`fit` itself takes a step count and returns no time stamps, so a cell
cannot drive it yet (PERF.md, "for the tracing issue")."""

import contextlib
import importlib
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from burst_attn_tpu.data import DataLoader
from burst_attn_tpu.models.train import (
    TrainConfig, batch_from_host, init_train_state, jit_train_step, loss_fn,
    make_mesh, make_train_step, prefetch_batches,
)
from burst_attn_tpu.models.transformer import ModelConfig, forward
from burst_attn_tpu.parallel import layouts

from .. import harness, traffic


def model_config(model):
    """The configuration file's keys (the source's names) as the program's
    ModelConfig; everything not named keeps the trainer's default."""
    return ModelConfig(
        vocab=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], d_head=model["head_dim"],
        d_ff=model["intermediate_size"], rope_theta=model["rope_theta"],
        seq_axes=("sp",), batch_axis=None, head_axis=None)


def _fingerprint(params):
    """Sum of squares of every parameter leaf, in float32, on the host."""
    sq = jax.jit(lambda p: jax.tree.map(
        lambda x: jnp.sum(jnp.square(x.astype(jnp.float32))), p))(params)
    return [float(x) for x in jax.tree.leaves(sq)]


class Session:
    def __init__(self, ctx, stack):
        model, mix = ctx.cell["config"], ctx.cell["traffic"]
        self.reference = importlib.import_module(
            f"chipbench.references.{model['reference']}")
        self.cfg = cfg = model_config(model)
        tcfg = TrainConfig()
        self.mesh = mesh = make_mesh({"sp": mix["sp"]},
                                     devices=ctx.devices[:mix["sp"]])
        self.checks, self.detail = {}, {}
        batch, seq = mix["batch"], mix["seq"]

        tmp = stack.enter_context(tempfile.TemporaryDirectory(
            dir=ctx.out_dir))
        data = os.path.join(tmp, "tokens.batd")
        traffic.write_token_file(data, ctx.seed, **mix)
        loader = dict(shard_id=0, num_shards=1, seed=ctx.seed, num_threads=2)

        # the compiler's count for the timed program, from shapes alone;
        # this compile is the one the step's first call then finds cached
        self.state = init_train_state(jax.random.PRNGKey(ctx.seed), cfg,
                                      tcfg, mesh)
        with DataLoader(data, batch, seq, **loader) as dl:
            first_x, first_y = dl.next()
        ctx.mark("state_and_data")
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding),
            (self.state, batch_from_host(first_x, first_y, cfg, mesh)))
        self.program_bytes = harness.program_bytes(
            jit_train_step(cfg, tcfg, mesh).lower(*shapes).compile())
        ctx.mark("step_program")

        self._against_reference(model, first_x, first_y, mix["check_seq"])
        ctx.mark("reference_check")
        self.before = _fingerprint(self.state[0])
        self.n_params = sum(x.size for x in jax.tree.leaves(self.state[0]))
        self.detail.update(n_params=self.n_params, batch=batch, seq=seq)

        self.step_fn = make_train_step(cfg, tcfg, mesh)
        dl = stack.enter_context(DataLoader(data, batch, seq, **loader))
        self.batches = prefetch_batches(dl, cfg, mesh)
        self.tokens_per_step = batch * seq
        self.losses = []

    def _against_reference(self, model, x, y, seq):
        """The system's logits and loss on the first `seq` tokens of the
        first sequence, seeded weights, against the plain float32 forward
        of the same weights on the same tokens."""
        x, y = x[:1, :seq], y[:1, :seq]
        cfg, mesh = self.cfg, self.mesh
        world = mesh.devices.size

        def system(params, batch):
            logits = forward(params, batch["tokens"], batch["positions"],
                             cfg, mesh)
            loss = loss_fn(params, batch["tokens"], batch["positions"],
                           batch["labels"], cfg, mesh)
            return layouts.from_layout(logits, cfg.layout, world, axis=1), loss

        got = jax.jit(system)(self.state[0],
                              batch_from_host(x, y, cfg, mesh))
        want = self.reference.reference(
            self.state[0], jnp.asarray(x), jnp.asarray(y),
            rope_theta=model["rope_theta"],
            rms_norm_eps=model["rms_norm_eps"])
        errs, ok = self.reference.compare(*got, *want)
        self.checks["matches_reference"] = ok
        self.detail.update(check_seq=seq, reference_errors=errs,
                           first_loss=float(got[1]),
                           reference_loss=float(want[1]))

    def step(self, span):
        with span("bench.next_batch"):
            batch = next(self.batches)
        with span("bench.dispatch"):
            self.state, metrics = self.step_fn(self.state, batch)
        with span("bench.block"):
            jax.block_until_ready(self.state)
        self.losses.append(metrics["loss"])

    def finish(self, first, last):
        losses = [float(x) for x in self.losses[first:last]]
        bad = int(np.sum(~np.isfinite(losses)))
        after = _fingerprint(self.state[0])
        moved = sum(a != b for a, b in zip(self.before, after))
        self.checks.update(loss_finite=bad == 0,
                           loss_fell=losses[-1] < losses[0],
                           params_changed=moved == len(self.before))
        self.detail.update(losses=losses,
                           leaves_changed=[moved, len(self.before)])
        return bad


def run(ctx):
    with contextlib.ExitStack() as stack:
        return harness.measure_steps(Session(ctx, stack), ctx)
