"""The latent-attention MoE trainer cells: `runners/train_bd_moe.py`'s session
for the plain causal objective over a stack with a layer pattern (latent
attention, a leading dense layer, sparse layers with a sigmoid router, a
choice-only bias and shared experts).  The same calls in the same order
(`init_train_state`, `make_train_step`, `DataLoader`, `prefetch_batches`,
block on the new state each step), the same spans.  What does not differ is
that module's own (`step_text` and its `_RAN`, `check_layer`, the session's
`program_bytes`); a session base for every trainer runner is queued for the
next `benchmark` issue (PERF.md section 7 l).

    python3 -m chipbench.runners.train_mla_moe --seed <n>

prints the reading the reference's bounds are set against: the reference
with every activation rounded through an 8-bit float, held to the float32
reference (references/mla_moe_lm.py; not part of a cell's run).
"""

import contextlib
import importlib
import os
import re
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from burst_attn_tpu.data import DataLoader
from burst_attn_tpu.models import train, transformer
from burst_attn_tpu.models.train import TrainConfig
from burst_attn_tpu.models.transformer import (
    DenseMLP, ExpertMLP, LatentAttn, LayerSpec, ModelConfig,
)

from .. import harness, traffic
from . import train_bd_moe
from .train_bd_moe import _RAN, check_layer, step_text  # noqa: F401


def model_config(model):
    """The configuration file's keys (the source's names, and the cut's) as
    the program's ModelConfig: the layer pattern from the source's
    `first_k_dense_replace`; everything not named keeps the trainer's
    default."""
    if not (model["norm_topk_prob"] and model["n_group"] == 1
            and model["topk_group"] == 1 and model["q_lora_rank"] is None
            and model["rope_interleave"]):
        raise ValueError("the program renormalises the chosen gates, has no "
                         "expert groups, projects q at full rank and pairs "
                         "the rotary channels interleaved")
    attn = LatentAttn(
        kv_latent=model["kv_lora_rank"], qk_nope=model["qk_nope_head_dim"],
        qk_rope=model["qk_rope_head_dim"], v_head=model["v_head_dim"])
    width = model["moe_intermediate_size"]
    sparse = ExpertMLP(
        d_ff=width, n_experts=model["router_outputs"],
        top_k=model["num_experts_per_tok"],
        held=tuple(model["experts_held"]), score=model["scoring_func"],
        # noaux_tc: the choice is steered by e_score_correction_bias
        choice_bias=model["topk_method"] == "noaux_tc",
        gate_scale=model["routed_scaling_factor"],
        shared_ff=model["n_shared_experts"] * width)
    dense = model["first_k_dense_replace"]
    layers = model["num_hidden_layers"]
    pattern = ((LayerSpec(DenseMLP(model["intermediate_size"]), attn),) * dense
               + (LayerSpec(sparse, attn),) * (layers - dense))
    return ModelConfig(
        vocab=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=layers, n_heads=model["num_attention_heads"],
        rope_theta=model["rope_theta"], pattern=pattern,
        seq_axes=("sp",), batch_axis=None, head_axis=None)


def train_config(model):
    return TrainConfig(moe_aux_weight=model["router_aux_loss_coef"])


def reference_keywords(model):
    return dict(held=tuple(model["experts_held"]),
                top_k=model["num_experts_per_tok"],
                gate_scale=model["routed_scaling_factor"],
                qk_nope=model["qk_nope_head_dim"],
                kv_latent=model["kv_lora_rank"],
                rope_theta=model["rope_theta"],
                rms_norm_eps=model["rms_norm_eps"])


def system_outputs(params, batch, cfg, mesh):
    """What the program makes of the check's tokens, in ONE program through
    the trainer's own functions: {logits, loss, every sparse layer's chosen
    expert sets [sparse layers, B, S, k], the loss's gradient by
    check_layer's parameters}."""
    layer = check_layer(cfg)

    def scalar(part):
        layers = list(params["layers"])
        layers[layer] = part
        logits, (_, moe) = transformer.forward_with_aux(
            {**params, "layers": layers}, batch["tokens"], batch["positions"],
            cfg, mesh, moe_stats=True)
        labels = batch["labels"]
        value = train.masked_nll_sum(logits, labels) / jnp.maximum(
            jnp.sum(labels >= 0), 1)
        return value, {"logits": logits, "loss": value, "chosen": moe.choice}

    (_, out), grads = jax.value_and_grad(scalar, has_aux=True)(
        params["layers"][layer])
    return {**out, "grads": grads}


# The routers' biases before training: the family's own balancing rule
# (`topk_method: noaux_tc`: a bias rises where its expert's load is under the
# mean and falls where it is over, by a fixed step), run on the first batch
# at the seeded weights with a step that shrinks, then HELD.  On the WHOLE
# batch, by a forward program of its own: biases balanced on the check's
# 4,096 tokens do not balance 16,384 (a token's router input depends on how
# many tokens its causal mean runs over: PERF.md section 6, PR 34).
BALANCE_PASSES, BALANCE_STEP, BALANCE_DECAY = 40, 0.05, 0.9


def choices_of(batch, cfg, mesh):
    """params -> every sparse layer's chosen expert sets on `batch`
    [sparse layers, B, S, k], by the trainer's own forward: one program."""
    chosen = jax.jit(lambda params, batch: transformer.forward_with_aux(
        params, batch["tokens"], batch["positions"], cfg, mesh,
        moe_stats=True)[1][1].choice)
    return lambda params: chosen(params, batch)


def balance_biases(params, chosen_of, cfg):
    """`params` with every sparse layer's `router_bias` set so that the
    choices `chosen_of(params)` [sparse layers, ..., k] are balanced over ALL
    the router's experts (b += step * sign(mean load - load), BALANCE_PASSES
    passes), and what the last pass read: each layer's share of choices on
    the held experts and its fullest expert over the mean one.  At seeded
    weights 40 % of a router input's power is common to all tokens and a
    few experts would take most of them (PERF.md section 6, PR 34): a
    trained model's bias is what evens that out, and a share's load would
    else be the luck of which experts those are."""
    sparse = [i for i, spec in enumerate(cfg.pattern)
              if isinstance(spec.mlp, ExpertMLP)]
    held = cfg.pattern[sparse[0]].mlp.held
    for n in range(BALANCE_PASSES + 1):
        loads = [np.bincount(row.reshape(-1),
                             minlength=params["layers"][i]["router_bias"].size)
                 for row, i in zip(np.asarray(chosen_of(params)), sparse)]
        if n == BALANCE_PASSES:
            break
        layers = list(params["layers"])
        for load, i in zip(loads, sparse):
            bias = layers[i]["router_bias"]
            new = np.asarray(bias) + BALANCE_STEP * BALANCE_DECAY ** n * (
                np.sign(load.mean() - load))
            layers[i] = {**layers[i], "router_bias": jax.device_put(
                new.astype(np.float32), bias.sharding)}
        params = {**params, "layers": layers}
    return params, {
        "held_share": [float(load[slice(*held)].sum() / load.sum())
                       for load in loads],
        "load_max_over_mean": [float(load.max() / load.mean())
                               for load in loads]}


def _fingerprint(params):
    """{leaf's path: sum of its squares, float32, on the host}."""
    sq = jax.jit(lambda p: jax.tree.map(
        lambda x: jnp.sum(jnp.square(x.astype(jnp.float32))), p))(params)
    return {jax.tree_util.keystr(path): float(x)
            for path, x in jax.tree_util.tree_flatten_with_path(sq)[0]}


def _is_state(path):
    return any(f"'{name}'" in path for name in transformer.STATE_LEAVES)


class Session(train_bd_moe.Session):
    """train_bd_moe.Session's `program_bytes`; the rest is this objective's."""

    def __init__(self, ctx, stack):
        model, mix = ctx.cell["config"], ctx.cell["traffic"]
        self.reference = importlib.import_module(
            f"chipbench.references.{model['reference']}")
        self.cfg = cfg = model_config(model)
        self.tcfg = tcfg = train_config(model)
        self.mesh = mesh = train.make_mesh({"sp": mix["sp"]},
                                           devices=ctx.devices[:mix["sp"]])
        self.checks, self.detail = {}, {}
        batch, seq = mix["batch"], mix["seq"]

        tmp = stack.enter_context(tempfile.TemporaryDirectory(
            dir=ctx.out_dir))
        data = os.path.join(tmp, "tokens.batd")
        traffic.write_token_file(data, ctx.seed, **mix)
        loader = dict(shard_id=0, num_shards=1, seed=ctx.seed, num_threads=2)

        self.state = train.init_train_state(jax.random.PRNGKey(ctx.seed),
                                            cfg, tcfg, mesh)
        with DataLoader(data, batch, seq, **loader) as dl:
            first_x, first_y = dl.next()
        ctx.mark("state_and_data")
        params, balance = balance_biases(self.state[0], choices_of(
            train.batch_from_host(first_x, first_y, cfg, mesh), cfg, mesh),
            cfg)
        self.state = (params, self.state[1])
        self.detail.update(bias_balance=balance)
        ctx.mark("bias_balance")

        self._against_reference(model, first_x, first_y, mix["check_seq"])
        ctx.mark("reference_check")
        self.before = _fingerprint(self.state[0])
        self.n_params = sum(x.size for x in jax.tree.leaves(self.state[0]))
        self.detail.update(n_params=self.n_params, batch=batch, seq=seq)

        # compiled once, by its first call, and kept, so that its size and
        # its text are read off the object that runs (train_bd_moe.py)
        self.name = ctx.cell["name"]
        self.step_fn = train.make_train_step(cfg, tcfg, mesh,
                                             keep_executable=True)
        dl = stack.enter_context(DataLoader(data, batch, seq, **loader))
        self.batches = train.prefetch_batches(dl, cfg, mesh)
        self.tokens_per_step = batch * seq
        self.losses = []

    def _against_reference(self, model, x, y, seq):
        """On the first `seq` tokens of the first sequence, at the seeded
        weights: the program's logits, loss, chosen expert sets and one
        layer's gradient against the plain float32 model's."""
        cfg, mesh = self.cfg, self.mesh
        x, y = x[:1, :seq], y[:1, :seq]
        got = jax.jit(lambda params, batch: system_outputs(
            params, batch, cfg, mesh))(
                self.state[0], train.batch_from_host(x, y, cfg, mesh))
        want = self.reference.reference(
            self.state[0], jnp.asarray(x), jnp.asarray(y),
            grads_of=check_layer(cfg), **reference_keywords(model))
        errs, ok = self.reference.compare(got, want)
        self.checks["matches_reference"] = ok
        self.detail.update(check_seq=seq, reference_errors=errs,
                           first_loss=float(got["loss"]),
                           reference_loss=float(want["loss"]))

    def step(self, span):
        with span("bench.next_batch"):
            batch = next(self.batches)
        with span("bench.dispatch"):
            self.state, metrics = self.step_fn(self.state, batch)
        with span("bench.block"):
            jax.block_until_ready(self.state)
        self.losses.append((metrics["loss"], metrics["moe_slots_here"]))

    def finish(self, first, last):
        every = np.asarray(jax.device_get(self.losses[:last]), float)
        losses, slots = every[first:].T
        bad = int(np.sum(~np.isfinite(every[first:])))
        after = _fingerprint(self.state[0])
        # every TRAINED leaf moved; the state leaves (the routers' biases:
        # transformer.STATE_LEAVES) are exempt, and held as they came
        trained = [k for k in self.before if not _is_state(k)]
        state = [k for k in self.before if _is_state(k)]
        moved = sum(self.before[k] != after[k] for k in trained)
        held = sum(self.before[k] == after[k] for k in state)
        quarter = max(len(losses) // 4, 1)
        text = _RAN[self.name] = self.step_fn.executable().as_text()
        kernels = sorted(set(re.findall(r"burst_flash_\w+", text)))
        mosaic = text.count('custom_call_target="tpu_custom_call"')
        self.detail.update(kernels=kernels, mosaic_calls=mosaic)
        self.checks.update(
            kernels_compiled=mosaic >= 2 and "burst_flash_fwd" in kernels,
            loss_finite=bad == 0,
            loss_fell=bool(np.mean(losses[-quarter:])
                           < np.mean(losses[:quarter])),
            params_changed=moved == len(trained),
            state_leaves_held=held == len(state))
        self.detail.update(
            losses=losses.tolist(),
            # the load the expert layer's time follows, step by step
            moe_slots_here=slots.tolist(),
            moe_slots_here_mean=float(np.mean(slots)),
            warmup_losses=every[:first, 0].tolist(),
            warmup_moe_slots_here=every[:first, 1].tolist(),
            leaves_changed=[moved, len(trained)],
            state_leaves=[held, len(state)])
        return bad


def run(ctx):
    with contextlib.ExitStack() as stack:
        return harness.measure_steps(Session(ctx, stack), ctx)


def lower_precision_reading(seed, workload="train_kanana2_mla_1x16k"):
    """The reference with every activation rounded through float8_e4m3fn,
    against the float32 reference, at the state a cell's check is made in:
    seeded weights, a batch drawn as the cell's are, the biases balanced on
    the whole of it by the program's own choices, and the first `check_seq`
    tokens of it compared: what the bounds have to refuse."""
    from ..run import load_cell

    cell = load_cell(workload)
    model, mix = cell["config"], cell["traffic"]
    reference = importlib.import_module(
        f"chipbench.references.{model['reference']}")
    cfg = model_config(model)
    mesh = train.make_mesh({"sp": 1}, devices=jax.devices()[:1])
    params, _ = train.init_train_state(
        jax.random.PRNGKey(seed), cfg, train_config(model), mesh)
    window = np.random.default_rng(seed).integers(
        0, mix["token_ids"], size=(1, mix["seq"] + 1)).astype(np.int32)
    tokens, labels = window[:, :-1], window[:, 1:]
    params, balance = balance_biases(params, choices_of(
        train.batch_from_host(tokens, labels, cfg, mesh), cfg, mesh), cfg)
    check = mix["check_seq"]
    tokens, labels = tokens[:, :check], labels[:, :check]
    kw = dict(reference_keywords(model), grads_of=check_layer(cfg))
    want = reference.reference(params, tokens, labels, **kw)
    low = reference.reference(params, tokens, labels, **kw,
                              round_to=jnp.float8_e4m3fn)
    errs, ok = reference.compare(low, want)
    return {**errs, "bias_balance": balance}, ok


if __name__ == "__main__":
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    seed = ap.parse_args().seed
    errs, ok = lower_precision_reading(seed)
    print(json.dumps({"round_to": "float8_e4m3fn", "seed": seed,
                      "errors": errs,
                      "within_bounds": ok,
                      "device": jax.devices()[0].device_kind}))
