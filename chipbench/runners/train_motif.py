"""The Motif-3 trainer cell: `runners/train_mla_moe.py`'s session for a stack
of grouped differential latent attention, sliding and full layers, the mHC
residual of four streams and PolyNorm MLPs, with the same calls in the same
order, the same spans, and the routers' biases balanced and held by that
module's rule.  What differs is the configuration's reading (`model_config`),
the reference's keywords, that the balancing reads its chosen sets off the
check's own program (the check is the step's first batch, so set-up
compiles no forward of its own: the cell's programs then fit the compile
cache together), and that the check runs BEFORE the optimizer's moments
exist: the step needs 13.4 GiB of the chip's 15.75, and the float32
reference at 4,096 tokens 8.8.

    python3 -m chipbench.runners.train_motif --seed <n> [<n> ...]

prints, for each seed, the readings the reference's bounds are set
between: the program, and the reference with every activation rounded
through bfloat16 and through an 8-bit float, each held to the float32
reference (references/motif_lm.py) at the cell's check state; not part of
a cell's run.
"""

import contextlib
import functools
import importlib
import os
import tempfile
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from burst_attn_tpu.data import DataLoader
from burst_attn_tpu.models import train, transformer
from burst_attn_tpu.models.train import TrainConfig
from burst_attn_tpu.models.transformer import (
    MHC, DenseMLP, ExpertMLP, GDLAttn, LayerSpec, ModelConfig,
)
from burst_attn_tpu.ops.polynorm import PolyNorm

from .. import harness, traffic
from . import train_mla_moe
from .train_bd_moe import _RAN, check_layer, step_text  # noqa: F401
from .train_mla_moe import balance_biases, system_outputs

# what the program computes, by the configuration's own keys: anything else
# is refused by name, not run as something else
COMPUTED = {
    "attention_cls": "gdla", "diff_v2": True, "k_ratio": 1,
    "elementwise_attn_output_gate": True, "headwise_attn_output_gate": False,
    "hidden_act": "poly_norm", "mhc_enabled": True,
    "use_sliding_window": True, "sliding_window_pattern": "interleave",
    "score_func": "sigmoid", "route_norm": True,
    "score_before_experts": False, "interleave_moe_layer_step": 1,
    "polynorm_output_scale_per_layer": {}, "num_nextn_predict_layers": 0,
}


def layer_kinds(model):
    """Each held layer's mask by the source's rule: layer i (0-based over the
    whole stack) is full where (i + 1) % sliding_window_period == 0."""
    lo, hi = model["layers_held"]
    period = model["sliding_window_period"]
    return ["full_attention" if (i + 1) % period == 0 else "sliding_attention"
            for i in range(lo, hi)]


def model_config(model):
    """The configuration file's keys (the source's names, and the cut's) as
    the program's ModelConfig; everything not named keeps the trainer's
    default."""
    wrong = {k: model.get(k) for k, v in COMPUTED.items()
             if model.get(k) != v}
    scaling = model["rope_scaling"]
    if scaling["apply_yarn_scaling"] or model["swa_rope_theta"] != \
            model["rope_theta"]:
        wrong["rope_scaling"] = scaling
    if model["num_noise_heads"] != model["num_key_value_heads"]:
        wrong["num_noise_heads"] = model["num_noise_heads"]
    if wrong:
        raise ValueError(
            "the program computes one noise head a KV group, differential "
            "attention with an elementwise gate, PolyNorm MLPs, mHC, the "
            "interleaved window pattern, a sigmoid router renormalised over "
            f"the chosen and plain RoPE; this configuration has {wrong}")
    lo, hi = model["layers_held"]
    if layer_kinds(model) != model["layer_types"] or \
            hi - lo != model["num_hidden_layers"]:
        raise ValueError(f"layer_types {model['layer_types']} is not the "
                         f"pattern's {layer_kinds(model)} for layers "
                         f"[{lo}, {hi})")
    attn = GDLAttn(
        kv_latent=model["kv_lora_rank"], qk_nope=model["qk_nope_head_dim"],
        qk_rope=model["qk_rope_head_dim"], v_head=model["v_head_dim"],
        q_latent=model["q_lora_rank"], kv_heads=model["num_key_value_heads"],
        noise_heads=model["num_noise_heads"])
    act = PolyNorm(output_scale=model["polynorm_output_scale"],
                   bias_clamp=model["polynorm_bias_clamp"],
                   eps=model["polynorm_eps"])
    width = model["moe_intermediate_size"]
    sparse = ExpertMLP(
        d_ff=width, n_experts=model["router_outputs"],
        top_k=model["experts_top_k"], held=tuple(model["experts_held"]),
        score=model["score_func"],
        # torchtitan's load-balancing bias steers the choice alone
        choice_bias=model["load_balance_coeff"] is not None,
        gate_scale=model["route_scale"],
        shared_ff=model["num_shared_experts"] * width)
    dense = DenseMLP(model["intermediate_size"])
    pattern = tuple(
        LayerSpec(dense if i < model["n_dense_first_layers"] else sparse,
                  attn, window=(model["sliding_window"]
                                if kind == "sliding_attention" else None),
                  act=act)
        for i, kind in zip(range(lo, hi), model["layer_types"]))
    return ModelConfig(
        vocab=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"], rope_theta=model["rope_theta"],
        pattern=pattern, layout="contig",
        mhc=MHC(streams=model["mhc_expansion_rate"],
                sinkhorn_iters=model["mhc_sinkhorn_iters"],
                clamp=model["hidden_clamp"]),
        norm_eps=model["rms_norm_eps"],
        seq_axes=("sp",), batch_axis=None, head_axis=None)


def train_config(model):
    # no auxiliary loss: the family balances through the bias (assumed)
    return TrainConfig(moe_aux_weight=0.0)


def reference_keywords(model):
    cfg = model_config(model)
    return dict(held=tuple(model["experts_held"]),
                top_k=model["experts_top_k"],
                gate_scale=model["route_scale"],
                qk_nope=model["qk_nope_head_dim"],
                kv_latent=model["kv_lora_rank"],
                rope_theta=model["rope_theta"],
                rms_norm_eps=model["rms_norm_eps"],
                windows=tuple(spec.window for spec in cfg.pattern),
                streams=model["mhc_expansion_rate"],
                sinkhorn_iters=model["mhc_sinkhorn_iters"],
                hidden_clamp=model["hidden_clamp"],
                poly_scale=model["polynorm_output_scale"],
                poly_clamp=model["polynorm_bias_clamp"],
                poly_eps=model["polynorm_eps"])


def init_params(key, cfg, mesh):
    """The trainer's parameters alone (train.init_train_state's, without
    the optimizer's moments), placed as it places them."""
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             transformer.param_specs(cfg),
                             is_leaf=lambda x: isinstance(x, P))
    return jax.jit(partial(transformer.init_params, cfg=cfg),
                   out_shardings=shardings)(key)


@functools.cache
def check_program(cfg, mesh):
    """The check's one program, `train_mla_moe.system_outputs` jitted:
    logits, loss, every sparse layer's chosen expert sets and
    check_layer's gradient, at `params` on `batch`."""
    return jax.jit(lambda params, batch: system_outputs(params, batch, cfg,
                                                        mesh))


def balanced_check(params, x, y, cfg, mesh):
    """(params with every router's bias balanced on the batch (x, y) by
    `train_mla_moe.balance_biases`, what its last pass read, the check
    program's outputs there, on the host).  The balancing reads its chosen
    sets off the check's program, so set-up compiles no forward of its own,
    and its last pass, at the balanced biases, is the check's."""
    batch = train.batch_from_host(x, y, cfg, mesh)
    program = check_program(cfg, mesh)
    last = {}

    def chosen_of(params):
        last["out"] = program(params, batch)
        return last["out"]["chosen"]

    params, balance = balance_biases(params, chosen_of, cfg)
    return params, balance, jax.device_get(last["out"])


class Session(train_mla_moe.Session):
    """train_mla_moe.Session's steps, checks and `program_bytes`; set-up
    makes the parameters, balances the biases and runs the check on the
    step's own first batch, and only then the optimizer's moments."""

    def __init__(self, ctx, stack):
        model, mix = ctx.cell["config"], ctx.cell["traffic"]
        batch, seq = mix["batch"], mix["seq"]
        if (batch, mix["check_seq"]) != (1, seq):
            raise ValueError(
                "the biases are balanced on the check's tokens, so the check "
                f"is the whole first batch of one sequence; this traffic has "
                f"batch {batch}, seq {seq}, check_seq {mix['check_seq']}")
        self.reference = importlib.import_module(
            f"chipbench.references.{model['reference']}")
        self.cfg = cfg = model_config(model)
        self.tcfg = tcfg = train_config(model)
        self.mesh = mesh = train.make_mesh({"sp": mix["sp"]},
                                           devices=ctx.devices[:mix["sp"]])
        self.checks, self.detail = {}, {}

        tmp = stack.enter_context(tempfile.TemporaryDirectory(
            dir=ctx.out_dir))
        data = os.path.join(tmp, "tokens.batd")
        traffic.write_token_file(data, ctx.seed, **mix)
        loader = dict(shard_id=0, num_shards=1, seed=ctx.seed, num_threads=2)

        params = init_params(jax.random.PRNGKey(ctx.seed), cfg, mesh)
        with DataLoader(data, batch, seq, **loader) as dl:
            first_x, first_y = dl.next()
        ctx.mark("state_and_data")
        params, balance, got = balanced_check(params, first_x, first_y, cfg,
                                              mesh)
        self.state = (params, None)
        self.detail.update(bias_balance=balance)
        ctx.mark("bias_balance")

        self._against_reference(model, got, first_x, first_y)
        ctx.mark("reference_check")
        opt_specs = train.state_specs(cfg, tcfg, params)[1]
        self.state = (params, jax.jit(
            train._optimizer(tcfg).init, out_shardings=jax.tree.map(
                lambda s: NamedSharding(mesh, s), opt_specs,
                is_leaf=lambda x: isinstance(x, P)))(params))
        self.before = train_mla_moe._fingerprint(self.state[0])
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

    def _against_reference(self, model, got, x, y):
        """The program's outputs `got` on the first sequence (x, y), at the
        seeded weights: logits, loss, chosen expert sets and one layer's
        gradient against the plain float32 model's."""
        want = jax.device_get(self.reference.reference(
            self.state[0], x, y, grads_of=check_layer(self.cfg),
            **reference_keywords(model)))
        errs, ok = self.reference.compare(got, want)
        self.checks["matches_reference"] = ok
        self.detail.update(check_seq=x.shape[1], reference_errors=errs,
                           first_loss=float(got["loss"]),
                           reference_loss=float(want["loss"]))


def run(ctx):
    with contextlib.ExitStack() as stack:
        return harness.measure_steps(Session(ctx, stack), ctx)


ROUNDED = ("bfloat16", "float8_e4m3fn")


def check_readings(seed, workload="train_motif3_gdla_1x4k"):
    """At the state a cell's check is made in (seeded weights, the cell's
    own first batch, the biases balanced on it): {"system": the program,
    "bfloat16" / "float8_e4m3fn": the reference with every activation
    rounded through that type}, each (errors, within bounds) against the
    float32 reference.  The bounds have to pass the first and refuse the
    last; the middle shows what rounding alone reads."""
    from ..run import load_cell

    cell = load_cell(workload)
    model, mix = cell["config"], cell["traffic"]
    reference = importlib.import_module(
        f"chipbench.references.{model['reference']}")
    cfg = model_config(model)
    mesh = train.make_mesh({"sp": 1}, devices=jax.devices()[:1])
    params = init_params(jax.random.PRNGKey(seed), cfg, mesh)
    with tempfile.TemporaryDirectory() as tmp:
        data = os.path.join(tmp, "tokens.batd")
        traffic.write_token_file(data, seed, **mix)
        with DataLoader(data, mix["batch"], mix["seq"], shard_id=0,
                        num_shards=1, seed=seed, num_threads=2) as dl:
            x, y = dl.next()
    params, balance, got = balanced_check(params, x, y, cfg, mesh)
    kw = dict(reference_keywords(model), grads_of=check_layer(cfg))
    want = jax.device_get(reference.reference(params, x, y, **kw))
    out = {"system": reference.compare(got, want)}
    for name in ROUNDED:
        low = jax.device_get(reference.reference(
            params, x, y, **kw, round_to=jnp.dtype(name).type))
        out[name] = reference.compare(low, want)
    return out, balance


if __name__ == "__main__":
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    for seed in ap.parse_args().seed:
        readings, balance = check_readings(seed)
        print(json.dumps({"seed": seed, "bias_balance": balance,
                          **{name: {"errors": errs, "within_bounds": ok}
                             for name, (errs, ok) in readings.items()},
                          "device": jax.devices()[0].device_kind}),
              flush=True)
