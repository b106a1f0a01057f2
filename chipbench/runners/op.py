"""The op cells: forward + backward of `burst_attn` on a sequence-parallel
mesh, through the library's top-level entry point, as one compiled program
that is kept (its text names the kernels, its memory analysis sizes it)."""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import burst_attn_tpu as bat

from .. import harness, traffic


def _finite(x):
    return bool(jnp.all(jnp.isfinite(x.astype(jnp.float32))))


class Session:
    def __init__(self, ctx):
        cfg, mix = ctx.cell["config"], ctx.cell["traffic"]
        self.reference = importlib.import_module(
            f"chipbench.references.{cfg['reference']}")
        devices = ctx.devices[:mix["sp"]]
        self.mesh = Mesh(np.array(devices), ("sp",))
        self.world = len(devices)
        self.cfg = cfg
        self.shape = dict(batch=mix["batch"],
                          heads=cfg["num_attention_heads"],
                          kv_heads=cfg["num_key_value_heads"],
                          d_head=cfg["head_dim"],
                          dtype=jnp.dtype(cfg["dtype"]))
        self.checks, self.detail = {}, {}
        self._parity(ctx.seed + 1, mix["parity_seq"], devices[0])
        ctx.mark("parity_check")

        # the timed program's inputs are drawn straight in ring order: a
        # permutation of independent normals is the same distribution, and
        # the step is timed without the layout copies around it
        self.args = traffic.attention_inputs(ctx.seed, self.mesh,
                                             seq=mix["seq"], **self.shape)
        self.compiled = self._compile(self.args)
        ctx.mark("inputs_and_program")
        text = self.compiled.as_text()
        kernels = sorted(set(re.findall(r"burst_flash_\w+", text)))
        mosaic = text.count('custom_call_target="tpu_custom_call"')
        self.checks["kernels_compiled"] = (mosaic >= 2
                                           and "burst_flash_fwd" in kernels)
        if self.world > 1:
            self.checks["collective_permutes"] = "collective-permute" in text
        self.detail.update(kernels=kernels, mosaic_calls=mosaic,
                           shape=[mix["batch"], self.shape["heads"],
                                  mix["seq"], self.shape["d_head"]])
        self.program_bytes = harness.program_bytes(self.compiled)
        self.tokens_per_step = mix["batch"] * mix["seq"]
        self.outs = None

    def _attn(self, q, k, v):
        return bat.burst_attn(q, k, v, mesh=self.mesh,
                              causal=self.cfg["causal"],
                              layout=self.cfg["layout"],
                              backend=self.cfg["backend"])

    def _compile(self, args):
        return jax.jit(self.reference.fwd_bwd(self._attn)).lower(
            *args).compile()

    def _parity(self, seed, seq, device):
        """o, dq, dk, dv at `seq` tokens through the same kernels, natural
        order in and out, against the float32 oracle on one chip."""
        sharding = NamedSharding(self.mesh, P(None, None, "sp", None))
        # jitted onto the sequence sharding: done eagerly, the permutations
        # leave every chip holding the whole array
        lay, unlay = (
            jax.jit(lambda *xs, f=f: tuple(
                f(x, self.cfg["layout"], self.world, axis=2) for x in xs),
                out_shardings=sharding)
            for f in (bat.layouts.to_layout, bat.layouts.from_layout))
        natural = traffic.attention_inputs(seed, self.mesh, seq=seq,
                                           **self.shape)
        args = lay(*natural)
        got = unlay(*self._compile(args)(*args))
        want = self.reference.reference_grads(
            *(jax.device_put(x, device) for x in natural))
        errs, ok = self.reference.parity(
            [jax.device_put(x, device) for x in got], want)
        self.checks["parity"] = ok
        self.detail.update(parity_seq=seq, max_abs_err=errs)

    def step(self, span):
        with span("bench.dispatch"):
            self.outs = self.compiled(*self.args)
        with span("bench.block"):
            jax.block_until_ready(self.outs)

    def finish(self, first, last):
        self.checks["finite"] = all(_finite(x) for x in self.outs)
        if self.world > 1:
            held = {s.device for s in self.outs[0].addressable_shards}
            self.checks["every_device_holds_a_shard"] = (
                len(held) == self.world)
        return 0 if self.checks["finite"] else last - first


def run(ctx):
    return harness.measure_steps(Session(ctx), ctx)
