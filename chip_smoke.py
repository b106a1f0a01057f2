"""First light on the chip: `burst_attn` forward+backward and a few steps of
the trainer, through the entry points a user calls, in ONE process.

    python chip_smoke.py              # one chip: op phase, then train phase
    python chip_smoke.py --multichip  # four chips: the sp=4 ring and trainer,
                                      # each against its one-chip comparison

Every phase prints one JSON line; the last line of standard output is
`{"ok": ..., "device": {"platform", "kind", "count"}}`.  The exit code is 0
only on a TPU with every check of every phase true; no phase's exception is
caught.  The phases are functions of their sizes (REAL / REAL_MULTICHIP
below are what the command line runs; tests/test_chip_smoke.py rehearses
them at a tiny size on the CPU).
"""

import argparse
import contextlib
import json
import os
import re
import statistics
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import burst_attn_tpu as bat
from burst_attn_tpu.data import write_token_file
from burst_attn_tpu.models import runner
from burst_attn_tpu.models.train import batch_from_host, jit_train_step
from burst_attn_tpu.utils.compile_cache import place_compile_cache

# The op is the paper's shape (BASELINE.json: 1 x 32 heads x 64K x 128, bf16,
# causal).  The model is benchmarks/train_smoke.py's: d_model 2048, 16
# layers, 16 heads x 128, d_ff 8192, vocab 32768 (1.21 B parameters, bf16,
# AdamW moments in bf16 too), remat on, batch 1.  Its sequence is cut from
# 32768 and no width is: compiled for a described v5e the 32K step needs
# 17.16 GiB of the chip's 15.75 and the 16K step 15.74.  The 16K step did
# run on the chip, alone in its process (PR 22: 1.52 s a step), but with 10
# MiB to spare a smoke test would fail for what else a process holds; 8192
# needs 10.9 GiB (tests/test_tpu_compile.py keeps that compile).
MODEL = dict(d_model=2048, n_layers=16, n_heads=16, d_ff=8192, vocab=32768)
REAL = dict(
    op=dict(heads=32, d_head=128, seq=65536, ref_seq=8192),
    train=dict(MODEL, seq=8192, steps=4),
)
# Four chips: 32K per shard for the op (item 2's memory_analysis: 8.1 GB a
# chip), the trainer at 4x the one-chip sequence; each is compared with one
# chip at a size both hold.
REAL_MULTICHIP = dict(
    op=dict(heads=32, d_head=128, seq=131072, cmp_seq=65536),
    train=dict(MODEL, seq=32768, cmp_seq=8192, steps=3),
)

# bf16 bounds of the chip-gated kernel tests (tests/test_fused_bwd.py)
TOL_O, TOL_GRAD = 4e-2, 5e-2
TOL_LOSS = 5e-3  # first loss, sp=4 against sp=1 (1.9e-4 on the chip, PR 22)

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _CompileClock:
    """Seconds JAX spent in backend compiles (cache reads included), and
    persistent-cache hits and misses, since the last `take()`."""

    def __init__(self):
        self._s, self._hits, self._misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == _COMPILE_EVENT:
            self._s += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self._hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self._misses += 1

    def take(self):
        out = {"compile_s": round(self._s, 2), "cache_hits": self._hits,
               "cache_misses": self._misses}
        self._s, self._hits, self._misses = 0.0, 0, 0
        return out


def _peak_bytes(device):
    stats = device.memory_stats()  # None on the CPU
    return None if stats is None else stats["peak_bytes_in_use"]


def _finite(x):
    return bool(jnp.all(jnp.isfinite(x.astype(jnp.float32))))


def _max_err(a, b):
    b = jax.device_put(b, a.sharding)  # one chip's answer, onto the ring
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def _seq_mesh(devices):
    return Mesh(np.array(devices), ("sp",))


def _seq_sharding(mesh):
    return NamedSharding(mesh, P(None, None, "sp", None))


def _fwd_bwd(attn):
    """(q, k, v, do) -> (o, dq, dk, dv) of `attn` under loss = sum(o * do)."""
    def fwd_bwd(q, k, v, do):
        def loss(q, k, v):
            o = attn(q, k, v)
            return jnp.sum(o.astype(jnp.float32)
                           * do.astype(jnp.float32)), o

        (_, o), grads = jax.value_and_grad(loss, (0, 1, 2),
                                           has_aux=True)(q, k, v)
        return (o, *grads)

    return fwd_bwd


def _parity(got, want):
    """Max abs error of o, dq, dk, dv, and whether all are in bounds."""
    errs = {n: _max_err(g, w)
            for n, g, w in zip(("o", "dq", "dk", "dv"), got, want)}
    return errs, errs["o"] < TOL_O and all(
        errs[n] < TOL_GRAD for n in ("dq", "dk", "dv"))


def _inputs(seed, mesh, heads, d_head, seq):
    """q, k, v, do in natural token order, bf16, drawn on the mesh from the
    seed (the same numbers whatever the mesh)."""
    def draw(key):
        return tuple(jax.random.normal(k, (1, heads, seq, d_head),
                                       jnp.bfloat16)
                     for k in jax.random.split(key, 4))

    return jax.jit(draw, out_shardings=_seq_sharding(mesh))(
        jax.random.PRNGKey(seed))


def _attn_grads(mesh, q, k, v, do):
    """o, dq, dk, dv of causal zigzag `burst_attn(backend="auto")` on `mesh`
    (natural order in and out), the compiled program's text, and the
    devices that held a shard of the ring's output."""
    world = mesh.devices.size
    # the permutations are jitted onto the sequence sharding: done eagerly
    # they leave every chip holding the whole array
    lay, unlay = (
        jax.jit(lambda *xs, f=f: tuple(f(x, "zigzag", world, axis=2)
                                       for x in xs),
                out_shardings=_seq_sharding(mesh))
        for f in (bat.layouts.to_layout, bat.layouts.from_layout))
    args = lay(q, k, v, do)
    compiled = jax.jit(_fwd_bwd(lambda q, k, v: bat.burst_attn(
        q, k, v, mesh=mesh, causal=True, layout="zigzag",
        backend="auto"))).lower(*args).compile()
    outs = compiled(*args)
    held = {shard.device for shard in outs[0].addressable_shards}
    return unlay(*outs), compiled.as_text(), held


def _reference_grads(q, k, v, do, head_chunk=4):
    """The same four through the repo's dense float32 softmax oracle
    (ops/reference.dense_attention), a few heads at a time."""
    fwd_bwd = _fwd_bwd(lambda q, k, v: bat.reference.dense_attention(
        q, k, v, causal=True))
    chunks = []
    with jax.default_matmul_precision("highest"):
        for h in range(0, q.shape[1], head_chunk):
            chunks.append(fwd_bwd(*(x[:, h:h + head_chunk].astype(jnp.float32)
                                    for x in (q, k, v, do))))
    return tuple(jnp.concatenate(c, axis=1) for c in zip(*chunks))


def _kernel_facts(text):
    """Which Pallas kernels the compiled program holds (the backward is
    `burst_flash_bwd_tri`, the triangular fused one, `_rect`, the
    rectangular fused one, or the split `_dq` + `_dkdv` pair), and how many
    Mosaic compiled: interpreted, they leave no custom call."""
    return {
        "kernels": sorted(set(re.findall(r"burst_flash_\w+", text))),
        "mosaic_calls": text.count('custom_call_target="tpu_custom_call"'),
    }


def op_phase(devices, clock, *, heads, d_head, seq, ref_seq, seed):
    """burst_attn forward and grad on a one-device mesh: finite at `seq`,
    and at `ref_seq` within the bf16 bounds of the float32 reference."""
    mesh = _seq_mesh(devices[:1])
    outs, text, _ = _attn_grads(mesh,
                                *_inputs(seed, mesh, heads, d_head, seq))
    facts = _kernel_facts(text)
    checks = {
        "backend_is_pallas": bat.parallel.burst._resolve_backend("auto")
        == "pallas",
        "kernels_compiled": facts["mosaic_calls"] >= 2
        and "burst_flash_fwd" in facts["kernels"],
        "finite": all(_finite(x) for x in outs),
    }
    del outs
    small = _inputs(seed + 1, mesh, heads, d_head, ref_seq)
    got = _attn_grads(mesh, *small)[0]
    errs, checks["parity"] = _parity(got, _reference_grads(*small))
    rec = {"phase": "op", "shape": [1, heads, seq, d_head], "dtype": "bf16",
           "causal": True, "layout": "zigzag", **facts,
           "ref_seq": ref_seq, "max_abs_err": errs,
           "tol": {"o": TOL_O, "grad": TOL_GRAD}, **clock.take(),
           "peak_bytes_in_use": _peak_bytes(devices[0])}
    return rec, checks


@contextlib.contextmanager
def _token_file(seq, seed):
    """A BATD file of 8 windows of tokens drawn uniformly from the first 256
    ids: learning which ids occur at all takes the loss from ln(vocab)
    towards ln(256) within a few steps, so a falling loss is a real check."""
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "tokens.batd")
        write_token_file(path, np.random.default_rng(seed).integers(
            0, 256, size=8 * (seq + 1)))
        yield path


def _train_argv(data, *, mesh, d_model, n_layers, n_heads, d_ff, vocab, seq,
                steps, seed):
    return ["--data", data, "--steps", str(steps), "--batch", "1",
            "--seq-len", str(seq), "--mesh", mesh, "--log-every", "1",
            "--seed", str(seed), "--vocab", str(vocab),
            "--d-model", str(d_model), "--n-layers", str(n_layers),
            "--n-heads", str(n_heads), "--d-ff", str(d_ff)]


def _fingerprint(params):
    """Sum of squares of every parameter leaf, in float32, on the host."""
    sq = jax.jit(lambda p: jax.tree.map(
        lambda x: jnp.sum(jnp.square(x.astype(jnp.float32))), p))(params)
    return [float(x) for x in jax.tree.leaves(sq)]


def _loss_checks(rows):
    losses = [h["loss"] for h in rows]
    return {"loss_finite": all(np.isfinite(losses)),
            "loss_fell": losses[-1] < losses[0]}


def _step_seconds(rows):
    """Every step's seconds, and the median from the third step on: the
    first compiles, and the second has been 2-4x a later one on the chip."""
    secs = [round(h["step_s"], 4) for h in rows]
    return {"steps_s": secs,
            "step_s": statistics.median(secs[2:]) if secs[2:] else None}


def train_phase(devices, clock, *, seq, steps, seed, **model):
    """`steps` steps of the runner CLI on one chip: finite, falling loss and
    parameters that moved from their seeded initial values."""
    with _token_file(seq, seed) as data:
        argv = dict(mesh="sp=1", seq=seq, seed=seed, **model)
        # zero steps of the same command line return the seeded initial
        # state; only its fingerprint is kept, the chip has no room for two
        state0, _ = runner.main(_train_argv(data, steps=0, **argv))
        before = _fingerprint(state0[0])
        del state0
        state, rows = runner.main(_train_argv(data, steps=steps, **argv))
    after = _fingerprint(state[0])
    n_params = sum(x.size for x in jax.tree.leaves(state[0]))
    moved = sum(a != b for a, b in zip(before, after))
    checks = {**_loss_checks(rows), "all_steps_logged": len(rows) == steps,
              "params_changed": moved == len(before)}
    rec = {"phase": "train", "model": model, "n_params": n_params,
           "seq": seq, "batch": 1, "mesh": "sp=1", "remat": True,
           "seq_note": "cut from 32768, no width is: with bf16 AdamW state "
                       "the 32K step needs 17.16 GiB of the chip's 15.75 "
                       "and the 16K step 15.74 (compiler's count)",
           "losses": [round(h["loss"], 4) for h in rows],
           "leaves_changed": [moved, len(before)],
           **_step_seconds(rows), **clock.take(),
           "peak_bytes_in_use": _peak_bytes(devices[0])}
    return rec, checks


def ring_phase(devices, clock, *, heads, d_head, seq, cmp_seq, seed):
    """burst_attn forward and grad over all of `devices` (sp ring): finite
    at `seq` with every device holding a shard, and at `cmp_seq` equal,
    within bf16 bounds, to the same inputs on a one-device mesh."""
    ring, one = _seq_mesh(devices), _seq_mesh(devices[:1])
    outs, text, shard_devices = _attn_grads(
        ring, *_inputs(seed, ring, heads, d_head, seq))
    checks = {
        "finite": all(_finite(x) for x in outs),
        "every_device_holds_a_shard": len(shard_devices) == len(devices),
        "collective_permutes": text.count("collective-permute") > 0,
    }
    facts = _kernel_facts(text)
    n_permutes = text.count("collective-permute-start")
    del outs
    got = _attn_grads(ring, *_inputs(seed + 1, ring, heads, d_head,
                                     cmp_seq))[0]
    want = _attn_grads(one, *_inputs(seed + 1, one, heads, d_head,
                                     cmp_seq))[0]
    errs, checks["ring_matches_one_chip"] = _parity(got, want)
    rec = {"phase": "ring", "mesh": f"sp={len(devices)}",
           "shape": [1, heads, seq, d_head], "per_shard": seq // len(devices),
           "shard_devices": sorted(d.id for d in shard_devices),
           # models/train.make_mesh rings the chips in jax.devices() order;
           # on a 2x2 some of its hops are not between neighbours (S2)
           "ring_order_coords": [list(getattr(d, "coords", ())) for d in devices],
           "collective_permute_starts": n_permutes, **facts,
           "cmp_seq": cmp_seq, "max_abs_err_vs_one_chip": errs,
           "tol": {"o": TOL_O, "grad": TOL_GRAD}, **clock.take(),
           "peak_bytes_in_use": _peak_bytes(devices[0])}
    return rec, checks


def sharded_train_phase(devices, clock, *, seq, cmp_seq, steps, seed, **model):
    """The runner CLI on `--mesh sp=<all devices>` for `steps` steps at
    `seq`, and its first loss at `cmp_seq` against one chip's."""
    world = len(devices)
    ring = f"sp={world}"
    with _token_file(seq, seed) as data:
        argv = _train_argv(data, mesh=ring, seq=seq, steps=steps, seed=seed,
                           **model)
        state, rows = runner.main(argv)
        cfg, tcfg, _, mesh = runner.parse_args(argv)
        host = np.zeros((1, seq), np.int32)
        batch = batch_from_host(host, host, cfg, mesh)
        text = jit_train_step(cfg, tcfg, mesh).lower(
            state, batch).compile().as_text()
        checks = {
            **_loss_checks(rows),
            "params_span_the_mesh": all(
                len(x.sharding.device_set) == world
                for x in jax.tree.leaves(state)),
            "batch_spans_the_mesh": all(
                len({s.device for s in x.addressable_shards}) == world
                and x.addressable_shards[0].data.shape == (1, seq // world)
                for x in batch.values()),
            "collective_permutes": text.count("collective-permute") > 0,
        }
        n_permutes = text.count("collective-permute-start")
        del state, batch
        first = {}
        for mesh_arg in (ring, "sp=1"):
            # [1]: the state is dropped at once, a chip cannot hold two
            first[mesh_arg] = runner.main(_train_argv(
                data, mesh=mesh_arg, seq=cmp_seq, steps=1, seed=seed,
                **model))[1][0]["loss"]
    diff = abs(first[ring] - first["sp=1"])
    checks["first_loss_matches_one_chip"] = diff < TOL_LOSS
    rec = {"phase": "sharded_train", "model": model, "mesh": ring,
           "seq": seq, "per_shard": seq // world, "batch": 1,
           "losses": [round(h["loss"], 4) for h in rows],
           **_step_seconds(rows),
           "collective_permute_starts": n_permutes,
           "cmp_seq": cmp_seq, "first_loss": first,
           "first_loss_abs_diff": diff, "tol_loss": TOL_LOSS,
           **clock.take(), "peak_bytes_in_use": _peak_bytes(devices[0])}
    return rec, checks


def _cache_entries(path):
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run only the four-chip phases (needs four chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache_dir = place_compile_cache()
    entries_before = _cache_entries(cache_dir)
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(json.dumps({"phase": "start", "jax": jax.__version__, **device,
                      "compile_cache": cache_dir,
                      "cache_entries_before": entries_before,
                      "cache": "warm" if entries_before else "cold"}),
          flush=True)
    need = 4 if args.multichip else 1
    if device["platform"] != "tpu" or len(devices) < need:
        print(json.dumps({"ok": False, "device": device,
                          "error": f"needs {need} TPU chip(s)"}))
        return 1

    clock = _CompileClock()
    if args.multichip:
        sizes = REAL_MULTICHIP
        phases = (("op", ring_phase), ("train", sharded_train_phase))
        devices = devices[:4]
    else:
        sizes = REAL
        phases = (("op", op_phase), ("train", train_phase))
    ok = True
    t0 = time.perf_counter()
    for key, phase in phases:
        rec, checks = phase(devices, clock, seed=args.seed, **sizes[key])
        ok = ok and all(checks.values())
        print(json.dumps({**rec, "checks": checks,
                          "elapsed_s": round(time.perf_counter() - t0, 1)}),
              flush=True)
    print(json.dumps({"phase": "end",
                      "cache_entries_after": _cache_entries(cache_dir)}))
    print(json.dumps({"ok": ok, "device": device}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
