"""End-to-end train-step MFU smoke on real hardware (round-1 verdict item 3).

Trains the flagship LM on synthetic data for a few steps on the real chip,
reports tokens/s + MFU, and captures an XLA profile — the kernel-occupancy /
pipelining evidence the reference never had (its benchmarks stop at the op).

MFU convention: model FLOPs/token = 6 * n_params  (fwd+bwd dense matmuls)
              + 12 * n_layers * s * d_head * n_heads / (2 if causal)
              (attention scores+pv, fwd+bwd at 2x+... folded into the 12x;
              causal halves the live score area), against the chip's peak
              bf16 TFLOPs (v5e: 197).

    python -m benchmarks.train_smoke --steps 8 --seq 32768 \
        --trace-dir /root/repo/results/trace_smoke
"""

import argparse
import json
import sys

import numpy as np


# keyed by ops/tuning.canonical_kind so device-kind strings are interpreted
# in exactly one place
PEAK_BF16 = {"v5e": 197e12, "v4": 275e12, "v5p": 459e12, "v6": 918e12}


def peak_flops(device):
    """Peak bf16 FLOPs/s of `device`; a generation without a row is an
    error, not another chip's peak."""
    from burst_attn_tpu.ops.tuning import canonical_kind

    kind = canonical_kind(device)
    if kind not in PEAK_BF16:
        raise ValueError(
            f"no bf16 peak for device kind "
            f"{getattr(device, 'device_kind', '?')!r}; add it to "
            f"benchmarks/train_smoke.PEAK_BF16")
    return PEAK_BF16[kind]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--seq", type=int, default=32768)
    ap.add_argument("--d-model", type=int, default=2048)
    ap.add_argument("--n-layers", type=int, default=16)
    ap.add_argument("--n-heads", type=int, default=16)
    ap.add_argument("--vocab", type=int, default=32768)
    ap.add_argument("--trace-dir", default=None,
                    help="capture an XLA profile of the traced steps here")
    ap.add_argument("--trace-steps", type=int, default=2)
    ap.add_argument("--out", default="results/results_smoke.jsonl")
    args = ap.parse_args(argv)

    import jax

    from burst_attn_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    if jax.default_backend() != "tpu":
        print("train_smoke: not on TPU; refusing to record numbers",
              file=sys.stderr)
        sys.exit(1)

    from burst_attn_tpu.models import ModelConfig
    from burst_attn_tpu.models.train import (
        TrainConfig, init_train_state, make_batch, make_mesh, make_train_step,
    )
    from burst_attn_tpu.obs import StepTimer

    cfg = ModelConfig(
        vocab=args.vocab, d_model=args.d_model, n_layers=args.n_layers,
        n_heads=args.n_heads, n_kv_heads=args.n_heads,
        d_head=args.d_model // args.n_heads, d_ff=4 * args.d_model,
        batch_axis=None, head_axis=None, seq_axes=("sp",), remat=True,
    )
    mesh = make_mesh({"sp": 1}, devices=jax.devices()[:1])
    tcfg = TrainConfig()
    state = init_train_state(jax.random.PRNGKey(0), cfg, tcfg, mesh)
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(state[0]))
    step = make_train_step(cfg, tcfg, mesh)
    batch = make_batch(jax.random.PRNGKey(1), cfg, mesh, batch=args.batch,
                       seq=args.seq)

    # at least one warmup: the first call compiles, and `metrics` must be
    # bound before the sync below
    for _ in range(max(1, args.warmup)):
        state, metrics = step(state, batch)
    float(metrics["loss"])  # sync

    timer = StepTimer()
    for _ in range(args.steps):
        with timer:
            state, metrics = step(state, batch)
            timer.watch(metrics["loss"])
    loss = float(metrics["loss"])

    if args.trace_dir:
        with jax.profiler.trace(args.trace_dir):
            for _ in range(args.trace_steps):
                state, metrics = step(state, batch)
            float(metrics["loss"])
    step.close()  # the last step's open `train.step` span

    tokens = args.batch * args.seq
    step_s = min(timer.times)  # best step; summary() has the spread
    tok_per_s = tokens / step_s
    # fwd+bwd matmul FLOPs: 6 FLOPs/param/token; attention: s^2*d*n scores +
    # pv = 4*s^2*n*d per layer fwd (/2 causal), x3.5 fwd+bwd
    attn_flops = (args.n_layers * 3.5 * 4 * args.batch * args.seq * args.seq
                  * args.n_heads * (args.d_model // args.n_heads) / 2)
    flops_step = 6.0 * n_params * tokens + attn_flops
    dev = jax.devices()[0]
    peak = peak_flops(dev)
    mfu = flops_step / step_s / peak
    rec = {
        "device": dev.device_kind, "params": n_params, "batch": args.batch,
        "seq": args.seq, "d_model": args.d_model, "n_layers": args.n_layers,
        "steps": args.steps, "loss": round(loss, 4),
        "step_ms": round(step_s * 1e3, 1),
        "tokens_per_s": round(tok_per_s, 1),
        "model_tflops_per_s": round(flops_step / step_s / 1e12, 1),
        "mfu": round(mfu, 4),
        "peak_bf16_tflops": peak / 1e12,
        "trace_dir": args.trace_dir,
    }
    print(json.dumps(rec))
    with open(args.out, "a") as f:
        f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
