"""Host cost of make_train_step's always-on bookkeeping: microseconds a step
that `guarded_step` adds around a STUB jitted step (nothing runs on a
device), and the same with the `train.loader_wait` / `train.h2d` spans of
`prefetch_batches` around a stub loader.  A CPU number, of host code only.

    JAX_PLATFORMS=cpu python benchmarks/train_step_overhead.py [steps]
"""

import json
import statistics
import sys
import time

import numpy as np

from burst_attn_tpu.models import train
from burst_attn_tpu.models.transformer import ModelConfig


def main(steps=20000):
    cfg = ModelConfig(vocab=64, d_model=32, n_layers=1, n_heads=2,
                      n_kv_heads=2, d_head=16, d_ff=64, seq_axes=("sp",),
                      batch_axis=None, head_axis=None)
    mesh = train.make_mesh({"sp": 1})
    out = ((), {"loss": 0.0})
    stub = lambda state, batch: out
    real_jit, real_h2d = train.jit_train_step, train.batch_from_host
    train.jit_train_step = lambda *a: stub
    train.batch_from_host = lambda x, y, **kw: x
    try:
        step = train.make_train_step(cfg, train.TrainConfig(), mesh)
        xy = (np.zeros(1), np.zeros(1))
        batches = train.prefetch_batches((xy for _ in range(steps + 8)), cfg,
                                         mesh)
        per_step = []
        for _ in range(steps):
            t0 = time.perf_counter()
            batch = next(batches)
            step((), batch)
            per_step.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        for _ in range(steps):
            stub((), None)
        bare = (time.perf_counter() - t0) / steps
    finally:
        train.jit_train_step, train.batch_from_host = real_jit, real_h2d
    q = statistics.quantiles(per_step, n=100)
    print(json.dumps({
        "steps": steps, "us_per_step_median": 1e6 * statistics.median(per_step),
        "us_per_step_mean": 1e6 * statistics.fmean(per_step),
        "us_per_step_p99": 1e6 * q[98], "us_bare_call": 1e6 * bare}))


if __name__ == "__main__":
    main(*(int(a) for a in sys.argv[1:2]))
