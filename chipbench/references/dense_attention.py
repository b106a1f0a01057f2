"""Dense causal softmax attention in float32, and its gradients: the oracle
the op cells are held to (a copy of the arithmetic of the repo's
ops/reference.dense_attention, kept here so that no PR can move it)."""

import jax
import jax.numpy as jnp

# bf16 bounds of the repo's chip-gated kernel tests (tests/test_fused_bwd.py,
# chip_smoke.py): one bf16 ulp at |o| < 4 is 1.6e-2; measured on the chip at
# 8192 tokens 1.3e-2 (o) and 2.0-2.1e-2 (grads) (PERF.md, PR 22)
TOL_O, TOL_GRAD = 4e-2, 5e-2


def attention(q, k, v):
    """q [B, N, S, D], k and v [B, N_kv, S, D] (GQA: N_kv divides N), causal,
    scale D**-0.5; float32 in, float32 out."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
    s = jnp.einsum("bnid,bnjd->bnij", q, k) * q.shape[-1] ** -0.5
    rows = jnp.arange(q.shape[2])[:, None]
    cols = jnp.arange(k.shape[2])[None, :]
    s = jnp.where(cols <= rows, s, -jnp.inf)
    return jnp.einsum("bnij,bnjd->bnid", jax.nn.softmax(s, axis=-1), v)


def fwd_bwd(attn):
    """(q, k, v, do) -> (o, dq, dk, dv) of `attn` under loss = sum(o * do)."""
    def run(q, k, v, do):
        def loss(q, k, v):
            o = attn(q, k, v)
            return jnp.sum(o.astype(jnp.float32)
                           * do.astype(jnp.float32)), o

        (_, o), grads = jax.value_and_grad(loss, (0, 1, 2),
                                           has_aux=True)(q, k, v)
        return (o, *grads)

    return run


def reference_grads(q, k, v, do, head_chunk=4):
    """o, dq, dk, dv of the oracle, a few query heads at a time (a head's
    float32 scores at 8192 tokens are 256 MiB).  MHA only: the op
    configurations that exist have as many KV heads as query heads."""
    if q.shape[1] != k.shape[1]:
        raise ValueError("reference_grads chunks heads and wants n_kv == n")
    run = jax.jit(fwd_bwd(attention))
    chunks = []
    with jax.default_matmul_precision("highest"):
        for h in range(0, q.shape[1], head_chunk):
            chunks.append(run(*(x[:, h:h + head_chunk].astype(jnp.float32)
                                for x in (q, k, v, do))))
    return tuple(jnp.concatenate(c, axis=1) for c in zip(*chunks))


def parity(got, want):
    """Max abs error of o, dq, dk, dv against the oracle, and whether all
    are inside the bf16 bounds."""
    errs = {name: float(jnp.max(jnp.abs(g.astype(jnp.float32) - w)))
            for name, g, w in zip(("o", "dq", "dk", "dv"), got, want)}
    ok = errs["o"] < TOL_O and all(errs[n] < TOL_GRAD
                                   for n in ("dq", "dk", "dv"))
    return errs, ok
