"""A decoder-only LM's forward pass and next-token loss in plain float32:
RMSNorm, GQA with rotary embeddings (the half-split convention), dense causal
softmax attention, SwiGLU, untied output head.  Mistral-7B's block as its
`config.json` describes it; no kernel, no remat, no sharding.

Departure from the source, because the program under test hard-codes it:
the norm epsilon is the configuration file's `rms_norm_eps` as RUN (1e-6),
not Mistral's published 1e-5.

Parameters are the trainer's own pytree (the weights under test, cast up):
embed [V, D], layers[i] {attn_norm, wq [D, N, H], wk, wv [D, N_kv, H],
wo [N, H, D], mlp_norm, w_gate, w_up [D, F], w_down [F, D]}, final_norm,
lm_head [V, D].
"""

import jax
import jax.numpy as jnp

from .dense_attention import attention

# Relative RMS error of the system's bf16 logits against this float32
# forward, and the absolute difference of the two losses.  Measured on the
# chip at 1,024 tokens, seeded weights, eight seeds (PERF.md, PR 24):
# 1.79e-2 to 1.85e-2 and 1e-4 to 1.9e-3: bf16 keeps 8 bits, and some forty
# roundings between the embedding and the logits add up to that.  The bounds
# are about twice and five times the largest seen; activations in an 8-bit
# float (3 bits of mantissa, 32 x bf16's rounding) would miss them by far.
TOL_LOGITS_REL_RMS = 4e-2
TOL_LOSS_ABS = 1e-2


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rope(x, positions, theta):
    h = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, h, 2, dtype=jnp.float32) / h))
    angles = positions[:, None, :, None].astype(jnp.float32) * freqs
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits(params, tokens, *, rope_theta, rms_norm_eps):
    """tokens [B, S] in natural order -> float32 logits [B, S, V]."""
    f32 = lambda a: a.astype(jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1])[None],
                                 tokens.shape)
    x = f32(params["embed"])[tokens]
    for p in params["layers"]:
        h = _rms_norm(x, p["attn_norm"], rms_norm_eps)
        q = _rope(jnp.einsum("bsd,dnh->bnsh", h, f32(p["wq"])), positions,
                  rope_theta)
        k = _rope(jnp.einsum("bsd,dnh->bnsh", h, f32(p["wk"])), positions,
                  rope_theta)
        v = jnp.einsum("bsd,dnh->bnsh", h, f32(p["wv"]))
        x = x + jnp.einsum("bnsh,nhd->bsd", attention(q, k, v), f32(p["wo"]))
        h = _rms_norm(x, p["mlp_norm"], rms_norm_eps)
        gate = jax.nn.silu(h @ f32(p["w_gate"])) * (h @ f32(p["w_up"]))
        x = x + gate @ f32(p["w_down"])
    x = _rms_norm(x, params["final_norm"], rms_norm_eps)
    return x @ f32(params["lm_head"]).T


def loss(logit, labels):
    """Mean next-token cross entropy; labels < 0 are masked out."""
    valid = labels >= 0
    logp = jax.nn.log_softmax(logit, axis=-1)
    nll = -jnp.take_along_axis(logp, jnp.where(valid, labels, 0)[..., None],
                               axis=-1)[..., 0]
    return jnp.sum(jnp.where(valid, nll, 0.0)) / jnp.maximum(valid.sum(), 1)


def reference(params, tokens, labels, *, rope_theta, rms_norm_eps):
    """(logits, loss) under `default_matmul_precision("highest")`."""
    def run(params, tokens, labels):
        out = logits(params, tokens, rope_theta=rope_theta,
                     rms_norm_eps=rms_norm_eps)
        return out, loss(out, labels)

    with jax.default_matmul_precision("highest"):
        return jax.jit(run)(params, tokens, labels)


def compare(got_logits, got_loss, want_logits, want_loss):
    """Errors of the system against the reference, and whether in bounds."""
    diff = got_logits.astype(jnp.float32) - want_logits
    errs = {
        "logits_rel_rms": float(jnp.sqrt(jnp.mean(diff * diff)
                                         / jnp.mean(want_logits ** 2))),
        "logits_max_abs": float(jnp.max(jnp.abs(diff))),
        "loss_abs": abs(float(got_loss) - float(want_loss)),
    }
    ok = (errs["logits_rel_rms"] < TOL_LOGITS_REL_RMS
          and errs["loss_abs"] < TOL_LOSS_ABS)
    return errs, ok
