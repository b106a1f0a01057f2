"""PolyNorm, the gated MLP activation of the Motif family: the gate product u
becomes a learned mix of u, u^2 and u^3, each RMS-normalised over the MLP's
own width, plus a bias:

    PolyNorm(u) = s * (w1 n(u) + w2 n(u^2) + w3 n(u^3) + clip(b, -c, c)),
    n(z) = z / sqrt(mean(z^2) + eps)

with s the output scale and c the bias clamp.  Each MLP has its own four
weights (w1, w2, w3, b), one float32 leaf [4]; the routed experts of a layer
one row each, [experts, 4].  Readers: models/transformer.py (the dense MLP),
parallel/moe.py (`moe_held`'s `act`: shared and routed experts).
"""

from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class PolyNorm:
    """The activation's constants; the weights are leaves of the model."""

    output_scale: float = 0.5
    bias_clamp: float = 0.5
    eps: float = 1e-6


def poly_norm(u, w, spec: PolyNorm):
    """PolyNorm of `u` [..., F] with weights `w` [..., 4] (w1, w2, w3, b),
    whose leading dims broadcast against u's: float32 inside, u's dtype
    out."""
    dtype = u.dtype
    u = u.astype(jnp.float32)
    w = w.astype(jnp.float32)

    def n(z):
        return z * jax.lax.rsqrt(jnp.mean(z * z, axis=-1, keepdims=True)
                                 + spec.eps)

    out = (w[..., 0:1] * n(u) + w[..., 1:2] * n(u * u)
           + w[..., 2:3] * n(u * u * u)
           + jnp.clip(w[..., 3:4], -spec.bias_clamp, spec.bias_clamp))
    return (spec.output_scale * out).astype(dtype)


def init_weights(key, shape=()):
    """Seeded weights [*shape, 4]: each w near 1/3 (the family's own start),
    the bias near 0, each drawn apart so that no two terms can pass for each
    other."""
    draw = 0.1 * jax.random.normal(key, (*shape, 4), jnp.float32)
    return draw + jnp.asarray([1 / 3, 1 / 3, 1 / 3, 0.0], jnp.float32)
