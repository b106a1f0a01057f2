"""The one generator of inputs: it reads a traffic file's parameters and
makes, from the seed, what the runners feed the program.  A new mix is a new
file of parameters under `traffic/`, never new code."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


def attention_inputs(seed, mesh, *, batch, seq, heads, kv_heads, d_head,
                     dtype=jnp.bfloat16):
    """q, do [B, N, S, D] and k, v [B, N_kv, S, D], standard normal, drawn
    on the mesh in ONE jitted call, sharded over its "sp" axis along the
    sequence (the same numbers whatever the mesh)."""
    def draw(key):
        kq, kk, kv, kdo = jax.random.split(key, 4)
        q_shape = (batch, heads, seq, d_head)
        kv_shape = (batch, kv_heads, seq, d_head)
        return (jax.random.normal(kq, q_shape, dtype),
                jax.random.normal(kk, kv_shape, dtype),
                jax.random.normal(kv, kv_shape, dtype),
                jax.random.normal(kdo, q_shape, dtype))

    sharding = NamedSharding(mesh, P(None, None, "sp", None))
    return jax.jit(draw, out_shardings=sharding)(jax.random.PRNGKey(seed))


def write_token_file(path, seed, *, batch, seq, file_windows, token_ids, **_):
    """A BATD file of `file_windows` batches' worth of windows, ids uniform
    under `token_ids`: learning which ids occur at all takes the loss from
    ln(vocab) towards ln(token_ids), so a falling loss is a real check
    (chip_smoke.py's recipe)."""
    from burst_attn_tpu.data import write_token_file as write

    write(path, np.random.default_rng(seed).integers(
        0, token_ids, size=file_windows * batch * (seq + 1)))
