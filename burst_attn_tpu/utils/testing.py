"""Test helpers — the reference's numeric checker (test/checker.py) rebuilt.

check_close keeps the reference's tolerance convention (rtol=1e-3, atol=1e-2
in half precision, test/checker.py:10) and its NaN probe (checker.py:21).
"""

import jax.numpy as jnp
import numpy as np

RTOL = 1e-3
ATOL = 1e-2


def check_close(a, b, rtol=RTOL, atol=ATOL, msg=""):
    a = np.asarray(a, dtype=np.float32)
    b = np.asarray(b, dtype=np.float32)
    assert not np.isnan(a).any(), f"NaN in actual {msg}"
    assert not np.isnan(b).any(), f"NaN in expected {msg}"
    np.testing.assert_allclose(a, b, rtol=rtol, atol=atol, err_msg=msg)


def random_qkv(key, batch, heads, seq, dim, kv_heads=None, dtype=jnp.bfloat16,
               d_v=None):
    """q, k `dim` wide; v and do `d_v` wide (None: dim)."""
    import jax

    kv_heads = kv_heads or heads
    d_v = dim if d_v is None else d_v
    kq, kk, kv, kg = jax.random.split(key, 4)
    q = jax.random.normal(kq, (batch, heads, seq, dim), dtype=dtype)
    k = jax.random.normal(kk, (batch, kv_heads, seq, dim), dtype=dtype)
    v = jax.random.normal(kv, (batch, kv_heads, seq, d_v), dtype=dtype)
    do = jax.random.normal(kg, (batch, heads, seq, d_v), dtype=dtype)
    return q, k, v, do
