"""Collective wrappers (parallel/collectives.py) — reference comm.py parity."""

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from burst_attn_tpu.parallel import collectives as C


def _mesh():
    return Mesh(np.array(jax.devices()[:8]), ("sp",))


def _run(fn, x, out_specs=P("sp")):
    return shard_map(
        fn, mesh=_mesh(), in_specs=P("sp"), out_specs=out_specs, check_vma=False
    )(x)


def test_all_reduce_sum():
    x = jnp.arange(8.0)
    out = _run(lambda s: C.all_reduce(s, "sp"), x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, x.sum()))


def test_broadcast():
    x = jnp.arange(8.0)
    out = _run(lambda s: C.broadcast(s, "sp", root=3), x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, 3.0))


def test_rank_and_size():
    x = jnp.zeros(8)
    out = _run(lambda s: s + C.rank("sp") * 1.0 + C.world_size("sp") / 100.0, x)
    np.testing.assert_allclose(np.asarray(out), np.arange(8) + 0.08)


def test_all_gather_reduce_scatter_roundtrip():
    x = jnp.arange(16.0)
    def fn(s):
        g = C.all_gather(s, "sp", axis=0)  # every shard sees the full array
        return C.reduce_scatter(g, "sp", axis=0) / 8.0  # psum_scatter undoes it
    out = _run(fn, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x))


def test_synchronize_and_gather_obj_single_process():
    C.synchronize()
    assert C.gather_obj({"a": 1}) == [{"a": 1}]
