"""Ring schedule verification — the reference validates its double-ring
schedule by logging each rank's visited partition ids (`record`,
burst_attn_interface.py:213-217); here the in-shard_map schedule
(partition_at_round) must replay the host-side expectation (ring_schedule)
on simulated meshes."""

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
import pytest

from burst_attn_tpu.parallel.ring import partition_at_round, ring_schedule


@pytest.mark.parametrize("shape", [(8,), (2, 4), (4, 2)])
def test_schedule_matches_host_expectation(shape):
    if len(shape) == 1:
        names, inter, intra = ("sp",), 1, shape[0]
        intra_axis, inter_axis = "sp", None
    else:
        names, (inter, intra) = ("inter", "intra"), shape
        intra_axis, inter_axis = "intra", "inter"
    world = inter * intra
    mesh = Mesh(np.array(jax.devices()[:world]).reshape(shape), names)

    def fn(x):
        ids = [partition_at_round(jnp.int32(r), intra_axis, inter_axis)
               for r in range(world)]
        return jnp.stack(ids)[None] + 0 * x.astype(jnp.int32)

    out = shard_map(
        fn, mesh=mesh,
        in_specs=P(names if len(names) > 1 else names[0]),
        out_specs=P(names if len(names) > 1 else names[0], None),
        check_vma=False,
    )(jnp.zeros(world))
    np.testing.assert_array_equal(np.asarray(out), ring_schedule(intra, inter))


def test_schedule_visits_every_partition():
    sched = ring_schedule(4, 2)
    for row in sched:
        assert sorted(row) == list(range(8))
