"""Collectives beyond the ring — parity with the reference's comm.py surface
(broadcast :16, all_reduce :67, synchronize :336, gather_obj :345, rank/size
helpers :74-101), expressed as XLA collectives / jax utilities.

Inside shard_map these are one-op wrappers over lax primitives; outside, the
host-level helpers use jax.experimental.multihost_utils (the multi-controller
analogue of the reference's object gather over NCCL)."""

import jax
import jax.numpy as jnp
from jax import lax
from jax.lax import axis_size


# ---- in-shard_map collectives (SPMD) ----


def all_reduce(x, axis_name: str, op: str = "sum"):
    """Reference comm.all_reduce (comm.py:67): psum/pmax/pmin/pmean."""
    if op == "sum":
        return lax.psum(x, axis_name)
    if op == "mean":
        return lax.pmean(x, axis_name)
    if op == "max":
        return lax.pmax(x, axis_name)
    if op == "min":
        return lax.pmin(x, axis_name)
    raise ValueError(f"unknown op {op!r}")


def broadcast(x, axis_name: str, root: int = 0):
    """Reference comm.broadcast (comm.py:16): every member gets root's copy."""
    idx = lax.axis_index(axis_name)
    masked = jnp.where(idx == root, x, jnp.zeros_like(x))
    return lax.psum(masked, axis_name)


def all_gather(x, axis_name: str, axis: int = 0, tiled: bool = True):
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: str, axis: int = 0):
    return lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


def rank(axis_name: str):
    """Reference comm.get_rank (comm.py:74-101)."""
    return lax.axis_index(axis_name)


def world_size(axis_name: str):
    return axis_size(axis_name)


# ---- host-level helpers (multi-controller) ----


def synchronize():
    """Barrier across processes (reference comm.synchronize, comm.py:336)."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices("burst_attn_tpu.synchronize")
    else:
        for d in jax.live_arrays():
            d.block_until_ready()


def gather_obj(obj):
    """Gather a picklable object from every process to all processes
    (reference comm.gather_obj, comm.py:345)."""
    if jax.process_count() == 1:
        return [obj]
    from jax.experimental import multihost_utils

    return multihost_utils.process_allgather(obj)
