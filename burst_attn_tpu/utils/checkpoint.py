"""Checkpoint / resume for the training layer (orbax-backed).

The reference has NO checkpointing (SURVEY.md §5 — it is an op library and
delegates training-state concerns to host frameworks).  The TPU framework is
a full training stack, so checkpointing is first-class here: sharded arrays
are saved/restored in their native on-device layout (orbax handles per-shard
IO and multi-host coordination), and restore rebuilds the exact
NamedSharding placement from the model's PartitionSpec tree, so a run can
resume on the same mesh without any gather/scatter through host memory.

Usage:
    ckpt = Checkpointer(dir)
    ckpt.save(step, state)                      # state = (params, opt_state)
    state, step = ckpt.restore_latest(cfg, tcfg, mesh)   # sharded restore
"""

import os
from typing import Any, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class Checkpointer:
    """Thin orbax CheckpointManager wrapper bound to one run directory."""

    def __init__(self, directory: str, max_to_keep: int = 3):
        import orbax.checkpoint as ocp

        self._ocp = ocp
        self._mgr = ocp.CheckpointManager(
            os.path.abspath(directory),
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep, create=True
            ),
        )

    def save(self, step: int, state, *, wait: bool = False) -> None:
        """Save (params, opt_state) at `step`; async by default."""
        self._mgr.save(step, args=self._ocp.args.StandardSave(state))
        if wait:
            self._mgr.wait_until_finished()

    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def restore(self, step: int, cfg, tcfg, mesh: Mesh) -> Tuple[Any, int]:
        """Restore the state saved at `step`, placed per the model's
        PartitionSpec tree on `mesh` (no host round trip of full arrays)."""
        from ..models.train import _optimizer, state_specs, init_params

        def shapes():
            params = init_params(jax.random.PRNGKey(0), cfg)
            return params, _optimizer(tcfg).init(params)

        params_shape, opt_shape = jax.eval_shape(shapes)
        pspecs, opt_specs = state_specs(cfg, tcfg, params_shape)

        def as_target(shape_leaf, spec):
            return jax.ShapeDtypeStruct(
                shape_leaf.shape, shape_leaf.dtype,
                sharding=NamedSharding(mesh, spec),
            )

        target = (
            jax.tree.map(as_target, params_shape, pspecs,
                         is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct)),
            jax.tree_util.tree_map(
                as_target, opt_shape, opt_specs,
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct)),
        )
        state = self._mgr.restore(
            step, args=self._ocp.args.StandardRestore(target)
        )
        return state, step

    def restore_latest(self, cfg, tcfg, mesh: Mesh) -> Tuple[Any, Optional[int]]:
        """Restore the most recent checkpoint, or (None, None) if none."""
        step = self.latest_step()
        if step is None:
            return None, None
        return self.restore(step, cfg, tcfg, mesh)

    def close(self):
        self._mgr.wait_until_finished()
        self._mgr.close()
