"""Sharded checkpoint/resume for training state.

The reference platform's resume story is PVC persistence plus "model
checkpoints from inside the notebook" (SURVEY.md §5 checkpoint/resume:
workspace PVCs created by JWA, mounted at /home/jovyan, survive
cull/restart cycles). This module is the in-notebook half for the TPU
rebuild: orbax-backed, **sharding-aware** checkpoints of the trainer
state that

- save asynchronously (device→host copy happens at ``save``; the write
  overlaps subsequent train steps);
- restore *into the current mesh* — the target tree carries
  ``NamedSharding``s, so a checkpoint written on one topology (say a
  v5e-8 fsdp ring) restores onto another (a v5p-8 with dp×fsdp) with
  orbax resharding each array straight to its destination shards;
- keep at most ``max_to_keep`` steps and garbage-collect the rest, so a
  notebook PVC or GCS prefix doesn't grow unboundedly.

Works against any fsspec-ish path orbax supports: local PVC paths and
``gs://`` buckets (the platform-side Tensorboard controller reads the
same bucket layout, SURVEY.md §3.5).
"""

from __future__ import annotations

import os
from typing import Any, Optional

import jax
import numpy as np
import orbax.checkpoint as ocp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Params = dict[str, Any]


def _abstract_like(tree, mesh: Mesh, spec_tree):
    """ShapeDtypeStruct tree with NamedShardings — the restore target
    orbax uses to place every array directly onto its mesh shards."""
    shapes = jax.eval_shape(lambda t: t, tree)
    # tree_map flattens spec_tree up to `shapes`' leaves, so a P (which
    # is itself a tuple) arrives whole at each ShapeDtypeStruct leaf.
    return jax.tree_util.tree_map(
        lambda s, p: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, p)
        ),
        shapes,
        spec_tree,
    )


class CheckpointManager:
    """Thin wrapper over ``orbax.checkpoint.CheckpointManager`` pinned to
    this repo's trainer-state layout: ``{"trainable": ..., "opt_state":
    ...}`` plus the step number carried by orbax itself."""

    def __init__(
        self,
        directory: str,
        *,
        max_to_keep: int = 3,
        save_interval_steps: int = 1,
        async_save: bool = True,
    ):
        if "://" not in directory:
            directory = os.path.abspath(directory)
        self.directory = directory
        self._mngr = ocp.CheckpointManager(
            directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                save_interval_steps=save_interval_steps,
                enable_async_checkpointing=async_save,
            ),
        )

    def save(self, step: int, state: Params, *, force: bool = False) -> bool:
        return self._mngr.save(
            step, args=ocp.args.StandardSave(state), force=force
        )

    def restore(self, state_like: Params, step: Optional[int] = None) -> Params:
        """``state_like`` is either a matching tree of arrays or an
        abstract (ShapeDtypeStruct + sharding) tree; arrays land sharded
        per the target's NamedShardings. A numpy leaf restores to a
        numpy array in host memory (no device is touched)."""
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {self.directory}")
        abstract = jax.tree_util.tree_map(
            lambda x: x
            if isinstance(x, (jax.ShapeDtypeStruct, np.ndarray))
            else jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
            state_like,
        )
        return self._mngr.restore(step, args=ocp.args.StandardRestore(abstract))

    def latest_step(self) -> Optional[int]:
        return self._mngr.latest_step()

    def all_steps(self):
        return self._mngr.all_steps()

    def wait_until_finished(self) -> None:
        self._mngr.wait_until_finished()

    def close(self) -> None:
        self._mngr.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
