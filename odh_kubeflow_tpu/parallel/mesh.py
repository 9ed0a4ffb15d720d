"""Device-mesh construction for TPU slices.

The platform half of this repo schedules a notebook pod onto a TPU slice
(see ``controllers/notebook.py``); *this* module is what user code inside
that notebook uses to turn the slice into a ``jax.sharding.Mesh``.

Axis convention (the "How to Scale Your Model" recipe):

- ``data``    — pure data parallelism (gradients all-reduced). On
  multi-slice/multi-host deployments this is the axis that rides DCN.
- ``fsdp``    — data parallelism with parameters/optimizer sharded
  (ZeRO-3 style); XLA inserts all-gather on use, reduce-scatter on grads.
- ``tensor``  — megatron-style tensor parallelism inside a layer; the
  highest-bandwidth (ICI-neighbour) axis.
- ``context`` — sequence/context parallelism (ring attention over
  ``ppermute``, see ``parallel/ring_attention.py``).

The reference platform has no parallelism layer at all (SURVEY.md §2.4:
distribution there is one-StatefulSet-pod-per-notebook); for the TPU
rebuild the mesh is a first-class runtime component.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS_DATA = "data"
AXIS_FSDP = "fsdp"
AXIS_TENSOR = "tensor"
AXIS_CONTEXT = "context"
AXIS_EXPERT = "expert"
AXIS_PIPE = "pipe"

# Order matters: earlier axes change slowest across the physical device
# grid, so put the bandwidth-hungry axes (tensor, context) last — they
# land on ICI-adjacent chips, and `data` (the gradient all-reduce that
# can tolerate DCN latency) lands across hosts/slices. `pipe` comes
# right after data: stage-to-stage transfers are point-to-point and
# latency-tolerant (the GPipe bubble hides them), so pipeline stages
# are the natural thing to spread across slices. `expert` sits in the
# middle: its all-to-all wants ICI but tolerates more hops than
# tensor-parallel all-reduces.
AXIS_ORDER = (
    AXIS_DATA,
    AXIS_PIPE,
    AXIS_FSDP,
    AXIS_EXPERT,
    AXIS_CONTEXT,
    AXIS_TENSOR,
)


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Logical mesh shape. Product must equal the device count."""

    data: int = 1
    pipe: int = 1
    fsdp: int = 1
    expert: int = 1
    context: int = 1
    tensor: int = 1

    @property
    def shape(self) -> tuple[int, ...]:
        return (
            self.data,
            self.pipe,
            self.fsdp,
            self.expert,
            self.context,
            self.tensor,
        )

    @property
    def num_devices(self) -> int:
        return math.prod(self.shape)

    def validate(self, n_devices: int) -> None:
        if self.num_devices != n_devices:
            raise ValueError(
                f"mesh shape {self.shape} = {self.num_devices} devices, "
                f"but {n_devices} devices are available"
            )


def local_mesh_config(devices: Optional[Sequence[jax.Device]] = None) -> MeshConfig:
    """Default mesh for whatever is attached: everything on fsdp.

    FSDP is the right single-axis default for fine-tuning: parameters and
    optimizer state shard across the slice, and XLA overlaps the
    all-gathers with compute.
    """
    n = len(devices if devices is not None else jax.devices())
    return MeshConfig(fsdp=n)


def build_mesh(
    config: Optional[MeshConfig] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    if config is None:
        config = local_mesh_config(devices)
    config.validate(len(devices))
    if len(devices) == 1 or _all_cpu(devices):
        device_grid = np.array(devices).reshape(config.shape)
    else:
        device_grid = mesh_utils.create_device_mesh(
            config.shape, devices=devices
        )
    return Mesh(device_grid, AXIS_ORDER)


def _all_cpu(devices: Sequence[jax.Device]) -> bool:
    """Virtual CPU devices have no topology: any assignment is
    functionally correct, so list order is used. On an accelerator a
    failed topology-aware assignment is an error, never a reshape —
    list order there silently puts the bandwidth-hungry axes on
    non-adjacent chips."""
    return all(d.platform == "cpu" for d in devices)


def build_hybrid_mesh(
    ici: MeshConfig,
    dcn: MeshConfig,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Multi-slice mesh: ``ici`` factors live inside a slice (fast
    ICI), ``dcn`` factors span slices (data-center network). The per-
    axis extents multiply — e.g. ici=(fsdp=8) × dcn=(data=4) is four
    v5e-8 slices doing FSDP inside each slice and gradient all-reduce
    across slices, the standard multislice recipe. On real TPU
    multislice the topology-aware assignment keeps DCN axes on slice
    boundaries; virtual CPU devices take a plain reshape (functionally
    identical)."""
    devices = list(devices if devices is not None else jax.devices())
    shape = tuple(i * d for i, d in zip(ici.shape, dcn.shape))
    if math.prod(shape) != len(devices):
        raise ValueError(
            f"ici {ici.shape} × dcn {dcn.shape} = {math.prod(shape)} devices, "
            f"but {len(devices)} are available"
        )
    if _all_cpu(devices):
        device_grid = np.array(devices).reshape(shape)
    else:
        device_grid = mesh_utils.create_hybrid_device_mesh(
            ici.shape, dcn.shape, devices=devices
        )
    return Mesh(device_grid, AXIS_ORDER)


def batch_spec() -> P:
    """PartitionSpec for a [batch, seq] token batch.

    The expert axis doubles as a data axis for non-MoE computation
    (the standard MoE-training layout): dense layers see it as more
    batch shards, and the MoE dispatch einsum turns it into the
    token⇄expert all-to-all."""
    return P((AXIS_DATA, AXIS_FSDP, AXIS_EXPERT), AXIS_CONTEXT)


def constrain(x, spec: P):
    """``with_sharding_constraint`` that degrades to a no-op when no mesh
    is active (single-device eager use), and drops spec axes the active
    mesh doesn't define (partial meshes in tests) or that are Manual
    (inside ``shard_map`` — e.g. model code running under the pipeline
    combinator — constraints may only name Auto axes)."""
    am = jax.sharding.get_abstract_mesh()
    if am.empty:
        return x
    names = {
        name
        for name, t in zip(am.axis_names, am.axis_types)
        if t == jax.sharding.AxisType.Auto
    }
    if not names:
        return x

    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in names)
            return kept if kept else None
        return entry if entry in names else None

    filtered = P(*(keep(e) for e in spec))
    return jax.lax.with_sharding_constraint(x, filtered)


def named_sharding(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def shard_tree(tree, mesh: Mesh, spec_tree):
    """Device-put a pytree according to a matching tree of PartitionSpecs."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, spec_tree
    )
