"""Device-mesh construction from TPU topology.

The TPU-native replacement for the reference master's membership / rank
duties (reference: AllreduceMaster.scala:30-44, :66-74): instead of actors
registering over gossip and being handed ranks by arrival order, ranks ARE
mesh coordinates — ``jax.devices()`` enumerates the slice in topology order
and a named :class:`jax.sharding.Mesh` fixes each chip's position. Collective
traffic then rides ICI along mesh axes; cross-host coordination rides the
JAX distributed runtime (runtime/coordinator.py).

Meshes are created with ``Auto`` axis types: the framework's collective ops
use ``shard_map`` + explicit ``lax`` collectives (psum / psum_scatter /
all_gather / ppermute), which operate on manual shards. (JAX >= 0.9 defaults
``make_mesh`` to Explicit axes, which type-checks ordinary indexing against
global shardings instead — not what a hand-scheduled collective layer wants.)
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Named axis sizes for the standard 5-axis layout: pipeline, data,
    expert, sequence, tensor(model). Size 1 axes cost nothing — they simply
    don't shard. Axis ORDER is the bandwidth hierarchy: the last (fastest-
    varying) axis maps to nearest-neighbor ICI links, so tp — the most
    latency/bandwidth-hungry collective traffic — sits innermost, while pp
    — one point-to-point activation handoff per stage per tick — sits
    outermost, happy to ride the longest hops (or DCN across slices)."""

    dp: int = 1
    tp: int = 1
    sp: int = 1
    ep: int = 1
    pp: int = 1

    @property
    def size(self) -> int:
        return self.dp * self.tp * self.sp * self.ep * self.pp

    def axis_names(self) -> tuple[str, ...]:
        return ("pp", "dp", "ep", "sp", "tp")

    def axis_sizes(self) -> tuple[int, ...]:
        return (self.pp, self.dp, self.ep, self.sp, self.tp)


def make_device_mesh(spec: Optional[MeshSpec] = None,
                     devices: Optional[Sequence[jax.Device]] = None,
                     axis_names: Optional[Sequence[str]] = None,
                     axis_sizes: Optional[Sequence[int]] = None) -> Mesh:
    """Build a Mesh over the slice (or an explicit device list).

    Either pass a :class:`MeshSpec` (standard dp/tp/sp/ep axes) or raw
    ``axis_names`` + ``axis_sizes``. Device order follows ``jax.devices()``
    — TPU topology order, so the fastest-varying (last) axis rides
    nearest-neighbor ICI links.
    """
    devices = list(devices if devices is not None else jax.devices())
    if spec is not None:
        names, sizes = spec.axis_names(), spec.axis_sizes()
    else:
        if axis_names is None or axis_sizes is None:
            raise ValueError("pass either spec or axis_names+axis_sizes")
        names, sizes = tuple(axis_names), tuple(axis_sizes)
    total = math.prod(sizes)
    if total != len(devices):
        raise ValueError(
            f"mesh of {sizes} needs {total} devices, have {len(devices)}")
    dev_array = np.asarray(devices).reshape(sizes)
    return Mesh(dev_array, names,
                axis_types=(AxisType.Auto,) * len(names))


def single_axis_mesh(axis_name: str = "dp",
                     devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """All available devices on one axis — the pure-DP layout matching the
    reference's flat worker group."""
    devices = list(devices if devices is not None else jax.devices())
    return make_device_mesh(axis_names=(axis_name,),
                            axis_sizes=(len(devices),), devices=devices)


def local_axis_size(mesh: Mesh, axis_name: str) -> int:
    return mesh.shape[axis_name]


def place_global_batch(array, mesh: Mesh, spec: PartitionSpec):
    """Build a GLOBAL jax.Array for ``array`` (an identical host copy on
    every process — the deterministic-batch contract of data.py makes this
    free) sharded by ``spec`` over ``mesh``. Each process supplies only its
    addressable shards, so this works unchanged from one process to a
    multi-host mesh where no process could hold the whole array on device.
    """
    sharding = NamedSharding(mesh, spec)
    return jax.make_array_from_callback(
        array.shape, sharding, lambda idx: array[idx])


def place_tree(tree: Any, specs: Any, mesh: Mesh) -> Any:
    """Place a pytree onto ``mesh`` with per-leaf PartitionSpecs. Values are
    preserved — only placement/sharding changes. The one canonical placement
    helper: initial sharding of host-built state (models/train.py) and
    post-churn resharding (runtime/elastic.py) both route here."""
    def place(x, s):
        sharding = NamedSharding(mesh, s)
        if not sharding.is_fully_addressable and \
                getattr(x, "is_fully_addressable", True):
            # multi-process mesh, host-replicated value (every process
            # built the same tree — the deterministic-init contract):
            # supply only this process's shards. jax.device_put would
            # first run multihost_utils.assert_equal — one cross-process
            # broadcast per leaf to check what that contract already
            # guarantees
            x = np.asarray(x)
            return jax.make_array_from_callback(
                x.shape, sharding, lambda idx: x[idx])
        return jax.device_put(x, sharding)

    return jax.tree.map(
        place, tree, specs,
        is_leaf=lambda x: isinstance(x, PartitionSpec))
