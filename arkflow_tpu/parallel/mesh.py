"""Device mesh + sharding helpers.

The reference's only multi-node mechanism is Ballista SQL offload
(SURVEY.md section 2.7); it has no model parallelism. Here multi-chip scale is
first-class: a ``jax.sharding.Mesh`` over (dp, tp, sp) axes, parameter
PartitionSpec pytrees from each model family, and GSPMD inserting the
collectives (the scaling-book recipe: pick a mesh, annotate shardings, let XLA
place psum/all-gather/reduce-scatter on ICI).

Axes:
- ``dp``  data parallel (batch)
- ``tp``  tensor parallel (heads / FFN)
- ``sp``  sequence parallel (long-context; pairs with ring attention)
- ``ep``  expert parallel (MoE dispatch/combine)
- ``pp``  pipeline parallel (layer stages; parallel/pipeline.py schedule)
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class MeshSpec:
    dp: int = 1
    tp: int = 1
    sp: int = 1
    ep: int = 1  # expert parallel (MoE)
    pp: int = 1  # pipeline parallel (layer stages)
    axis_names: tuple = ("dp", "tp", "sp", "ep", "pp")

    @property
    def num_devices(self) -> int:
        return self.dp * self.tp * self.sp * self.ep * self.pp


def create_mesh(spec: Optional[MeshSpec] = None, devices=None) -> Mesh:
    """Build a Mesh; defaults to all devices on the dp axis."""
    devices = devices if devices is not None else jax.devices()
    if spec is None:
        spec = MeshSpec(dp=len(devices))
    if spec.num_devices > len(devices):
        raise ValueError(
            f"mesh {spec} needs {spec.num_devices} devices, have {len(devices)}"
        )
    arr = np.array(devices[: spec.num_devices]).reshape(
        spec.dp, spec.tp, spec.sp, spec.ep, spec.pp)
    return Mesh(arr, spec.axis_names)


def shard_params(params, specs, mesh: Mesh):
    """Place a param pytree onto the mesh per a PartitionSpec pytree.

    ``specs`` must mirror the param tree (model families produce it via
    ``param_specs``); ``None`` replicates everything.
    """

    def place(x, spec):
        s = NamedSharding(mesh, spec if spec is not None else P())
        return jax.device_put(x, s)

    if specs is None:
        return jax.tree_util.tree_map(lambda x: place(x, None), params)
    return jax.tree_util.tree_map(
        place, params, specs, is_leaf=lambda x: x is None or isinstance(x, P)
    )


def named(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def dp_size(mesh: Mesh) -> int:
    """Size of the data-parallel axis (1 when the mesh has no ``dp``)."""
    return int(dict(zip(mesh.axis_names, mesh.devices.shape)).get("dp", 1))


def tp_size(mesh: Mesh) -> int:
    """Size of the tensor-parallel axis (1 when the mesh has no ``tp``)."""
    return int(dict(zip(mesh.axis_names, mesh.devices.shape)).get("tp", 1))


def replicated(mesh: Mesh) -> NamedSharding:
    """Fully-replicated placement on the mesh (scalars, page tables, token
    ids — everything the paged serving path keeps static-shaped and global)."""
    return NamedSharding(mesh, P())


def kv_pool_sharding(mesh: Mesh, row_major: bool = False) -> NamedSharding:
    """Sharding of the paged KV pools under tensor-parallel serving.

    A pool is ``[layers, num_pages, page, kv_heads, dh]``: the jitted steps'
    in/out sharding, and the constraint on the pools the layer scan carries
    (so GSPMD keeps them partitioned instead of all-gathering hundreds of MB
    per step). KV heads split over ``tp``; the page dims stay replicated, so
    page-table gathers/scatters remain static-shaped and local. A
    ``row_major`` pool (a head narrower than 128 lanes) is ``[layers,
    num_pages, page, kv_heads * dh]``: its last axis splits into the same
    contiguous groups of heads."""
    if tp_size(mesh) > 1:
        return NamedSharding(mesh, P(None, None, None, "tp") if row_major
                             else P(None, None, None, "tp", None))
    return replicated(mesh)


def validate_tp_heads(tp: int, kv_heads: int, who: str = "serving") -> None:
    """Tensor-parallel serving shards attention state over KV heads, so the
    tp degree must divide ``kv_heads`` (GQA keeps ``heads % kv_heads == 0``,
    so query heads divide automatically)."""
    if tp > 1 and kv_heads % tp != 0:
        from arkflow_tpu.errors import ConfigError

        raise ConfigError(
            f"{who}: mesh tp={tp} must divide the model's kv_heads={kv_heads} "
            "(the KV cache shards over heads on the tp axis)")


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for model INPUTS/OUTPUTS under serving: leading (batch) dim
    split over ``dp``, everything else replicated. On a mesh without a dp
    axis (or dp=1) this degenerates to full replication, which is exactly
    what tensor-parallel-only serving wants for its activations' batch dim."""
    return NamedSharding(mesh, P("dp") if dp_size(mesh) > 1 else P())


def param_shardings(params):
    """The sharding each param leaf ALREADY has (post ``shard_params``), as a
    pytree usable for ``jax.jit``'s ``in_shardings`` — pinning params to
    their placement keeps a host-numpy input from dragging them through a
    fresh layout decision on every executable."""
    return jax.tree_util.tree_map(lambda x: x.sharding, params)
