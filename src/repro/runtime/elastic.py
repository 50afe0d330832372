"""Elastic re-meshing: shrink the data axis when hosts fail, reshard
from checkpoint, continue.

Policy: the ``model`` (TP/EP) axis is sacred — losing a chip there
breaks weight shards, so evictions remove whole data-parallel rows.
``plan_remesh`` computes the largest viable data extent given survivors;
``reshard`` lands a host pytree onto the new mesh (restore path — the
checkpoint is mesh-agnostic since it stores full arrays).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh

from repro.distributed import sharding as shlib
from repro.launch.mesh import auto_mesh


@dataclass(frozen=True)
class RemeshPlan:
    old_shape: Tuple[int, ...]
    new_shape: Tuple[int, ...]
    axes: Tuple[str, ...]
    dropped_hosts: Tuple[int, ...]
    global_batch_scale: float  # keep per-replica batch fixed; scale global


def plan_remesh(mesh_shape: Sequence[int], axes: Sequence[str],
                failed_data_rows: Sequence[int]) -> RemeshPlan:
    """Drop failed rows from the ``data`` axis; keep ``model`` intact."""
    shape = tuple(mesh_shape)
    axes = tuple(axes)
    di = axes.index("data")
    new_data = shape[di] - len(set(failed_data_rows))
    if new_data < 1:
        raise RuntimeError("no healthy data-parallel rows remain")
    new_shape = shape[:di] + (new_data,) + shape[di + 1:]
    return RemeshPlan(
        old_shape=shape,
        new_shape=new_shape,
        axes=axes,
        dropped_hosts=tuple(sorted(set(failed_data_rows))),
        global_batch_scale=new_data / shape[di],
    )


def build_mesh(plan: RemeshPlan, devices=None) -> Mesh:
    n = 1
    for s in plan.new_shape:
        n *= s
    devices = (devices if devices is not None else jax.devices())[:n]
    return auto_mesh(plan.new_shape, plan.axes, devices)


def reshard(tree: Any, spec_tree: Any, mesh: Mesh, rules) -> Any:
    """device_put a host pytree onto a new mesh under the same rules."""
    sh = shlib.tree_shardings_from_specs(spec_tree, mesh, rules)
    return jax.tree.map(lambda x, s: jax.device_put(x, s), tree, sh)
