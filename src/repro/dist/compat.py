"""The few jax entry points whose defaults this repo pins in one place.

Every caller goes through `dist.shard_map(...)` / `dist.compat.make_mesh(...)`
so a change of jax default lands here once.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def shard_map(f, mesh, in_specs, out_specs, *, check: bool = False):
    """`jax.shard_map`; `check` maps onto `check_vma`."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def make_mesh(axis_shapes, axis_names) -> Mesh:
    """`jax.make_mesh` with Auto axes.  jax's default became Explicit
    axes, under which `with_sharding_constraint` and a gather from a
    sharded table are refused; the rule tables of `dist.sharding` leave
    layout to the compiler and need Auto."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def process_allgather(tree):
    """Host-local numpy copy of a tree of (possibly process-spanning)
    global arrays; a collective — every process must call it.  Lives here
    because `multihost_utils` is still under `jax.experimental` and may
    move like `shard_map` did."""
    from jax.experimental import multihost_utils
    return multihost_utils.process_allgather(tree, tiled=True)
