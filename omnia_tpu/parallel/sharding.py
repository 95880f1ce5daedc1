"""Sharding helpers: map PartitionSpec pytrees onto a mesh."""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec


def _is_spec(x) -> bool:
    return isinstance(x, PartitionSpec)


def named_sharding_tree(specs, mesh: Mesh):
    """PartitionSpec pytree → NamedSharding pytree."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=_is_spec
    )


def shard_pytree(tree, specs, mesh: Mesh):
    """device_put every leaf of `tree` with the matching spec in `specs`."""
    return jax.tree.map(
        lambda s, x: jax.device_put(x, NamedSharding(mesh, s)),
        specs,
        tree,
        is_leaf=_is_spec,
    )


def init_sharded(fn, specs, mesh):
    """``fn()`` under jit with every leaf of the result born on its own
    shards: no device ever holds the whole tree. This is how the engine
    makes seeded parameters and zeroed KV — built whole on one device
    and moved, a model that only fits sharded never fits. ``mesh=None``
    is the same program unsharded, so one seed gives one set of values
    whatever the mesh (eager and jitted init differ in the last bit)."""
    out = None if mesh is None else named_sharding_tree(specs, mesh)
    return jax.jit(fn, out_shardings=out)()
