"""The one spelling of ``jax.shard_map`` this repo uses: manual over the
named axes, replication (VMA) check off."""

from __future__ import annotations

from typing import Optional

import jax


def shard_map(f, mesh, in_specs, out_specs,
              manual_axes: Optional[set] = None):
    """`manual_axes=None` = manual over every mesh axis; a set = manual over
    exactly those axes (the rest stay auto-sharded)."""
    kw = {}
    if manual_axes is not None:
        kw["axis_names"] = set(manual_axes)
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False, **kw
    )
