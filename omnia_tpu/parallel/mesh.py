"""Device-mesh construction.

Axis conventions used across omnia_tpu:

- "dp": data parallel — request batch slots in serving, global batch in
  training/eval. Maps across slices/hosts (DCN-tolerant: only batch-sharded
  activations cross it).
- "tp": tensor parallel — attention heads, FFN hidden, expert dim, vocab.
  Must stay inside a slice so its all-reduces ride ICI.

The reference platform has no device meshes at all (its parallelism is K8s
replica scaling — reference internal/controller/autoscaling.go:74); the mesh
is the new TPU-native scaling substrate underneath that same autoscaling
surface.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.experimental import mesh_utils
from jax.sharding import Mesh


def make_mesh(
    dp: int = 1,
    tp: int = 1,
    sp: int = 1,
    pp: int = 1,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a ("dp", "tp") mesh, with "pp" and/or "sp" axes inserted
    (("dp", "pp", "sp", "tp") order) when those degrees exceed 1.

    "sp" (sequence/context parallel — ring attention) sits between dp and
    tp so that the ring ppermute hops between ICI neighbors: consecutive
    devices differ in the sp coordinate while sharing the dp coordinate.

    "pp" (pipeline parallel — parallel/pipeline.py) sits OUTSIDE sp/tp:
    a pp stage boundary is the cross-host/DCN cut (one activation hop per
    microbatch), so all of a stage's tp/sp collectives stay inside the
    stage's slice on ICI while consecutive pp coordinates map to
    different hosts.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = dp * tp * sp * pp
    if len(devices) < n:
        raise ValueError(
            f"mesh {dp}x{pp}x{sp}x{tp} needs {n} devices, have {len(devices)}"
        )
    dims = [("dp", dp), ("pp", pp), ("sp", sp), ("tp", tp)]
    keep = [
        (name, size) for name, size in dims
        if size > 1 or name in ("dp", "tp")
    ]
    shape = tuple(size for _, size in keep)
    names = tuple(name for name, _ in keep)
    # Raises on a TPU topology the shape does not fit — a plain reshape
    # there would put "tp" neighbours on chips that are not.
    dev_array = mesh_utils.create_device_mesh(shape, devices=devices[:n])
    return Mesh(dev_array, names)


def single_device_mesh() -> Mesh:
    return make_mesh(1, 1)
