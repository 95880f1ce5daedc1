from omnia_tpu.parallel.mesh import make_mesh, single_device_mesh
from omnia_tpu.parallel.sharding import (
    init_sharded,
    named_sharding_tree,
    shard_pytree,
)
from omnia_tpu.parallel.ring_attention import ring_attention
from omnia_tpu.parallel.pipeline import pipeline_forward
from omnia_tpu.parallel.distributed import maybe_initialize_distributed

__all__ = [
    "make_mesh",
    "single_device_mesh",
    "shard_pytree",
    "init_sharded",
    "named_sharding_tree",
    "ring_attention",
    "pipeline_forward",
    "maybe_initialize_distributed",
]
