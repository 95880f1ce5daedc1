"""Seeded weights, born on the device (sharded under a mesh) in one jitted
call, in the type they are served in. The key is an ARGUMENT of the jitted
call: the engine's own `params=None` path closes over the key, which bakes
the seed into the program, so every new seed compiled it anew (22 s of
set-up a seed on the chip, PR 24)."""

from __future__ import annotations

import jax


def seeded_params(model_cfg, engine_cfg, devices, seed: int, dtype):
    from omnia_tpu.models import llama
    from omnia_tpu.parallel import make_mesh, named_sharding_tree

    out = None
    if engine_cfg.dp * engine_cfg.tp * engine_cfg.sp > 1:
        mesh = make_mesh(engine_cfg.dp, engine_cfg.tp, sp=engine_cfg.sp, devices=devices)
        out = named_sharding_tree(llama.param_specs(model_cfg), mesh)
    init = jax.jit(lambda key: llama.init_params(model_cfg, key, dtype=dtype),
                   out_shardings=out)
    return init(jax.random.key(seed & 0x7FFFFFFF))
