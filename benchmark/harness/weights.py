"""Seeded weights, born on the device (sharded under a mesh) in one jitted
call, in the type they are served in. The key is an ARGUMENT of the jitted
call: the engine's own `params=None` path closes over the key, which bakes
the seed into the program, so every new seed compiled it anew (22 s of
set-up a seed on the chip, PR 24)."""

from __future__ import annotations

import jax

from harness.manifest import DEFAULT_MODEL_MODULE, load_model_module


def seeded_params(model_cfg, engine_cfg, devices, seed: int, dtype,
                  model_module: str = DEFAULT_MODEL_MODULE):
    """`init_params(cfg, key, dtype=)` of the program's model module that the
    configuration names, laid out by its `param_specs(cfg)` under a mesh."""
    from omnia_tpu.parallel import make_mesh, named_sharding_tree

    model = load_model_module(model_module)

    out = None
    if engine_cfg.dp * engine_cfg.tp * engine_cfg.sp > 1:
        mesh = make_mesh(engine_cfg.dp, engine_cfg.tp, sp=engine_cfg.sp, devices=devices)
        out = named_sharding_tree(model.param_specs(model_cfg), mesh)
    init = jax.jit(lambda key: model.init_params(model_cfg, key, dtype=dtype),
                   out_shardings=out)
    return init(jax.random.key(seed & 0x7FFFFFFF))
