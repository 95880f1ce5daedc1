"""A test-tiny model that echoes, and a prompt that echoes itself.

Prompt-lookup speculation proposes what followed the context's trailing
n-gram earlier in the context. Whether a *random* tiny model ever emits
something the lookup has seen is an accident of its numerics; tests that
need proposals (and acceptances) get them by construction from this pair.
"""

ECHO_PERIOD = 8
# The cycle 0 → 1 → … → 7 → 0, entered mid-way and run past one period.
ECHO = [5, 6, 7, 0, 1, 2, 3, 4, 5, 6, 7, 0, 1, 2]


def echo_params():
    """test-tiny float32 parameters under which greedy decode provably
    cycles t → (t + 1) mod ECHO_PERIOD: every layer's output projection
    is zero, so the residual stream is the token's embedding (a basis
    vector), and lm_head sends basis vector t to token t + 1."""
    import jax
    import jax.numpy as jnp

    from omnia_tpu.models import get_config, llama

    cfg = get_config("test-tiny")
    p = llama.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    p["layers"]["attn"]["wo"] = jnp.zeros_like(p["layers"]["attn"]["wo"])
    p["layers"]["mlp"]["wd"] = jnp.zeros_like(p["layers"]["mlp"]["wd"])
    p["embed"] = jnp.eye(cfg.vocab_size, cfg.hidden_size, dtype=jnp.float32)
    t = jnp.arange(ECHO_PERIOD)
    p["lm_head"] = jnp.zeros_like(p["lm_head"]).at[
        t, (t + 1) % ECHO_PERIOD
    ].set(10.0)
    return p
