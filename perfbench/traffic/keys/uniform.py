"""uniform: full-range int32 keys, each value equally likely, drawn below
INT32_MAX (the untagged sort path reserves that value as its pad
sentinel)."""
from harness.traffic import INT32_MAX, INT32_MIN


def draw(key, n: int, mix: dict):
    """n keys from a jax PRNG key, traced inside one jitted call."""
    import jax
    import jax.numpy as jnp
    return jax.random.randint(key, (n,), INT32_MIN, INT32_MAX,
                              dtype=jnp.int32)
