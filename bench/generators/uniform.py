"""Task types drawn uniformly over the fleet's types (the paper's mix)."""
import jax
import jax.numpy as jnp

ROLE = "mix"


def sample(key, n_tasks: int, n_types: int):
    return jax.random.randint(key, (n_tasks,), 0, n_types).astype(jnp.int32)
