"""Stationary Poisson arrivals: exponential gaps at the nominal rate
(the paper's Sec. VI-A workload)."""
import jax
import jax.numpy as jnp

ROLE = "arrivals"


def sample(key, n_tasks: int, rate):
    gaps = jax.random.exponential(key, (n_tasks,)) / rate
    return jnp.cumsum(gaps).astype(jnp.float32)
