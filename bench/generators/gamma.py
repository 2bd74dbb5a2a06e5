"""Actual run times: Gamma around each EET entry with coefficient of
variation ``cv_run`` (the paper's execution-time uncertainty)."""
import jax
import jax.numpy as jnp

ROLE = "runtime"


def sample(key, eet, task_type, *, cv_run: float):
    means = jnp.asarray(eet)[task_type]  # (N, M)
    shape = 1.0 / cv_run**2
    draw = jax.random.gamma(key, shape, means.shape)
    return (draw * (means * cv_run**2)).astype(jnp.float32)
