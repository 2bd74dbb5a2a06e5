"""Deadlines by the paper's Eq. 4: arrival + mean EET of the task's type
over the machines + the mean of those type means."""
import jax.numpy as jnp

ROLE = "deadline"


def deadlines(arrival, task_type, eet):
    eet = jnp.asarray(eet, jnp.float32)
    e_bar_i = eet.mean(axis=1)
    e_bar = e_bar_i.mean()
    return jnp.asarray(arrival, jnp.float32) + e_bar_i[task_type] + e_bar
