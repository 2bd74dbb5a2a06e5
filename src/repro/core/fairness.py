"""Fairness measure over task types (Sec. V, Algorithm 4).

The rates and the mask are jitted: Eq. 3's exact float32 arithmetic is a
few hundred integer ops, and a jitted function is traced once per input
shape and reused by every sweep that traces it again.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import equations


@jax.jit
def completion_rates(completed_by_type, arrived_by_type):
    """cr_i = on-time completions of type i / arrivals of type i (so far).

    Types with no arrivals yet report rate 1.0 (they cannot have suffered).
    The quotient is IEEE float32 on every backend
    (:func:`~repro.core.equations.ratio_f32`), for counts below 2**30.
    """
    arrived = jnp.asarray(arrived_by_type).astype(jnp.int32)
    completed = jnp.asarray(completed_by_type).astype(jnp.int32)
    rate = equations.ratio_f32(completed, jnp.maximum(arrived, 1))
    return jnp.where(arrived > 0, rate, jnp.float32(1.0))


@functools.partial(jax.jit, static_argnames=("min_arrivals",))
def suffered_types(completed_by_type, arrived_by_type, fairness_factor,
                   min_arrivals: int = 1):
    """Algorithm 4 — the suffered-task-type mask.

    A type is suffered iff its completion rate is <= the fairness limit
    (Eq. 3). ``min_arrivals`` guards cold-start noise: a type is only
    judged once it has arrived at least that many times.
    """
    cr = completion_rates(completed_by_type, arrived_by_type)
    eps = equations.fairness_limit(cr, fairness_factor)
    judged = jnp.asarray(arrived_by_type) >= min_arrivals
    return (cr <= eps) & judged


def jain_index(values):
    """Jain's fairness index over per-type completion rates (reporting aid;
    1.0 = perfectly fair). Not part of the paper's method, used in benchmarks
    to summarize Fig. 7-style bar charts as a scalar."""
    v = jnp.asarray(values, jnp.float32)
    s1 = v.sum()
    s2 = (v * v).sum()
    n = v.shape[0]
    return jnp.where(s2 > 0, s1 * s1 / (n * s2), 1.0)
