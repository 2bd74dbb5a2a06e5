"""Pallas TPU kernel for ELARE Phase-I (Algorithm 2, fused).

One pass over the (tasks x machines) grid computes completion times (Eq. 1),
expected energies (Eq. 2), the feasibility mask, and the per-task masked
argmin over machines — the scheduler's hot loop as a single VMEM-resident
kernel. Tasks are tiled ``BLOCK_N`` per grid step; the (padded) machine dim
stays lane-resident.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.map_fused.kernel import min_first

BIG = 1e30  # python scalar: jnp constants become captured consts in pallas
BLOCK_N = 128


def _phase1_kernel(avail_ref, pdyn_ref, qfree_ref, eet_ref, dl_ref,
                   pend_ref, bestm_ref, bestec_ref):
    """Block shapes:
    avail/pdyn/qfree: (1, Mp) VMEM-resident machine state
    eet: (BLOCK_N, Mp); dl/pend: (BLOCK_N, 1)
    out bestm: (BLOCK_N, 1) int32; bestec: (BLOCK_N, 1) f32
    """
    e = eet_ref[...]                          # (bn, Mp)
    s = avail_ref[...]                        # (1, Mp) broadcast
    d = dl_ref[...]                           # (bn, 1)
    pend = pend_ref[...] != 0                 # (bn, 1)
    qfree = qfree_ref[...] != 0               # (1, Mp)

    feas = (s + e <= d) & pend & qfree        # (bn, Mp)
    ec = pdyn_ref[...] * e                    # Eq. 2 middle row (feasible)
    ec = jnp.where(feas, ec, BIG)
    bestec_ref[...], bestm_ref[...] = min_first(ec, axis=1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def phase1_map_padded(avail, p_dyn, qfree, eet_rows, deadline, pending,
                      *, interpret: bool = True):
    """Padded entry: N % BLOCK_N == 0, M padded to 128 with qfree=0."""
    N, Mp = eet_rows.shape
    grid = (N // BLOCK_N,)
    return pl.pallas_call(
        _phase1_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Mp), lambda i: (0, 0)),
            pl.BlockSpec((1, Mp), lambda i: (0, 0)),
            pl.BlockSpec((1, Mp), lambda i: (0, 0)),
            pl.BlockSpec((BLOCK_N, Mp), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_N, 1), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_N, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BLOCK_N, 1), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_N, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, 1), jnp.int32),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
        ],
        name="phase1_map",
        interpret=interpret,
    )(
        avail.reshape(1, Mp), p_dyn.reshape(1, Mp), qfree.reshape(1, Mp),
        eet_rows, deadline.reshape(N, 1), pending.reshape(N, 1),
    )
