"""Pallas TPU kernels for the fused per-event map decision.

One grid pass over the (tasks x machines) EET grid computes everything a
two-phase mapping event needs: Eq. 1 completion / Eq. 2 energy
feasibility, the Phase-I nomination of each pending task, the drop-rule
mask, and the Phase-II per-machine minimum-key nominee — accumulated
across task tiles into lane-resident (1, Mp) running argmins for the
suffered (hi) and non-suffered (lo) nominee pools, so the FELARE
priority Phase-II is a two-line lax epilogue over the kernel outputs.

Tiling mirrors ``kernels/phase1_map``: tasks are tiled ``BLOCK_N`` per
grid step, the (padded) machine dim stays lane-resident, and the
(padded) EET table rides along whole so task-type rows are gathered
in-kernel with one select per type row (a copy, bit-exact). Padding
contracts (see ``ops.py``): padded machine lanes read start=BIG /
qfree=0 / eet=BIG — byte-identical to
how the engine's masked site views already present out-of-site machines
— and padded task rows read pending=0, so neither can nominate, win a
tie-break, or affect a row min.

Every arithmetic expression deliberately matches the lax policy path op
for op (``core/policy/components.py``, ``core/policy/base.py:phase2``,
``core/equations.py``): min/argmin are order-exact, cross-tile
accumulation uses strict ``<`` improvement so the argmin lowest-index
tie-break is preserved, and the energy score ``where(feas, pdyn*e,
BIG)`` equals the masked Eq. 2 because feasibility implies the on-time
branch. Bit-exactness is pinned event-level in
``tests/test_map_fused.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG = 1e30  # python scalar: jnp constants become captured consts in pallas
BIG_INT = 1 << 30  # int load pad: above any dead-site penalty + queue load
BLOCK_N = 128

#: Nominator / Phase-II key / drop-rule kinds the kernel implements —
#: exactly the builtin composition space (all 8 paper heuristics).
NOMINATOR_KINDS = ("min_energy_feasible", "min_completion",
                   "min_execution", "random_hash")
KEY_KINDS = ("value", "deadline", "urgency", "fcfs")
DROP_KINDS = ("stale", "stale_hopeless")


def _type_rows(ttype, eet):
    """(bn, Mp) EET row of each task's type, one select per type row.

    A select copies the stored f32 value, so the row is exact on every
    backend. A one-hot matmul would put the gather on the MXU, whose f32
    passes decide whether the product comes back exact.
    """
    e = jnp.zeros((ttype.shape[0], eet.shape[1]), jnp.float32)
    for s in range(eet.shape[0]):
        e = jnp.where(ttype == s, eet[s:s + 1, :], e)
    return e


def min_first(x, axis):
    """``(min, argmin)`` of ``x`` along ``axis``, keepdims, ties to the
    lowest index -- the tie-break of ``jnp.argmin`` in the lax path.

    Spelled as a min plus the lowest index that holds it because the
    compiled TPU kernel's ``jnp.argmin`` does not keep that tie-break.
    """
    m = jnp.min(x, axis=axis, keepdims=True)
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    return m, jnp.min(jnp.where(x == m, idx, x.shape[axis]), axis=axis,
                      keepdims=True)


def _f32_to_u32(x):
    """``x.astype(uint32)`` for 0 <= x < 2**32, via the i32 cast Mosaic has.

    The TPU compiler refuses a direct f32 -> u32 cast. Values at or above
    2**31 are whole numbers in f32, so subtracting 2**31 is exact and the
    result equals the lax cast over the whole u32 range.
    """
    two31 = 2147483648.0
    hi = x >= two31
    low = jnp.where(hi, x - two31, x).astype(jnp.int32).astype(jnp.uint32)
    return low + jnp.where(hi, jnp.uint32(1 << 31), jnp.uint32(0))


def _nominate(kind, *, s, e, d, pend, alive, qfree, pdyn, now, gidx,
              n_machines):
    """Phase-I: (best (bn,1) i32, value (bn,1) f32, valid (bn,1) bool).

    Mirrors the lax nominators in ``core/policy/components.py`` op for
    op (same masks, same BIG sentinel, same argmin tie-break).
    """
    if kind == "random_hash":
        h = (gidx.astype(jnp.uint32) * jnp.uint32(2654435761)
             + _f32_to_u32(now * 1e3)) % jnp.uint32(n_machines)
        return h.astype(jnp.int32), gidx.astype(jnp.float32), alive
    if kind == "min_energy_feasible":
        feas = (s + e <= d) & pend & qfree
        score = jnp.where(feas, pdyn * e, BIG)
    elif kind == "min_completion":
        on_time = s + e <= d
        started = s < d
        comp = jnp.where(on_time, s + e,
                         jnp.where(started, jnp.broadcast_to(d, e.shape),
                                   jnp.broadcast_to(s, e.shape)))
        score = jnp.where(alive & qfree, comp, BIG)
    elif kind == "min_execution":
        score = jnp.where(alive & qfree, e, BIG)
    else:  # pragma: no cover - ops.py validates kinds
        raise ValueError(f"unsupported nominator kind {kind!r}")
    value, best = min_first(score, axis=1)
    return best, value, value < BIG


def _phase2_key(kind, *, value, d, e, best, now, gidx):
    """(bn, 1) Phase-II tie-break key — lower = better, lax-exact."""
    if kind == "value":
        return value
    if kind == "deadline":
        return d + 1e-6 * value
    if kind == "urgency":
        bn, mp = e.shape
        lanes = jax.lax.broadcasted_iota(jnp.int32, (bn, mp), 1)
        e_best = jnp.sum(jnp.where(lanes == best, e, 0.0), axis=1,
                         keepdims=True)
        slack = d - now - e_best
        return -(1.0 / jnp.where(jnp.abs(slack) < 1e-9, 1e-9, slack))
    if kind == "fcfs":
        return gidx.astype(jnp.float32)
    raise ValueError(f"unsupported key kind {kind!r}")  # pragma: no cover


def _map_decide_kernel(now_ref, start_ref, pdyn_ref, qfree_ref, eet_ref,
                       dl_ref, pend_ref, ttype_ref, suff_ref,
                       drop_ref, hikey_ref, hitask_ref, lokey_ref,
                       lotask_ref, *, nominator, phase2_key, drop_rule,
                       n_machines):
    """Block shapes:
    now: (1, 1); start/pdyn/qfree: (1, Mp) VMEM-resident machine state;
    eet: (Sp, Mp) whole padded table; dl/pend/ttype/suff: (BLOCK_N, 1).
    Outputs: drop (BLOCK_N, 1) per tile; hi/lo key+task (1, Mp)
    accumulated across tiles (constant out index map).
    """
    i = pl.program_id(0)
    mp = start_ref.shape[1]

    @pl.when(i == 0)
    def _init():
        hikey_ref[...] = jnp.full((1, mp), BIG, jnp.float32)
        hitask_ref[...] = jnp.zeros((1, mp), jnp.int32)
        lokey_ref[...] = jnp.full((1, mp), BIG, jnp.float32)
        lotask_ref[...] = jnp.zeros((1, mp), jnp.int32)

    now = now_ref[0, 0]
    s = start_ref[...]                        # (1, Mp) broadcast
    pdyn = pdyn_ref[...]
    qfree = qfree_ref[...] != 0
    d = dl_ref[...]                           # (bn, 1)
    pend = pend_ref[...] != 0
    suff = suff_ref[...] != 0
    bn = d.shape[0]
    gidx = (i * BLOCK_N
            + jax.lax.broadcasted_iota(jnp.int32, (bn, 1), 0))

    e = _type_rows(ttype_ref[...], eet_ref[...])          # (bn, Mp)
    min_exec = jnp.min(e, axis=1, keepdims=True)          # pad lanes = BIG
    stale = pend & (now >= d)
    alive = pend & ~stale

    # -- drop rule (view-independent: identical on pre/post-eviction ctx) --
    if drop_rule == "stale_hopeless":
        drop = stale | (pend & (now + min_exec > d))
    else:
        drop = stale
    drop_ref[...] = drop.astype(jnp.int32)

    # -- Phase-I nomination + Phase-II key --------------------------------
    best, value, valid = _nominate(
        nominator, s=s, e=e, d=d, pend=pend, alive=alive, qfree=qfree,
        pdyn=pdyn, now=now, gidx=gidx, n_machines=n_machines)
    key = _phase2_key(phase2_key, value=value, d=d, e=e, best=best,
                      now=now, gidx=gidx)

    # -- Phase-II tile reduction + cross-tile running argmin --------------
    lanes = jax.lax.broadcasted_iota(jnp.int32, (bn, mp), 1)
    nominee = valid & (best == lanes)
    for pool_suff, key_ref, task_ref in (
            (True, hikey_ref, hitask_ref), (False, lokey_ref, lotask_ref)):
        pool = nominee & (suff if pool_suff else ~suff)
        masked = jnp.where(pool, key, BIG)
        tile_min, tile_row = min_first(masked, axis=0)          # (1, Mp)
        tile_task = i * BLOCK_N + tile_row
        # strict < keeps the earliest tile on ties; within a tile the
        # lowest row wins — together the global lowest-index tie-break
        # of jnp.argmin(axis=0).
        better = tile_min < key_ref[...]
        key_ref[...] = jnp.where(better, tile_min, key_ref[...])
        task_ref[...] = jnp.where(better, tile_task, task_ref[...])


@functools.partial(
    jax.jit,
    static_argnames=("nominator", "phase2_key", "drop_rule", "n_machines",
                     "interpret"))
def map_decide_padded(now, start, p_dyn, qfree, eet, deadline, pending,
                      task_type, suffered_task, *, nominator, phase2_key,
                      drop_rule, n_machines, interpret: bool):
    """Padded entry: N % BLOCK_N == 0, machine/type dims lane/sublane
    padded (start=BIG, qfree=0, eet=BIG, pending=0 in the padding)."""
    N = deadline.shape[0]
    Sp, Mp = eet.shape
    grid = (N // BLOCK_N,)
    machine_row = pl.BlockSpec((1, Mp), lambda i: (0, 0))
    task_col = pl.BlockSpec((BLOCK_N, 1), lambda i: (i, 0))
    acc_row = pl.BlockSpec((1, Mp), lambda i: (0, 0))
    kernel = functools.partial(
        _map_decide_kernel, nominator=nominator, phase2_key=phase2_key,
        drop_rule=drop_rule, n_machines=n_machines)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            machine_row, machine_row, machine_row,
            pl.BlockSpec((Sp, Mp), lambda i: (0, 0)),
            task_col, task_col, task_col, task_col,
        ],
        out_specs=[task_col, acc_row, acc_row, acc_row, acc_row],
        name="map_decide",
        out_shape=[
            jax.ShapeDtypeStruct((N, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, Mp), jnp.float32),
            jax.ShapeDtypeStruct((1, Mp), jnp.int32),
            jax.ShapeDtypeStruct((1, Mp), jnp.float32),
            jax.ShapeDtypeStruct((1, Mp), jnp.int32),
        ],
        interpret=interpret,
    )(
        now.reshape(1, 1), start.reshape(1, Mp), p_dyn.reshape(1, Mp),
        qfree.reshape(1, Mp), eet, deadline.reshape(N, 1),
        pending.reshape(N, 1), task_type.reshape(N, 1),
        suffered_task.reshape(N, 1),
    )


def _evict_stats_kernel(start_ref, qfree_ref, eet_ref, dl_ref, pend_ref,
                        ttype_ref, feas_ref, minexec_ref):
    """Per-task grid reductions for the Sec. V eviction planner:
    feasible-now on some free machine (any) and fastest EET (min)."""
    s = start_ref[...]                        # (1, Mp)
    qfree = qfree_ref[...] != 0
    d = dl_ref[...]                           # (bn, 1)
    pend = pend_ref[...] != 0
    e = _type_rows(ttype_ref[...], eet_ref[...])
    feas_now = (s + e <= d) & pend
    feas_ref[...] = jnp.any(feas_now & qfree, axis=1,
                            keepdims=True).astype(jnp.int32)
    minexec_ref[...] = jnp.min(e, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def evict_stats_padded(start, qfree, eet, deadline, pending, task_type, *,
                       interpret: bool):
    """Padded entry for the eviction-stats pass (same contracts as
    :func:`map_decide_padded`, pre-eviction machine state)."""
    N = deadline.shape[0]
    Sp, Mp = eet.shape
    grid = (N // BLOCK_N,)
    machine_row = pl.BlockSpec((1, Mp), lambda i: (0, 0))
    task_col = pl.BlockSpec((BLOCK_N, 1), lambda i: (i, 0))
    return pl.pallas_call(
        _evict_stats_kernel,
        grid=grid,
        in_specs=[
            machine_row, machine_row,
            pl.BlockSpec((Sp, Mp), lambda i: (0, 0)),
            task_col, task_col, task_col,
        ],
        out_specs=[task_col, task_col],
        out_shape=[
            jax.ShapeDtypeStruct((N, 1), jnp.int32),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
        ],
        name="evict_stats",
        interpret=interpret,
    )(
        start.reshape(1, Mp), qfree.reshape(1, Mp), eet,
        deadline.reshape(N, 1), pending.reshape(N, 1),
        task_type.reshape(N, 1),
    )


def _balance_kernel(load_ref, new_ref, tgt_ref, home_ref, out_ref, *,
                    n_tasks):
    """The dispatcher's sequential least-loaded scan, in-kernel.

    One grid step; the (1, Fp) load vector stays register/VMEM-resident
    across the whole admission walk instead of round-tripping through a
    lax.scan carry. Mirrors ``core/dispatch/base.py:sequential_balance``
    step for step (integer arithmetic, argmin lowest-index ties).

    The per-task rows live in SMEM: the walk reads and writes one scalar
    per step at a dynamic index, which Mosaic only allows there. The
    argmin is spelled as an int min plus a lowest-lane min, because
    Mosaic's argmin takes f32 only.
    """
    fp = load_ref.shape[1]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, fp), 1)

    def body(k, load):
        best = jnp.min(jnp.where(load == jnp.min(load), lanes, fp))
        s = jnp.where(tgt_ref[0, k] != 0, best, home_ref[0, k])
        out_ref[0, k] = s
        return load + jnp.where((lanes == s) & (new_ref[0, k] != 0), 1, 0)

    jax.lax.fori_loop(0, n_tasks, body, load_ref[...])


@functools.partial(jax.jit, static_argnames=("n_tasks", "interpret"))
def balance_scan_padded(load0, new, tgt, home, *, n_tasks: int,
                        interpret: bool):
    """Padded entry: site lanes padded with ``BIG_INT`` load (never win
    an argmin); task columns beyond ``n_tasks`` are never visited."""
    Fp = load0.shape[0]
    Np = new.shape[0]
    row = pl.BlockSpec((1, Np), lambda i: (0, 0), memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_balance_kernel, n_tasks=n_tasks),
        grid=(1,),
        in_specs=[pl.BlockSpec((1, Fp), lambda i: (0, 0)), row, row, row],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((1, Np), jnp.int32),
        name="balance_scan",
        interpret=interpret,
    )(
        load0.reshape(1, Fp), new.reshape(1, Np), tgt.reshape(1, Np),
        home.reshape(1, Np),
    )
