"""Scheduling context: everything a mapping policy may look at, computed once.

The legacy heuristics threaded a 7-positional-argument convention
``(now, pending, task_type, deadline, view, sysarr, suffered)`` through every
helper and recomputed the (N, M) start/exec grids, the free-slot mask and the
stale/hopeless masks in every sub-step. :class:`SchedContext` freezes that
tuple into one object and caches each derived grid the first time a policy
component asks for it, so one mapping event computes each grid exactly once
(and under ``jit`` the trace contains one instance of each op).

Shapes follow the paper: N tasks, M machines, Q local-queue slots, S types.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax.numpy as jnp

from repro.core.types import SystemArrays

BIG = jnp.float32(1e30)


class MachineView(NamedTuple):
    """Scheduler-visible machine state at a mapping event."""

    avail_base: jnp.ndarray  # (M,) max(now, expected end of running task)
    queue: jnp.ndarray       # (M, Q) int32 task idx, -1 = empty, FCFS order
    qlen: jnp.ndarray        # (M,) int32


def queued_eet(view: MachineView, task_type, sysarr: SystemArrays):
    """(M, Q) expected execution time of each queued task on its machine."""
    M, Q = view.queue.shape
    occ = view.queue >= 0
    ttype = jnp.where(occ, task_type[jnp.clip(view.queue, 0)], 0)
    cols = jnp.arange(M)[:, None]
    e = sysarr.eet[ttype, jnp.broadcast_to(cols, (M, Q))]
    return jnp.where(occ, e, 0.0)


def avail_time(view: MachineView, task_type, sysarr: SystemArrays):
    """(M,) expected time each machine can start a newly-appended task."""
    return view.avail_base + queued_eet(view, task_type, sysarr).sum(axis=1)


@dataclasses.dataclass(frozen=True)
class SchedContext:
    """Frozen snapshot of one mapping event.

    Constructor fields are the raw scheduler inputs; every derived quantity
    is a ``cached_property`` so policies can compose freely without paying
    for grids they do not read (or paying twice for grids they share).
    """

    now: jnp.ndarray         # () f32 current event time
    pending: jnp.ndarray     # (N,) bool — task is in the arriving queue
    task_type: jnp.ndarray   # (N,) int32
    deadline: jnp.ndarray    # (N,) f32
    view: MachineView
    sysarr: SystemArrays
    suffered: jnp.ndarray    # (S,) bool — fairness monitor (Alg. 4); all
    #   False for a policy whose description says no fairness

    # -- static shapes ------------------------------------------------------
    @property
    def n_tasks(self) -> int:
        return self.pending.shape[0]

    @property
    def n_machines(self) -> int:
        return self.sysarr.eet.shape[1]

    @property
    def queue_slots(self) -> int:
        return self.view.queue.shape[1]

    # -- derived machine state ---------------------------------------------
    @functools.cached_property
    def qfree(self):
        """(M,) bool — machine has at least one free local-queue slot."""
        return self.view.qlen < self.queue_slots

    @functools.cached_property
    def avail(self):
        """(M,) f32 — expected start time of a newly-appended task."""
        return avail_time(self.view, self.task_type, self.sysarr)

    @functools.cached_property
    def start(self):
        """(M,) f32 — mapping-event start times: max(avail, now)."""
        return jnp.maximum(self.avail, self.now)

    @functools.cached_property
    def machine_arange(self):
        """(1, M) int32 — broadcast helper for nominee grids."""
        return jnp.arange(self.n_machines)[None, :]

    # -- derived (N, M) pair grids -----------------------------------------
    @functools.cached_property
    def exec_grid(self):
        """(N, M) f32 — expected execution time of each task on each machine."""
        return self.sysarr.eet[self.task_type]

    @functools.cached_property
    def start_grid(self):
        """(N, M) f32 — :attr:`start` broadcast across tasks."""
        return jnp.broadcast_to(self.start[None, :], self.exec_grid.shape)

    # -- derived task masks ------------------------------------------------
    @functools.cached_property
    def stale(self):
        """(N,) bool — pending and past its deadline (must be purged)."""
        return self.pending & (self.now >= self.deadline)

    @functools.cached_property
    def alive(self):
        """(N,) bool — pending and not yet stale."""
        return self.pending & ~self.stale

    @functools.cached_property
    def min_exec(self):
        """(N,) f32 — each task's execution time on its fastest machine."""
        return self.exec_grid.min(axis=1)

    @functools.cached_property
    def hopeless(self):
        """(N,) bool — would miss its deadline even on an idle machine.

        ELARE's proactive-cancellation predicate (Alg. 1): deferring such a
        task cannot help, so drop rules may cancel it now instead of burning
        mapping events until staleness.
        """
        return self.pending & (self.now + self.min_exec > self.deadline)

    @functools.cached_property
    def suffered_tasks(self):
        """(N,) bool — pending tasks whose type is currently suffered."""
        return self.suffered[self.task_type] & self.pending

    # -- derived contexts --------------------------------------------------
    def with_view(self, view: MachineView) -> "SchedContext":
        """A fresh context over modified machine state (e.g. post-eviction).

        All cached grids are recomputed lazily against the new view.
        """
        return dataclasses.replace(self, view=view)

    def with_qfree(self, qfree) -> "SchedContext":
        """A fresh context whose free-slot mask is overridden.

        For legacy callers that computed ``qfree`` themselves (the old
        ``elare_phase1`` signature). Kept here, next to the
        ``cached_property`` it pre-seeds, so a refactor of :attr:`qfree`
        cannot miss it.
        """
        ctx = dataclasses.replace(self)
        ctx.__dict__["qfree"] = qfree
        return ctx
