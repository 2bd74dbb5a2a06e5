"""Fused-kernel mapping: the whole per-event decision as one Pallas pass.

:class:`FusedMapPolicy` wraps a composed two-phase policy (optionally
fairness-wrapped) and replaces its multi-pass lax ``select`` with calls
into ``kernels/map_fused``:

  * non-fair: one ``map_decide`` kernel pass computes Phase-I nomination,
    the drop mask, and per-machine Phase-II running argmins; the Phase-II
    assignment is a three-line lax epilogue over the (M,) kernel outputs.
  * fair (FELARE): an ``evict_stats`` pass yields the two per-task grid
    reductions the Sec. V eviction planner needs; the shared
    ``fair._plan_eviction_from_stats`` plans the eviction, and the same
    ``map_decide`` pass then runs against the post-eviction view with the
    suffered split live — the priority Phase-II becomes a ``where`` chain
    over the hi/lo kernel argmins.

The wrapper is bit-exact with the lax path (pinned by
``tests/test_map_fused.py``): every kernel expression mirrors
``components.py``/``base.py:phase2`` op for op, and the drop rule is
view-independent so computing it inside the post-eviction kernel pass
still equals ``drop_rule.drop(ctx)`` on the pre-eviction context.

``interpret`` is resolved once at construction
(:func:`repro.kernels.pallas_backend.default_interpret`), never inside
the jitted ``select`` (analyzer rule JD003).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.policy import fair as fair_mod
from repro.core.policy.base import BIG, PolicyDesc, finalize
from repro.core.policy.context import MachineView, SchedContext
from repro.core.types import MapAction, SystemArrays

#: Kinds the fused kernel implements (mirrors kernels/map_fused/kernel.py;
#: imported lazily there to keep policy import free of jax.experimental).
SUPPORTED_NOMINATORS = ("min_energy_feasible", "min_completion",
                        "min_execution", "random_hash")
SUPPORTED_KEYS = ("value", "deadline", "urgency", "fcfs")
SUPPORTED_DROPS = ("stale", "stale_hopeless")


def supports_fused_map(desc: PolicyDesc) -> bool:
    """Is this composed policy within the fused kernel's kind space?"""
    return (desc.nominator in SUPPORTED_NOMINATORS
            and desc.phase2_key in SUPPORTED_KEYS
            and desc.drop_rule in SUPPORTED_DROPS)


@dataclasses.dataclass(frozen=True)
class FusedMapPolicy:
    """A composed policy whose map decision runs as one fused kernel pass.

    ``base`` is the wrapped :class:`TwoPhasePolicy` or
    :class:`~repro.core.policy.fair.FairnessPolicy`; its ``describe()``
    kinds select the kernel's static specialization. Frozen and hashable
    like every policy so jit closes over it statically.
    """

    base: object
    interpret: bool

    def __post_init__(self):
        desc = self.base.describe()
        if not supports_fused_map(desc):
            raise ValueError(
                f"fused map kernel does not implement {desc!r}; "
                f"use with_pallas_map() which no-ops on unsupported policies"
            )

    def select(self, ctx: SchedContext) -> MapAction:
        from repro.kernels import map_fused

        desc = self.base.describe()
        if desc.fairness:
            with jax.named_scope("engine.fairness"):
                task_feas_now, min_exec = map_fused.evict_stats(
                    ctx.start, ctx.qfree, ctx.sysarr.eet, ctx.deadline,
                    ctx.pending, ctx.task_type, interpret=self.interpret)
                qdrop = fair_mod._plan_eviction_from_stats(
                    ctx, task_feas_now, min_exec)
                ctx2 = ctx.with_view(fair_mod._evicted_view(ctx, qdrop))
            suffered_task = ctx.suffered_tasks
        else:
            qdrop = None
            ctx2 = ctx
            # Empty hi pool: the priority epilogue degenerates to the
            # plain Phase-II argmin over all nominees.
            suffered_task = jnp.zeros_like(ctx.pending)

        drop, hi_key, hi_task, lo_key, lo_task = map_fused.map_decide(
            ctx.now, ctx2.start, ctx.sysarr.p_dyn, ctx2.qfree,
            ctx.sysarr.eet, ctx.deadline, ctx.pending, ctx.task_type,
            suffered_task, nominator=desc.nominator,
            phase2_key=desc.phase2_key, drop_rule=desc.drop_rule,
            interpret=self.interpret)

        # Priority Phase-II epilogue over the per-machine running argmins
        # (== base.py:phase2 / fair.py's hi-then-lo chain).
        qfree2 = ctx2.qfree
        assign_hi = jnp.where((hi_key < BIG) & qfree2, hi_task,
                              jnp.int32(-1))
        taken = assign_hi >= 0
        assign_lo = jnp.where((lo_key < BIG) & qfree2 & ~taken, lo_task,
                              jnp.int32(-1))
        assign = jnp.where(taken, assign_hi, assign_lo)
        return finalize(ctx, assign, drop, qdrop)

    def __call__(self, now, pending, task_type, deadline, view: MachineView,
                 sysarr: SystemArrays, suffered) -> MapAction:
        return self.select(SchedContext(
            now, pending, task_type, deadline, view, sysarr, suffered
        ))

    # -- introspection / variants ------------------------------------------
    def describe(self) -> PolicyDesc:
        return self.base.describe()

    @property
    def supports_phase1_impl(self) -> bool:
        # Phase-I is already inside the fused kernel; the phase1_map hook
        # does not compose on top.
        return False

    def with_phase1_impl(self, impl) -> "FusedMapPolicy":
        return self
