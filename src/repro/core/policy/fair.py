"""The Sec. V fairness wrapper: suffered-type priority + queue eviction.

``with_fairness(base)`` lifts any two-phase policy into its fairness-aware
variant; FELARE is exactly ``with_fairness(ELARE)``. The wrapper adds:

  1. Queue eviction for the earliest-deadline *rescuable* suffered task:
     non-suffered victims are dropped tail-first from its best-matching
     (fastest) machine until the task becomes feasible there — and only if
     the eviction actually rescues it.
  2. Priority Phase-II: suffered-type nominees are served first; machines
     left unassigned then serve the non-suffered nominees (keeps the
     collective completion rate from collapsing — Fig. 7's "negligible
     degradation").

Phase-I and the drop rule are the base policy's own, re-run against the
post-eviction machine state via ``SchedContext.with_view``. The eviction
plan and the priority Phase-II run under ``jax.named_scope(
"engine.fairness")``, like the engine's Eq. 3 monitor.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import equations
from repro.core.policy.base import (
    PolicyDesc,
    TwoPhasePolicy,
    finalize,
    phase2,
)
from repro.core.policy.context import (
    BIG,
    MachineView,
    SchedContext,
    queued_eet,
)
from repro.core.types import MapAction, SystemArrays


def _plan_eviction(ctx: SchedContext) -> jnp.ndarray:
    """(M, Q) bool eviction mask rescuing the most urgent suffered task.

    Candidates are suffered, currently infeasible on every free machine, and
    not hopeless on an empty machine (eviction cannot beat an empty
    machine). Victims are non-suffered queued tasks, taken tail-first from
    the target's fastest machine while the target still does not fit.
    """
    s, e, d = ctx.start_grid, ctx.exec_grid, ctx.deadline[:, None]
    feas_now = equations.feasible(s, e, d) & ctx.pending[:, None]
    task_feas_now = jnp.any(feas_now & ctx.qfree[None, :], axis=1)
    return _plan_eviction_from_stats(ctx, task_feas_now, ctx.min_exec)


def _plan_eviction_from_stats(ctx: SchedContext, task_feas_now, min_exec):
    """Eviction plan from precomputed per-task grid reductions.

    ``task_feas_now`` (N,) bool and ``min_exec`` (N,) f32 are the only two
    quantities :func:`_plan_eviction` derives from the (N, M) grid — the
    fused kernel path (``kernels/map_fused.evict_stats``) computes them in
    one pass and re-enters here, so the target/victim selection below is
    shared verbatim between the lax and kernel paths.
    """
    M, Q = ctx.view.queue.shape
    rescuable = (
        ctx.suffered_tasks
        & ~task_feas_now
        & (ctx.now + min_exec <= ctx.deadline)
    )
    cand_key = jnp.where(rescuable, ctx.deadline, BIG)
    tgt = jnp.argmin(cand_key).astype(jnp.int32)
    have_tgt = cand_key[tgt] < BIG

    # fastest (best-matching) machine for the target: min expected completion.
    comp_tgt = ctx.avail + ctx.sysarr.eet[ctx.task_type[tgt]]
    mstar = jnp.argmin(comp_tgt).astype(jnp.int32)

    # evict non-suffered victims tail-first until the target fits on mstar.
    q_eet = queued_eet(ctx.view, ctx.task_type, ctx.sysarr)        # (M, Q)
    row = ctx.view.queue[mstar]                                    # (Q,)
    occ = row >= 0
    victim_ok = occ & ~ctx.suffered[ctx.task_type[jnp.clip(row, 0)]]
    e_tgt = ctx.sysarr.eet[ctx.task_type[tgt], mstar]
    base = jnp.maximum(ctx.view.avail_base[mstar], ctx.now)
    # tail-first greedy: walk q = Q-1 .. 0, evicting while still infeasible.
    evict = jnp.zeros((Q,), bool)
    remaining = q_eet[mstar].sum()
    for q in range(Q - 1, -1, -1):
        start_if = base + remaining
        need = start_if + e_tgt > ctx.deadline[tgt]
        take = need & victim_ok[q]
        evict = evict.at[q].set(take)
        remaining = remaining - jnp.where(take, q_eet[mstar, q], 0.0)
    feasible_after = base + remaining + e_tgt <= ctx.deadline[tgt]
    evict = evict & feasible_after & have_tgt  # only evict if it rescues
    return jnp.zeros((M, Q), bool).at[mstar].set(evict)


def _evicted_view(ctx: SchedContext, qdrop) -> MachineView:
    """The post-eviction machine view the base policy re-runs against."""
    return MachineView(
        avail_base=ctx.view.avail_base,
        queue=jnp.where(qdrop, jnp.int32(-1), ctx.view.queue),
        qlen=ctx.view.qlen - qdrop.sum(axis=1).astype(ctx.view.qlen.dtype),
    )


@dataclasses.dataclass(frozen=True)
class FairnessPolicy:
    """A two-phase policy wrapped with the Sec. V fairness mechanisms."""

    base: TwoPhasePolicy

    def select(self, ctx: SchedContext) -> MapAction:
        with jax.named_scope("engine.fairness"):
            qdrop = _plan_eviction(ctx)
            ctx2 = ctx.with_view(_evicted_view(ctx, qdrop))

        # Re-run the base policy's Phase-I against post-eviction state.
        nom = self.base.nominator.nominate(ctx2)
        nominee = nom.grid(ctx2)
        key = jnp.broadcast_to(
            self.base.phase2_key.key(ctx2, nom)[:, None], nominee.shape
        )

        # Priority Phase-II: suffered-type nominees claim machines first.
        with jax.named_scope("engine.fairness"):
            hi = nominee & ctx.suffered_tasks[:, None]
            assign_hi = phase2(hi, key, ctx2.qfree)
            taken = assign_hi >= 0
            lo = nominee & ~ctx.suffered_tasks[:, None]
            assign_lo = phase2(lo, key, ctx2.qfree & ~taken)
            assign = jnp.where(taken, assign_hi, assign_lo)

        return finalize(ctx, assign, self.base.drop_rule.drop(ctx), qdrop)

    def __call__(self, now, pending, task_type, deadline, view: MachineView,
                 sysarr: SystemArrays, suffered) -> MapAction:
        return self.select(SchedContext(
            now, pending, task_type, deadline, view, sysarr, suffered
        ))

    # -- introspection / variants ------------------------------------------
    def describe(self) -> PolicyDesc:
        return self.base.describe()._replace(fairness=True)

    @property
    def supports_phase1_impl(self) -> bool:
        return self.base.supports_phase1_impl

    def with_phase1_impl(self, impl) -> "FairnessPolicy":
        return dataclasses.replace(
            self, base=self.base.with_phase1_impl(impl)
        )


def with_fairness(base: TwoPhasePolicy) -> FairnessPolicy:
    """Wrap ``base`` with suffered-type priority + queue eviction (Sec. V)."""
    return FairnessPolicy(base)
