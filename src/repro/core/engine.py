"""Discrete-event simulation engine for the HEC system, in pure JAX.

The whole simulator is a ``lax.while_loop`` over events with fixed-shape
state, so a full workload trace is one jittable computation and a batch of
traces is one ``vmap``. Semantics follow Sec. III of the paper:

  * mapping events fire on task arrival and task completion (plus a progress
    event at the earliest pending deadline so stale tasks are always purged);
  * machines serve their bounded local queues FCFS;
  * a running task that passes its deadline is killed at the deadline (its
    dynamic energy is wasted, Eq. 2 row 1);
  * a queued task whose deadline passed before it starts is dropped with zero
    energy (Eq. 2 row 3);
  * per-type completion counters feed the fairness monitor continuously.

Each event is processed as six named stages, threading an
:class:`~repro.core.types.EngineState` = ``(SimState, aux)``:

  ``finalize`` -> ``admit`` -> ``faults`` -> ``dispatch`` -> ``map`` -> ``start``

``faults`` evolves the per-machine health state under a pluggable
:class:`~repro.core.faults.MachineDynamics` (failures, site outages,
stragglers): dead machines read avail=BIG/EET=BIG downstream exactly
like out-of-site machines, their queued tasks and running task become
*orphans* re-entering the dispatch queue (bounded retry count), and
``with_backup``-wrapped policies fail orphans over to pre-nominated
backup machines. With the default ``dynamics="none"`` the stage is
skipped entirely — no masking enters the traced program and the loop is
bit-exact with the pre-faults engine (observers never see a ``faults``
stage then). Because ``finalize`` runs first, a task completing at
exactly the instant its machine dies *completes* — the deterministic
tie rule both engines share.

``dispatch`` is the federation's first level: a pluggable
:class:`~repro.core.dispatch.Dispatcher` assigns each newly-admitted task
to one of F *sites* (bounded partitions of the machine set), and ``map``
then evaluates the mapping policy as one ``jax.vmap`` over the F
site-masked :class:`~repro.core.policy.MachineView` batches — the site
count enters the program as *data* (array extents), never as program
structure, so trace size and compile time are flat in F: an F=100
federation compiles the same program as an F=2 one. With one site (every
spec built before the federation layer) the dispatch stage degenerates
to "site 0" and the map stage is the exact pre-federation computation,
so flat runs stay bit-identical.

With a non-trivial :mod:`repro.core.network` model attached, the
dispatch stage additionally *pays each task's link*: the chosen site's
transfer latency shifts the task's ready time (the mapper cannot place
an in-transit task until it lands — landings drive events of their own)
and the link's transfer energy is charged to Eq. 2's dynamic account
(and tallied per destination tier for the ``network`` observer). With
the default ``network="none"`` every network field stays out of the
state pytree and the loop is bit-exact with the pre-network engine.

After every stage, each attached :class:`~repro.core.observe.Observer`
folds the stage name and the fresh :class:`~repro.core.types.SimState`
into its own fixed-shape ``aux`` pytree, so time-resolved telemetry
(queue/energy/fairness trajectories, per-task logs) rides inside the same
single jitted ``while_loop`` — and *dynamic* observers (the energy
budget) can expose a ``halted`` flag the engine consults to stop
admitting work (Eq. 2's energy-limited regime). With no observers the
loop is structurally and bit-for-bit identical to the bare engine.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fairness
from repro.core.dispatch.base import DispatchContext
from repro.core.policy import BIG, MachineView
from repro.core.types import (
    CANCELLED,
    COMPLETED,
    MISSED,
    PENDING,
    QUEUED,
    RUNNING,
    UNARRIVED,
    EngineState,
    MapAction,
    Metrics,
    SimState,
    SystemArrays,
    Trace,
    site_membership,
)

INF = jnp.float32(jnp.inf)

#: Stage names, in event order. Observers receive each after it ran
#: (``faults`` only fires when a non-trivial dynamics is attached).
STAGES = ("finalize", "admit", "faults", "dispatch", "map", "start")


def _init_state(trace: Trace, n_machines: int, queue_size: int,
                n_types: int, *, backup_k: int = 0,
                network: bool = False, n_tiers: int = 1,
                fair: bool = False) -> SimState:
    n = trace.arrival.shape[0]
    M, Q, S = n_machines, queue_size, n_types
    f = jnp.float32
    return SimState(
        now=f(0.0),
        status=jnp.full((n,), UNARRIVED, jnp.int32),
        site=jnp.full((n,), -1, jnp.int32),
        run_task=jnp.full((M,), -1, jnp.int32),
        run_start=jnp.zeros((M,), f),
        run_end_act=jnp.full((M,), jnp.inf, f),
        run_end_exp=jnp.zeros((M,), f),
        run_success=jnp.zeros((M,), bool),
        queue=jnp.full((M, Q), -1, jnp.int32),
        qlen=jnp.zeros((M,), jnp.int32),
        busy_time=jnp.zeros((M,), f),
        e_dyn=f(0.0),
        e_wasted=f(0.0),
        completed=jnp.zeros((S,), jnp.int32),
        missed=jnp.zeros((S,), jnp.int32),
        cancelled=jnp.zeros((S,), jnp.int32),
        arrived=jnp.zeros((S,), jnp.int32),
        steps=jnp.int32(0),
        alive=jnp.ones((M,), bool),
        slowdown=jnp.ones((M,), f),
        retries=jnp.zeros((n,), jnp.int32),
        backup=jnp.full((n, backup_k), -1, jnp.int32),
        # network fields stay absent (None) with network="none" so the
        # default pytree — and therefore the traced program — is exactly
        # the pre-network one.
        ready=(trace.arrival.astype(f) if network else None),
        e_xfer=(jnp.zeros((n_tiers,), f) if network else None),
        # fairness counters only where the policy reads the mask
        fair_evicted=(jnp.int32(0) if fair else None),
        suffered_maps=(jnp.int32(0) if fair else None),
    )


def _uses_fairness(select_fn) -> bool:
    """Whether the policy reads the suffered-type mask: a composed policy
    says so in its description; an opaque callable may, so it gets one."""
    describe = getattr(select_fn, "describe", None)
    return describe is None or bool(describe().fairness)


def _type_onehot(task_type: jnp.ndarray, n_types: int) -> jnp.ndarray:
    """The (N, S) grid ``task_type[k] == s``: fixed for a trace, so the
    loop builds it once and every N-wide per-type count reads it."""
    return task_type[:, None] == jnp.arange(n_types, dtype=task_type.dtype)


def _count_by_type(mask: jnp.ndarray, type_onehot: jnp.ndarray) -> jnp.ndarray:
    """Per-type int32 count of an (N,) task mask, as a compare-and-sum.

    ``segment_sum(mask, task_type, S)`` gives the same integers, but
    under ``vmap`` it lowers to one scatter-add of B x N updates, which
    the TPU applies one after another on every event; this is a reduce.
    """
    return jnp.sum(mask[:, None] & type_onehot, axis=0, dtype=jnp.int32)


def _next_event_time(st: SimState, trace: Trace,
                     halted: Optional[jnp.ndarray] = None,
                     wake_ts: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    pending = st.status == PENDING
    unarrived = st.status == UNARRIVED
    t_arr = jnp.min(jnp.where(unarrived, trace.arrival, jnp.inf))
    if halted is not None:
        # energy-limited shutdown: un-admitted arrivals no longer drive
        # events (they would otherwise pin the next-event time forever).
        t_arr = jnp.where(halted, jnp.inf, t_arr)
    t_comp = jnp.min(st.run_end_act)
    # progress guard: earliest pending deadline (so stale tasks get purged
    # even when no machine is busy and no arrivals remain).
    t_dead = jnp.min(jnp.where(pending, trace.deadline, jnp.inf))
    t = jnp.minimum(jnp.minimum(t_arr, t_comp), t_dead)
    if st.ready is not None:
        # in-transit landings: a dispatched task becomes mappable at its
        # site-arrival time, which must drive an event even when no
        # machine is busy and no arrivals remain.
        t_ready = jnp.min(jnp.where(pending & (st.ready > st.now),
                                    st.ready, jnp.inf))
        t = jnp.minimum(t, t_ready)
    if wake_ts is not None:
        # scheduled-dynamics wake-ups (outage window edges): each fires at
        # most once — strictly future times only, and the event it drives
        # sets ``now`` onto (at or past) it.
        t_wake = jnp.min(jnp.where(wake_ts > st.now, wake_ts, jnp.inf))
        t = jnp.minimum(t, t_wake)
    return t


# ---------------------------------------------------------------------------
# Event stages. Each is a pure SimState -> SimState map; the loop body runs
# them in STAGES order and hands the result to every observer in between.
# ---------------------------------------------------------------------------
def _stage_finalize(st: SimState, trace: Trace, sysarr: SystemArrays):
    """Close out machines whose running task's actual end <= now."""
    done = (st.run_task >= 0) & (st.run_end_act <= st.now)
    idx = jnp.where(done, st.run_task, 0)
    ttype = trace.task_type[idx]
    dur = jnp.where(done, st.run_end_act - st.run_start, 0.0)
    energy = sysarr.p_dyn * dur
    ok = done & st.run_success
    ko = done & ~st.run_success

    completed = st.completed.at[ttype].add(ok.astype(jnp.int32))
    missed = st.missed.at[ttype].add(ko.astype(jnp.int32))
    e_dyn = st.e_dyn + energy.sum()
    e_wasted = st.e_wasted + jnp.where(ko, energy, 0.0).sum()
    busy = st.busy_time + dur
    sidx = jnp.where(done, idx, st.status.shape[0])  # OOB sentinel -> dropped
    status = st.status.at[sidx].set(
        jnp.where(ok, COMPLETED, MISSED), mode="drop"
    )
    return st._replace(
        status=status,
        run_task=jnp.where(done, -1, st.run_task),
        run_end_act=jnp.where(done, jnp.inf, st.run_end_act),
        run_end_exp=jnp.where(done, st.now, st.run_end_exp),
        run_success=jnp.where(done, False, st.run_success),
        completed=completed,
        missed=missed,
        cancelled=st.cancelled,
        e_dyn=e_dyn,
        e_wasted=e_wasted,
        busy_time=busy,
    )


def _stage_admit(st: SimState, trace: Trace, type_onehot: jnp.ndarray,
                 halted: Optional[jnp.ndarray] = None):
    """Admit newly-arrived tasks to the arriving queue.

    When a dynamic observer reports ``halted`` (battery exhausted), the
    system stops taking work: nothing is admitted, every pending task is
    cancelled, and local queues are flushed (their tasks cancelled with
    zero energy). Tasks already running finish normally — the one-event
    slack the energy-budget contract allows.
    """
    newly = (st.status == UNARRIVED) & (trace.arrival <= st.now)
    if halted is not None:
        newly = newly & ~halted
    status = jnp.where(newly, PENDING, st.status)
    arrived = st.arrived + _count_by_type(newly, type_onehot)
    st = st._replace(status=status, arrived=arrived)
    if halted is None:
        return st
    return _halt_shutdown(st, trace, type_onehot, halted)


def _halt_shutdown(st: SimState, trace: Trace, type_onehot: jnp.ndarray,
                   halted: jnp.ndarray):
    """Cancel pending tasks and flush local queues once ``halted``."""
    n, n_types = st.status.shape[0], st.cancelled.shape[0]
    drop = halted & (st.status == PENDING)
    status = jnp.where(drop, CANCELLED, st.status)
    cancelled = st.cancelled + _count_by_type(drop, type_onehot)
    victim = halted & (st.queue >= 0)
    vidx = jnp.where(victim, st.queue, n)  # OOB sentinel -> dropped
    status = status.at[vidx.reshape(-1)].set(CANCELLED, mode="drop")
    cancelled = cancelled + jax.ops.segment_sum(
        victim.reshape(-1).astype(jnp.int32),
        trace.task_type[jnp.clip(vidx, 0, n - 1)].reshape(-1),
        n_types,
    )
    return st._replace(
        status=status,
        cancelled=cancelled,
        queue=jnp.where(victim, -1, st.queue),
        qlen=jnp.where(halted, 0, st.qlen),
    )


def _stage_faults(st: SimState, trace: Trace, sysarr: SystemArrays,
                  dynamics, horizon, n_types: int, backup_k: int,
                  site_of_machine: np.ndarray, n_sites: int):
    """Evolve machine health and orphan the casualties.

    Order within the stage (mirrored exactly by the oracle):

      1. ``dynamics.step`` proposes the next ``(alive, slowdown)``.
      2. Newly-dead machines flush their local queues — each queued task
         is *orphaned*: its retry count increments and it re-enters the
         dispatch queue (PENDING, site cleared) unless the count exceeds
         ``dynamics.max_retries``, in which case it is CANCELLED.
      3. Newly-dead machines kill their running task: the partial run's
         dynamic energy is spent *and* wasted (the work is lost), then
         the task is orphaned like a queue victim — except that under a
         ``with_backup`` policy a running-task orphan with a healthy,
         non-full backup machine fails over: it is enqueued there
         directly (QUEUED on the backup's site), skipping the
         dispatch/map round-trip. Queue victims never fail over — they
         had no primary yet in the FEST sense.

    Orphans made PENDING here are re-dispatched at *this same event*
    (the dispatch stage follows), so a one-event outage costs at most
    one retry. Machines revive with clean state; the finalize stage ran
    first, so a task completing at exactly the death instant completes.
    """
    from repro.core.faults.base import FaultContext

    M, Q = st.queue.shape
    n = st.status.shape[0]
    max_retries = int(getattr(dynamics, "max_retries", 3))
    ctx = FaultContext(
        now=st.now,
        steps=st.steps,
        horizon=horizon,
        alive=st.alive,
        slowdown=st.slowdown,
        site_of_machine=np.asarray(site_of_machine, np.int32),
        n_sites=n_sites,
    )
    alive_new, slow_new = dynamics.step(ctx)
    alive_new = alive_new.astype(bool)
    slow_new = slow_new.astype(jnp.float32)
    died = st.alive & ~alive_new

    # -- 2. flush dead machines' local queues (queued tasks orphan) --------
    qvict = died[:, None] & (st.queue >= 0)
    qidx = jnp.where(qvict, st.queue, n)          # OOB sentinel -> dropped
    retries = st.retries.at[qidx.reshape(-1)].add(1, mode="drop")
    qsafe = jnp.clip(qidx, 0, n - 1)
    q_exh = qvict & (retries[qsafe] > max_retries)
    status = st.status.at[qidx.reshape(-1)].set(
        jnp.where(q_exh, CANCELLED, PENDING).reshape(-1), mode="drop"
    )
    cancelled = st.cancelled + jax.ops.segment_sum(
        q_exh.reshape(-1).astype(jnp.int32),
        trace.task_type[qsafe].reshape(-1),
        n_types,
    )
    # surviving orphans lose their site (re-dispatched this same event);
    # exhausted ones keep it, like any other cancelled task.
    site = st.site.at[
        jnp.where(qvict & ~q_exh, st.queue, n).reshape(-1)
    ].set(-1, mode="drop")
    queue = jnp.where(died[:, None], -1, st.queue)
    qlen = jnp.where(died, 0, st.qlen)

    # -- 3. kill running tasks on newly-dead machines ----------------------
    kill = died & (st.run_task >= 0)
    vict = jnp.where(kill, st.run_task, 0)
    dur = jnp.where(kill, st.now - st.run_start, 0.0)
    energy = sysarr.p_dyn * dur
    e_dyn = st.e_dyn + energy.sum()
    e_wasted = st.e_wasted + jnp.where(kill, energy, 0.0).sum()
    busy = st.busy_time + dur
    retries = retries.at[jnp.where(kill, vict, n)].add(1, mode="drop")
    r_exh = kill & (retries[vict] > max_retries)
    ttype_v = trace.task_type[vict]

    if backup_k == 0:
        status = status.at[jnp.where(kill, vict, n)].set(
            jnp.where(r_exh, CANCELLED, PENDING), mode="drop"
        )
        cancelled = cancelled + jax.ops.segment_sum(
            r_exh.astype(jnp.int32), ttype_v, n_types
        )
        site = site.at[jnp.where(kill & ~r_exh, vict, n)].set(
            -1, mode="drop"
        )
    else:
        # Failover scan, machine index order (queue capacity is consumed
        # sequentially — two orphans favoring the same backup must not
        # both land in its last slot).
        sids = jnp.asarray(np.asarray(site_of_machine, np.int32))
        bks_all = st.backup[vict]                 # (M, k)

        def step(carry, xs):
            status, site, queue, qlen, cancelled = carry
            kill_m, v, exh, bks, tt = xs
            chosen = jnp.int32(-1)
            for i in range(backup_k):
                b = bks[i]
                bc = jnp.clip(b, 0)
                okb = ((chosen < 0) & (b >= 0) & alive_new[bc]
                       & (qlen[bc] < Q))
                chosen = jnp.where(okb, b, chosen)
            fail_over = kill_m & ~exh & (chosen >= 0)
            bc = jnp.clip(chosen, 0)
            slot = jnp.clip(qlen[bc], 0, Q - 1)
            queue = queue.at[bc, slot].set(
                jnp.where(fail_over, v, queue[bc, slot])
            )
            qlen = qlen.at[bc].add(jnp.where(fail_over, 1, 0))
            new_stat = jnp.where(
                exh, CANCELLED, jnp.where(fail_over, QUEUED, PENDING)
            )
            status = status.at[v].set(
                jnp.where(kill_m, new_stat, status[v])
            )
            new_site = jnp.where(
                fail_over, sids[bc], jnp.where(exh, site[v], -1)
            )
            site = site.at[v].set(jnp.where(kill_m, new_site, site[v]))
            cancelled = cancelled.at[tt].add(
                jnp.where(kill_m & exh, 1, 0)
            )
            return (status, site, queue, qlen, cancelled), None

        (status, site, queue, qlen, cancelled), _ = jax.lax.scan(
            step, (status, site, queue, qlen, cancelled),
            (kill, vict, r_exh, bks_all, ttype_v),
        )

    return st._replace(
        alive=alive_new,
        slowdown=slow_new,
        status=status,
        site=site,
        queue=queue,
        qlen=qlen,
        retries=retries,
        cancelled=cancelled,
        run_task=jnp.where(kill, -1, st.run_task),
        run_end_act=jnp.where(kill, jnp.inf, st.run_end_act),
        run_end_exp=jnp.where(kill, st.now, st.run_end_exp),
        run_success=jnp.where(kill, False, st.run_success),
        e_dyn=e_dyn,
        e_wasted=e_wasted,
        busy_time=busy,
    )


def _stage_dispatch(st: SimState, trace: Trace, sysarr: SystemArrays,
                    dispatcher, site_of_machine: np.ndarray, n_sites: int,
                    fairness_factor: float, type_onehot: jnp.ndarray,
                    health: bool = False, net=None):
    """Assign newly-admitted tasks to federation sites (dispatch-once).

    A task is dispatched at the first event where it is PENDING and still
    siteless; its site never changes afterwards. With one site the
    dispatcher is bypassed entirely (every task -> site 0), so flat
    systems carry zero dispatch ops in the traced loop body.

    With ``health`` (a non-trivial dynamics attached) the context's EET
    table is health-masked — dead machines' columns read BIG, straggler
    columns are slowdown-scaled — and ``ctx.alive`` carries the raw
    mask, from which ``ctx.site_alive`` derives the heartbeat aggregate
    ("site alive iff >= 1 healthy machine") that ``sequential_balance``
    and ``health_aware`` route on. ``min_eet`` needs no code of its own:
    a fully-dead site's ``eet_min_by_site`` column is BIG automatically.

    With ``net`` (a non-trivial network model attached — a 4-tuple
    ``(lat_task, en_task, site_tier, n_tiers)`` of per-task (N, F) link
    costs and the static tier map) each fresh dispatch *pays its link*:

      * the task's ready time at the chosen site becomes ``now +
        lat_task[k, site]`` — the map stage will not place it before it
        lands (dispatch decisions are made at admission, on the
        information available then; the transfer is committed);
      * ``en_task[k, site]`` joules are charged to the Eq. 2 dynamic
        account and tallied per destination tier (``e_xfer``);
      * in-transit tasks whose deadline passes before they land are
        CANCELLED here (the map stage cannot see them, so the stale-drop
        policies never get the chance) — the transfer energy already
        spent stays spent, but is not counted as *wasted* compute
        energy, matching Eq. 2's row-3 zero-compute-energy drop.

    An orphan re-dispatched by the faults stage (site cleared) pays the
    transfer again from its origin; a backup failover does not — FEST-
    style backups pre-stage their inputs at nomination time.
    """
    new = (st.status == PENDING) & (st.site < 0)
    if n_sites == 1:
        sites = 0  # scalar — broadcasts in the wheres below, like PR 8
    else:
        eet = sysarr.eet
        alive = None
        if health:
            alive = st.alive
            eet = jnp.where(alive[None, :], eet * st.slowdown[None, :], BIG)
        ctx = DispatchContext(
            now=st.now,
            unassigned=new,
            task_type=trace.task_type,
            deadline=trace.deadline,
            qlen=st.qlen,
            running=st.run_task >= 0,
            completed=st.completed,
            arrived=st.arrived,
            eet=eet,
            site_of_machine=site_of_machine,
            n_sites=n_sites,
            fairness_factor=fairness_factor,
            alive=alive,
            xfer_lat=None if net is None else net[0],
            xfer_energy=None if net is None else net[1],
        )
        sites = jnp.clip(dispatcher.dispatch(ctx).astype(jnp.int32),
                         0, n_sites - 1)
    st = st._replace(site=jnp.where(new, sites, st.site))
    if net is None:
        return st
    lat_task, en_task, site_tier, n_tiers = net
    s = jnp.clip(jnp.where(new, sites, 0), 0, n_sites - 1)
    lat = jnp.take_along_axis(lat_task, s[:, None], axis=1)[:, 0]
    en = jnp.take_along_axis(en_task, s[:, None], axis=1)[:, 0]
    ready = jnp.where(new, st.now + lat, st.ready)
    pay = jnp.where(new, en, 0.0)
    e_xfer = st.e_xfer + jax.ops.segment_sum(pay, site_tier[s], n_tiers)
    stale = ((st.status == PENDING) & (ready > st.now)
             & (st.now >= trace.deadline))
    status = jnp.where(stale, CANCELLED, st.status)
    cancelled = st.cancelled + _count_by_type(stale, type_onehot)
    return st._replace(ready=ready, e_dyn=st.e_dyn + pay.sum(),
                       e_xfer=e_xfer, status=status, cancelled=cancelled)


def _stage_map(st: SimState, trace: Trace, sysarr: SystemArrays,
               select_fn: Callable, fairness_factor: float,
               type_onehot: jnp.ndarray,
               site_members: Optional[np.ndarray] = None,
               site_of_machine: Optional[np.ndarray] = None,
               health: bool = False, backup_k: int = 0,
               suffered: Optional[jnp.ndarray] = None):
    """Run the per-site mapping policy and apply the combined MapAction.

    ``site_members`` is the (F, M) partition grid — a host constant whose
    *values* are data, not program structure: the policy is evaluated once
    as a single ``jax.vmap`` over the F site-masked machine views, so the
    traced program contains exactly one copy of the mapping computation
    regardless of F (trace size and compile time are flat in the site
    count; only array extents grow). Machines outside a site appear full
    (``qlen = Q``), empty-queued, and infinitely far away (``avail_base =
    BIG``, EET rows ``BIG``), so nominators, feasibility guards and the
    fairness eviction all see a site-local system — in particular
    ``hopeless``/``rescuable`` use the site's own fastest machine.

    The F per-site :class:`MapAction` batches are combined by gathers:
    machine ``m`` takes its owning site's ``assign``/``queue_drop`` row
    (``site_of_machine`` is the (M,) owner map), and task ``n`` takes its
    dispatched site's ``drop`` entry — the same one-owner-per-entry
    semantics the PR 5 static unroll realized with F masked merges
    (pinned bit-exact in ``tests/test_siteloop_vmap.py``). With F=1 the
    branch below is literally the pre-federation computation (no masking
    ops), keeping flat runs bit-exact.
    """
    action = _map_action(st, trace, sysarr, select_fn, fairness_factor,
                         site_members, site_of_machine, health, suffered)
    st2 = _apply_action(st, trace, action, type_onehot)
    if backup_k > 0:
        st2 = _nominate_backups(st2, trace, sysarr, action, backup_k)
    return st2


def _nominate_backups(st: SimState, trace: Trace, sysarr: SystemArrays,
                      action: MapAction, backup_k: int) -> SimState:
    """Record k backup machines for each task enqueued this event.

    FEST-style greedy: per assigned task, the k healthy machines
    (primary excluded, disjoint among themselves) minimizing expected
    completion ``avail_base + EET`` — iterative masked argmins, ties to
    the lowest machine index. Backups are passive standbys written into
    ``st.backup``; the faults stage reads them only when the primary
    dies mid-run. ``-1`` marks "no eligible backup" (fewer than k
    healthy candidates).
    """
    M, Q = st.queue.shape
    n = st.status.shape[0]
    a = jnp.clip(action.assign, 0)
    ok = (action.assign >= 0) & (st.status[a] == QUEUED)
    eet_eff = jnp.where(
        st.alive[None, :], sysarr.eet * st.slowdown[None, :], BIG
    )
    avail_base = jnp.maximum(
        jnp.where(st.run_task >= 0, st.run_end_exp, st.now), st.now
    )
    avail_base = jnp.where(st.alive, avail_base, BIG)
    score = avail_base[None, :] + eet_eff[trace.task_type[a]]   # (M, M)
    cols = jnp.arange(M)
    score = jnp.where(cols[None, :] == cols[:, None], BIG, score)
    picks = []
    for _ in range(backup_k):
        b = jnp.argmin(score, axis=1).astype(jnp.int32)
        has = jnp.take_along_axis(score, b[:, None], axis=1)[:, 0] < BIG
        picks.append(jnp.where(ok & has, b, -1))
        score = jnp.where(cols[None, :] == b[:, None], BIG, score)
    backup = st.backup.at[jnp.where(ok, a, n)].set(
        jnp.stack(picks, axis=1), mode="drop"
    )
    return st._replace(backup=backup)


def _map_action(st: SimState, trace: Trace, sysarr: SystemArrays,
                select_fn: Callable, fairness_factor: float,
                site_members: Optional[np.ndarray] = None,
                site_of_machine: Optional[np.ndarray] = None,
                health: bool = False,
                suffered: Optional[jnp.ndarray] = None) -> MapAction:
    """The combined :class:`MapAction` of one mapping event (pre-apply).

    ``suffered`` is this event's Eq. 3 mask where the caller has it
    already (:func:`_suffered` otherwise).

    With ``health`` the machine view is masked *before* the single-site /
    block-diagonal / masked-vmap split: dead machines read avail=BIG,
    empty queues, qlen=Q and EET=BIG — byte-identical to how out-of-site
    machines already look — and straggler EET columns are slowdown-
    scaled. Policies therefore route around failures with zero
    policy-side code (in particular ``stale_hopeless`` cancels a dead
    site's pending tasks: its fastest machine reads BIG).
    """
    if suffered is None:
        suffered = _suffered(st, select_fn, fairness_factor)
    pending = st.status == PENDING
    if st.ready is not None:
        # network subsystem: in-transit tasks (dispatched, not yet landed
        # at their site) are invisible to the mapper until they arrive.
        pending = pending & (st.ready <= st.now)
    avail_base = jnp.maximum(
        jnp.where(st.run_task >= 0, st.run_end_exp, st.now), st.now
    )
    queue_v, qlen_v = st.queue, st.qlen
    if health:
        Q = st.queue.shape[1]
        sysarr = sysarr._replace(eet=jnp.where(
            st.alive[None, :], sysarr.eet * st.slowdown[None, :], BIG
        ))
        avail_base = jnp.where(st.alive, avail_base, BIG)
        queue_v = jnp.where(st.alive[:, None], st.queue, -1)
        qlen_v = jnp.where(st.alive, st.qlen, Q)
    n_sites = 1 if site_members is None else site_members.shape[0]
    if n_sites == 1:
        view = MachineView(avail_base=avail_base, queue=queue_v,
                           qlen=qlen_v)
        return select_fn(
            st.now,
            pending,
            trace.task_type,
            trace.deadline,
            view,
            sysarr,
            suffered,
        )

    M, Q = st.queue.shape
    owner_np = np.asarray(site_of_machine, np.int32)
    m = M // n_sites
    if M % n_sites == 0 and (
            owner_np == np.repeat(np.arange(n_sites), m)).all():
        # Block-diagonal fast path: every fleet whose sites are equal
        # contiguous machine blocks (all `paper_xF` scalings) reshapes the
        # (M,)-wide state into (F, m) per-site views instead of masking —
        # the widest op in the vmapped policy is O(m), not O(M), keeping
        # both XLA codegen time and warm runtime flat in F. Bit-exact vs
        # the masked path: every machine-axis reduction in policy code is
        # a min/argmin whose assignment is gated on feasibility
        # (`phase2`'s `key < BIG`), so dropping the BIG-padded outside
        # machines changes no reduced value and no tie-break order.
        S = sysarr.eet.shape[0]

        def one_block(avail_s, queue_s, qlen_s, eet_s, p_dyn_s, p_idle_s, s):
            view_s = MachineView(avail_base=avail_s, queue=queue_s,
                                 qlen=qlen_s)
            sysarr_s = SystemArrays(eet=eet_s, p_dyn=p_dyn_s,
                                    p_idle=p_idle_s)
            return select_fn(
                st.now,
                pending & (st.site == s),
                trace.task_type,
                trace.deadline,
                view_s,
                sysarr_s,
                suffered,
            )

        acts = jax.vmap(one_block)(
            avail_base.reshape(n_sites, m),
            queue_v.reshape(n_sites, m, Q),
            qlen_v.reshape(n_sites, m),
            jnp.moveaxis(sysarr.eet.reshape(S, n_sites, m), 0, 1),
            sysarr.p_dyn.reshape(n_sites, m),
            sysarr.p_idle.reshape(n_sites, m),
            jnp.arange(n_sites, dtype=jnp.int32),
        )
        assign = acts.assign.reshape(M)
        tsite = jnp.clip(st.site, 0, n_sites - 1)
        drop = (jnp.take_along_axis(acts.drop, tsite[None, :], axis=0)[0]
                & (st.site >= 0))
        queue_drop = acts.queue_drop.reshape(M, Q)
        return MapAction(assign, drop, queue_drop)

    def one_site(in_site, s):
        view_s = MachineView(
            avail_base=jnp.where(in_site, avail_base, BIG),
            queue=jnp.where(in_site[:, None], queue_v, -1),
            qlen=jnp.where(in_site, qlen_v, Q),
        )
        sysarr_s = sysarr._replace(
            eet=jnp.where(in_site[None, :], sysarr.eet, BIG)
        )
        return select_fn(
            st.now,
            pending & (st.site == s),
            trace.task_type,
            trace.deadline,
            view_s,
            sysarr_s,
            suffered,
        )

    acts = jax.vmap(one_site)(
        jnp.asarray(site_members), jnp.arange(n_sites, dtype=jnp.int32)
    )  # MapAction with (F,)-leading leaves
    owner = jnp.asarray(site_of_machine, jnp.int32)  # (M,) constant
    assign = jnp.take_along_axis(acts.assign, owner[None, :], axis=0)[0]
    tsite = jnp.clip(st.site, 0, n_sites - 1)
    drop = (jnp.take_along_axis(acts.drop, tsite[None, :], axis=0)[0]
            & (st.site >= 0))
    queue_drop = jnp.take_along_axis(
        acts.queue_drop, owner[None, :, None], axis=0
    )[0]
    return MapAction(assign, drop, queue_drop)


def _suffered(st: SimState, select_fn: Callable, fairness_factor: float):
    """The Eq. 3 monitor (Alg. 4) at this event; all False, and no ops,
    for a policy that never reads it."""
    if not _uses_fairness(select_fn):
        return jnp.zeros(st.completed.shape, bool)
    with jax.named_scope("engine.fairness"):
        return fairness.suffered_types(st.completed, st.arrived,
                                       fairness_factor)


def _apply_action(st: SimState, trace: Trace, action,
                  type_onehot: jnp.ndarray):
    """Apply a MapAction: queue evictions, proactive drops, assignments."""
    M, Q = st.queue.shape
    n_types = st.cancelled.shape[0]
    # --- queue evictions (FELARE victims) -> CANCELLED ----------------------
    victim = action.queue_drop & (st.queue >= 0)
    vidx = jnp.where(victim, st.queue, st.status.shape[0])
    status = st.status.at[vidx.reshape(-1)].set(CANCELLED, mode="drop")
    cancelled = st.cancelled + jax.ops.segment_sum(
        victim.reshape(-1).astype(jnp.int32),
        trace.task_type[jnp.clip(vidx, 0, st.status.shape[0] - 1)].reshape(-1),
        n_types,
    )
    if st.fair_evicted is not None:
        st = st._replace(fair_evicted=st.fair_evicted
                         + victim.sum(dtype=jnp.int32))
    # compact queues (stable: keep FCFS order of survivors)
    keep = ~victim & (st.queue >= 0)
    order = jnp.argsort(~keep, axis=1, stable=True)  # survivors first
    queue = jnp.take_along_axis(jnp.where(keep, st.queue, -1), order, axis=1)
    qlen = keep.sum(axis=1).astype(jnp.int32)

    # --- proactive drops from the arriving queue ----------------------------
    drop = action.drop & (status == PENDING)
    status = jnp.where(drop, CANCELLED, status)
    cancelled = cancelled + _count_by_type(drop, type_onehot)

    # --- assignments: append to queue tails ---------------------------------
    assign = action.assign  # (M,)
    # guard: task must still be PENDING (not dropped above) and slot free
    tstat = status[jnp.clip(assign, 0)]
    ok = (assign >= 0) & (tstat == PENDING) & (qlen < Q)
    slot = jnp.clip(qlen, 0, Q - 1)
    queue = queue.at[jnp.arange(M), slot].set(
        jnp.where(ok, assign, queue[jnp.arange(M), slot])
    )
    qlen = jnp.where(ok, qlen + 1, qlen)
    status = status.at[jnp.where(ok, assign, st.status.shape[0])].set(
        QUEUED, mode="drop"
    )
    return st._replace(status=status, queue=queue, qlen=qlen,
                       cancelled=cancelled)


def _stage_start(st: SimState, trace: Trace, sysarr: SystemArrays,
                 health: bool = False):
    """Idle machines pop their queue head (one pop per machine per event).

    A popped task whose deadline already passed "runs" for zero time with
    success=False and zero energy — the next loop iteration (same timestamp)
    finalizes it and pops again, which realizes Eq. 1/2's third row without
    an inner loop.

    With ``health``, dead machines never pop (their queues are empty
    anyway — the faults stage flushed them) and straggler machines run
    every task ``slowdown``× longer, both in actual and expected time.
    """
    M = st.run_task.shape[0]
    can = (st.run_task < 0) & (st.qlen > 0)
    if health:
        can = can & st.alive
    head = jnp.where(can, st.queue[:, 0], 0)
    ttype = trace.task_type[head]
    dl = trace.deadline[head]
    e_act = trace.exec_actual[head, jnp.arange(M)]
    e_exp = sysarr.eet[ttype, jnp.arange(M)]
    if health:
        e_act = e_act * st.slowdown
        e_exp = e_exp * st.slowdown
    dead_on_arrival = st.now >= dl
    end_act = jnp.where(
        dead_on_arrival, st.now, jnp.minimum(st.now + e_act, dl)
    )
    success = ~dead_on_arrival & (st.now + e_act <= dl)
    end_exp = jnp.where(
        dead_on_arrival, st.now, jnp.minimum(st.now + e_exp, dl)
    )

    queue = jnp.where(
        can[:, None],
        jnp.concatenate(
            [st.queue[:, 1:], jnp.full((M, 1), -1, jnp.int32)], axis=1
        ),
        st.queue,
    )
    status = st.status.at[jnp.where(can, head, st.status.shape[0])].set(
        RUNNING, mode="drop"
    )
    return st._replace(
        status=status,
        run_task=jnp.where(can, head, st.run_task),
        run_start=jnp.where(can, st.now, st.run_start),
        run_end_act=jnp.where(can, end_act, st.run_end_act),
        run_end_exp=jnp.where(can, end_exp, st.run_end_exp),
        run_success=jnp.where(can, success, st.run_success),
        queue=queue,
        qlen=jnp.where(can, st.qlen - 1, st.qlen),
    )


# Backwards-compatible aliases for the pre-stage-split helper names.
_finalize_completions = _stage_finalize
_admit_arrivals = _stage_admit
_start_tasks = _stage_start


def make_simulator(select_fn: Callable, sysarr: SystemArrays, *,
                   queue_size: int, fairness_factor: float = 1.0,
                   max_steps: int | None = None,
                   observers: tuple = (),
                   dispatcher=None,
                   site_of_machine: tuple | None = None,
                   dynamics=None,
                   network=None,
                   tier_of_site: tuple | None = None) -> Callable:
    """Build ``simulate(trace)`` for one mapping policy.

    ``dynamics`` is the machine-failure process — a registered
    :mod:`repro.core.faults` name or :class:`~repro.core.faults.
    MachineDynamics` instance, closed over statically like the policy.
    ``None``/``"none"`` (the default) skips the faults stage entirely,
    keeping the loop bit-exact with the pre-faults engine; any other
    dynamics turns on health masking at the dispatch/map/start stages
    and orphan re-dispatch at the ``faults`` stage. A ``with_backup``-
    wrapped policy additionally activates k-failure backup nomination
    (inert without a dynamics — backups only matter if machines can
    die).

    ``network`` is the inter-site cost model — a registered
    :mod:`repro.core.network` name or :class:`~repro.core.network.
    NetworkModel` instance, closed over statically. ``None``/``"none"``
    (the default) skips all transfer arithmetic, keeping the loop
    bit-exact with the pre-network engine; any other model prices each
    task's ``origin -> chosen site`` link at the dispatch stage (ready-
    time shift + Eq. 2 transfer energy; see :func:`_stage_dispatch`).
    ``tier_of_site`` is the static (F,) site-tier partition (device=0 /
    edge=1 / cloud=2; ``None`` = all device-tier) the model prices and
    the ``network`` observer aggregates on.

    ``select_fn(now, pending, task_type, deadline, view, sysarr, suffered)``
    is any :class:`repro.core.policy.Policy` (e.g. from
    ``policy.get(name)``) or a bare function with the same signature; it is
    closed over statically so jit specializes per policy.

    ``site_of_machine`` is the *static* federation partition — a tuple of
    per-machine site ids (``None`` = one site) — and ``dispatcher`` the
    :class:`repro.core.dispatch.Dispatcher` assigning newly-admitted
    tasks to sites (``None`` = the default ``sticky``; irrelevant with
    one site, where the dispatch stage is the constant "site 0"). Both
    are closed over statically, like the policy.

    ``observers`` is a tuple of :class:`repro.core.observe.Observer`
    instances (hashable, closed over statically — attaching observers
    never retraces per call). With ``observers=()`` the simulator returns
    bare :class:`Metrics`, bit-identical to the pre-observer engine; with
    observers it returns ``(Metrics, aux)`` where ``aux`` maps each
    observer's name to its finalized pytree.
    """
    from repro.core import dispatch as dispatch_mod
    from repro.core import faults as faults_mod
    from repro.core import network as network_mod

    S, M = sysarr.eet.shape
    fair = _uses_fairness(select_fn)
    dynamics = faults_mod.resolve(dynamics)
    if getattr(dynamics, "kind", None) == "none":
        dynamics = None
    backup_k = (int(getattr(select_fn, "backup_k", 0))
                if dynamics is not None else 0)
    wake = (tuple(float(w) for w in dynamics.wake_fracs())
            if dynamics is not None and hasattr(dynamics, "wake_fracs")
            else ())
    sites = ((0,) * M if site_of_machine is None
             else tuple(int(s) for s in site_of_machine))
    if len(sites) != M:
        raise ValueError(
            f"site_of_machine has {len(sites)} entries for {M} machines"
        )
    n_sites = max(sites) + 1
    sites_np = np.asarray(sites, np.int32)
    site_members = (site_membership(sites_np, n_sites)
                    if n_sites > 1 else None)
    dispatcher = dispatch_mod.resolve(dispatcher)
    tiers = ((0,) * n_sites if tier_of_site is None
             else tuple(int(t) for t in tier_of_site))
    if len(tiers) != n_sites:
        raise ValueError(
            f"tier_of_site has {len(tiers)} entries for {n_sites} sites"
        )
    network = network_mod.resolve(network)
    if getattr(network, "kind", None) == "none":
        network = None
    if network is not None:
        n_tiers = max(tiers) + 1
        lat_np, en_np = network.cost_tables(tiers, S)
        origins = network_mod.origin_sites(tiers)
        net_salt = int(getattr(network, "salt", 0))
        tiers_np = np.asarray(tiers, np.int32)
    observers = tuple(
        ob.with_engine_config(fairness_factor=fairness_factor,
                              queue_size=queue_size,
                              site_of_machine=sites,
                              tier_of_site=tiers)
        if hasattr(ob, "with_engine_config") else ob
        for ob in observers
    )
    names = [ob.name for ob in observers]
    if len(set(names)) != len(names):
        raise ValueError(f"duplicate observer names {names}")
    gaters = tuple(ob for ob in observers if getattr(ob, "is_dynamic", False))

    def _halt(st, aux):
        h = jnp.bool_(False)
        for ob in gaters:
            h = h | ob.halted(aux[ob.name], st)
        return h

    def simulate(trace: Trace):
        n = trace.arrival.shape[0]
        steps_cap = max_steps if max_steps is not None else 8 * n + 64
        netted = network is not None
        st = _init_state(trace, M, queue_size, S, backup_k=backup_k,
                         network=netted,
                         n_tiers=n_tiers if netted else 1, fair=fair)
        if netted:
            # Per-task (N, F) link costs, gathered once outside the loop:
            # row k prices task k's origin (a salted counter hash over the
            # device-tier sites) against every destination site.
            origin = network_mod.hash_origins(n, origins, net_salt)
            lat_task = jnp.asarray(lat_np)[trace.task_type, origin]
            en_task = jnp.asarray(en_np)[trace.task_type, origin]
            net = (lat_task, en_task, jnp.asarray(tiers_np), n_tiers)
        else:
            net = None
        aux = {ob.name: ob.init(trace, sysarr) for ob in observers}
        health = dynamics is not None
        horizon = (jnp.max(trace.deadline).astype(jnp.float32)
                   if health else None)
        wake_ts = (jnp.asarray(wake, jnp.float32) * horizon
                   if wake else None)
        type_onehot = _type_onehot(trace.task_type, S)

        def cond(est: EngineState):
            st, aux = est
            halted = _halt(st, aux) if gaters else None
            with jax.named_scope("engine.next_event"):
                t = _next_event_time(st, trace, halted, wake_ts)
            return jnp.isfinite(t) & (st.steps < steps_cap)

        def notify(stage, aux, st):
            return {
                ob.name: ob.on_event(stage, aux[ob.name], st, trace, sysarr)
                for ob in observers
            }

        def body(est: EngineState):
            st, aux = est
            halted = _halt(st, aux) if gaters else None
            with jax.named_scope("engine.next_event"):
                t = _next_event_time(st, trace, halted, wake_ts)
                st = st._replace(now=jnp.maximum(t, st.now))
            with jax.named_scope("engine.finalize"):
                st = _stage_finalize(st, trace, sysarr)
            aux = notify("finalize", aux, st)
            with jax.named_scope("engine.admit"):
                st = _stage_admit(st, trace, type_onehot, halted)
            aux = notify("admit", aux, st)
            if health:
                with jax.named_scope("engine.faults"):
                    st = _stage_faults(st, trace, sysarr, dynamics, horizon,
                                       S, backup_k, sites_np, n_sites)
                aux = notify("faults", aux, st)
            with jax.named_scope("engine.dispatch"):
                st = _stage_dispatch(st, trace, sysarr, dispatcher, sites_np,
                                     n_sites, fairness_factor, type_onehot,
                                     health, net)
            aux = notify("dispatch", aux, st)
            with jax.named_scope("engine.map"):
                suffered = (_suffered(st, select_fn, fairness_factor)
                            if fair else None)
                st = _stage_map(st, trace, sysarr, select_fn, fairness_factor,
                                type_onehot, site_members, sites_np, health,
                                backup_k, suffered=suffered)
                if fair:
                    st = st._replace(suffered_maps=st.suffered_maps
                                     + jnp.any(suffered).astype(jnp.int32))
            aux = notify("map", aux, st)
            with jax.named_scope("engine.start"):
                st = _stage_start(st, trace, sysarr, health)
            aux = notify("start", aux, st)
            return EngineState(st._replace(steps=st.steps + 1), aux)

        st, aux = jax.lax.while_loop(cond, body, EngineState(st, aux))
        makespan = st.now
        e_idle = (sysarr.p_idle * (makespan - st.busy_time)).sum()
        metrics = Metrics(
            completed_by_type=st.completed,
            missed_by_type=st.missed,
            cancelled_by_type=st.cancelled,
            arrived_by_type=st.arrived,
            energy_dynamic=st.e_dyn,
            energy_wasted=st.e_wasted,
            energy_idle=e_idle,
            makespan=makespan,
            steps=st.steps,
            fair_evicted=st.fair_evicted if fair else jnp.int32(0),
            suffered_maps=st.suffered_maps if fair else jnp.int32(0),
        )
        if not observers:
            return metrics
        aux_out = {ob.name: ob.finalize(aux[ob.name], st) for ob in observers}
        return metrics, aux_out

    return simulate


@functools.partial(jax.jit, static_argnames=("select_fn", "observers",
                                             "queue_size", "fairness_factor",
                                             "max_steps", "batched",
                                             "dispatcher", "sites",
                                             "dynamics", "network", "tiers"))
def _simulate_jit(trace, eet, p_dyn, p_idle, select_fn, observers,
                  queue_size, fairness_factor, max_steps, batched,
                  dispatcher=None, sites=None, dynamics=None,
                  network=None, tiers=None):
    """The one cached jit entry point behind ``simulate``/``simulate_batch``.

    Keyed on ``(select_fn, observers, dispatcher, sites, dynamics,
    network, tiers, static config)`` — re-calling with the same (frozen,
    hashable) policy, observer, dispatcher, dynamics and network objects
    hits the jit cache instead of re-tracing, including the vmapped
    batch path. ``sites`` is the static site-partition tuple (``None`` =
    single site); ``dynamics`` is the static machine-dynamics instance
    (``None`` = no faults stage); ``network``/``tiers`` are the static
    network model and (F,) site-tier tuple (``None`` = no transfer
    arithmetic).
    """
    sysarr = SystemArrays(
        eet=eet, p_dyn=p_dyn, p_idle=p_idle,
        site_of_machine=(None if sites is None
                         else jnp.asarray(sites, jnp.int32)),
    )
    sim = make_simulator(
        select_fn, sysarr, queue_size=queue_size,
        fairness_factor=fairness_factor, max_steps=max_steps,
        observers=observers, dispatcher=dispatcher, site_of_machine=sites,
        dynamics=dynamics, network=network, tier_of_site=tiers,
    )
    return jax.vmap(sim)(trace) if batched else sim(trace)


def _simulate(trace, spec, heuristic, observers, max_steps, batched,
              dispatcher=None, dynamics=None, network=None):
    from repro.core import dispatch as dispatch_mod
    from repro.core import faults as faults_mod
    from repro.core import network as network_mod
    from repro.core import observe, policy

    obs = observe.resolve(observers)
    sites = getattr(spec, "site_of_machine", None)
    sites = None if sites is None else tuple(int(s) for s in sites)
    # Single-site systems bypass the dispatch stage entirely, so the
    # dispatcher must not enter the static jit cache key there — else two
    # bit-identical flat runs under different dispatcher names would each
    # pay a full recompile.
    disp = (None if sites is None or max(sites) == 0
            else dispatch_mod.resolve(dispatcher))
    # Same idea for dynamics: the trivial "none" dynamics is normalized
    # to None before the jit key, so ``dynamics="none"`` and the default
    # share one cache entry (and one bit-exact program).
    dyn = faults_mod.resolve(dynamics)
    if getattr(dyn, "kind", None) == "none":
        dyn = None
    # And for networks: "none" and the default share the PR 8 program.
    net = network_mod.resolve(network)
    if getattr(net, "kind", None) == "none":
        net = None
    net_tiers = (None if net is None
                 else tuple(int(t) for t in spec.tiers)
                 if hasattr(spec, "tiers") else None)
    return _simulate_jit(
        trace,
        jnp.asarray(spec.eet, jnp.float32),
        jnp.asarray(spec.p_dyn, jnp.float32),
        jnp.asarray(spec.p_idle, jnp.float32),
        policy.get(heuristic) if isinstance(heuristic, str) else heuristic,
        obs,
        spec.queue_size,
        float(spec.fairness_factor),
        max_steps,
        batched,
        disp,
        sites,
        dyn,
        net,
        net_tiers,
    )


def simulate(trace: Trace, spec, heuristic: str, *, observers=(),
             max_steps=None, dispatcher=None, dynamics=None, network=None):
    """Convenience entry point: one trace, one SystemSpec, one heuristic.

    The heuristic name is resolved through the policy registry, observer
    names through the observer registry, the dispatcher name through the
    dispatcher registry, and the dynamics/network names through their
    registries — all *outside* the jit boundary; the (frozen, hashable)
    policy/observer/dispatcher/dynamics/network objects are the static
    cache key — so re-registering a name with ``overwrite=True`` takes
    effect instead of silently hitting a stale name-keyed jit cache.
    ``spec.site_of_machine`` (if set) partitions the machines into
    federation sites served through ``dispatcher``; ``dynamics``
    (default ``None`` = ``"none"``) injects machine failures at the
    ``faults`` stage (see :mod:`repro.core.faults`); ``network``
    (default ``None`` = ``"none"``) prices inter-site dispatch over
    ``spec.tier_of_site`` (see :mod:`repro.core.network`).

    Returns :class:`Metrics` when ``observers`` is empty, else
    ``(Metrics, aux)`` with ``aux`` keyed by observer name.
    """
    return _simulate(trace, spec, heuristic, observers, max_steps, False,
                     dispatcher, dynamics, network)


def simulate_batch(traces: Trace, spec, heuristic: str, *, observers=(),
                   max_steps=None, dispatcher=None, dynamics=None,
                   network=None):
    """vmap over a stacked batch of traces (the paper's 30-trace studies).

    Shares the cached ``_simulate_jit`` with :func:`simulate`: calling it
    in a loop over heuristics compiles each policy exactly once instead of
    rebuilding and re-jitting the vmapped simulator per call.
    """
    return _simulate(traces, spec, heuristic, observers, max_steps, True,
                     dispatcher, dynamics, network)
