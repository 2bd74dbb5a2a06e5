"""Independent pure-Python oracle simulator.

Deliberately written with plain loops and numpy (no shared code with the JAX
engine beyond the dataclasses) so hypothesis property tests can cross-check
the vectorized `repro.core.engine` implementation event-by-event.

Policies are interpreted from their declarative description
(:class:`repro.core.policy.PolicyDesc` — nominator × phase-2 key × drop rule
× fairness flag) rather than hard-coded name branches, so any policy
composed from the registered pieces is oracle-checkable, including
user-registered compositions. Opaque policies (custom callables without a
``describe()``) have no oracle interpretation and raise ``TypeError``.

Federations are interpreted the same way: when ``spec.site_of_machine``
partitions the machines into F sites, a ``dispatch`` step assigns each
newly-pending task a site (interpreting the dispatcher's ``kind`` +
dataclass fields — every built-in of :mod:`repro.core.dispatch` has a
plain-loop mirror here) and the mapping event then runs once per site
over the site's own pending tasks and machines, with site-local
feasibility (``hopeless``/``rescuable`` consult the site's fastest
machine, exactly like the engine's BIG-masked EET rows).

Machine dynamics (:mod:`repro.core.faults`) are interpreted too: a
``faults`` step between arrivals and dispatch evolves per-machine
``(alive, slowdown)`` (each built-in ``kind`` has a plain-loop mirror,
down to the integer hash driving ``bernoulli_updown``), orphans the
dead machines' tasks with the engine's exact retry/cancel/failover
rules, and every decision table (EET columns, availability, per-site
fastest machine) is re-derived with dead machines masked to BIG —
byte-identical to how the engine masks out-of-site machines.

Network models (:mod:`repro.core.network`) are interpreted the same
way: task origins come from the same salted counter hash
(``hash_origins_host``), each dispatch stamps the task's site ready
time ``f32(now) + f32(lat)`` and charges the link's transfer energy,
in-transit tasks are invisible to the mapper until they land (landings
drive events), and an in-transit task whose deadline passes is
cancelled at the dispatch step — all mirroring the engine's f32
transfer arithmetic operation-for-operation.

Precision note: trace times are dyadic (the tests round them), so event
timestamps are exact in both engines. Everything derived from the EET table
(availability sums, feasibility boundaries, energy keys, the fairness limit)
is NOT dyadic, and the JAX engine computes it in float32 — a float64 oracle
flips near-tie mapping decisions and diverges. All decision arithmetic below
therefore mirrors the engine's float32 operation order exactly; only the
reported energy accumulators stay float64 (tests compare them with rel
tolerance).
"""
from __future__ import annotations

import numpy as np

from repro.core.types import (
    CANCELLED,
    COMPLETED,
    MISSED,
    PENDING,
    QUEUED,
    RUNNING,
    UNARRIVED,
)

BIG = 1e30
F = np.float32


class _Machine:
    def __init__(self, j):
        self.j = j
        self.run = -1
        self.run_start = 0.0
        self.run_end_act = np.inf
        self.run_end_exp = F(0.0)
        self.run_success = False
        self.queue: list[int] = []
        self.busy = 0.0


def _completion(s, e, d):
    if s + e <= d:
        return s + e
    if s < d:
        return d
    return s


def _lookup(table, kind, what):
    """kind -> handler, with the guard and the dispatch one data structure."""
    try:
        return table[kind]
    except KeyError:
        raise NotImplementedError(
            f"oracle has no interpretation for {what} {kind!r}"
        ) from None


def _dispatch_interpreter(dispatcher, n_sites: int):
    """``kind`` + fields -> a plain-loop ``assign_sites`` closure.

    ``assign_sites(new, ttype, suffered, load, eet_min_site, site_alive,
    xfer_lat)`` returns ``{task index: site}`` for the indices in ``new``
    (walked in ascending order), mutating ``load`` for the
    load-balancing kinds exactly like the engine's
    ``sequential_balance`` scan; ``eet_min_site`` is the (S, F) per-site
    fastest-machine table ``min_eet`` consults. ``site_alive`` is the
    faults subsystem's heartbeat mask (``None`` with no dynamics
    attached); the caller has already folded the engine's dead-site load
    penalty into ``load``, so only ``health_aware`` reads the mask
    directly (for its home check). ``xfer_lat`` is the network
    subsystem's (n, F) per-task link-latency row table (``None`` with no
    network attached); only ``tier_aware`` reads it.
    """
    from repro.core import dispatch as dispatch_mod

    d = dispatch_mod.resolve(dispatcher)
    F = n_sites

    def _hash(k, salt):
        return ((k * 2654435761 + salt) & 0xFFFFFFFF) % F

    if d.kind == "sticky":
        def assign(new, ttype, suffered, load, eet_min_site, site_alive,
                   xfer_lat):
            return {k: (ttype[k] % F if d.by_type else _hash(k, d.salt))
                    for k in new}
    elif d.kind == "round_robin":
        def assign(new, ttype, suffered, load, eet_min_site, site_alive,
                   xfer_lat):
            return {k: k % F for k in new}
    elif d.kind == "least_queued":
        def assign(new, ttype, suffered, load, eet_min_site, site_alive,
                   xfer_lat):
            out = {}
            for k in new:  # ascending index order, like the engine's scan
                s = int(np.argmin(load))
                load[s] += 1
                out[k] = s
            return out
    elif d.kind == "min_eet":
        def assign(new, ttype, suffered, load, eet_min_site, site_alive,
                   xfer_lat):
            return {k: int(np.argmin(eet_min_site[ttype[k]])) for k in new}
    elif d.kind == "fair_spill":
        def assign(new, ttype, suffered, load, eet_min_site, site_alive,
                   xfer_lat):
            out = {}
            for k in new:
                s = (int(np.argmin(load)) if suffered[ttype[k]]
                     else _hash(k, d.salt))
                load[s] += 1
                out[k] = s
            return out
    elif d.kind == "health_aware":
        def assign(new, ttype, suffered, load, eet_min_site, site_alive,
                   xfer_lat):
            out = {}
            for k in new:
                home = _hash(k, d.salt)
                s = (home if site_alive is None or site_alive[home]
                     else int(np.argmin(load)))
                load[s] += 1
                out[k] = s
            return out
    elif d.kind == "tier_aware":
        def assign(new, ttype, suffered, load, eet_min_site, site_alive,
                   xfer_lat):
            # engine: score = eet_min_by_site[type] (+ xfer_lat); argmin.
            # f32 + f32 row addition mirrors the traced add exactly.
            out = {}
            for k in new:
                row = eet_min_site[ttype[k]]
                if xfer_lat is not None:
                    row = (row + xfer_lat[k]).astype(np.float32)
                out[k] = int(np.argmin(row))
            return out
    else:
        raise NotImplementedError(
            f"oracle has no interpretation for dispatcher {d.kind!r}"
        )
    return assign


def simulate(trace, spec, heuristic: str, dispatcher=None, dynamics=None,
             network=None):
    """Run one trace; returns a dict mirroring Metrics.

    The dict also carries a ``"task_log"`` entry mirroring the JAX
    engine's ``task_log`` observer (:mod:`repro.core.observe`): per-task
    map/start/end times, machine, federation site, final status, orphan
    retry count and (with a network attached) site ready time, stamped
    at the same event timestamps — the cross-check is event-for-event,
    not just end-of-trace.
    """
    from repro.core import faults as faults_mod
    from repro.core import network as network_mod
    from repro.core import policy as policy_mod
    from repro.core.faults.base import hash_uniform_host

    desc = policy_mod.describe(heuristic)
    eet = np.asarray(spec.eet, np.float32)
    p_dyn = np.asarray(spec.p_dyn, np.float32)
    p_idle = np.asarray(spec.p_idle, np.float64)
    S, M = eet.shape
    Q = spec.queue_size
    fair_f = F(spec.fairness_factor)

    arr = np.asarray(trace.arrival, np.float64)
    ttype = np.asarray(trace.task_type)
    dl = np.asarray(trace.deadline, np.float64)
    exec_act = np.asarray(trace.exec_actual, np.float64)
    n = len(arr)

    # --- federation structure (F=1 for flat pre-federation specs) ----------
    sites = np.asarray(getattr(spec, "sites", (0,) * M), int)
    F_sites = int(sites.max()) + 1
    site_machines = [[j for j in range(M) if sites[j] == s]
                     for s in range(F_sites)]
    task_site = np.full(n, -1, int)
    assign_sites = (_dispatch_interpreter(dispatcher, F_sites)
                    if F_sites > 1 else None)

    # --- network costs (None = no transfer arithmetic, like the engine) ----
    net = network_mod.resolve(network)
    if getattr(net, "kind", None) == "none":
        net = None
    lat_task = en_task = None
    ready = arr.copy()  # site ready time; == arrival until first dispatch
    if net is not None:
        tiers = tuple(getattr(spec, "tiers", (0,) * F_sites))
        lat_tab, en_tab = net.cost_tables(tiers, S)
        origin = network_mod.hash_origins_host(
            n, network_mod.origin_sites(tiers), int(getattr(net, "salt", 0))
        )
        lat_task = np.asarray(lat_tab, F)[ttype, origin]  # (n, F) rows
        en_task = np.asarray(en_tab, F)[ttype, origin]

    # --- machine dynamics (None = no faults step, like the engine) ---------
    dyn = faults_mod.resolve(dynamics)
    if getattr(dyn, "kind", None) == "none":
        dyn = None
    backup_k = int(getattr(desc, "backup_k", 0)) if dyn is not None else 0
    max_retries = int(getattr(dyn, "max_retries", 3))
    horizon = F(dl.max())
    wake_ts = ([float(F(F(w) * horizon)) for w in dyn.wake_fracs()]
               if dyn is not None and hasattr(dyn, "wake_fracs") else [])
    alive = np.ones(M, bool)
    slowdown = np.ones(M, np.float32)
    retries = np.zeros(n, int)
    backup = np.full((n, backup_k), -1, int)

    # Decision tables, re-derived whenever health changes: dead machines'
    # EET columns read BIG (the engine's out-of-site masking, reused) and
    # straggler columns are slowdown-scaled. With no dynamics these are
    # exactly the raw tables (x * 1.0 is f32-exact).
    eet_c = eet
    eet_min_site = np.stack(
        [eet[:, ms].min(axis=1) for ms in site_machines], axis=1
    )

    def _refresh_tables():
        nonlocal eet_c, eet_min_site
        eet_c = np.where(
            alive[None, :], (eet * slowdown[None, :]).astype(F), F(BIG)
        ).astype(F)
        eet_min_site = np.stack(
            [eet_c[:, ms].min(axis=1) for ms in site_machines], axis=1
        )

    status = np.full(n, UNARRIVED)
    machines = [_Machine(j) for j in range(M)]
    completed = np.zeros(S, int)
    missed = np.zeros(S, int)
    cancelled = np.zeros(S, int)
    arrived = np.zeros(S, int)
    fair_evicted = suffered_maps = 0  # the fairness step's counters
    e_dyn = 0.0
    e_wasted = 0.0
    now = 0.0

    # task_log mirror: stamped once, at the event that made the transition
    # (``machine`` restamps at every start — it reports the task's last
    # placement, which moves on failover/re-dispatch).
    log_map = np.full(n, -1.0)
    log_start = np.full(n, -1.0)
    log_end = np.full(n, -1.0)
    log_machine = np.full(n, -1, int)

    def _end(k):
        if log_end[k] < 0:
            log_end[k] = now

    def next_event():
        ts = [arr[k] for k in range(n) if status[k] == UNARRIVED]
        ts += [m.run_end_act for m in machines if m.run >= 0]
        ts += [dl[k] for k in range(n) if status[k] == PENDING]
        ts += [w for w in wake_ts if w > now]  # outage window edges
        if net is not None:  # in-transit landings drive events too
            ts += [ready[k] for k in range(n)
                   if status[k] == PENDING and ready[k] > now]
        return min(ts) if ts else np.inf

    def avail_base(m):
        if not alive[m.j]:
            return F(BIG)
        return F(max(now, m.run_end_exp if m.run >= 0 else now))

    def qsum(m):
        # f32 slot-order reduction, like the engine's queued_eet(...).sum(1)
        s = F(0.0)
        for k in m.queue:
            s = F(s + eet_c[ttype[k], m.j])
        return s

    def avail(m):
        return F(avail_base(m) + qsum(m))

    def suffered_mask():
        cr = np.where(
            arrived > 0,
            completed.astype(F) / np.maximum(arrived, 1).astype(F),
            F(1.0),
        ).astype(F)
        mu = cr.mean(dtype=F)
        sigma = cr.std(dtype=F)
        eps = max(F(mu - F(fair_f * sigma)), F(0.0))
        return (cr <= eps) & (arrived >= 1)

    # --- Phase-I: one (task, machine, value) nomination per task -----------
    def _nominate_min_energy_feasible(pend, free):
        pairs = []
        for k in pend:
            best = None
            for j in free:
                s = avail(machines[j])
                e = eet_c[ttype[k], j]
                if F(s + e) <= dl[k]:
                    ec = F(p_dyn[j] * e)
                    if best is None or ec < best[2]:
                        best = (k, j, ec)
            if best:
                pairs.append(best)
        return pairs

    def _nominate_min_completion(pend, free):
        pairs = []
        for k in pend:
            best = None
            for j in free:
                s = avail(machines[j])
                c = _completion(s, eet_c[ttype[k], j], dl[k])
                if best is None or c < best[2]:
                    best = (k, j, c)
            if best:
                pairs.append(best)
        return pairs

    def _nominate_min_execution(pend, free):
        pairs = []
        for k in pend:
            best = None
            for j in free:
                e = eet_c[ttype[k], j]
                if best is None or e < best[2]:
                    best = (k, j, e)
            if best:
                pairs.append(best)
        return pairs

    def _nominate_random_hash(pend, free):
        t32 = int(np.uint32(F(F(now) * F(1e3))))
        return [(k, ((k * 2654435761 + t32) & 0xFFFFFFFF) % M, float(k))
                for k in pend]

    # --- Phase-II keys (lower = better), float32 with the engine's op order
    # so tie-breaking is bit-identical --------------------------------------
    def _key_urgency(k, j, val):
        slack = F(F(F(dl[k]) - F(now)) - eet_c[ttype[k], j])
        if abs(slack) < 1e-9:
            slack = F(1e-9)
        return F(-(F(1.0) / slack))

    nominate = _lookup({
        "min_energy_feasible": _nominate_min_energy_feasible,
        "min_completion": _nominate_min_completion,
        "min_execution": _nominate_min_execution,
        "random_hash": _nominate_random_hash,
    }, desc.nominator, "nominator")
    phase2_key = _lookup({
        "value": lambda k, j, val: F(val),
        "deadline": lambda k, j, val: F(F(dl[k]) + F(F(1e-6) * F(val))),
        "urgency": _key_urgency,
        "fcfs": lambda k, j, val: float(k),
    }, desc.phase2_key, "phase-2 key")
    drop_hopeless = _lookup({
        "stale": False,
        "stale_hopeless": True,
    }, desc.drop_rule, "drop rule")

    def phase2(pairs, machines_free):
        """pairs: list of (task, machine, key). One task per machine, min key."""
        assign = {}
        for j in machines_free:
            cand = [(key, k) for (k, jj, key) in pairs if jj == j]
            if cand:
                key, k = min(cand)
                assign[j] = k
        # a task may not be assigned twice (cannot happen: each task appears
        # with exactly one machine in `pairs`)
        return assign

    def dispatch_event():
        """Assign newly-pending tasks to sites (dispatch-once).

        With a network attached, each assignment also stamps the link's
        ready time and charges its transfer energy, and any in-transit
        task whose deadline passed is cancelled here — the engine does
        all three inside ``_stage_dispatch``.
        """
        nonlocal e_dyn
        new = [k for k in range(n)
               if status[k] == PENDING and task_site[k] < 0]
        if F_sites == 1:
            for k in new:
                task_site[k] = 0
        elif new:
            suffered = suffered_mask()
            load = np.asarray(
                [sum(len(machines[j].queue) for j in site_machines[s])
                 + sum(1 for j in site_machines[s] if machines[j].run >= 0)
                 for s in range(F_sites)], int)
            site_alive = None
            if dyn is not None:
                site_alive = np.asarray(
                    [any(alive[j] for j in site_machines[s])
                     for s in range(F_sites)])
                # engine's sequential_balance dead-site penalty
                load = load + np.where(site_alive, 0, 1_000_000)
            for k, s in assign_sites(new, ttype, suffered, load,
                                     eet_min_site, site_alive,
                                     lat_task).items():
                task_site[k] = min(max(int(s), 0), F_sites - 1)
        if net is None:
            return
        for k in new:
            s = task_site[k]
            # engine: ready = f32(now) + f32(lat); orphans re-pay on
            # re-dispatch (their task_site was reset to -1).
            ready[k] = float(F(F(now) + lat_task[k, s]))
            e_dyn += float(en_task[k, s])
        for k in range(n):  # stale in-transit purge (energy stays spent)
            if status[k] == PENDING and ready[k] > now and now >= dl[k]:
                status[k] = CANCELLED
                cancelled[ttype[k]] += 1
                _end(k)

    def mapping_event():
        nonlocal suffered_maps
        suffered = suffered_mask()
        if desc.fairness:
            suffered_maps += bool(suffered.any())
        for s in range(F_sites):
            _map_site(s, suffered)

    def _map_site(s, suffered):
        nonlocal status, fair_evicted
        msite = site_machines[s]
        pend = [k for k in range(n)
                if status[k] == PENDING and task_site[k] == s
                and (net is None or ready[k] <= now)]  # in transit: invisible

        def site_hopeless(k):
            return F(F(now) + eet_min_site[ttype[k], s]) > dl[k]

        # stale purge (all policies: stale tasks are never nominated)
        for k in list(pend):
            if now >= dl[k]:
                status[k] = CANCELLED
                cancelled[ttype[k]] += 1
                _end(k)
                pend.remove(k)

        if desc.fairness:
            # queue eviction for the earliest-deadline rescuable suffered task
            resc = [
                k for k in pend
                if suffered[ttype[k]]
                and not any(
                    F(avail(machines[j]) + eet_c[ttype[k], j]) <= dl[k]
                    for j in msite if alive[j] and len(machines[j].queue) < Q
                )
                and F(F(now) + eet_min_site[ttype[k], s]) <= dl[k]
            ]
            if resc:
                k = min(resc, key=lambda k: dl[k])
                mstar = min(
                    msite,
                    key=lambda j: F(avail(machines[j]) + eet_c[ttype[k], j]),
                )
                m = machines[mstar]
                e_tgt = eet_c[ttype[k], mstar]
                evict = []
                base = avail_base(m)
                rem = qsum(m)
                for qi in range(len(m.queue) - 1, -1, -1):
                    t = m.queue[qi]
                    if F(F(base + rem) + e_tgt) <= dl[k]:
                        break
                    if not suffered[ttype[t]]:
                        evict.append(qi)
                        rem = F(rem - eet_c[ttype[t], mstar])
                if F(F(base + rem) + e_tgt) <= dl[k]:
                    fair_evicted += len(evict)
                    for qi in evict:
                        t = m.queue.pop(qi)
                        status[t] = CANCELLED
                        cancelled[ttype[t]] += 1
                        _end(t)

        free = [j for j in msite if alive[j] and len(machines[j].queue) < Q]

        # Phase-I + Phase-II (fairness: suffered-type pairs claim machines
        # first, remaining machines serve the non-suffered pairs).
        pairs = [(k, j, phase2_key(k, j, val))
                 for (k, j, val) in nominate(pend, free)]
        if desc.fairness:
            hi = [p for p in pairs if suffered[ttype[p[0]]]]
            lo = [p for p in pairs if not suffered[ttype[p[0]]]]
            assign = phase2(hi, free)
            rest = [j for j in free if j not in assign]
            taken = set(assign.values())
            assign.update(
                phase2([p for p in lo if p[0] not in taken], rest)
            )
        else:
            assign = phase2(pairs, free)

        # proactive drops: never drop a task assigned this very event
        if drop_hopeless:
            assigned = set(assign.values())
            for k in list(pend):
                if k not in assigned and site_hopeless(k):
                    status[k] = CANCELLED
                    cancelled[ttype[k]] += 1
                    _end(k)
                    pend.remove(k)

        for j, k in assign.items():
            if status[k] == PENDING and len(machines[j].queue) < Q:
                machines[j].queue.append(k)
                status[k] = QUEUED
                if log_map[k] < 0:
                    log_map[k] = now
                if backup_k:
                    _nominate_backup(k, j)

    def _nominate_backup(k, jprim):
        """k cheapest completion-score backups, primary/dead masked to BIG.

        Mirrors the engine's ``_nominate_backups``: score is the *current*
        base availability (queue backlog ignored, FEST-style) plus the
        health-masked EET, and the iterative argmin naturally yields
        distinct machines in score order (picked slots are re-masked).
        """
        sc = np.empty(M, F)
        for j2 in range(M):
            if j2 == jprim:
                sc[j2] = F(BIG)
                continue
            m2 = machines[j2]
            ab = (F(BIG) if not alive[j2]
                  else F(max(now, m2.run_end_exp if m2.run >= 0 else now)))
            sc[j2] = F(ab + eet_c[ttype[k], j2])
        for slot in range(backup_k):
            b = int(np.argmin(sc))
            backup[k, slot] = b if sc[b] < F(BIG) else -1
            sc[b] = F(BIG)

    def start_tasks():
        # One pop per machine per event; a dead-on-arrival task becomes a
        # zero-duration run (finalized as MISSED with zero energy at the same
        # timestamp) — mirrors the JAX engine's event structure exactly.
        for m in machines:
            if m.run < 0 and m.queue and alive[m.j]:
                k = m.queue.pop(0)
                m.run = k
                m.run_start = now
                status[k] = RUNNING
                if log_start[k] < 0:
                    log_start[k] = now
                log_machine[k] = m.j  # last placement (moves on failover)
                if now >= dl[k]:
                    m.run_success = False
                    m.run_end_act = now
                    m.run_end_exp = F(now)
                else:
                    e_act = exec_act[k, m.j] * float(slowdown[m.j])
                    m.run_success = now + e_act <= dl[k]
                    m.run_end_act = min(now + e_act, dl[k])
                    m.run_end_exp = F(
                        _completion(F(now), eet_c[ttype[k], m.j], F(dl[k]))
                    )

    # --- faults step: evolve health, orphan the dead machines' tasks -------
    def dyn_step(it):
        """Plain-loop mirror of the registered dynamics' ``step``."""
        alive_new = alive.copy()
        slow_new = slowdown.copy()
        if dyn.kind == "bernoulli_updown":
            for j in range(M):
                u = hash_uniform_host(j, it, dyn.seed)
                alive_new[j] = (u >= F(dyn.p_fail)) if alive[j] \
                    else (u < F(dyn.p_recover))
        elif dyn.kind == "site_outage":
            dead = np.zeros(M, bool)
            for (s, a, b) in dyn.outages:
                t0 = F(F(a) * horizon)
                t1 = F(F(b) * horizon)
                dead |= (sites == s) & (now >= t0) & (now < t1)
            alive_new = ~dead
        elif dyn.kind == "degrade":
            if dyn.machines is not None:
                mask = np.asarray(
                    [j in dyn.machines for j in range(M)])
            else:
                mask = np.asarray(
                    [hash_uniform_host(j, 0, dyn.seed) < F(dyn.p)
                     for j in range(M)])
            slow_new = np.where(mask, F(dyn.factor), F(1.0)).astype(F)
        else:
            raise NotImplementedError(
                f"oracle has no interpretation for dynamics {dyn.kind!r}"
            )
        return alive_new, slow_new

    def faults_event(it):
        nonlocal e_dyn, e_wasted
        alive_new, slow_new = dyn_step(it)
        died = alive & ~alive_new
        # flush dead machines' queues (machine index order, like the scan)
        for m in machines:
            if not died[m.j]:
                continue
            for k in m.queue:
                retries[k] += 1
                if retries[k] > max_retries:
                    status[k] = CANCELLED
                    cancelled[ttype[k]] += 1
                    _end(k)  # site kept: records where it gave up
                else:
                    status[k] = PENDING
                    task_site[k] = -1  # re-enters dispatch this event
            m.queue.clear()
        # kill running tasks: partial energy is spent AND wasted
        for m in machines:
            if not (died[m.j] and m.run >= 0):
                continue
            k = m.run
            dur = now - m.run_start
            en = float(p_dyn[m.j]) * dur
            e_dyn += en
            e_wasted += en
            m.busy += dur
            retries[k] += 1
            if retries[k] > max_retries:
                status[k] = CANCELLED
                cancelled[ttype[k]] += 1
                _end(k)
            else:
                fb = -1  # first live backup with queue room
                for b in backup[k] if backup_k else ():
                    if b >= 0 and alive_new[b] and \
                            len(machines[b].queue) < Q:
                        fb = int(b)
                        break
                if fb >= 0:
                    machines[fb].queue.append(k)
                    status[k] = QUEUED
                    task_site[k] = int(sites[fb])
                else:
                    status[k] = PENDING
                    task_site[k] = -1
            m.run = -1
            m.run_end_act = np.inf
            m.run_end_exp = F(now)
            m.run_success = False
        alive[:] = alive_new
        slowdown[:] = slow_new
        _refresh_tables()

    max_steps = 16 * n + 64
    for it in range(max_steps):
        t = next_event()
        if not np.isfinite(t):
            break
        now = max(now, t)
        # finalize completions
        for m in machines:
            if m.run >= 0 and m.run_end_act <= now:
                k = m.run
                dur = m.run_end_act - m.run_start
                en = float(p_dyn[m.j]) * dur
                e_dyn += en
                m.busy += dur
                if m.run_success:
                    status[k] = COMPLETED
                    completed[ttype[k]] += 1
                else:
                    status[k] = MISSED
                    missed[ttype[k]] += 1
                    e_wasted += en
                _end(k)
                m.run = -1
                m.run_end_act = np.inf
                m.run_end_exp = F(now)
        # arrivals
        for k in range(n):
            if status[k] == UNARRIVED and arr[k] <= now:
                status[k] = PENDING
                arrived[ttype[k]] += 1
        if dyn is not None:
            faults_event(it)
        dispatch_event()
        mapping_event()
        start_tasks()
    makespan = now
    e_idle = float(sum(p_idle[m.j] * (makespan - m.busy) for m in machines))
    return dict(
        completed_by_type=completed,
        missed_by_type=missed,
        cancelled_by_type=cancelled,
        arrived_by_type=arrived,
        energy_dynamic=e_dyn,
        energy_wasted=e_wasted,
        energy_idle=e_idle,
        makespan=makespan,
        fair_evicted=fair_evicted,
        suffered_maps=suffered_maps,
        backup=backup.copy(),
        task_log=dict(
            map_time=log_map,
            start_time=log_start,
            end_time=log_end,
            machine=log_machine,
            site=task_site.copy(),
            status=status.copy(),
            retries=retries.copy(),
            ready_time=(ready.copy() if net is not None
                        else np.full(n, -1.0)),
        ),
    )
