"""The plain reference: one trace through the scheduling rules, in loops.

It shares no code with the program. Only a fleet's numbers (a file in
``bench/configs/``) and one trace (as the harness generated it) go in;
the result is the program's per-trace ``Metrics`` as a dict.

The rules are the paper's (arXiv:2206.00065, Secs. IV-VI) as the program
defines them:

  event     the earliest of: next arrival, a running task's end, a
            pending task's deadline. Per event, in this order:
  finalize  a run ends on time (completed) or at its deadline (missed);
            dynamic energy p_dyn * run time, wasted on a miss;
  admit     arrivals join the pending set;
  map       with pending tasks: drop stale tasks; with fairness,
            evict unsuffered queued tasks (tail first) so the earliest-
            deadline rescuable suffered task fits; Phase I nominates one
            machine per task among machines with queue room; Phase II
            gives each machine its lowest-keyed nominee (suffered nominees
            first under fairness); ELARE/FELARE then drop hopeless tasks;
            winners join their machine's queue tail;
  start     each idle machine pops its queue head; a head already past its
            deadline runs for zero time and misses.

Precision: trace times are multiples of ``QUANTUM`` below ``TIME_LIMIT``
(``bench/generators``), so event times are exact in float32 and float64
alike, and are kept as Python floats. Everything
derived from the EET table (availability, feasibility, energy keys,
Phase-II keys, the fairness limit) is rounded to the configuration's
precision after every operation, in the program's operation order:
``float32`` for the reference, ``bfloat16`` for the control. Reported
energies accumulate in float64.
"""
from __future__ import annotations

import numpy as np

from bench.generators import QUANTUM, TIME_LIMIT

BIG = 1e30

# name -> (Phase-I nominator, Phase-II key, drop hopeless, fairness)
HEURISTICS = {
    "MM": ("min_completion", "value", False, False),
    "MSD": ("min_completion", "deadline", False, False),
    "MMU": ("min_completion", "urgency", False, False),
    "ELARE": ("min_energy_feasible", "value", True, False),
    "FELARE": ("min_energy_feasible", "value", True, True),
}


def rounding(precision: str):
    """x -> x rounded to ``precision``, returned as a numpy float32."""
    if precision == "float32":
        return np.float32
    if precision == "bfloat16":
        import ml_dtypes

        bf16 = ml_dtypes.bfloat16
        return lambda x: np.float32(bf16(np.float32(x)))
    raise ValueError(f"no rounding for precision {precision!r}")


def simulate(trace: dict, fleet: dict, heuristic: str,
             precision: str = "float32") -> dict:
    """One trace under one heuristic; returns the program's Metrics fields.

    ``trace``: ``arrival`` (n,), ``task_type`` (n,), ``deadline`` (n,),
    ``exec_actual`` (n, M) numpy arrays. ``fleet``: a configuration dict
    (``eet``, ``p_dyn``, ``p_idle``, ``queue_size``, ``fairness_factor``)
    of one site.
    """
    R = rounding(precision)
    nominator, key_kind, drop_hopeless, fairness = HEURISTICS[heuristic]
    eet = [[R(x) for x in row] for row in fleet["eet"]]
    p_dyn = [R(x) for x in fleet["p_dyn"]]
    p_idle = [float(np.float32(x)) for x in fleet["p_idle"]]
    S, M = len(eet), len(eet[0])
    Q = int(fleet["queue_size"])
    fair_f = R(fleet["fairness_factor"])
    if fleet.get("site_of_machine") or fleet.get("dispatcher"):
        raise NotImplementedError("the reference simulates one site")
    n_sites = 1
    site_machines = [list(range(M))]
    # fastest machine of each type at each site
    eet_min_site = [[min(eet[i][j] for j in ms) for ms in site_machines]
                    for i in range(S)]

    arr = [float(x) for x in np.asarray(trace["arrival"])]
    ttype = [int(x) for x in np.asarray(trace["task_type"])]
    dl = [float(x) for x in np.asarray(trace["deadline"])]
    exec_act = np.asarray(trace["exec_actual"], np.float64)
    n = len(arr)
    for t in (arr, dl, exec_act):  # the exactness premise above
        t = np.asarray(t, np.float64)
        if np.any(t / QUANTUM % 1) or np.any(t >= TIME_LIMIT):
            raise ValueError("trace times are not multiples of QUANTUM "
                             "below TIME_LIMIT")
    order = sorted(range(n), key=lambda k: (arr[k], k))

    completed = [0] * S
    missed = [0] * S
    cancelled = [0] * S
    arrived = [0] * S
    e_dyn = e_wasted = 0.0
    busy = [0.0] * M
    # machine state
    run = [-1] * M
    run_start = [0.0] * M
    run_end_act = [np.inf] * M
    run_end_exp = [R(0.0)] * M
    run_success = [False] * M
    queue = [[] for _ in range(M)]
    pending = [set() for _ in range(n_sites)]  # by site
    now = 0.0
    next_arr = 0

    def suffered_mask():
        cr = [R(R(completed[i]) / R(max(arrived[i], 1))) if arrived[i] > 0
              else R(1.0) for i in range(S)]
        mu = R(np.mean(np.asarray(cr, np.float32), dtype=np.float32))
        sigma = R(np.std(np.asarray(cr, np.float32), dtype=np.float32))
        eps = max(R(mu - R(fair_f * sigma)), R(0.0))
        return [cr[i] <= eps and arrived[i] >= 1 for i in range(S)]

    def avail_base(j):
        return R(max(now, run_end_exp[j] if run[j] >= 0 else now))

    def qsum(j):
        s = R(0.0)
        for k in queue[j]:
            s = R(s + eet[ttype[k]][j])
        return s

    def cancel(k):
        cancelled[ttype[k]] += 1

    def map_site(s, suffered):
        msite = site_machines[s]
        pend = sorted(pending[s])
        for k in [k for k in pend if now >= dl[k]]:  # stale purge
            pending[s].discard(k)
            cancel(k)
        pend = [k for k in pend if now < dl[k]]
        if not pend:
            return
        if fairness:
            avail = {j: R(avail_base(j) + qsum(j)) for j in msite}
            room = [j for j in msite if len(queue[j]) < Q]
            resc = [k for k in pend if suffered[ttype[k]]
                    and not any(R(avail[j] + eet[ttype[k]][j]) <= dl[k]
                                for j in room)
                    and R(R(now) + eet_min_site[ttype[k]][s]) <= dl[k]]
            if resc:
                k = min(resc, key=lambda k: dl[k])
                mstar = min(msite,
                            key=lambda j: R(avail[j] + eet[ttype[k]][j]))
                e_tgt = eet[ttype[k]][mstar]
                base, rem = avail_base(mstar), qsum(mstar)
                evict = []
                q = queue[mstar]
                for qi in range(len(q) - 1, -1, -1):
                    if R(R(base + rem) + e_tgt) <= dl[k]:
                        break
                    t = q[qi]
                    if not suffered[ttype[t]]:
                        evict.append(qi)
                        rem = R(rem - eet[ttype[t]][mstar])
                if R(R(base + rem) + e_tgt) <= dl[k]:
                    for qi in evict:  # descending, so indices stay valid
                        cancel(q.pop(qi))
        free = [j for j in msite if len(queue[j]) < Q]
        avail = {j: R(avail_base(j) + qsum(j)) for j in free}
        # Phase I: one (task, machine, value) nomination per task
        pairs = []
        for k in pend:
            i, best = ttype[k], None
            for j in free:
                e = eet[i][j]
                if nominator == "min_energy_feasible":
                    if R(avail[j] + e) > dl[k]:
                        continue
                    v = R(p_dyn[j] * e)
                else:  # min_completion, Eq. 1
                    st = avail[j]
                    c = R(st + e)
                    v = c if c <= dl[k] else (R(dl[k]) if st < dl[k] else st)
                if best is None or v < best[1]:
                    best = (j, v)
            if best is not None:
                pairs.append((k, best[0], phase2_key(k, best[0], best[1])))
        # Phase II: each machine takes its lowest (key, task) nominee
        if fairness:
            hi = [p for p in pairs if suffered[ttype[p[0]]]]
            lo = [p for p in pairs if not suffered[ttype[p[0]]]]
            assign = phase2(hi, free)
            taken = set(assign.values())
            assign.update(phase2([p for p in lo if p[0] not in taken],
                                 [j for j in free if j not in assign]))
        else:
            assign = phase2(pairs, free)
        if drop_hopeless:  # never a task assigned this very event
            won = set(assign.values())
            for k in pend:
                if k not in won and R(R(now) + eet_min_site[ttype[k]][s]) \
                        > dl[k]:
                    pending[s].discard(k)
                    cancel(k)
        for j, k in assign.items():
            if k in pending[s] and len(queue[j]) < Q:
                queue[j].append(k)
                pending[s].discard(k)

    def phase2_key(k, j, val):
        if key_kind == "value":
            return R(val)
        if key_kind == "deadline":
            return R(R(dl[k]) + R(R(1e-6) * R(val)))
        # urgency: -1 / slack, slack clamped away from 0
        slack = R(R(R(dl[k]) - R(now)) - eet[ttype[k]][j])
        if abs(slack) < 1e-9:
            slack = R(1e-9)
        return R(-(R(1.0) / slack))

    def phase2(pairs, machines):
        out = {}
        for j in machines:
            cand = [(key, k) for (k, jj, key) in pairs if jj == j]
            if cand:
                out[j] = min(cand)[1]
        return out

    for _ in range(16 * n + 64):
        ts = [arr[order[next_arr]]] if next_arr < n else []
        ts += [run_end_act[j] for j in range(M) if run[j] >= 0]
        ts += [dl[k] for p in pending for k in p]
        if not ts:
            break
        now = max(now, min(ts))
        for j in range(M):  # finalize
            if run[j] >= 0 and run_end_act[j] <= now:
                k = run[j]
                dur = run_end_act[j] - run_start[j]
                en = float(p_dyn[j]) * dur
                e_dyn += en
                busy[j] += dur
                if run_success[j]:
                    completed[ttype[k]] += 1
                else:
                    missed[ttype[k]] += 1
                    e_wasted += en
                run[j] = -1
                run_end_act[j] = np.inf
                run_end_exp[j] = R(now)
        while next_arr < n and arr[order[next_arr]] <= now:  # admit
            k = order[next_arr]
            next_arr += 1
            arrived[ttype[k]] += 1
            pending[0].add(k)
        if any(pending):
            suffered = suffered_mask() if fairness else None
            for s in range(n_sites):
                if pending[s]:
                    map_site(s, suffered)
        for j in range(M):  # start
            if run[j] < 0 and queue[j]:
                k = queue[j].pop(0)
                run[j] = k
                run_start[j] = now
                if now >= dl[k]:
                    run_success[j] = False
                    run_end_act[j] = now
                    run_end_exp[j] = R(now)
                else:
                    e_act = float(exec_act[k, j])
                    run_success[j] = now + e_act <= dl[k]
                    run_end_act[j] = min(now + e_act, dl[k])
                    e = eet[ttype[k]][j]
                    run_end_exp[j] = R(min(R(R(now) + e), R(dl[k])))
    makespan = now
    e_idle = sum(p_idle[j] * (makespan - busy[j]) for j in range(M))
    return dict(completed_by_type=np.asarray(completed),
                missed_by_type=np.asarray(missed),
                cancelled_by_type=np.asarray(cancelled),
                arrived_by_type=np.asarray(arrived),
                energy_dynamic=e_dyn, energy_wasted=e_wasted,
                energy_idle=e_idle, makespan=makespan)
