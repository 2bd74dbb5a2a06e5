"""Bit-exactness battery: masked-vmap site loop vs the PR 5 static unroll.

The flat-compile refactor replaced the engine's statically unrolled
per-site map stage (one ``select_fn`` copy per site in the traced program)
with a single ``jax.vmap`` over site-masked machine views. These tests pin
the refactor to the frozen snapshot in ``tests/_legacy_siteloop.py``:

  * event-level — for every event of a driven simulation, the combined
    :class:`MapAction` and the full post-map :class:`SimState` agree leaf
    for leaf, bit for bit (``jnp.array_equal`` inside one jitted
    comparator per combo);
  * trace-level — full simulations (``make_simulator`` + the task_log
    observer) agree on every metrics leaf and every task_log event field,
    byte for byte, with the legacy formulation monkeypatched in;

for F in {1, 2, 4} under every built-in dispatcher x ELARE/FELARE, on
exhaustive grids plus hypothesis-drawn Poisson traces. Comparators and
simulator pairs are cached per combo so hypothesis examples re-run the
compiled programs instead of re-tracing.
"""
import functools

import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings

import _legacy_siteloop as legacy
from repro import scenarios
from repro.core import engine, observe, policy, workload
from repro.core.types import MapAction, SimState, site_membership

FLEETS = {1: "paper", 2: "paper_x2", 4: "paper_x4"}
DISPATCHERS = ("sticky", "round_robin", "least_queued", "min_eet",
               "fair_spill")
POLICIES = ("ELARE", "FELARE")
# With one site the dispatch stage is bypassed (every task -> site 0), so
# the dispatcher axis collapses; F>1 runs the full grid.
GRID = tuple((1, "sticky", h) for h in POLICIES) + tuple(
    (F, d, h) for F in (2, 4) for d in DISPATCHERS for h in POLICIES
)
LEAF_NAMES = tuple(f"action.{f}" for f in MapAction._fields) + tuple(
    f"state.{f}" for f in SimState._fields
)


def _dyadic(x):
    return (np.round(np.asarray(x) * 64) / 64).astype(np.float32)


def _trace(seed, n, rate, eet):
    tr = workload.poisson_trace(jax.random.PRNGKey(seed), n, rate, eet)
    return tr._replace(
        arrival=jnp.asarray(_dyadic(tr.arrival)),
        deadline=jnp.asarray(_dyadic(tr.deadline)),
        exec_actual=jnp.asarray(_dyadic(tr.exec_actual)),
    )


# ------------------------------------------------------------ event level
N_EVENT_TASKS = 16


@functools.lru_cache(maxsize=None)
def _comparator(n_sites: int, heuristic: str, k: int = 10):
    """Jitted k-event driver comparing both map formulations per event.

    Each event runs the real pre-map stages once, evaluates the masked-vmap
    ``engine._map_action`` AND the frozen ``legacy.map_action_unrolled`` on
    the identical pre-map state, applies both, and records per-leaf
    equality; the simulation continues from the new-formulation state.
    Returns a (k, n_leaves) bool array.

    The dispatch stage is replaced by its dispatch-once contract with an
    *arbitrary* per-task site array (``assigned``) — data, not a new trace
    — so one compiled comparator per (F, policy) covers every site pattern
    any dispatcher could produce (and adversarial ones none would). The
    real dispatchers run in the full-trace parity grid below.
    """
    system = scenarios.get_fleet(FLEETS[n_sites]).build()
    sysarr = system.as_jax()
    pol = policy.get(heuristic)
    sites_np = np.asarray(system.sites, np.int32)
    members = (site_membership(sites_np, system.n_sites)
               if system.n_sites > 1 else None)
    S, M = system.eet.shape
    Q, ff = system.queue_size, float(system.fairness_factor)

    def compare(trace, assigned):
        stt = engine._init_state(trace, M, Q, S)
        onehot = engine._type_onehot(trace.task_type, S)
        oks = []
        for _ in range(k):
            t = engine._next_event_time(stt, trace)
            # freeze time once the event queue drains (the while_loop's
            # cond would have exited) so trailing events are no-ops for
            # both formulations instead of poisoning the state with inf.
            t = jnp.where(jnp.isfinite(t), t, stt.now)
            stt = stt._replace(now=jnp.maximum(t, stt.now))
            stt = engine._stage_finalize(stt, trace, sysarr)
            stt = engine._stage_admit(stt, trace, onehot)
            new = (stt.status == engine.PENDING) & (stt.site < 0)
            stt = stt._replace(site=jnp.where(new, assigned, stt.site))
            a_new = engine._map_action(stt, trace, sysarr, pol, ff,
                                       members, sites_np)
            a_old = legacy.map_action_unrolled(stt, trace, sysarr, pol, ff,
                                               members)
            st_new = engine._apply_action(stt, trace, a_new, onehot)
            st_old = engine._apply_action(stt, trace, a_old, onehot)
            oks.append(jnp.stack(
                [jnp.array_equal(x, y) for x, y in
                 zip(jax.tree.leaves(a_new), jax.tree.leaves(a_old))]
                + [jnp.array_equal(x, y) for x, y in
                   zip(jax.tree.leaves(st_new), jax.tree.leaves(st_old))]
            ))
            stt = engine._stage_start(st_new, trace, sysarr)
            stt = stt._replace(steps=stt.steps + 1)
        return jnp.stack(oks)

    return jax.jit(compare)


def _assert_events_equal(ok, label):
    ok = np.asarray(ok)
    if not ok.all():
        ev, leaf = np.argwhere(~ok)[0]
        pytest.fail(f"{label}: event {ev} diverges at {LEAF_NAMES[leaf]}")


def _site_patterns(n_sites, n, seed=None):
    """Representative site assignments: round-robin, blocky, random."""
    if n_sites == 1:
        return [np.zeros((n,), np.int32)]  # the engine's F=1 bypass
    rng = np.random.default_rng(0xFE1A if seed is None else seed)
    return [np.arange(n, dtype=np.int32) % n_sites,
            np.minimum(np.arange(n) // (n // n_sites), n_sites - 1)
            .astype(np.int32),
            rng.integers(0, n_sites, n).astype(np.int32)]


@pytest.mark.parametrize("heuristic", POLICIES)
@pytest.mark.parametrize("n_sites", [1, 2, 4])
def test_event_level_map_parity(n_sites, heuristic):
    """MapAction + post-map SimState bit-equal between formulations at
    every event, across round-robin / blocky / random site partitions."""
    cmp_fn = _comparator(n_sites, heuristic)
    eet = scenarios.get_fleet(FLEETS[n_sites]).build().eet
    for seed in (0, 3):
        tr = _trace(seed, N_EVENT_TASKS, 4.0, eet)
        for i, assigned in enumerate(_site_patterns(n_sites, N_EVENT_TASKS)):
            ok = cmp_fn(tr, jnp.asarray(assigned))
            _assert_events_equal(
                ok, f"F={n_sites}/{heuristic}/seed{seed}/pattern{i}")


@given(combo=st.sampled_from(tuple((F, h) for F in (1, 2, 4)
                                   for h in POLICIES)),
       seed=st.integers(0, 10_000), rate=st.floats(0.5, 10.0))
@settings(max_examples=25, deadline=None)
def test_event_level_map_parity_property(combo, seed, rate):
    """Hypothesis sweep: drawn Poisson traces x drawn site assignments
    through the cached compiled comparators."""
    n_sites, heuristic = combo
    cmp_fn = _comparator(n_sites, heuristic)
    eet = scenarios.get_fleet(FLEETS[n_sites]).build().eet
    tr = _trace(seed, N_EVENT_TASKS, rate, eet)
    assigned = _site_patterns(n_sites, N_EVENT_TASKS, seed=seed)[-1]
    ok = cmp_fn(tr, jnp.asarray(assigned))
    _assert_events_equal(
        ok, f"F={n_sites}/{heuristic}/seed{seed}/rate{rate}")


# ------------------------------------------------------------ trace level
def _legacy_stage_map(st_, trace, sysarr, select_fn, fairness_factor,
                      type_onehot, site_members=None, site_of_machine=None,
                      health=False, backup_k=0, suffered=None):
    """Signature shim: the live engine body -> the frozen PR 5 unroll.

    ``health``/``backup_k`` are the PR 7 faults-subsystem knobs; this
    battery runs without a dynamics attached, where both are inert
    (False/0), so the frozen unroll simply ignores them. ``suffered`` is
    the event's Eq. 3 mask, which the unroll computes again itself.
    """
    assert not health and backup_k == 0
    return legacy.stage_map_unrolled(st_, trace, sysarr, select_fn,
                                     fairness_factor, type_onehot,
                                     site_members)


@functools.lru_cache(maxsize=None)
def _sim_pair(n_sites: int, dispatcher: str, heuristic: str):
    """(new, legacy) jitted full simulators with the task_log observer.

    Built via ``engine.make_simulator`` + a fresh ``jax.jit`` — NOT
    ``engine.simulate`` — because ``_simulate_jit``'s cache key doesn't
    include the (monkeypatched) ``_stage_map``. The legacy simulator runs
    with ``engine._stage_map`` swapped for the frozen unroll on every
    call, so its (lazy, first-call) trace picks up the old formulation.
    """
    system = scenarios.get_fleet(FLEETS[n_sites]).build()
    kw = dict(queue_size=system.queue_size,
              fairness_factor=float(system.fairness_factor),
              observers=observe.resolve(("task_log",)),
              dispatcher=dispatcher, site_of_machine=system.sites)
    pol = policy.get(heuristic)
    sysarr = system.as_jax()
    new_sim = jax.jit(engine.make_simulator(pol, sysarr, **kw))
    legacy_jit = jax.jit(engine.make_simulator(pol, sysarr, **kw))

    def legacy_sim(trace):
        orig = engine._stage_map
        engine._stage_map = _legacy_stage_map
        try:
            return legacy_jit(trace)
        finally:
            engine._stage_map = orig

    return new_sim, legacy_sim


@pytest.mark.parametrize("n_sites,dispatcher,heuristic", GRID)
def test_full_trace_task_log_parity(n_sites, dispatcher, heuristic):
    """Whole simulations agree byte for byte: every metrics leaf and every
    task_log field (map/start/end times, machine, site, status)."""
    new_sim, legacy_sim = _sim_pair(n_sites, dispatcher, heuristic)
    eet = scenarios.get_fleet(FLEETS[n_sites]).build().eet
    tr = _trace(1, 40, 4.0, eet)
    (m_new, aux_new), (m_old, aux_old) = new_sim(tr), legacy_sim(tr)
    for f in m_new._fields:
        a, b = np.asarray(getattr(m_new, f)), np.asarray(getattr(m_old, f))
        assert a.tobytes() == b.tobytes(), \
            f"F={n_sites}/{dispatcher}/{heuristic}: metrics.{f}"
    for f, a in aux_new["task_log"].items():
        a, b = np.asarray(a), np.asarray(aux_old["task_log"][f])
        assert a.tobytes() == b.tobytes(), \
            f"F={n_sites}/{dispatcher}/{heuristic}: task_log.{f}"


@given(seed=st.integers(0, 10_000), rate=st.floats(0.5, 10.0))
@settings(max_examples=15, deadline=None)
def test_full_trace_task_log_parity_property(seed, rate):
    """Hypothesis workloads through one cached simulator pair per F."""
    for n_sites, dispatcher, heuristic in (
            (1, "sticky", "FELARE"), (2, "fair_spill", "ELARE"),
            (4, "round_robin", "FELARE")):
        new_sim, legacy_sim = _sim_pair(n_sites, dispatcher, heuristic)
        eet = scenarios.get_fleet(FLEETS[n_sites]).build().eet
        tr = _trace(seed, 40, rate, eet)
        (m_new, aux_new), (m_old, aux_old) = new_sim(tr), legacy_sim(tr)
        for f in m_new._fields:
            assert (np.asarray(getattr(m_new, f)).tobytes()
                    == np.asarray(getattr(m_old, f)).tobytes()), \
                f"F={n_sites} seed{seed}: metrics.{f}"
        for f, a in aux_new["task_log"].items():
            assert (np.asarray(a).tobytes()
                    == np.asarray(aux_old["task_log"][f]).tobytes()), \
                f"F={n_sites} seed{seed}: task_log.{f}"
