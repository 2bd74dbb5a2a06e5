"""The event loop's N-wide per-type counters.

The loop counts arrivals and cancellations per task type over all N tasks
on every event. Under ``vmap`` a ``segment_sum`` there lowers to one
scatter-add of B x N updates, which the TPU applies one after another, so
the engine counts with a compare-and-sum over a loop-invariant one-hot
(``engine._count_by_type``). Pinned three ways:

  * the helper equals ``segment_sum`` in dtype and value;
  * no scatter-add of N or more updates is left in the traced loop;
  * every ``Metrics`` field of a vmapped batch equals the values frozen
    from the scatter-based engine
    (``tests/data/counter_scatter_metrics.json``; the fairness counters
    ``fair_evicted`` and ``suffered_maps``, added later, were checked
    against the oracle before they were frozen).

The fairness counters are also compared with the oracle directly.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import scenarios
from repro.core import engine, observe, policy, pyengine
from repro.core.types import Trace

SNAPSHOT = os.path.join(os.path.dirname(__file__), "data",
                        "counter_scatter_metrics.json")
HEURISTICS = ("MM", "MSD", "MMU", "ELARE", "FELARE")
RATES = (2.0, 4.0, 6.0, 8.0)


# ------------------------------------------------------------ the helper
@pytest.mark.parametrize("batched", [False, True])
@pytest.mark.parametrize("mask_kind", ["random", "none", "all"])
@pytest.mark.parametrize("n_types", [1, 4, 7])
def test_count_by_type_equals_segment_sum(n_types, mask_kind, batched):
    rng = np.random.default_rng(n_types)
    shape = (3, 257) if batched else (257,)
    task_type = jnp.asarray(rng.integers(0, n_types, shape), jnp.int32)
    mask = {"random": jnp.asarray(rng.random(shape) < 0.4),
            "none": jnp.zeros(shape, bool),
            "all": jnp.ones(shape, bool)}[mask_kind]

    def mine(m, tt):
        return engine._count_by_type(m, engine._type_onehot(tt, n_types))

    def ref(m, tt):
        return jax.ops.segment_sum(m.astype(jnp.int32), tt, n_types)

    if batched:
        mine, ref = jax.vmap(mine), jax.vmap(ref)
    got, want = mine(mask, task_type), ref(mask, task_type)
    assert got.dtype == want.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if mask_kind == "all":
        assert int(got.sum()) == task_type.size


# -------------------------------------------------------- structural guard
def _trace(eet, n, rate, seed):
    """An arrival-sorted trace from numpy's generator: the same bits on
    every JAX version. Times are multiples of 1/64 s."""
    rng = np.random.default_rng(seed)
    S, M = eet.shape
    q = lambda x: (np.round(np.asarray(x) * 64) / 64).astype(np.float32)
    arrival = q(np.cumsum(rng.exponential(1.0 / rate, n)))
    task_type = rng.integers(0, S, n).astype(np.int32)
    mean_eet = np.asarray(eet, np.float64).mean(axis=1)[task_type]
    deadline = q(arrival + mean_eet * rng.uniform(1.0, 3.0, n) + 1 / 64)
    exec_actual = q(np.asarray(eet)[task_type]
                    * rng.gamma(100.0, 0.01, (n, M)) + 1 / 64)
    return Trace(arrival=jnp.asarray(arrival),
                 task_type=jnp.asarray(task_type),
                 deadline=jnp.asarray(deadline),
                 exec_actual=jnp.asarray(exec_actual))


def _batch(eet, n, seed):
    """One trace per rate in ``RATES``, scaled by the fleet's machines
    over the paper's 4, so every fleet sees the same load per machine."""
    scale = eet.shape[1] / 4
    traces = [_trace(eet, n, r * scale, seed + i)
              for i, r in enumerate(RATES)]
    return jax.tree.map(lambda *x: jnp.stack(x), *traces)


def _int_scatter_add_sizes(jaxpr, out):
    """Update counts of every integer scatter-add, nested jaxprs too."""
    for eqn in jaxpr.eqns:
        upd = eqn.invars[2].aval if eqn.primitive.name == "scatter-add" \
            else None
        if upd is not None and jnp.issubdtype(upd.dtype, jnp.integer):
            out.append(int(np.prod(upd.shape)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _int_scatter_add_sizes(sub, out)
    return out


@pytest.mark.parametrize("fleet,heuristic,kw", [
    ("paper", "MM", {}),
    ("paper", "ELARE", {}),
    ("paper", "ELARE", {"observers": ("energy_budget",)}),
    ("tiered_x16", "ELARE", {"network": "tiered",
                             "dispatcher": "tier_aware"}),
], ids=["paper-MM", "paper-ELARE", "halt-path", "network-path"])
def test_no_n_wide_scatter_add_in_the_loop(fleet, heuristic, kw):
    """Counts over M machines or M x Q slots may scatter; none over the
    N tasks may. The ``status`` writes are plain scatters, not adds; the
    network's per-tier transfer energy is a float sum and keeps its
    scatter, since a reduce would add in another order."""
    system = scenarios.get_fleet(fleet).build()
    n = 1024  # above the B x M x Q updates of the queue-victim counts
    assert n > len(RATES) * system.eet.shape[1] * system.queue_size
    sim = engine.make_simulator(
        policy.get(heuristic), system.as_jax(),
        queue_size=system.queue_size,
        fairness_factor=float(system.fairness_factor),
        site_of_machine=system.sites, tier_of_site=system.tiers,
        observers=observe.resolve(kw.get("observers", ())),
        dispatcher=kw.get("dispatcher"), network=kw.get("network"))
    tr = _batch(system.eet, n, seed=0)
    jaxpr = jax.make_jaxpr(jax.vmap(sim))(tr).jaxpr
    sizes = _int_scatter_add_sizes(jaxpr, [])
    assert sizes, "the loop's M-sized counters should still scatter-add"
    assert max(sizes) < n, sizes


# ------------------------------------------------------------ bit-exactness
def _metrics(fleet, heuristic):
    system = scenarios.get_fleet(fleet).build()
    m = engine.simulate_batch(_batch(system.eet, 120, seed=11), system,
                              heuristic)
    return {f: np.asarray(v) for f, v in m._asdict().items()}


@pytest.mark.parametrize("fleet", ["paper", "paper_x8"])
def test_metrics_match_the_scatter_engine(fleet):
    """Every Metrics field of a vmapped batch (rates 2-8 tasks/s) equals
    the scatter-based engine's, bit for bit, under every heuristic."""
    with open(SNAPSHOT) as f:
        snap = json.load(f)
    for h in HEURISTICS:
        got = _metrics(fleet, h)
        want = snap[f"{fleet}/{h}"]
        assert sorted(want) == sorted(got), h
        for f, v in want.items():
            ref = np.asarray(v, got[f].dtype)
            assert got[f].tobytes() == ref.tobytes(), f"{fleet}/{h}/{f}"
        if h in ("ELARE", "FELARE"):  # the proactive drops were counted
            assert int(got["cancelled_by_type"].sum()) > 0, h


@pytest.mark.parametrize("fleet", ["paper", "paper_x8"])
def test_fairness_counters_match_the_oracle(fleet):
    """FELARE's evictions and suffered mapping events equal the oracle's,
    trace for trace, at rates 2-8 tasks/s; every other heuristic reads 0."""
    system = scenarios.get_fleet(fleet).build()
    tr = _batch(system.eet, 120, seed=11)
    for h in HEURISTICS:
        m = engine.simulate_batch(tr, system, h)
        for f in ("fair_evicted", "suffered_maps"):
            got = np.asarray(getattr(m, f))
            assert got.dtype == np.int32 and got.shape == (len(RATES),), f
            if h != "FELARE":
                assert not got.any(), (h, f)
                continue
            want = [pyengine.simulate(jax.tree.map(lambda x: x[b], tr),
                                      system, h)[f]
                    for b in range(len(RATES))]
            np.testing.assert_array_equal(got, want, f"{fleet}/{h}/{f}")
            assert got.sum() > 0, f
