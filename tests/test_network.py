"""Tests for the network subsystem (tiers, link costs, tier-aware dispatch).

Contracts under test:

  * degeneracy — ``network="none"`` (and the default) is bit-identical
    to the pre-network engine: every metric leaf and the full task log
    match the frozen PR 8 snapshot
    (``tests/data/pr8_engine_snapshot.json``) for all dispatchers x
    ELARE/FELARE, and a *zero-cost* tiered network is bit-identical to
    the flat federation for every dispatcher (hypothesis battery);
  * oracle — the pure-Python interpreter replays ``uniform_latency``
    and ``tiered`` event-for-event on the tiered fleet (metrics,
    energies and full task logs including site ready times);
  * dispatch — ``tier_aware`` == ``min_eet`` bit-for-bit when no
    network is attached, and routes around expensive links when one is;
  * safety — no task ever starts before its ready time (hypothesis);
  * plumbing — the ``network`` observer, registries, tiered fleets,
    ``--network`` / ``--list-networks`` / ``--list-fleets``, SweepSpec
    JSON round-trips (old payloads default to ``"none"``), and the
    scale smoke (full size under ``REPRO_SCALE_FULL=1``).
"""
import json
import os

import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings

from repro import experiments, scenarios
from repro.core import dispatch, engine, network, pyengine, workload
from repro.experiments import runner, sweep

SPEC2 = scenarios.get_fleet("paper_x2").build()
TIERED = scenarios.get_fleet("tiered_x4").build()

ZERO3 = ((0.0, 0.0, 0.0),) * 3
FREE_TIERED = network.Tiered(latency=ZERO3, energy=ZERO3)


def _dyadic(x):
    return (np.round(np.asarray(x) * 64) / 64).astype(np.float32)


def _trace(seed, n, rate, eet):
    tr = workload.poisson_trace(jax.random.PRNGKey(seed), n, rate, eet)
    return tr._replace(
        arrival=jnp.asarray(_dyadic(tr.arrival)),
        deadline=jnp.asarray(_dyadic(tr.deadline)),
        exec_actual=jnp.asarray(_dyadic(tr.exec_actual)),
    )


# -------------------------------------------------------------- registries
def test_builtin_networks_registered():
    names = network.list_networks()
    for name in ("none", "uniform_latency", "tiered"):
        assert name in names
        assert network.is_registered(name)
        assert network.describe(name)  # non-empty one-liner
    assert isinstance(network.get("NONE"), network.NoNetwork)  # case-insens
    with pytest.raises(KeyError, match="choose from"):
        network.get("nope")
    with pytest.raises(TypeError, match="NetworkModel protocol"):
        network.register("bad", object())


def test_network_json_round_trip():
    for m in (network.NoNetwork(),
              network.UniformLatency(latency=0.5, energy=0.25, salt=3),
              network.Tiered(),
              network.Tiered(input_size=(0.5, 1.0, 2.0, 4.0), salt=1),
              FREE_TIERED):
        back = network.from_json_dict(
            json.loads(json.dumps(network.to_json_dict(m))))
        assert back == m
    with pytest.raises(ValueError, match="unknown network kind"):
        network.from_json_dict({"kind": "nope"})


def test_network_validation():
    with pytest.raises(ValueError, match=">= 0"):
        network.UniformLatency(latency=-0.1)
    with pytest.raises(ValueError, match="square"):
        network.Tiered(latency=((0.0, 1.0),))
    with pytest.raises(ValueError):
        # matrix covers 3 tiers; a fleet using tier 3 must be rejected
        network.Tiered().cost_tables((0, 1, 3), 4)
    with pytest.raises(ValueError, match="input_size"):
        network.Tiered(input_size=(1.0, 2.0)).cost_tables((0, 1, 2), 4)


def test_cost_tables_shape_and_zero_diagonal():
    tiers = TIERED.tiers
    F = len(tiers)
    for name in ("uniform_latency", "tiered"):
        lat, en = network.get(name).cost_tables(tiers, 4)
        assert lat.shape == en.shape == (4, F, F)
        assert lat.dtype == en.dtype == np.float32
        for t in range(4):
            assert np.all(np.diag(lat[t]) == 0.0)
            assert np.all(np.diag(en[t]) == 0.0)
        assert lat.min() >= 0.0 and en.min() >= 0.0


def test_hash_origins_host_mirrors_jax_bit_for_bit():
    """The oracle's plain-int origin hash reproduces the jitted draw
    exactly — the property that makes transfer traces cross-checkable."""
    for salt in (0, 7, 123):
        for elig in ((0,), (0, 1, 2), (2, 5, 6, 11)):
            dev = np.asarray(network.hash_origins(64, elig, salt))
            host = network.hash_origins_host(64, elig, salt)
            np.testing.assert_array_equal(dev, host)
            assert set(host) <= set(elig)


def test_origin_sites_lowest_tier_only():
    assert network.origin_sites((0, 0, 0, 2)) == (0, 1, 2)
    assert network.origin_sites((1, 2, 1)) == (0, 2)  # lowest tier present
    assert network.origin_sites((0, 0)) == (0, 1)  # flat: every site


# ------------------------------------------------------------ tiered fleets
def test_tiered_fleet_structure():
    assert TIERED.tiers == (0, 0, 0, 2)
    assert TIERED.n_tiers == 3
    assert TIERED.n_sites == 4
    S, M = TIERED.eet.shape
    cloud = [j for j in range(M) if TIERED.sites[j] == 3]
    device = [j for j in range(M) if TIERED.sites[j] != 3]
    assert cloud and device
    # cloud machines: mains-powered (no idle draw) and faster than base
    p_idle = np.asarray(TIERED.p_idle)
    assert np.all(p_idle[cloud] == 0.0)
    assert np.all(p_idle[device] > 0.0)
    eet = np.asarray(TIERED.eet)
    assert eet[:, cloud].min() < eet[:, device].min()
    big = scenarios.get_fleet("tiered_x16").build()
    assert big.n_sites == 16
    assert big.tiers == (0,) * 15 + (2,)


def test_systemspec_tier_validation():
    import dataclasses

    with pytest.raises(ValueError, match="tier_of_site"):
        dataclasses.replace(SPEC2, tier_of_site=(0,))  # len != n_sites
    with pytest.raises(ValueError, match="tiers must be >= 0"):
        dataclasses.replace(SPEC2, tier_of_site=(-1, 0))
    assert SPEC2.tiers == (0, 0)  # untirered default: all device tier
    assert SPEC2.n_tiers == 1


# ------------------------------------------------- degeneracy (bit-exact)
def test_network_none_bit_exact_with_pr8_snapshot():
    """network="none" (and the default) reproduce the frozen pre-network
    engine bit for bit: metrics and task logs for all dispatchers x 2
    mapping heuristics."""
    with open("tests/data/pr8_engine_snapshot.json") as f:
        snap = json.load(f)
    tr = _trace(1, 40, 4.0, SPEC2.eet)
    for key, want in snap.items():
        d, h = key.split("/")
        m, aux = engine.simulate(tr, SPEC2, h, observers=("task_log",),
                                 dispatcher=d, network="none")
        # the snapshot's own fields: Metrics has gained fields since
        for f in [f for f in want if f != "task_log"]:
            got = np.asarray(getattr(m, f), np.float32)
            ref = np.asarray(want[f], np.float32)
            assert got.tobytes() == ref.tobytes(), f"{key}/{f}"
        log = {k: np.asarray(v) for k, v in aux["task_log"].items()}
        for f, ref in want["task_log"].items():
            got = log[f]
            ref = np.asarray(ref, got.dtype)
            assert got.tobytes() == ref.tobytes(), f"{key}/task_log.{f}"
        # without a network the ready column is the -1 sentinel fill
        assert np.all(log["ready_time"] == -1.0), key


def test_default_network_is_none():
    tr = _trace(1, 40, 4.0, SPEC2.eet)
    a = engine.simulate(tr, SPEC2, "FELARE", dispatcher="fair_spill")
    b = engine.simulate(tr, SPEC2, "FELARE", dispatcher="fair_spill",
                        network="none")
    for f in a._fields:
        assert np.asarray(getattr(a, f)).tobytes() == \
            np.asarray(getattr(b, f)).tobytes(), f


@settings(deadline=None, max_examples=6)
@given(seed=st.integers(0, 31), rate=st.sampled_from([2.0, 4.0, 6.0]))
def test_zero_cost_tiered_degenerates_to_flat_federation(seed, rate):
    """A tiered network whose matrices are all-zero is bit-identical to
    the flat federation (no network) for every dispatcher x ELARE/FELARE:
    ready times collapse to dispatch times, transfer energy to zero, and
    the event order is untouched."""
    tr = _trace(seed, 40, rate, TIERED.eet)
    for d in dispatch.list_dispatchers():
        for h in ("ELARE", "FELARE"):
            m0, a0 = engine.simulate(tr, TIERED, h, observers=("task_log",),
                                     dispatcher=d)
            m1, a1 = engine.simulate(tr, TIERED, h, observers=("task_log",),
                                     dispatcher=d, network=FREE_TIERED)
            for f in m0._fields:
                assert np.asarray(getattr(m0, f)).tobytes() == \
                    np.asarray(getattr(m1, f)).tobytes(), f"{d}/{h}/{f}"
            l0 = {k: np.asarray(v) for k, v in a0["task_log"].items()}
            l1 = {k: np.asarray(v) for k, v in a1["task_log"].items()}
            for f in l0:
                if f == "ready_time":  # -1 fill vs stamped, by design
                    continue
                assert l0[f].tobytes() == l1[f].tobytes(), f"{d}/{h}/{f}"


def test_tier_aware_equals_min_eet_without_network():
    tr = _trace(2, 60, 4.0, TIERED.eet)
    for h in ("ELARE", "FELARE"):
        a, la = engine.simulate(tr, TIERED, h, observers=("task_log",),
                                dispatcher="tier_aware")
        b, lb = engine.simulate(tr, TIERED, h, observers=("task_log",),
                                dispatcher="min_eet")
        for f in a._fields:
            assert np.asarray(getattr(a, f)).tobytes() == \
                np.asarray(getattr(b, f)).tobytes(), f"{h}/{f}"
        assert np.asarray(la["task_log"]["site"]).tobytes() == \
            np.asarray(lb["task_log"]["site"]).tobytes(), h


# ------------------------------------------------------------------ oracle
@pytest.mark.parametrize("net", ["uniform_latency", "tiered"])
@pytest.mark.parametrize("dispatcher", ["tier_aware", "fair_spill"])
@pytest.mark.parametrize("heuristic", ["ELARE", "FELARE"])
def test_tiered_task_log_matches_oracle_event_for_event(
        net, dispatcher, heuristic):
    """Engine and oracle agree event-for-event on the tiered fleet with
    transfer costs attached: per-type counters, energies, and the full
    task log including site ready times."""
    for seed in (0, 3):
        tr = _trace(seed, 60, 4.0, TIERED.eet)
        m, aux = engine.simulate(tr, TIERED, heuristic,
                                 observers=("task_log",),
                                 dispatcher=dispatcher, network=net)
        ref = pyengine.simulate(tr, TIERED, heuristic,
                                dispatcher=dispatcher, network=net)
        for f in ("completed_by_type", "missed_by_type",
                  "cancelled_by_type", "arrived_by_type"):
            np.testing.assert_array_equal(
                np.asarray(getattr(m, f)), ref[f], err_msg=f)
        np.testing.assert_allclose(
            float(m.energy_dynamic), ref["energy_dynamic"], rtol=1e-4)
        np.testing.assert_allclose(
            float(m.energy_wasted), ref["energy_wasted"], rtol=1e-4,
            atol=1e-6)
        np.testing.assert_allclose(
            float(m.makespan), ref["makespan"], rtol=1e-5)
        log = {k: np.asarray(v) for k, v in aux["task_log"].items()}
        for f in ("map_time", "start_time", "end_time", "ready_time"):
            np.testing.assert_allclose(
                log[f], ref["task_log"][f], atol=1e-5, err_msg=f)
        for f in ("machine", "site", "status", "retries"):
            np.testing.assert_array_equal(
                log[f], ref["task_log"][f], err_msg=f)


# ------------------------------------------------------------------ safety
@settings(deadline=None, max_examples=10)
@given(seed=st.integers(0, 63), rate=st.sampled_from([2.0, 4.0, 8.0]),
       net=st.sampled_from(["uniform_latency", "tiered"]))
def test_no_task_starts_before_it_lands(seed, rate, net):
    """With a network attached, no task ever starts before its stamped
    ready time — in-transit tasks are invisible to the mapper."""
    tr = _trace(seed, 50, rate, TIERED.eet)
    _, aux = engine.simulate(tr, TIERED, "FELARE", observers=("task_log",),
                             dispatcher="tier_aware", network=net)
    log = {k: np.asarray(v) for k, v in aux["task_log"].items()}
    started = log["start_time"] >= 0
    assert np.all(log["start_time"][started]
                  >= log["ready_time"][started] - 1e-5)
    # in-transit expiry is CANCELLED, never silently dropped: every
    # arrived task has a terminal status
    from repro.core.types import PENDING, QUEUED, RUNNING, UNARRIVED

    final = log["status"]
    assert not np.any((final == PENDING) | (final == QUEUED)
                      | (final == RUNNING))
    assert np.all((final == UNARRIVED) | (log["site"] >= -1))


def test_cross_tier_latency_slows_uniform_dispatches():
    """uniform_latency with a visible price must not beat the same run
    with free links on ready times: every stamped ready >= dispatch-time
    floor, and total dynamic energy strictly grows with link energy."""
    tr = _trace(5, 60, 4.0, TIERED.eet)
    base = engine.simulate(tr, TIERED, "FELARE", dispatcher="sticky")
    paid = engine.simulate(
        tr, TIERED, "FELARE", dispatcher="sticky",
        network=network.UniformLatency(latency=0.25, energy=0.5))
    assert float(paid.energy_dynamic) > float(base.energy_dynamic)


# ------------------------------------------------------- network observer
def test_network_observer_shapes_and_accounting():
    # sticky scatters tasks across sites, so cross-site links are paid
    # (tier_aware would keep every task on its free origin site here)
    tr = _trace(3, 60, 4.0, TIERED.eet)
    _, aux = engine.simulate(tr, TIERED, "FELARE",
                             observers=("network", "task_log"),
                             dispatcher="sticky", network="tiered")
    net = aux["network"]
    K = 64
    T = TIERED.n_tiers
    assert np.asarray(net["tier_load"]).shape == (K, T)
    assert np.asarray(net["xfer_energy"]).shape == (K, T)
    assert np.asarray(net["in_transit"]).shape == (K,)
    xe = np.asarray(net["xfer_energy"])
    # cumulative per-tier transfer energy: monotone non-decreasing
    assert np.all(np.diff(xe, axis=0) >= -1e-6)
    assert xe.sum() > 0  # tiered matrices have visible prices
    assert np.asarray(net["tier_load"]).min() >= 0
    assert np.asarray(net["in_transit"]).min() >= 0


def test_network_observer_flat_without_network():
    tr = _trace(3, 50, 4.0, SPEC2.eet)
    _, aux = engine.simulate(tr, SPEC2, "ELARE", observers=("network",))
    net = aux["network"]
    assert np.all(np.asarray(net["xfer_energy"]) == 0.0)
    assert np.all(np.asarray(net["in_transit"]) == 0)


# ------------------------------------------------------------ CLI + spec
def test_cli_tiered_sweep_writes_artifacts(tmp_path):
    runner._TRACE_LOG.clear()
    out = tmp_path / "tiered"
    sweep.main([
        "--system", "tiered_x4", "--dispatcher", "tier_aware",
        "--network", "tiered", "--observers", "network,task_log",
        "--rates", "4.0", "--reps", "1", "--tasks", "40",
        "--heuristics", "FELARE", "--out", str(out),
    ])
    payload = json.loads((out / "sweep.json").read_text())
    assert payload["spec"]["network"] == "tiered"
    assert (out / "sweep.csv").exists()
    assert (out / "observers.json").exists()
    assert set(runner._TRACE_LOG) == {
        ("FELARE", "poisson", "tier_aware", "none", "tiered")}
    runner._TRACE_LOG.clear()


def test_cli_rejects_unknown_network(capsys):
    with pytest.raises(SystemExit):
        sweep.build_spec(["--network", "nope"])
    assert "unknown network" in capsys.readouterr().err


def test_cli_list_networks(capsys):
    with pytest.raises(SystemExit):
        sweep.build_spec(["--list-networks"])
    out = capsys.readouterr().out
    for name in ("none", "uniform_latency", "tiered"):
        assert name in out


def test_cli_list_fleets(capsys):
    with pytest.raises(SystemExit):
        sweep.build_spec(["--list-fleets"])
    out = capsys.readouterr().out
    for name in ("paper", "tiered_x4", "tiered_x16"):
        assert name in out
    assert "0,0,0,2" in out  # tier layout column for tiered_x4


def test_sweep_spec_network_round_trip():
    spec = experiments.SweepSpec(
        system="tiered_x4", rates=(4.0,), reps=1, n_tasks=20,
        heuristics=("FELARE",), network="tiered",
        dispatcher="tier_aware")
    back = experiments.SweepSpec.from_json_dict(
        json.loads(json.dumps(spec.to_json_dict())))
    assert back == spec
    # instance form round-trips through kind + fields
    spec2 = experiments.replace(
        spec, network=network.UniformLatency(latency=0.5))
    back2 = experiments.SweepSpec.from_json_dict(
        json.loads(json.dumps(spec2.to_json_dict())))
    assert back2.resolve_network() == network.UniformLatency(latency=0.5)


def test_sweep_spec_old_payload_defaults_to_none():
    """Pre-network sweep.json payloads (no "network" key) load as free
    links — re-running an old artifact reproduces the old numbers."""
    d = experiments.SweepSpec(rates=(4.0,), reps=1, n_tasks=20,
                              heuristics=("ELARE",)).to_json_dict()
    del d["network"]
    spec = experiments.SweepSpec.from_json_dict(d)
    assert spec.network == "none"
    assert isinstance(spec.resolve_network(), network.NoNetwork)


def test_sweep_spec_rejects_unknown_network():
    with pytest.raises(ValueError, match="unknown network"):
        experiments.SweepSpec(rates=(4.0,), reps=1, n_tasks=20,
                              heuristics=("ELARE",), network="nope")


def test_systemspec_tiered_serialization_round_trip():
    spec = experiments.SweepSpec(
        system=TIERED, rates=(4.0,), reps=1, n_tasks=20,
        heuristics=("FELARE",), network="tiered")
    back = experiments.SweepSpec.from_json_dict(
        json.loads(json.dumps(spec.to_json_dict())))
    assert back.system.tier_of_site == TIERED.tier_of_site
    assert back.system.site_of_machine == TIERED.site_of_machine


def test_run_study_accepts_network():
    from repro.core import api

    res = api.run_study("FELARE", [4.0], TIERED, n_traces=2, n_tasks=30,
                        dispatcher="tier_aware", network="tiered")
    assert len(res) == 1
    assert int(np.asarray(res[0].metrics.arrived_by_type).sum()) > 0


# ------------------------------------------------------------- scale smoke
@pytest.mark.slow
def test_scale_smoke_single_trace_per_tuple():
    """A large vmapped tiered sweep completes with exactly one jit trace
    per (policy, dispatcher, dynamics, network) tuple. Default size is
    CI-friendly; REPRO_SCALE_FULL=1 runs the full 10^3 x 10^4 grid."""
    full = os.environ.get("REPRO_SCALE_FULL", "") == "1"
    reps = 1000 if full else 100
    n_tasks = 10_000 if full else 200
    runner._TRACE_LOG.clear()
    result = experiments.run_sweep(experiments.SweepSpec(
        system="tiered_x4", rates=(4.0,), reps=reps, n_tasks=n_tasks,
        heuristics=("ELARE", "FELARE"), seed=2,
        dispatcher="tier_aware", network="tiered",
    ))
    assert list(runner._TRACE_LOG) == [
        (h, "poisson", "tier_aware", "none", "tiered")
        for h in ("ELARE", "FELARE")]
    runner._TRACE_LOG.clear()
    arrived = np.asarray(result.metrics.arrived_by_type)
    assert arrived.shape[:3] == (2, 1, reps)
    assert np.all(arrived.sum(axis=-1) == n_tasks)  # every task accounted
