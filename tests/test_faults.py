"""Tests for the faults subsystem (machine dynamics + orphans + backups).

Contracts under test:

  * degeneracy — ``dynamics="none"`` (the default) is bit-identical to
    the pre-faults engine: every metric leaf and the full task log match
    the frozen PR 6 snapshot (``tests/data/pr6_engine_snapshot.json``)
    for all 5 dispatchers x ELARE/FELARE;
  * oracle — the pure-Python interpreter replays ``bernoulli_updown``,
    ``site_outage`` and ``degrade`` event-for-event (metrics, energies
    and full task logs including orphan retry counts), with and without
    ``with_backup``;
  * safety — no task is ever started on a dead machine, and orphan
    retries are bounded by ``max_retries`` (hypothesis property);
  * single-jit — one trace per (policy, dispatcher, dynamics) triple,
    including through the CLI;
  * backups — ``with_backup`` is inert without a dynamics attached and
    validates its inputs;
  * plumbing — the ``health`` observer, registries, ``--dynamics`` /
    ``--list-dynamics``, and SweepSpec JSON round-trips.
"""
import json

import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings

from repro import experiments, scenarios
from repro.core import dispatch, engine, faults, pyengine, workload
from repro.core.types import CANCELLED, COMPLETED, MISSED
from repro.experiments import runner, sweep
from repro.launch import elastic

SPEC2 = scenarios.get_fleet("paper_x2").build()

BERNOULLI = faults.BernoulliUpDown(p_fail=0.05, p_recover=0.3, seed=7)
OUTAGE = faults.SiteOutage(outages=((0, 0.25, 0.5), (1, 0.5, 0.625)))
DEGRADE = faults.Degrade(factor=2.0, p=0.5, seed=3)  # 2.0: f32-exact scale


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
def test_builtin_dynamics_registered():
    names = faults.list_dynamics()
    for name in ("none", "bernoulli_updown", "site_outage", "degrade"):
        assert name in names
        assert faults.is_registered(name)
        assert faults.describe(name)  # non-empty one-liner
    assert isinstance(faults.get("NONE"), faults.NoDynamics)  # case-insens
    with pytest.raises(KeyError, match="choose from"):
        faults.get("nope")
    with pytest.raises(TypeError, match="MachineDynamics protocol"):
        faults.register("bad", object())


def test_dynamics_json_round_trip():
    for d in (faults.NoDynamics(), BERNOULLI, OUTAGE, DEGRADE,
              faults.Degrade(factor=1.5, machines=(0, 3)),
              faults.SiteOutage(outages=((1, 0.1, 0.9),), max_retries=5)):
        back = faults.from_json_dict(
            json.loads(json.dumps(faults.to_json_dict(d))))
        assert back == d
    with pytest.raises(ValueError, match="unknown dynamics kind"):
        faults.from_json_dict({"kind": "nope"})


def test_dynamics_validation():
    with pytest.raises(ValueError, match="start < end"):
        faults.SiteOutage(outages=((0, 0.5, 0.25),))
    with pytest.raises(ValueError, match="factor"):
        faults.Degrade(factor=0.0)


def test_hash_uniform_host_mirrors_jax_bit_for_bit():
    """The oracle's plain-int hash reproduces the jitted draw exactly —
    the property that makes bernoulli failure traces cross-checkable."""
    for seed in (0, 7, 123):
        for step in (0, 1, 17, 4096):
            dev = np.asarray(faults.hash_uniform(
                jnp.arange(16, dtype=jnp.uint32), jnp.uint32(step), seed))
            host = np.asarray(
                [faults.hash_uniform_host(j, step, seed) for j in range(16)],
                np.float32)
            np.testing.assert_array_equal(dev, host)


# ------------------------------------------------- degeneracy (bit-exact)
def test_dynamics_none_bit_exact_with_pr6_snapshot():
    """dynamics="none" (and the default) reproduce the frozen pre-faults
    engine bit for bit: metrics and task logs for 5 dispatchers x 2
    mapping heuristics."""
    with open("tests/data/pr6_engine_snapshot.json") as f:
        snap = json.load(f)
    tr = _trace(1, 40, 4.0, SPEC2.eet)
    for key, want in snap.items():
        d, h = key.split("/")
        m, aux = engine.simulate(tr, SPEC2, h, observers=("task_log",),
                                 dispatcher=d, dynamics="none")
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
        # the new retries column exists and stays all-zero without faults
        assert log["retries"].max() == 0, key


def test_default_dynamics_is_none():
    tr = _trace(1, 40, 4.0, SPEC2.eet)
    a = engine.simulate(tr, SPEC2, "FELARE", dispatcher="fair_spill")
    b = engine.simulate(tr, SPEC2, "FELARE", dispatcher="fair_spill",
                        dynamics="none")
    for f in a._fields:
        assert (np.asarray(getattr(a, f)).tobytes()
                == np.asarray(getattr(b, f)).tobytes()), f


def test_with_backup_inert_without_dynamics():
    """Backups only matter when machines can die: a wrapped policy maps
    bit-identically to its base on a fault-free run."""
    tr = _trace(1, 40, 4.0, SPEC2.eet)
    base = engine.simulate(tr, SPEC2, "FELARE", dispatcher="sticky")
    wrapped = engine.simulate(tr, SPEC2, faults.with_backup("FELARE", k=2),
                              dispatcher="sticky")
    for f in base._fields:
        assert (np.asarray(getattr(base, f)).tobytes()
                == np.asarray(getattr(wrapped, f)).tobytes()), f


# --------------------------------------------------------- oracle parity
def _assert_engine_matches_oracle(tr, spec, heuristic, dispatcher, dynamics,
                                  tag):
    m, aux = engine.simulate(tr, spec, heuristic, dispatcher=dispatcher,
                             dynamics=dynamics, observers=("task_log",))
    ref = pyengine.simulate(tr, spec, heuristic, dispatcher=dispatcher,
                            dynamics=dynamics)
    for f in ("arrived_by_type", "completed_by_type", "missed_by_type",
              "cancelled_by_type"):
        np.testing.assert_array_equal(
            np.asarray(getattr(m, f)), np.asarray(ref[f]),
            err_msg=f"{tag}/{f}")
    for f in ("energy_dynamic", "energy_wasted", "makespan"):
        np.testing.assert_allclose(
            float(getattr(m, f)), float(ref[f]), rtol=1e-5, atol=1e-6,
            err_msg=f"{tag}/{f}")
    log = {k: np.asarray(v) for k, v in aux["task_log"].items()}
    rlog = ref["task_log"]
    for f in ("status", "machine", "site", "retries"):
        np.testing.assert_array_equal(log[f], np.asarray(rlog[f]),
                                      err_msg=f"{tag}/task_log.{f}")
    for f in ("map_time", "start_time", "end_time"):
        np.testing.assert_allclose(
            log[f], np.asarray(rlog[f], np.float32), rtol=1e-6, atol=1e-6,
            err_msg=f"{tag}/task_log.{f}")


@pytest.mark.parametrize("dynamics", [BERNOULLI, OUTAGE, DEGRADE],
                         ids=["bernoulli_updown", "site_outage", "degrade"])
@pytest.mark.parametrize("heuristic", ["ELARE", "FELARE"])
def test_faulty_task_log_matches_oracle_event_for_event(heuristic, dynamics):
    """Engine vs oracle under failures on the 2-site paper fleet: per-task
    status/machine/site/retries and every timestamp agree at every event
    — including bit-equal bernoulli failure draws and f32-exact outage
    window edges."""
    tr = _trace(3, 48, 4.0, SPEC2.eet)
    for dispatcher in ("sticky", "health_aware"):
        _assert_engine_matches_oracle(
            tr, SPEC2, heuristic, dispatcher, dynamics,
            f"{heuristic}/{dispatcher}/{dynamics.kind}")


@pytest.mark.parametrize("k", [1, 2])
def test_backup_failover_matches_oracle_event_for_event(k):
    """with_backup(k) under machine churn: the oracle mirrors the backup
    nomination (greedy min completion, primary excluded) and the
    fail-straight-over path, so the full task logs still agree."""
    tr = _trace(3, 48, 4.0, SPEC2.eet)
    for heuristic in ("ELARE", "FELARE"):
        _assert_engine_matches_oracle(
            tr, SPEC2, faults.with_backup(heuristic, k=k), "sticky",
            BERNOULLI, f"{heuristic}+backup{k}")


# ------------------------------------------------------- safety properties
@given(seed=st.integers(0, 1000), rate=st.floats(2.0, 8.0),
       dispatcher=st.sampled_from(["sticky", "least_queued", "fair_spill",
                                   "health_aware"]))
@settings(max_examples=8, deadline=None)
def test_no_task_starts_on_a_dead_machine(seed, rate, dispatcher):
    """Under a scheduled outage, no task ever *starts* on a machine inside
    its site's dead window, and orphan retries stay within max_retries."""
    dyn = faults.SiteOutage(outages=((0, 0.25, 0.5),), max_retries=2)
    tr = _trace(seed, 80, rate, SPEC2.eet)
    _, aux = engine.simulate(tr, SPEC2, "FELARE", observers=("task_log",),
                             dispatcher=dispatcher, dynamics=dyn)
    log = {k: np.asarray(v) for k, v in aux["task_log"].items()}
    horizon = np.float32(np.asarray(tr.deadline).max())
    t0 = np.float32(np.float32(0.25) * horizon)
    t1 = np.float32(np.float32(0.5) * horizon)
    sites = np.asarray(SPEC2.site_of_machine)
    ran = np.isin(log["status"], (COMPLETED, MISSED)) & (log["machine"] >= 0)
    started = log["start_time"][ran]
    on_dead_site = sites[log["machine"][ran]] == 0
    in_window = (started >= t0) & (started < t1)
    assert not np.any(on_dead_site & in_window), (
        "task started on a machine during its site's outage")
    # bounded retry: a surviving task never exceeded max_retries; only a
    # CANCELLED task carries the exhausting (max+1)-th increment
    surviving = log["status"] != CANCELLED
    assert log["retries"][surviving].max(initial=0) <= dyn.max_retries
    assert log["retries"].max() <= dyn.max_retries + 1


def test_full_blackout_cancels_everything_without_hanging():
    """Both sites dark for the whole trace: every arrived task dies by
    retry exhaustion (no machine ever accepts work) and the loop
    terminates."""
    dyn = faults.SiteOutage(outages=((0, 0.0, 10.0), (1, 0.0, 10.0)),
                            max_retries=1)
    tr = _trace(0, 30, 4.0, SPEC2.eet)
    m, aux = engine.simulate(tr, SPEC2, "FELARE", observers=("task_log",),
                             dynamics=dyn, dispatcher="health_aware")
    assert int(np.asarray(m.completed_by_type).sum()) == 0
    log = {k: np.asarray(v) for k, v in aux["task_log"].items()}
    assert np.all(log["machine"] == -1)  # nothing ever ran
    assert int(np.asarray(m.cancelled_by_type).sum()) == 30


# ------------------------------------------------------------- single jit
def test_one_jit_trace_per_policy_dispatcher_dynamics():
    heuristics = ("ELARE", "FELARE")
    runner._TRACE_LOG.clear()
    for dyn in ("none", "site_outage"):
        experiments.run_sweep(experiments.SweepSpec(
            system="paper_x2", rates=(3.0,), reps=2, n_tasks=50,
            heuristics=heuristics, seed=1, dispatcher="health_aware",
            dynamics=dyn,
        ))
    expected = {(h, "poisson", "health_aware", dyn, "none")
                for h in heuristics for dyn in ("none", "site_outage")}
    assert set(runner._TRACE_LOG) == expected
    assert len(runner._TRACE_LOG) == len(expected)
    runner._TRACE_LOG.clear()


# --------------------------------------------------------------- backups
def test_with_backup_validation_and_describe():
    with pytest.raises(ValueError, match="k must be >= 1"):
        faults.with_backup("FELARE", k=0)
    with pytest.raises(TypeError, match="mapping policy"):
        faults.with_backup(42)
    pol = faults.with_backup("FELARE", k=2)
    assert pol.backup_k == 2
    assert pol.describe().backup_k == 2


def test_backup_slots_are_disjoint_and_exclude_primary():
    """Every nominated backup set: k distinct machines, none the primary,
    all within reach of the task (checked through the engine's own
    nomination on a deterministic single-event run)."""
    tr = _trace(3, 48, 4.0, SPEC2.eet)
    ref = pyengine.simulate(tr, SPEC2, faults.with_backup("FELARE", k=2),
                            dispatcher="sticky", dynamics=BERNOULLI)
    backup = np.asarray(ref["backup"])
    machine = np.asarray(ref["task_log"]["machine"])
    assert backup.shape == (48, 2)
    for k_, row in enumerate(backup):
        slots = row[row >= 0]
        assert len(set(slots.tolist())) == len(slots), f"task {k_} dup slot"


# ------------------------------------------------------- health observer
def test_health_observer_series():
    tr = _trace(2, 100, 5.0, SPEC2.eet)
    _, aux = engine.simulate(
        tr, SPEC2, "FELARE", dispatcher="health_aware",
        dynamics=faults.SiteOutage(outages=((0, 0.25, 0.5),)),
        observers=("health",))
    h = {k: np.asarray(v) for k, v in aux["health"].items()}
    M, F = SPEC2.n_machines, SPEC2.n_sites
    assert h["healthy"].shape == (64,)
    assert h["site_healthy"].shape == (64, F)
    assert h["site_alive"].shape == (64, F)
    # the outage is visible: site 0 drops to zero healthy machines inside
    # the window and recovers after
    assert h["healthy"].min() == M // 2
    assert h["healthy"].max() == M
    assert not h["site_alive"][:, 0].all()
    assert h["site_alive"][:, 1].all()
    np.testing.assert_array_equal(h["site_healthy"].sum(-1), h["healthy"])
    # orphan pressure is cumulative
    assert np.all(np.diff(h["orphans"]) >= 0)
    assert h["orphans"][-1] > 0

    # with no dynamics the series are trivially flat
    _, aux = engine.simulate(tr, SPEC2, "FELARE", observers=("health",))
    h = {k: np.asarray(v) for k, v in aux["health"].items()}
    assert np.all(h["healthy"] == M)
    assert np.all(h["orphans"] == 0)


# ------------------------------------------------------------ CLI + spec
def test_cli_faulty_sweep_writes_artifacts(tmp_path):
    runner._TRACE_LOG.clear()
    out = tmp_path / "faults"
    sweep.main([
        "--system", "paper_x2", "--dispatcher", "health_aware",
        "--dynamics", "site_outage", "--observers", "health",
        "--rates", "4.0", "--reps", "1", "--tasks", "40",
        "--heuristics", "ELARE", "--out", str(out),
    ])
    payload = json.loads((out / "sweep.json").read_text())
    assert payload["spec"]["dynamics"] == "site_outage"
    assert (out / "sweep.csv").exists()
    assert (out / "observers.json").exists()
    assert set(runner._TRACE_LOG) == {
        ("ELARE", "poisson", "health_aware", "site_outage", "none")}
    runner._TRACE_LOG.clear()


def test_cli_rejects_unknown_dynamics(capsys):
    with pytest.raises(SystemExit):
        sweep.build_spec(["--dynamics", "nope"])
    assert "unknown dynamics" in capsys.readouterr().err


def test_cli_list_dynamics(capsys):
    with pytest.raises(SystemExit):
        sweep.build_spec(["--list-dynamics"])
    out = capsys.readouterr().out
    for name in faults.list_dynamics():
        assert name in out


def test_spec_rejects_unknown_dynamics():
    with pytest.raises(ValueError, match="unknown dynamics"):
        experiments.SweepSpec(dynamics="nope")
    with pytest.raises(ValueError, match="MachineDynamics"):
        experiments.SweepSpec(dynamics=42)


def test_spec_json_roundtrip_with_dynamics():
    named = experiments.SweepSpec(system="paper_x2", dynamics="site_outage")
    back = experiments.SweepSpec.from_json_dict(
        json.loads(json.dumps(named.to_json_dict())))
    assert back == named
    inst = experiments.SweepSpec(
        system="paper_x2", dispatcher="health_aware",
        dynamics=faults.SiteOutage(outages=((1, 0.1, 0.4),), max_retries=5))
    back = experiments.SweepSpec.from_json_dict(
        json.loads(json.dumps(inst.to_json_dict())))
    assert back.dynamics == inst.dynamics
    # defaults stay "none" for old JSON payloads
    d = named.to_json_dict()
    d.pop("dynamics")
    assert experiments.SweepSpec.from_json_dict(d).dynamics == "none"


# ----------------------------------------------------------- launch demo
def test_elastic_launch_smoke():
    res = elastic.main(["--tasks", "60", "--rate", "4.0",
                        "--down", "1:0.25:0.5"])
    assert set(res) >= {"ontime", "orphans", "site_alive", "min_sites_live"}
    assert 0.0 <= res["ontime"] <= 1.0
    assert res["min_sites_live"] >= 1
