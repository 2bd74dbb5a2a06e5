"""A run with the timed path broken underneath reads ``correct`` false,
for each fault the cells can have; a sound run reads true."""
import jax
import jax.numpy as jnp
import pytest

from conftest import run_tiny

CELLS = ["paper.poisson_no_felare"]


def _wrap_sweep(monkeypatch, after):
    """simulate_sweep, with ``after(traces, metrics)`` applied to what it
    returns."""
    from repro.experiments import runner

    real = runner.simulate_sweep

    def broken(traces, *a, **kw):
        return after(traces, real(traces, *a, **kw), real, a, kw)

    monkeypatch.setattr(runner, "simulate_sweep", broken)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run(cell):
    res = run_tiny(cell)
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["attempted"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_step_returns_state_unchanged(cell, monkeypatch):
    from repro.core import engine

    for stage in ("_stage_finalize", "_stage_admit", "_stage_dispatch",
                  "_stage_map", "_stage_start"):
        monkeypatch.setattr(engine, stage, lambda st, *a, **k: st)
    res = run_tiny(cell)
    assert not res["correct"]
    assert res["check"]["lanes_bad"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_half_the_batch_left_out(cell, monkeypatch):
    """Only the first half of the lanes is simulated; the rest are
    answered with results of the simulated half."""
    def after(traces, out, real, a, kw):
        B = traces.arrival.shape[0]
        half = jax.tree.map(lambda x: x[: (B + 1) // 2], traces)
        done = real(half, *a, **kw)
        idx = jnp.arange(B) % ((B + 1) // 2)
        return jax.tree.map(lambda x: x[:, idx], done)

    _wrap_sweep(monkeypatch, after)
    res = run_tiny(cell)
    assert not res["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_energy_altered_where_produced(cell, monkeypatch):
    _wrap_sweep(monkeypatch, lambda t, out, *_: out._replace(
        energy_dynamic=out.energy_dynamic * 1.01))
    res = run_tiny(cell)
    assert not res["correct"]
    assert res["check"]["energy_rel_err"]["value"] > \
        res["check"]["energy_rel_err"]["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_answer_altered_where_produced(cell, monkeypatch):
    """One on-time completion of every lane is booked as a miss: the
    counts still add up, so only the reference can tell."""
    def after(t, out, *_):
        move = (out.completed_by_type > 0) & (
            jnp.cumsum(out.completed_by_type > 0, axis=-1) == 1)
        return out._replace(
            completed_by_type=out.completed_by_type - move,
            missed_by_type=out.missed_by_type + move)

    _wrap_sweep(monkeypatch, after)
    res = run_tiny(cell)
    assert not res["correct"]
    assert res["check"]["lanes_bad"]["value"] == 0
    assert res["check"]["counter_diff"]["value"] > 0
