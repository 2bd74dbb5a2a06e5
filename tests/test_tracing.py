"""What the program says about its own cost: the event loop's stage
scopes, its per-lane iteration count ``Metrics.steps``, and the sweep
runner's host spans.

Scopes are metadata only: a sweep lowered with them is the sweep lowered
without them, op for op, and returns the same bytes.
"""
import contextlib
import dataclasses
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import experiments
from repro.core import api, engine, observe
from repro.datapipe import synthetic
from repro.experiments import runner

SPEC = api.paper_system()
# two sites, so the dispatch stage has work; with a dynamics, faults too
FED = experiments.SweepSpec(system="paper_x2").resolve_system()
HEURISTICS = ("MM", "ELARE", "FELARE")
STAGE_SCOPES = tuple(f"engine.{s}" for s in engine.STAGES) + (
    "engine.next_event",)


def _flat(system, reps=3, n=40, rate=4.0, seed=0):
    st = synthetic.trace_stack(jax.random.PRNGKey(seed), (rate,), reps, n,
                               system.eet)
    return jax.tree.map(lambda x: x[0], st)


def _lowered(system, heuristics, **kw):
    fn = jax.jit(lambda tr: runner.simulate_sweep(tr, system, heuristics,
                                                  **kw))
    return fn.lower(_flat(system))


@contextlib.contextmanager
def _no_scopes():
    """Trace with ``jax.named_scope`` turned into a no-op."""
    real = jax.named_scope
    jax.named_scope = lambda name: contextlib.nullcontext()
    try:
        yield
    finally:
        jax.named_scope = real


def test_sweep_ops_carry_stage_and_heuristic_scopes():
    text = _lowered(FED, HEURISTICS, dispatcher="fair_spill",
                    dynamics="site_outage").as_text(debug_info=True)
    scopes = {part for op in re.findall(r'loc\("([^"]*)"', text)
              for part in op.split("/")}
    for scope in STAGE_SCOPES + ("engine.fairness",) + tuple(
            f"sweep.{h}" for h in HEURISTICS):
        assert scope in scopes, scope


def test_scopes_leave_the_program_unchanged():
    """The module handed to the compiler is the same text without the
    scopes; they live only in its debug locations (the compile-cache key
    leaves those out too)."""
    with_scopes = _lowered(SPEC, HEURISTICS)
    with _no_scopes():
        without = _lowered(SPEC, HEURISTICS)
    for scope in ("engine.map", "engine.fairness"):
        assert scope in with_scopes.as_text(debug_info=True), scope
        assert scope not in without.as_text(debug_info=True), scope
    assert with_scopes.as_text() == without.as_text()


@pytest.mark.parametrize("system,kw", [
    (SPEC, {}),
    (FED, dict(dispatcher="fair_spill", dynamics="site_outage")),
], ids=["paper", "paper_x2_outage"])
def test_metrics_byte_equal_with_and_without_scopes(system, kw):
    tr = _flat(system)
    got = runner.simulate_sweep(tr, system, HEURISTICS, **kw)
    with _no_scopes():
        want = runner.simulate_sweep(tr, system, HEURISTICS, **kw)
    for f in got._fields:
        a, b = np.asarray(getattr(got, f)), np.asarray(getattr(want, f))
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), f


def test_sweep_steps_equal_each_traces_own_loop():
    tr = _flat(SPEC, reps=4, rate=6.0)
    out = runner.simulate_sweep(tr, SPEC, HEURISTICS)
    assert out.steps.dtype == jnp.int32
    assert out.steps.shape == (len(HEURISTICS), 4)
    for h, name in enumerate(HEURISTICS):
        for b in range(4):
            one = jax.tree.map(lambda x: x[b], tr)
            m = engine.simulate(one, SPEC, name)
            assert int(m.steps) == int(out.steps[h, b]), (name, b)
            assert m.steps.shape == ()


@dataclasses.dataclass(frozen=True)
class _CountEvents(observe.Observer):
    """Counts the loop's iterations: ``start`` closes every event."""

    name = "count_events"

    def init(self, trace, sysarr):
        return jnp.int32(0)

    def on_event(self, stage, aux, st, trace, sysarr):
        return aux + 1 if stage == "start" else aux

    def finalize(self, aux, st):
        return aux


@pytest.mark.parametrize("heuristic", ["MM", "FELARE"])
def test_steps_equal_an_event_counting_observer(heuristic):
    tr = _flat(SPEC, reps=2, n=60, rate=5.0, seed=3)
    m, aux = engine.simulate_batch(tr, SPEC, heuristic,
                                   observers=(_CountEvents(),))
    np.testing.assert_array_equal(np.asarray(m.steps),
                                  np.asarray(aux["count_events"]))
    assert (np.asarray(m.steps) > 60).all()  # at least one event per task


def test_steps_stop_at_max_steps():
    tr = jax.tree.map(lambda x: x[0], _flat(SPEC, reps=1))
    assert int(engine.simulate(tr, SPEC, "MM", max_steps=7).steps) == 7


def test_run_sweep_steps_shaped_like_the_grid():
    res = experiments.run_sweep(experiments.SweepSpec(
        rates=(2.0, 5.0), reps=2, n_tasks=30, heuristics=("MM", "ELARE"),
        seed=4))
    assert res.metrics.steps.shape == (2, 2, 2)
    assert res.metrics_for("MM", 5.0).steps.shape == (2,)
    assert "steps" not in res.summary_rows()[0]


def _host_spans(log_dir) -> list:
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")
    return [(e.name, e.start_ns) for plane in ProfileData.from_file(
        path).planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("sweep.")]


def test_profiled_sweeps_write_one_trace_span_per_traced_body(tmp_path):
    tr = _flat(SPEC, reps=2, n=20)
    heuristics = ("MM", "ELARE")
    runner._TRACE_LOG.clear()
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(2):  # each call jits a fresh closure: it re-traces
            runner.simulate_sweep(tr, SPEC, heuristics)
        experiments.run_sweep(experiments.SweepSpec(
            rates=(3.0,), reps=1, n_tasks=20, heuristics=heuristics,
            seed=2))
    log = [entry[0] for entry in runner._TRACE_LOG]
    runner._TRACE_LOG.clear()
    names = [n for n, _ in sorted(_host_spans(tmp_path),
                                  key=lambda x: x[1])]
    traced = [n[len("sweep.trace."):] for n in names
              if n.startswith("sweep.trace.")]
    assert traced == log == list(heuristics) * 3
    assert names.count("sweep.build") == 3
    assert names.count("sweep.stack") == names.count("sweep.reduce") == 1
    # each call builds before it traces
    assert [n for n in names if n in ("sweep.build", "sweep.trace.MM")] \
        == ["sweep.build", "sweep.trace.MM"] * 3
