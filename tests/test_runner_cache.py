"""The sweep runner's program cache: a repeated ``simulate_sweep`` with
equal static inputs reuses the jitted program and traces nothing, and a
change to any static input, or to the trace shape, traces anew."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core import api, policy
from repro.datapipe import synthetic
from repro.experiments import runner

SPEC = api.paper_system()
NAME = "cache-ow"
FIRST = policy.TwoPhasePolicy(policy.MinCompletion(), policy.Fcfs(),
                              policy.DropStale())
SECOND = policy.TwoPhasePolicy(policy.MinExecution(), policy.Fcfs(),
                               policy.DropStale())
HEURISTICS = ("MM", NAME)


def _traces(n=24):
    st = synthetic.trace_stack(jax.random.PRNGKey(5), (4.0,), 2, n,
                               SPEC.eet)
    return jax.tree.map(lambda x: x[0], st)


def _system(**changes):
    """A system equal to ``SPEC`` but for ``changes``, sharing no array
    with it."""
    arrays = {f: np.array(getattr(SPEC, f))
              for f in ("eet", "p_dyn", "p_idle")}
    return dataclasses.replace(SPEC, **{**arrays, **changes})


def _eet_one_entry_changed():
    eet = np.array(SPEC.eet)
    eet[0, 0] += 0.5
    return eet


def _reregistered(call):
    policy.register(NAME, SECOND, overwrite=True)
    return call


# id -> change to the second call's arguments; "equal" must hit the cache
CHANGES = {
    "equal": lambda c: {**c, "system": _system(),
                        "heuristic_names": list(HEURISTICS)},
    "eet": lambda c: {**c, "system": _system(eet=_eet_one_entry_changed())},
    "queue_size": lambda c: {**c, "system": _system(queue_size=3)},
    "fairness_factor": lambda c: {**c,
                                  "system": _system(fairness_factor=1.5)},
    "heuristic_order": lambda c: {**c,
                                  "heuristic_names": HEURISTICS[::-1]},
    "observers": lambda c: {**c, "observers": ("health",)},
    "trace_label": lambda c: {**c, "trace_label": "other"},
    "max_steps": lambda c: {**c, "max_steps": 50},
    "trace_shape": lambda c: {**c, "traces": _traces(n=30)},
    "policy_overwrite": _reregistered,
}


@pytest.fixture
def registered():
    policy.register(NAME, FIRST)
    try:
        yield
    finally:
        policy.unregister(NAME)


def _bytes(out):
    return [np.asarray(x).tobytes() for x in jax.tree.leaves(out)]


@pytest.mark.parametrize("change", list(CHANGES))
def test_repeated_sweep_reuses_its_program_until_a_static_input_changes(
        change, registered):
    first_call = dict(traces=_traces(), system=SPEC,
                      heuristic_names=HEURISTICS)
    runner._TRACE_LOG.clear()
    first = runner.simulate_sweep(**first_call)
    assert [e[0] for e in runner._TRACE_LOG] == list(HEURISTICS)
    assert runner.sweep_cache_info()[:2] == (0, 1)

    call = CHANGES[change](dict(first_call))
    runner._TRACE_LOG.clear()
    second = runner.simulate_sweep(**call)
    log = [e[0] for e in runner._TRACE_LOG]
    hits, misses = runner.sweep_cache_info()[:2]
    if change == "equal":
        assert log == [] and (hits, misses) == (1, 1)
        assert _bytes(second) == _bytes(first)
        runner.clear_sweep_cache()
        again = runner.simulate_sweep(**call)
        assert [e[0] for e in runner._TRACE_LOG] == list(HEURISTICS)
        assert _bytes(again) == _bytes(first)
    elif change == "trace_shape":  # the same program, traced for the shape
        assert log == list(HEURISTICS) and (hits, misses) == (1, 1)
    else:
        assert log == list(call["heuristic_names"])
        assert (hits, misses) == (0, 2)
    runner._TRACE_LOG.clear()
