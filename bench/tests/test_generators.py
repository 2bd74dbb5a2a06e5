"""The copied yardstick equals what the program's own generators and
fleets give today, at a fixed seed."""
import json

import jax
import numpy as np
import pytest

from conftest import ROOT

from bench.generators import QUANTUM, make_stack, make_unit_generator

SCENARIO = {"poisson": "poisson", "poisson_no_felare": "poisson"}
FLEET = {"paper": "paper"}


@pytest.mark.parametrize("traffic", sorted(SCENARIO))
def test_traffic_equals_program_scenario(traffic):
    from repro import scenarios

    t = json.loads((ROOT / "bench" / "traffic" / f"{traffic}.json")
                   .read_text())
    fleet = scenarios.get_fleet("paper").build()
    key = jax.random.PRNGKey(1234)
    rates = tuple(float(r) for r in t["rates"])
    cv = t["generator"]["runtime"]["cv_run"]
    ours = make_stack(t["generator"])(key, rates, 3, 150, fleet.eet)
    theirs = scenarios.get(SCENARIO[traffic]).stack(
        key, rates, 3, 150, fleet.eet, cv_run=cv)
    for name, x in zip(theirs._fields, theirs):
        a, b = np.asarray(ours[name]), np.asarray(x)
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), name


@pytest.mark.parametrize("config", sorted(FLEET))
def test_config_equals_program_fleet(config):
    from repro import scenarios

    c = json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                   .read_text())
    spec = scenarios.get_fleet(FLEET[config]).build()
    for key in ("eet", "p_dyn", "p_idle"):
        assert np.array_equal(np.asarray(c[key], np.float32),
                              np.asarray(spec.__dict__[key])), key
    assert c["queue_size"] == spec.queue_size
    assert c["fairness_factor"] == spec.fairness_factor
    assert spec.site_of_machine is None


def test_seed_and_unit_draw_the_traces():
    """One seed gives the same unit twice; another unit, or another seed
    that differs only in its high word, draws other traces. Every time is
    a multiple of the quantum."""
    t = json.loads((ROOT / "bench" / "traffic" / "poisson.json").read_text())
    t.update(n_tasks=50, reps=4)
    c = json.loads((ROOT / "bench" / "configs" / "paper.json").read_text())
    gen = make_unit_generator(t, c["eet"])
    u = np.uint32
    a = gen(u(5), u(1), u(1))
    again = gen(u(5), u(1), u(1))
    assert all(np.array_equal(a[k], again[k]) for k in a)
    for other in (gen(u(5), u(1), u(2)), gen(u(5), u(2), u(1))):
        assert not np.array_equal(a["arrival"], other["arrival"])
    for k in ("arrival", "deadline", "exec_actual"):
        x = np.asarray(a[k], np.float64)
        assert np.array_equal(x / QUANTUM, np.round(x / QUANTUM)), k
    assert np.asarray(a["arrival"]).shape == (len(t["rates"]) * 4, 50)
