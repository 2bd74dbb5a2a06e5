"""The `aws.poisson` cell: its fleet is the program's AWS pair, the plain
reference agrees with the program on every lane of a small unit, and the
bfloat16 control does not."""
import json

import numpy as np

from conftest import ROOT, tiny

from bench import check, reference, run

CELL = "aws.poisson"


def test_config_equals_program_fleet():
    from repro import scenarios

    c = json.loads((ROOT / "bench" / "configs" / "aws.json").read_text())
    spec = scenarios.get_fleet("aws").build()
    for key in ("eet", "p_dyn", "p_idle"):
        assert np.array_equal(np.asarray(c[key], np.float32),
                              np.asarray(spec.__dict__[key])), key
    assert c["queue_size"] == spec.queue_size
    assert c["fairness_factor"] == spec.fairness_factor
    assert spec.site_of_machine is None


def test_reference_matches_every_lane():
    """Two task types put Eq. 3's limit on the lower rate whenever the rates
    differ, so FELARE's lanes hang on its float32 rounding here."""
    units = []
    c = tiny(CELL, n_tasks=150)
    res = run.run_cell(c, 31, 0.0, False, require_tpu=False, keep=units)
    assert res["correct"], res["check"]
    traces, metrics = units[0]
    assert metrics["suffered_maps"][c["traffic"]["heuristics"].index(
        "FELARE")].sum() > 0
    for h, name in enumerate(c["traffic"]["heuristics"]):
        for b in range(traces["arrival"].shape[0]):
            ref = reference.simulate({k: v[b] for k, v in traces.items()},
                                     c["fleet"], name)
            for k in check.COUNTERS:
                assert np.array_equal(metrics[k][h, b], ref[k]), (name, b, k)
            for k in check.REALS:
                assert abs(float(metrics[k][h, b]) - ref[k]) <= 1e-5 * max(
                    abs(ref[k]), 1.0), (name, b, k)


def test_control_is_not_correct():
    units = []
    c = tiny(CELL, n_tasks=300)
    run.run_cell(c, 77, 0.0, False, require_tpu=False, keep=units)
    out = check.compare(units, c["fleet"], c["traffic"], 77, control=True)
    assert not out["correct"]
    assert out["numbers"]["counter_diff"]["value"] > 0
