"""The fairness step's readers: its device stage, the evictions and the
share of mapping events with a suffered type, on events and outputs built
by hand and on the recorded CPU trace."""
import types

import numpy as np
import pytest

from bench import run
from bench import stage_trace as st

DEV = "/device:TPU:0"
LOOP = "jit(run_all)/sweep.FELARE/vmap()/while/body/engine.map"
NAMES = ("fairness_device_s_per_sweep", "fair_evicted_per_sweep",
         "suffered_map_share")


def _profile(fair: bool = True):
    inner = f"{LOOP}/engine.fairness" if fair else LOOP
    ops = [
        ("%while.1 = ...", 100, 200, "jit(run_all)/sweep.FELARE/while"),
        ("%fusion.1 = ...", 100, 130, f"{inner}/shift_left"),
        ("%fusion.2 = ...", 130, 170, f"{LOOP}/dot"),
        ("%fusion.3 = ...", 170, 180, f"{inner}/jit(_where)/select_n"),
        ("%fusion.4 = ...", 180, 200,
         "jit(run_all)/sweep.FELARE/while/body/engine.start/add"),
    ]
    return st.Profile(ops={DEV: ops},
                      modules={DEV: [("jit_run_all(3)", 100, 200)]},
                      host=[("bench_unit", 0, 300), ("sweep.build", 50, 60)])


def _reading(profile=None, outputs=None, whole=True, window=(0, 300)):
    return types.SimpleNamespace(units=2, jit_host_s=0.0,
                                 traced=dict(whole=whole), profile=profile,
                                 window=window, outputs=outputs)


def _outputs():
    """Two units of H=3 heuristics x B=2 lanes: MM, FELARE, ELARE."""
    def unit(steps, suffered, evicted):
        return ({}, {"steps": np.array(steps, np.int32),
                     "suffered_maps": np.array(suffered, np.int32),
                     "fair_evicted": np.array(evicted, np.int32)})
    return [unit([[10, 9], [8, 6], [7, 7]], [[0, 0], [4, 3], [0, 0]],
                 [[0, 0], [2, 1], [0, 0]]),
            unit([[12, 12], [10, 10], [9, 8]], [[0, 0], [5, 0], [0, 0]],
                 [[0, 0], [4, 0], [0, 0]])]


def test_fairness_stage_seconds():
    r = _reading(_profile())
    assert run.load_reader("fairness_device_s_per_sweep")(r) == \
        pytest.approx(40e-9)
    # the same ops under the map stage alone: the map reader takes them
    assert run.load_reader("map_device_s_per_sweep")(
        _reading(_profile(fair=False))) == pytest.approx(80e-9)
    assert run.load_reader("map_device_s_per_sweep")(r) == \
        pytest.approx(40e-9)


def test_counters():
    r = _reading(outputs=_outputs())
    # unit 1: 3 evictions, unit 2: 4
    assert run.load_reader("fair_evicted_per_sweep")(r) == 3.5
    # FELARE's rows only: (4 + 3 + 5 + 0) / (8 + 6 + 10 + 10)
    assert run.load_reader("suffered_map_share")(r) == pytest.approx(12 / 34)


def test_nothing_to_read_reads_none():
    # the harness as it stands hands none of profile, window, outputs
    bare = types.SimpleNamespace(units=3, jit_host_s=1.0,
                                 traced=dict(whole=True))
    assert all(run.load_reader(n)(bare) is None for n in NAMES)
    # a program without the scope and the counters (the parent's outputs)
    old = [({}, {k: v for k, v in m.items()
                 if k not in ("suffered_maps", "fair_evicted")})
           for _, m in _outputs()]
    r = _reading(_profile(fair=False), outputs=old)
    assert all(run.load_reader(n)(r) is None for n in NAMES)
    # a mix without a fairness policy: counters all 0
    zero = [({}, {k: np.zeros_like(v) if k != "steps" else v
                  for k, v in m.items()}) for _, m in _outputs()]
    r = _reading(outputs=zero)
    assert run.load_reader("suffered_map_share")(r) is None
    assert run.load_reader("fair_evicted_per_sweep")(r) == 0.0
    # a trace that misses part of the unit
    part = _reading(_profile(), whole=False)
    assert run.load_reader("fairness_device_s_per_sweep")(part) is None


def test_the_recorded_cpu_trace_reads_no_fairness_stage():
    from conftest import ROOT

    path = str(ROOT / "bench" / "tests" / "data" / "cpu_unit.xplane.pb")
    p = st.load(path, device_prefix="/host:CPU", ops_line="python")
    lo, hi = next((s, e) for n, s, e, _ in p.ops["/host:CPU"]
                  if n == "bench_unit")
    r = _reading(p, window=(lo, hi))
    assert run.load_reader("fairness_device_s_per_sweep")(r) is None
