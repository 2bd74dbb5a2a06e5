"""The stage reduction and the loop counters, on events and outputs built
by hand."""
import types

import numpy as np
import pytest

from bench import run
from bench import stage_trace as st

DEV = "/device:TPU:0"
SWEEP = "jit(run_all)/sweep.MM/vmap()/while"


def _profile():
    ops = [
        # a while loop holding its body: never counted itself
        ("%while.5 = ...", 100, 200, SWEEP),
        ("%fusion.1 = ...", 100, 130, f"{SWEEP}/body/engine.map/dot"),
        # the next-event scope inside the cond, under another name
        ("%fusion.2 = ...", 130, 140,
         f"{SWEEP}/body_pred/engine.next_event/reduce_min"),
        # innermost scope wins: a call inside the start stage
        ("%fusion.3 = ...", 140, 160,
         f"{SWEEP}/body/engine.start/jit(_where)/select_n"),
        # nested loop inside the body: its fusion is the leaf
        ("%while.6 = ...", 160, 190, f"{SWEEP}/body/engine.map/while"),
        ("%fusion.4 = ...", 160, 185, f"{SWEEP}/body/engine.map/while/add"),
        ("%copy.7 = ...", 190, 200, f"{SWEEP}/body/select_n"),  # unscoped
        # outside the sweep program: trace generation
        ("%fusion.8 = ...", 20, 60, "jit(gen)/add"),
        # after the traced unit
        ("%fusion.9 = ...", 400, 450, f"{SWEEP}/body/engine.map/dot"),
    ]
    modules = [("jit_gen(1)", 20, 60), ("jit_run_all(7)", 100, 200),
               ("jit_run_all(7)", 400, 450)]
    host = [("bench_unit", 0, 300), ("sweep.build", 70, 75),
            ("sweep.trace.MM", 76, 80), ("sweep.trace.ELARE", 80, 90),
            ("sweep.build", 310, 320)]
    return st.Profile(ops={DEV: ops}, modules={DEV: modules}, host=host)


def _reading(profile=None, outputs=None, whole=True):
    return types.SimpleNamespace(
        units=2, jit_host_s=0.0, traced=dict(whole=whole),
        profile=profile, window=(0, 300), outputs=outputs)


def test_leaves_keep_only_events_holding_no_other():
    names = [n for n, *_ in st.leaves(_profile().ops[DEV])]
    assert "%while.5 = ..." not in names and "%while.6 = ..." not in names
    assert set(names) == {f"%fusion.{i} = ..." for i in (1, 2, 3, 4, 8, 9)
                          } | {"%copy.7 = ..."}
    # equal intervals: one holds the other, the inner one is the leaf
    assert st.leaves([("a", 0, 5, ""), ("b", 0, 5, "")]) == [("b", 0, 5, "")]
    # neighbours whose stamps overlap without nesting are both leaves
    ops = [("a", 0, 5, ""), ("b", 4, 9, "")]
    assert st.leaves(ops) == ops


def test_stage_of_takes_the_innermost_engine_scope():
    assert st.stage_of(f"{SWEEP}/body/engine.start/jit(_where)/x") == "start"
    assert st.stage_of("a/engine.map/b/engine.admit/c") == "admit"
    assert st.stage_of(f"{SWEEP}/body/select_n") is None
    assert st.stage_of("") is None


def test_stage_seconds_count_leaf_self_time_in_the_sweep_program():
    s = st.stage_seconds(_profile(), 0, 300, st.SWEEP_PROGRAM)
    assert s == {"map": 55e-9, "next_event": 10e-9, "start": 20e-9,
                 None: 10e-9}
    # no time counted twice; the inner loop's own 5 ns outside its body's
    # ops is in no leaf
    assert sum(s.values()) == pytest.approx(95e-9)


def test_stage_readers_and_the_unscoped_share():
    r = _reading(_profile())
    read = {n: run.load_reader(n)(r) for n in (
        "next_event_device_s_per_sweep", "finalize_device_s_per_sweep",
        "admit_device_s_per_sweep", "map_device_s_per_sweep",
        "start_device_s_per_sweep", "unscoped_device_share")}
    assert read == pytest.approx({
        "next_event_device_s_per_sweep": 10e-9,
        "finalize_device_s_per_sweep": 0.0, "admit_device_s_per_sweep": 0.0,
        "map_device_s_per_sweep": 55e-9, "start_device_s_per_sweep": 20e-9,
        "unscoped_device_share": 10 / 95})


def test_host_span_readers():
    r = _reading(_profile())
    assert run.load_reader("build_host_s_per_sweep")(r) == pytest.approx(5e-9)
    assert run.load_reader("traces_per_sweep")(r) == 2


def _outputs():
    # two units of H=2 heuristics x B=3 lanes with unequal counts
    a = np.array([[10, 8, 6], [4, 4, 4]], np.int32)
    b = np.array([[12, 12, 12], [5, 3, 1]], np.int32)
    return [({}, {"steps": a}), ({}, {"steps": b})]


def test_loop_counters():
    r = _reading(outputs=_outputs())
    # unit 1: 10 + 4 = 14; unit 2: 12 + 5 = 17
    assert run.load_reader("loop_iters_per_sweep")(r) == 15.5
    ran = 3 * (14 + 17)
    used = (24 + 12) + (36 + 9)
    assert run.load_reader("idle_lane_share")(r) == pytest.approx(
        1 - used / ran)


def test_nothing_to_read_reads_none():
    names = ["next_event_device_s_per_sweep", "map_device_s_per_sweep",
             "unscoped_device_share", "build_host_s_per_sweep",
             "traces_per_sweep", "loop_iters_per_sweep", "idle_lane_share"]
    # the harness as it stands hands none of the new fields
    bare = types.SimpleNamespace(units=3, jit_host_s=1.0,
                                 traced=dict(whole=True))
    assert all(run.load_reader(n)(bare) is None for n in names)
    # a program without the scopes, spans and steps output
    p = _profile()
    p.ops = {DEV: [(n, s, e, "jit(run_all)/while/body/add")
                   for n, s, e, _ in p.ops[DEV]]}
    p.host = [ev for ev in p.host if not ev[0].startswith("sweep.")]
    old = _reading(p, outputs=[({}, {"makespan": np.zeros((2, 3))})])
    assert all(run.load_reader(n)(old) is None for n in names)
    # a trace that misses part of the unit
    part = _reading(_profile(), whole=False)
    assert run.load_reader("map_device_s_per_sweep")(part) is None


def test_op_names_from_event_metadata(tmp_path):
    X = st.xspace_class()
    space = X()
    plane = space.planes.add(name=DEV)
    for key, name in ((1, "tf_op"), (2, "flops"),
                      (3, f"{SWEEP}/body/engine.start/add:")):
        plane.stat_metadata.add(key=key).value.name = name
    md = plane.event_metadata.add().value
    md.name = "%fusion.1 = ..."
    md.stats.add(metadata_id=2, str_value="12")
    md.stats.add(metadata_id=1, str_value=f"{SWEEP}/body/engine.map/dot:")
    md = plane.event_metadata.add().value  # the string kept by reference
    md.name = "%fusion.2 = ..."
    md.stats.add(metadata_id=1, ref_value=3)
    plane.event_metadata.add().value.name = "%copy.3 = ..."  # no op_name
    space.planes.add(name="/host:CPU")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    names = st.op_names(str(path))
    assert names == {DEV: {"%fusion.1 = ...": f"{SWEEP}/body/engine.map/dot:",
                           "%fusion.2 = ...": f"{SWEEP}/body/engine.start/add:"}}
    assert [st.stage_of(v) for v in names[DEV].values()] == ["map", "start"]


def test_a_recorded_trace_loads():
    from conftest import ROOT

    path = str(ROOT / "bench" / "tests" / "data" / "cpu_unit.xplane.pb")
    # the CPU's host plane read as the device, its python thread as ops
    p = st.load(path, device_prefix="/host:CPU", ops_line="python")
    assert "bench_unit" in [n for n, *_ in p.ops["/host:CPU"]]
    assert list(st.op_names(path, device_prefix="/host:CPU")) == ["/host:CPU"]
