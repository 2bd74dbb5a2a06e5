"""The trace reduction, on intervals by hand and on a small trace
recorded on the CPU (``data/cpu_unit.xplane.pb``: one ``bench_unit``
span holding three jitted calls, each in ``simulate_sweep`` and
``wait_device`` spans)."""
from conftest import ROOT

from bench import trace_reduce as tr

CPU_TRACE = ROOT / "bench" / "tests" / "data" / "cpu_unit.xplane.pb"
CPU_OPS = "tf_XLAPjRtCpuClient"  # the CPU client's op thread


def test_merge_and_gaps_by_hand():
    merged = tr.merge([(5, 8), (0, 2), (1, 3), (7, 9), (20, 30)], 0, 25)
    assert merged == [[0, 3], [5, 9], [20, 25]]
    assert tr.total(merged) == 12
    spans = [("simulate_sweep", 0, 4), ("wait_device", 4, 30)]
    gaps = tr.idle_gaps(merged, 0, 25, spans)
    assert gaps == [["wait_device", 11e-9], ["simulate_sweep", 2e-9]]


def test_recorded_cpu_trace():
    t = tr.load(str(CPU_TRACE), device_prefix="/host:CPU")
    lo, hi = tr.host_span(t, "bench_unit")
    assert hi > lo
    busy = tr.busy(t, lo, hi, line=CPU_OPS)["/host:CPU"]
    assert 0 < tr.total(busy) < hi - lo
    ops = dict(tr.top_ops(t, lo, hi, line=CPU_OPS))
    assert any(n.startswith("dot_general") for n in ops)
    spans = [ev for ev in t.host if ev[0] in ("simulate_sweep",
                                              "wait_device")]
    assert len(spans) == 6
    gaps = tr.idle_gaps(busy, lo, hi, spans)
    assert gaps and all(s > 0 for _, s in gaps)
    assert sum(s for _, s in gaps) <= (hi - lo - tr.total(busy)) / 1e9 + 1e-12
    mod, names = tr.module_time(t, "dot_general", lo, hi, line=CPU_OPS)
    assert names and 0 < mod["/host:CPU"] <= tr.total(busy)


def test_dropped_buffers_end_the_window():
    ops = [("%while.1", 10, 40), ("%fusion.2", 50, 60)]
    t = tr.Trace(devices={"/device:TPU:0": {
        tr.OPS_LINE: ops, "XLA TraceMe": [(tr.DROPPED, 45, 200)]}}, host=[])
    assert tr.dropped_from(t, 0, 100) == 45
    assert tr.dropped_from(t, 0, 44) is None
    t.devices["/device:TPU:0"]["XLA TraceMe"] = []
    assert tr.dropped_from(t, 0, 100) is None


def test_readers_need_a_whole_unit():
    import types

    from bench import run

    traced = dict(window_s=10.0, whole=True, busy_s={"d0": 8.0},
                  sweep_s={"d0": 7.5}, sweep_programs=["jit_run_all(1)"])
    r = types.SimpleNamespace(units=3, jit_host_s=6.0, traced=traced)
    assert abs(run.load_reader("device_idle_share")(r) - 0.2) < 1e-12
    assert run.load_reader("sim_device_s_per_sweep")(r) == 7.5
    assert run.load_reader("jit_host_s_per_sweep")(r) == 2.0
    traced["whole"] = False
    assert run.load_reader("device_idle_share")(r) is None
    assert run.load_reader("sim_device_s_per_sweep")(r) is None
