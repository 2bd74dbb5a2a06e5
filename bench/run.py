#!/usr/bin/env python3
"""The benchmark command: warm Monte-Carlo sweeps of the engine on a TPU.

    python3 bench/run.py --workload paper.poisson --seed 12345 \\
        --seconds 30 --trace 0

A cell of ``BENCHMARK.json`` names a fleet (``bench/configs/``) and a
traffic mix (``bench/traffic/``). A *sweep unit* is what a user submits:
the traffic's rates x replicate traces, made on the device by
``bench/generators`` from the seed and the unit's index, simulated under
every heuristic by the program's ``simulate_sweep`` in one call, and
waited for.
The load is a closed loop: the next unit starts when the last returns.

Set-up (``setup_s``, from process start): JAX's persistent compile cache
at ``<checkout>/.jax_cache``, the fleet, the generator and one warm unit
(index 0). The window then runs units 1, 2, ... back to back until
``--seconds`` have passed and finishes the unit in flight;
``sim_tasks_per_s`` is heuristics x traces x tasks of every window unit
over the time from the first unit's start to the last one's end.

With ``--trace 1`` the result carries the per-layer metrics instead: the
profiler records window unit 2 (``bench_unit``) from the host work that
precedes its device program to its end, and ``bench/metrics/`` readers
reduce it.

After the window, ``bench/check.py`` decides ``correct`` against the plain
reference; its numbers and limits end standard error and the result line.
The last line of standard output is the result, one JSON object. No TPU,
fewer chips than the cell asks, or no program to run: a message on
standard error, a nonzero exit and no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
TRACED_UNIT = 2
# the name the program gives the sweep's jitted function (runner.run_all)
SWEEP_PROGRAM = "run_all"
# JAX's host-side compile path; backend_compile_duration spans the compile
# cache's read, so cache_retrieval_time_sec is not listed again
JIT_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
HOST_SPANS = ("generate_traces", "simulate_sweep", "wait_device")


class Refused(Exception):
    """The run cannot measure this cell here; exit nonzero, no result."""


def load_cell(name: str) -> dict:
    """The cell's entry, fleet, traffic and metric entries, by name."""
    try:
        bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    except FileNotFoundError:
        raise Refused(f"no BENCHMARK.json at {ROOT}") from None
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r}; cells: {sorted(cells)}")
    cell = cells[name]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    traffic = BENCH / "traffic" / f"{cell['traffic']}.json"

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return dict(cell=cell,
                fleet=json.loads((ROOT / config["file"]).read_text()),
                traffic=json.loads(traffic.read_text()),
                end_to_end=mine(bench["end_to_end"]),
                per_layer=mine(bench["per_layer"]))


def import_program():
    """The program under test, from this checkout's ``src`` only."""
    src = ROOT / "src"
    if not (src / "repro" / "__init__.py").is_file():
        raise Refused(f"no program at {src}")
    sys.path.insert(0, str(src))
    import repro

    if Path(repro.__file__).resolve().parents[1] != src:
        raise Refused(f"repro imported from {repro.__file__}, not {src}")


def devices_for(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise Refused(f"{chips} chips wanted, {len(devs)} visible")
    return devs


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def system_spec(fleet: dict):
    import numpy as np

    from repro.core.types import SystemSpec

    return SystemSpec(eet=np.asarray(fleet["eet"], np.float32),
                      p_dyn=np.asarray(fleet["p_dyn"], np.float32),
                      p_idle=np.asarray(fleet["p_idle"], np.float32),
                      queue_size=int(fleet["queue_size"]),
                      fairness_factor=float(fleet["fairness_factor"]))


def reduce_trace(used: list) -> tuple:
    """(traced summary for the readers, breakdown) of the traced unit.

    The trace holds the whole unit unless the TPU dropped trace buffers;
    the window then ends there."""
    from bench import trace_reduce as tr

    t = tr.load(tr.find(str(TRACE_DIR)))
    lo, hi = tr.host_span(t, "bench_unit")
    drop = tr.dropped_from(t, lo, hi)
    whole = drop is None
    if not whole:
        hi = drop
    line = tr.OPS_LINE
    busy = tr.busy(t, lo, hi, line)
    if not any(busy.values()):  # programs traced whole, without their ops
        line = tr.MODULES_LINE
        busy = tr.busy(t, lo, hi, line)
    sweep_ns, names = tr.module_time(t, SWEEP_PROGRAM, lo, hi)
    devs = sorted(busy)[:len(used)]
    traced = dict(window_s=(hi - lo) / 1e9, whole=whole,
                  busy_s={d: tr.total(busy[d]) / 1e9 for d in devs},
                  sweep_s={d: sweep_ns[d] / 1e9 for d in devs},
                  sweep_programs=names)
    spans = [ev for ev in t.host if ev[0] in HOST_SPANS]
    breakdown = dict(device_ops=tr.top_ops(t, lo, hi, line=line),
                     idle_gaps=tr.idle_gaps(busy[devs[0]], lo, hi, spans)
                     if devs else [])
    return traced, breakdown


def run_cell(c: dict, seed: int, seconds: float, trace: bool,
             t0: float = T0, require_tpu: bool = True, warm: bool = True,
             keep: list | None = None) -> dict:
    """Set up, run the window, check; returns the result object.

    ``warm=False`` skips the warm unit (a process that ran the cell
    already); ``keep`` receives the window's units as host arrays."""
    import jax
    import numpy as np

    from bench import check, trace_reduce
    from bench.generators import make_unit_generator

    cell, fleet, traffic = c["cell"], c["fleet"], c["traffic"]
    devs = devices_for(int(cell["chips"]), require_tpu)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from repro.compile_cache import enable_compile_cache
    from repro.core.types import Trace
    from repro.experiments.runner import simulate_sweep

    enable_compile_cache()
    system = system_spec(fleet)
    heuristics = tuple(traffic["heuristics"])
    used = devs[:1]
    gen = make_unit_generator(traffic, fleet["eet"])
    seed %= 2**64
    words = (np.uint32(seed & 0xFFFFFFFF), np.uint32(seed >> 32))
    annotate = jax.profiler.TraceAnnotation

    def unit(u: int):
        with annotate("generate_traces"):
            traces = gen(*words, np.uint32(u))
        with annotate("simulate_sweep"):
            out = simulate_sweep(Trace(**traces), system, heuristics)
        with annotate("wait_device"):
            jax.block_until_ready(out)
        return traces, out

    if warm:
        unit(0)
    setup_s = time.perf_counter() - t0

    jit_spans, in_window = [], [False]

    def on_duration(event, secs, **_):
        if in_window[0] and event in JIT_EVENTS:
            now = time.perf_counter()
            jit_spans.append((now - secs, now))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    units = []
    in_window[0] = True
    t_start = time.perf_counter()
    while True:
        u = len(units) + 1
        if trace and u == TRACED_UNIT:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            with annotate("bench_unit"):
                units.append(unit(u))
            jax.profiler.stop_trace()
        else:
            units.append(unit(u))
        if (time.perf_counter() - t_start >= seconds
                and (not trace or u >= TRACED_UNIT)):
            break
    t_end = time.perf_counter()
    in_window[0] = False

    stats = [d.memory_stats() or {} for d in used]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    host = [(jax.device_get(tr), jax.device_get(out._asdict()))
            for tr, out in units]
    del units
    gc.collect()
    if keep is not None:
        keep.extend(host)

    n_units = len(host)
    B = len(traffic["rates"]) * int(traffic["reps"])
    lanes = n_units * len(heuristics) * B
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    result = {}
    if trace:
        traced, breakdown = reduce_trace(used)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jit_s = trace_reduce.total(trace_reduce.merge(jit_spans, t_start,
                                                      t_end))
        reading = types.SimpleNamespace(units=n_units, jit_host_s=jit_s,
                                        traced=traced)
        metrics = {}
        for m in c["per_layer"]:
            v = load_reader(m["name"])(reading)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = (sum(traced["busy_s"].values())
                            / max(len(traced["busy_s"]), 1))
        device["window_s"] = traced["window_s"]
        result["breakdown"] = breakdown
    else:
        values = {"setup_s": setup_s,
                  "sim_tasks_per_s": lanes * int(traffic["n_tasks"])
                  / (t_end - t_start)}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in c["end_to_end"]}
    verdict = check.compare(host, fleet, traffic, seed)
    return {"correct": verdict["correct"], "attempted": lanes,
            "failed": verdict["lanes_bad"] + verdict["failed_sample"],
            "metrics": metrics, "device": device, **result,
            "check": verdict["numbers"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the checkout's own compile cache, whatever the environment says: the
    # path is part of the cache key, and nothing is shared outside it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(ROOT))
    try:
        c = load_cell(args.workload)
        import_program()
        result = run_cell(c, args.seed, args.seconds, bool(args.trace))
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, v in result["check"].items():
        print(f"check {name} {v['value']} limit {v['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
