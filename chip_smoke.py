#!/usr/bin/env python3
"""Smoke test of the sweep engine and its Pallas decision kernels on a TPU.

Drives the normal entry point, ``repro.experiments.run_sweep``, at the
paper's Sec. VI scale and at a 128-machine federation, and checks what
comes out by the repo's own means:

  device     the default device is a TPU and every Pallas kernel compiles
             (``REPRO_PALLAS_INTERPRET`` unset or 0, autodetect says so);
  paper      ``paper`` 4x4 fleet, rates 2-8, 30 traces x 2000 tasks, the
             five paper heuristics: task conservation per trace and type
             (which also catches a loop stopped by the step cap), then the
             same spec with ``use_pallas_map`` -- every policy wrapped as a
             compiled ``FusedMapPolicy`` and every metrics leaf
             byte-equal to the lax run;
  federation ``paper_x32`` (128 machines, 32 sites), bursty traffic,
             ``fair_spill`` dispatch, ELARE/FELARE: conservation, the
             dispatcher's compiled balance scan, lax == fused byte for byte;
  eq3        the fairness monitor against IEEE float32 (``--eq3``, below);
  oracle     dyadic-rounded traces generated on the chip (``paper``: 2 x
             2000 tasks, MM/ELARE/FELARE; ``paper_x32``: 1 x 500 tasks,
             FELARE/``fair_spill``, cut to keep the run short), simulated
             there and by the plain-Python oracle ``repro.core.pyengine``
             in a host thread while the sweeps above run: per-type
             counters equal, energies and makespan within 1e-3.

``--eq3`` runs only the Eq. 3 check: the program's fairness monitor on
the device against numpy's IEEE float32, the rule of ``bench/reference.py``
(the completion rate of every count pair 0 <= c <= a <= 2048, the root on
10**6 seeded values and every variance below, f32 add and multiply on 10**6
seeded pairs, ``equations.fairness_limit`` at f = 1.5 on the seeded
tuples' rates, and ``fairness.suffered_types`` on every equal-pairs tuple
with arrivals <= 64 and on 10**5 seeded tuples at f = 0.5, 1, 2). It
prints each disagreement count and fails unless all are 0. It reads only
``fairness.completion_rates`` and ``suffered_types``,
``equations.fairness_limit`` and ``equations.sqrt_f32`` where the program
has it (else ``jnp.sqrt``), so it runs against an older program too.

``--four-chips`` runs only the sharded sweep: the federation spec (lax,
with the ``task_log`` observer) through ``run_sweep(shard=True)`` over a
4-device mesh, against the unsharded run in the same process, every
metrics and observer leaf byte for byte.

The last line of standard output is ``{"ok": true, "device": {...}}`` only
when every check held; any failure exits nonzero without it. The printed
seconds are information, not benchmark metrics.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chips    # four chips
    python chip_smoke.py --eq3           # the Eq. 3 check alone
    JAX_PLATFORMS=cpu python chip_smoke.py --cpu-rehearsal
        # tiny sizes on the CPU, kernels interpreted; never reports ok
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
import traceback
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

FULL = dict(paper_reps=30, paper_n=2000, fed_reps=8, fed_n=2000,
            fed_rates=(64.0, 192.0), oracle_paper_n=2000, oracle_fed_n=500)
REHEARSAL = dict(paper_reps=2, paper_n=60, fed_reps=2, fed_n=80,
                 fed_rates=(64.0, 192.0), oracle_paper_n=60,
                 oracle_fed_n=60)
ORACLE_RTOL = 1e-3  # tests/test_engine.py's energy/makespan tolerance


class CheckFailed(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise CheckFailed(msg)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def _leaves(tree):
    import jax
    import numpy as np

    return [np.asarray(x) for x in jax.tree.leaves(tree)]


def _byte_equal(phase: str, what: str, ref, got) -> None:
    """Every leaf of ``got`` has the dtype, shape and bytes of ``ref``."""
    la, lb = _leaves(ref), _leaves(got)
    check(la and len(la) == len(lb),
          f"{what}: {len(la)} vs {len(lb)} leaves")
    bad = []
    for i, (a, b) in enumerate(zip(la, lb)):
        if a.dtype != b.dtype or a.shape != b.shape:
            bad.append(f"leaf {i}: {a.dtype}{a.shape} vs {b.dtype}{b.shape}")
        elif a.tobytes() != b.tobytes():
            n = int((a != b).sum())
            bad.append(f"leaf {i} {a.dtype}{a.shape}: {n} elements differ")
    check(not bad, f"{what}: " + "; ".join(bad))
    nbytes = sum(a.nbytes for a in la)
    say(phase, f"{what}: {len(la)} leaves, {nbytes} bytes byte-equal")


def _conservation(phase: str, result, n_tasks: int) -> None:
    """completed + missed + cancelled == arrived per trace and type, and
    every trace admitted all of its tasks."""
    import numpy as np

    m = result.metrics
    total = m.completed_by_type + m.missed_by_type + m.cancelled_by_type
    lost = int(np.abs(total - m.arrived_by_type).sum())
    check(lost == 0, f"conservation broken: {lost} tasks unaccounted")
    arrived = m.arrived_by_type.sum(-1)
    short = int((arrived != n_tasks).sum())
    check(short == 0,
          f"{short} traces arrived != {n_tasks} tasks (step cap hit?)")
    rates = result.completion_rate_traces.mean(axis=(1, 2))
    per_h = ", ".join(f"{h} {r:.4f}"
                      for h, r in zip(result.heuristics, rates))
    say(phase, f"conservation holds on {arrived.size} traces; "
               f"mean on-time share: {per_h}")


def _timed_sweep(phase: str, label: str, spec, **kw):
    """Run the sweep once and print its cold seconds (compile included).

    One call only: at full size a second call costs about as much as the
    first, and both would not fit the script's time limit."""
    from repro.experiments import run_sweep

    t0 = time.perf_counter()
    result = run_sweep(spec, **kw)  # numpy leaves: the device is done
    say(phase, f"{label}: {spec.n_simulations} simulations, cold "
               f"{time.perf_counter() - t0} s (information, not a metric)")
    return result


def _check_fused(phase: str, spec, expect_interpret: bool) -> None:
    """The runner wraps every policy (and, on a federation, the
    dispatcher's balance scan) in the fused kernels, compiled -- or
    interpreted, in a rehearsal."""
    from repro.core.policy.fused import FusedMapPolicy
    from repro.experiments import runner
    from repro.kernels.map_fused import balance_scan

    pols = runner._select_fns(spec.heuristics, spec.use_pallas_phase1,
                              spec.use_pallas_map)
    for name, pol in zip(spec.heuristics, pols):
        check(isinstance(pol, FusedMapPolicy),
              f"{name} was not wrapped: {type(pol).__name__}")
        check(pol.interpret is expect_interpret,
              f"{name}: FusedMapPolicy.interpret={pol.interpret}")
    say(phase, f"policies {', '.join(spec.heuristics)} run as "
               f"FusedMapPolicy(interpret={expect_interpret})")
    disp = runner._resolve_dispatcher(spec.dispatcher, spec.use_pallas_map)
    if spec.resolve_system().n_sites > 1:
        impl = getattr(disp, "balance_impl", None)
        check(isinstance(impl, functools.partial)
              and impl.func is balance_scan
              and impl.keywords == {"interpret": expect_interpret},
              f"dispatcher {spec.dispatcher} balance_impl={impl!r}")
        say(phase, f"dispatcher {spec.dispatcher} runs balance_scan"
                   f"(interpret={expect_interpret})")


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------
def phase_device(rehearsal: bool, n_chips: int) -> dict:
    import jax
    import jaxlib

    from repro.kernels import pallas_backend

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    say("device", f"platform={dev['platform']} kind={dev['kind']} "
                  f"count={dev['count']} jax={jax.__version__} "
                  f"jaxlib={jaxlib.__version__}")
    env = os.environ.get(pallas_backend.ENV_VAR)
    interp = pallas_backend.default_interpret()
    say("device", f"{pallas_backend.ENV_VAR}={env!r} "
                  f"default_interpret()={interp}")
    check(dev["count"] >= n_chips,
          f"{n_chips} devices wanted, {dev['count']} visible")
    if not rehearsal:
        check(dev["platform"] == "tpu", f"no TPU: platform {dev['platform']}")
        check(env != "1", f"{pallas_backend.ENV_VAR}=1 forces the interpreter")
        check(interp is False, "Pallas kernels would run interpreted")
    return dev


def _paper_spec(sz):
    from repro.experiments import SweepSpec
    from repro.experiments.spec import DEFAULT_HEURISTICS, DEFAULT_RATES

    return SweepSpec(system="paper", rates=DEFAULT_RATES,
                     reps=sz["paper_reps"], n_tasks=sz["paper_n"],
                     heuristics=DEFAULT_HEURISTICS)


def _federation_spec(sz, **kw):
    from repro.experiments import SweepSpec

    return SweepSpec(system="paper_x32", scenario="bursty",
                     dispatcher="fair_spill", heuristics=("ELARE", "FELARE"),
                     rates=sz["fed_rates"], reps=sz["fed_reps"],
                     n_tasks=sz["fed_n"], **kw)


def _lax_vs_fused(phase: str, spec, expect_interpret: bool) -> None:
    from repro.experiments import spec as spec_mod

    lax = _timed_sweep(phase, "lax", spec)
    _conservation(phase, lax, spec.n_tasks)
    fused_spec = spec_mod.replace(spec, use_pallas_map=True)
    _check_fused(phase, fused_spec, expect_interpret)
    fused = _timed_sweep(phase, "fused", fused_spec)
    _conservation(phase, fused, spec.n_tasks)
    _byte_equal(phase, "fused == lax", (lax.metrics, lax.aux),
                (fused.metrics, fused.aux))


def phase_paper(sz, rehearsal: bool) -> None:
    _lax_vs_fused("paper", _paper_spec(sz), rehearsal)


def phase_federation(sz, rehearsal: bool) -> None:
    spec = _federation_spec(sz)
    check(spec.resolve_system().n_machines == 128,
          "paper_x32 is not 128 machines")
    _lax_vs_fused("federation", spec, rehearsal)


def _dyadic(x):
    import numpy as np

    return (np.round(np.asarray(x) * 64) / 64).astype(np.float32)


def _dyadic_traces(scenario: str, system, rate: float, n: int, k: int,
                   seed: int):
    """k traces made on the default device, rounded to multiples of 1/64
    on the host (as tests/test_engine.py does) so the f64 oracle and the
    f32 engine do the same arithmetic."""
    import jax
    import numpy as np

    from repro import scenarios

    stacked = scenarios.get(scenario).stack(
        jax.random.PRNGKey(seed), (rate,), k, n, system.eet)
    host = jax.tree.map(lambda x: np.asarray(x)[0], stacked)
    return host._replace(arrival=_dyadic(host.arrival),
                         deadline=_dyadic(host.deadline),
                         exec_actual=_dyadic(host.exec_actual))


# fleet, scenario, rate (None: the federation cell's first), size key,
# traces, heuristics, dispatcher
ORACLE_CASES = (
    ("paper", "poisson", 4.0, "oracle_paper_n", 2, ("MM", "ELARE", "FELARE"),
     None),
    ("paper_x32", "bursty", None, "oracle_fed_n", 1, ("FELARE",),
     "fair_spill"),
)


def start_oracle(sz, pool) -> list:
    """Make the oracle's traces on the chip and start the plain-Python
    oracle on them in a host thread, so that its host time overlaps the
    device sweeps of the phases that follow."""
    import jax

    from repro import scenarios
    from repro.core import pyengine

    def run(host, system, k, heuristics, dispatcher):
        t0 = time.perf_counter()
        refs = [[pyengine.simulate(jax.tree.map(lambda x: x[b], host),
                                   system, h, dispatcher=dispatcher)
                 for b in range(k)] for h in heuristics]
        return refs, time.perf_counter() - t0

    cases = []
    for fleet, scenario, rate, n_key, k, heuristics, dispatcher in (
            ORACLE_CASES):
        system = scenarios.get_fleet(fleet).build()
        host = _dyadic_traces(scenario, system, rate or sz["fed_rates"][0],
                              sz[n_key], k, seed=11)
        refs = pool.submit(run, host, system, k, heuristics, dispatcher)
        cases.append((system, host, heuristics, dispatcher, refs))
    say("oracle", f"{len(cases)} trace sets made; the oracle runs on the "
                  "host meanwhile")
    return cases


def phase_oracle(cases) -> None:
    """The chip's engine on the oracle's traces: per-type counters equal,
    energies and makespan within ORACLE_RTOL."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.experiments.runner import simulate_sweep

    check(cases, "the oracle's traces were not made")
    counters = ("completed_by_type", "missed_by_type", "cancelled_by_type",
                "arrived_by_type")
    reals = ("energy_dynamic", "energy_wasted", "makespan")
    for system, host, heuristics, dispatcher, pending in cases:
        m = simulate_sweep(jax.tree.map(jnp.asarray, host), system,
                           heuristics, dispatcher=dispatcher)
        m = jax.tree.map(np.asarray, m)
        refs, host_s = pending.result()
        worst = 0.0
        for h_i, h in enumerate(heuristics):
            for b, ref in enumerate(refs[h_i]):
                where = f"{h} trace {b}"
                for c in counters:
                    got = getattr(m, c)[h_i, b]
                    check(np.array_equal(got, ref[c]),
                          f"{where} {c}: chip {got.tolist()} "
                          f"oracle {np.asarray(ref[c]).tolist()}")
                for r in reals:
                    got, want = float(getattr(m, r)[h_i, b]), float(ref[r])
                    err = abs(got - want) / max(abs(want), 1e-12)
                    check(err <= ORACLE_RTOL,
                          f"{where} {r}: chip {got} oracle {want} rel {err}")
                    worst = max(worst, err)
        k, n = host.arrival.shape
        say("oracle", f"{system.n_machines} machines, {k} x {n} tasks x "
                      f"{','.join(heuristics)}"
                      f"{' / ' + dispatcher if dispatcher else ''}: counters "
                      f"equal, worst energy/makespan rel err {worst} "
                      f"(oracle {host_s:.1f} s on the host)")


EQ3_FACTORS = (0.5, 1.0, 2.0)
EQ3_TYPES = 4


def eq3_equal_pairs(max_arrivals: int = 64):
    """Every count tuple whose rates are p, q, p, q in some arrangement,
    p < q, arrivals <= ``max_arrivals``: mean - std equals p exactly, so
    the mask rests on the last bit. The float32 quotient depends on c / a
    alone, so each rate comes once in lowest terms and once as its largest
    multiple (1/3 as 1 of 3 and 21 of 63). Returns (T, 4) int32 (c, a)."""
    import math

    import numpy as np

    fr = sorted(((c, a) for a in range(1, max_arrivals + 1)
                 for c in range(a + 1) if math.gcd(c, a) == 1),
                key=lambda x: x[0] / x[1])
    low = np.asarray(fr, np.int32)
    high = low * (max_arrivals // low[:, 1:2])
    i, j = np.triu_indices(len(fr), k=1)
    cs, as_ = [], []
    for slots in ((0, 1), (0, 2), (0, 3), (1, 2), (1, 3), (2, 3)):
        other = [t for t in range(EQ3_TYPES) if t not in slots]
        c = np.empty((len(i), EQ3_TYPES), np.int32)
        a = np.empty_like(c)
        for t, rep, idx in ((slots[0], low, i), (slots[1], high, i),
                            (other[0], low, j), (other[1], high, j)):
            c[:, t], a[:, t] = rep[idx, 0], rep[idx, 1]
        cs.append(c)
        as_.append(a)
    return np.concatenate(cs), np.concatenate(as_)


def eq3_seeded(n: int = 10**5, seed: int = 15, max_arrivals: int = 2000):
    """n seeded tuples: arrivals uniform in [0, max_arrivals], completions
    uniform in [0, arrivals]."""
    import numpy as np

    rng = np.random.default_rng(seed)
    a = rng.integers(0, max_arrivals + 1, (n, EQ3_TYPES)).astype(np.int32)
    c = np.floor(rng.random((n, EQ3_TYPES)) * (a + 1)).astype(np.int32)
    return c, a


def eq3_numpy(c, a, f):
    """IEEE float32 Eq. 3 over rows of counts, as ``bench/reference.py``
    rounds it: ``R(R(c) / R(a))`` (1.0 without arrivals), numpy's float32
    ``mean`` and ``std``, ``max(R(mu - R(f * sigma)), 0)``, suffered where
    ``cr <= eps`` and arrivals >= 1. Returns (rates, variances, mask)."""
    import numpy as np

    f32 = np.float32
    cr = np.where(a > 0, c.astype(f32) / np.maximum(a, 1).astype(f32),
                  f32(1.0))
    mu = np.mean(cr, axis=1, dtype=f32)
    var = np.var(cr, axis=1, dtype=f32)
    eps = np.maximum(mu - f32(f) * np.sqrt(var), f32(0.0))
    return cr, var, (cr <= eps[:, None]) & (a >= 1)


def phase_eq3(rehearsal: bool) -> dict:
    """Disagreements of the device's Eq. 3 with numpy's IEEE float32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import equations, fairness

    f32 = np.float32
    top, n_rand = (64, 10**4) if rehearsal else (2048, 10**6)
    rng = np.random.default_rng(2206)
    counts = {}

    def bits(x):
        return np.asarray(x, f32).view(np.int32)

    g = np.arange(top + 1)
    c, a = np.meshgrid(g, g, indexing="ij")
    keep = (c <= a) & (a > 0)
    c, a = c[keep].astype(np.int32), a[keep].astype(np.int32)
    got = jax.jit(fairness.completion_rates)(c, a)
    counts["ratio"] = int((bits(got) != bits(c.astype(f32)
                                                / a.astype(f32))).sum())

    x = rng.random(n_rand).astype(f32)
    y = (rng.random(n_rand) * 4).astype(f32)
    counts["add"] = int((bits(jax.jit(jnp.add)(x, y)) != bits(x + y)).sum())
    counts["multiply"] = int((bits(jax.jit(jnp.multiply)(x, y))
                              != bits(x * y)).sum())

    tuples = {"equal_pairs": eq3_equal_pairs(),
              "seeded": eq3_seeded(10**4 if rehearsal else 10**5)}
    root = getattr(equations, "sqrt_f32", jnp.sqrt)
    v = [np.exp2(rng.uniform(-40.0, 0.0, n_rand)).astype(f32)]
    v += [eq3_numpy(c, a, 1.0)[1] for c, a in tuples.values()]
    v = np.concatenate(v)
    counts["root"] = int((bits(jax.jit(root)(v)) != bits(np.sqrt(v))).sum())

    cr = eq3_numpy(*tuples["seeded"], 1.0)[0]
    limit = jax.jit(jax.vmap(equations.fairness_limit, (0, None)),
                    static_argnums=1)(cr, 1.5)
    mu = np.mean(cr, axis=1, dtype=f32)
    eps = np.maximum(mu - f32(1.5) * np.std(cr, axis=1, dtype=f32), f32(0))
    counts["limit.seeded.f1.5"] = int((bits(limit) != bits(eps)).sum())

    mask = jax.jit(jax.vmap(fairness.suffered_types, (0, 0, None)),
                   static_argnums=2)
    for name, (c, a) in tuples.items():
        for f in EQ3_FACTORS:
            got = np.asarray(mask(c, a, f))
            want = eq3_numpy(c, a, f)[2]
            counts[f"suffered.{name}.f{f}"] = int((got != want).any(1).sum())
    say("eq3", json.dumps(counts))
    bad = {k: v for k, v in counts.items() if v}
    check(not bad, f"Eq. 3 disagrees with IEEE float32: {bad}")
    return counts


def phase_four_chips(sz) -> None:
    import jax

    from repro.distributed import sharding

    mesh = sharding.sweep_mesh()
    check(mesh is not None and mesh.devices.size == 4,
          f"sweep mesh is {mesh} over {len(jax.devices())} devices, not 4")
    say("four-chips", f"mesh {dict(mesh.shape)} over "
                      f"{[d.id for d in mesh.devices.flat]}")
    spec = _federation_spec(sz, observers=("task_log",))
    ref = _timed_sweep("four-chips", "unsharded", spec)
    sharded = _timed_sweep("four-chips", "sharded", spec, shard=True)
    _conservation("four-chips", sharded, spec.n_tasks)
    _byte_equal("four-chips", "sharded == unsharded",
                (ref.metrics, ref.aux), (sharded.metrics, sharded.aux))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded sweep on a 4-device mesh")
    ap.add_argument("--eq3", action="store_true",
                    help="run only the Eq. 3 check against IEEE float32")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU with interpreted kernels; "
                         "never reports ok")
    args = ap.parse_args(argv)
    sz = REHEARSAL if args.cpu_rehearsal else FULL

    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    n_chips = 4 if args.four_chips else 1
    try:
        dev = phase_device(args.cpu_rehearsal, n_chips)
    except CheckFailed as e:
        print(f"FAIL device: {e}", flush=True)
        return 1
    pool = ThreadPoolExecutor(max_workers=1)
    oracle = []
    if args.four_chips:
        phases = [("four-chips", lambda: phase_four_chips(sz))]
    elif args.eq3:
        phases = [("eq3", lambda: phase_eq3(args.cpu_rehearsal))]
    else:
        phases = [("oracle traces",
                   lambda: oracle.extend(start_oracle(sz, pool))),
                  ("eq3", lambda: phase_eq3(args.cpu_rehearsal)),
                  ("paper", lambda: phase_paper(sz, args.cpu_rehearsal)),
                  ("federation",
                   lambda: phase_federation(sz, args.cpu_rehearsal)),
                  ("oracle", lambda: phase_oracle(oracle))]
    failed = []
    with pool:
        for name, run in phases:
            t0 = time.perf_counter()
            try:
                run()
                say(name, f"passed in {time.perf_counter() - t0:.1f} s")
            except Exception as e:  # report every phase, then fail the run
                traceback.print_exc()
                print(f"FAIL {name}: {type(e).__name__}: {e}", flush=True)
                failed.append(name)
    if failed:
        print(f"failed phases: {', '.join(failed)}", flush=True)
        return 1
    if args.cpu_rehearsal:
        print("rehearsal passed; a CPU run is not a chip run, so no ok",
              flush=True)
        return 2
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
