"""Benchmark orchestrator: one function per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV per the harness contract, then a
human-readable block per figure.

  PYTHONPATH=src python -m benchmarks.run [--only fig4] [--full]

``--perf-out DIR`` instead runs the engine perf benchmarks (the hot
vmapped sweep with observers off/on, the federation compile/warm scaling
sweep over F, the tiered edge-cloud network sweep, and the lax-vs-fused
map-decision sweep over N x M) and appends a
``BENCH_<n>.json`` artifact under DIR
— one numbered file per run, so the directory accumulates the project's
wall-clock/compile-time trajectory over time. ``--perf-baseline PATH``
additionally compares the fresh warm times against a checked-in baseline
(``benchmarks/BENCH_1.json`` carries the current reference, including the
per-F federation rows) and *fails* — exit status 1, the blocking CI bench
step — when any warm time exceeds 1.5x its baseline.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import re
import time


def perf_vmapped_sweep(*, reps: int = 4, n_tasks: int = 300,
                       rates=(2.0, 4.0)) -> dict:
    """Wall-clock + compile time of the hot vmapped-sweep path.

    Measures ``engine.simulate_batch`` (the cached ``_simulate_jit``
    entry: cold call = trace+compile+run, warm call = run only) for
    ELARE over a (rates x reps) CRN trace stack, with observers off and
    with the timeline+task_log observers attached, plus one end-to-end
    ``run_sweep`` wall-clock for scale.
    """
    import jax

    from repro import experiments
    from repro.core import api, engine
    from repro.datapipe import synthetic

    system = api.paper_system()
    stacked = synthetic.trace_stack(
        jax.random.PRNGKey(0), tuple(rates), reps, n_tasks, system.eet
    )
    flat = jax.tree.map(
        lambda x: x.reshape((len(rates) * reps,) + x.shape[2:]), stacked
    )

    results = []
    for observers in ((), ("timeline", "task_log")):
        # fresh observer instances would share the jit cache across rounds;
        # the cache key includes the observers tuple, so off/on differ.
        t0 = time.perf_counter()
        out = engine.simulate_batch(flat, system, "ELARE",
                                    observers=observers)
        jax.block_until_ready(out)
        cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = engine.simulate_batch(flat, system, "ELARE",
                                    observers=observers)
        jax.block_until_ready(out)
        warm_s = time.perf_counter() - t0
        results.append({
            "observers": list(observers),
            "cold_s": round(cold_s, 4),
            "warm_s": round(warm_s, 4),
            "compile_s": round(cold_s - warm_s, 4),
        })

    spec = experiments.SweepSpec(
        rates=tuple(rates), reps=reps, n_tasks=n_tasks,
        heuristics=("MM", "ELARE", "FELARE"), seed=0,
    )
    t0 = time.perf_counter()
    experiments.run_sweep(spec)
    sweep_s = time.perf_counter() - t0

    return {
        "bench": "vmapped_sweep",
        "unix_time": round(time.time(), 1),
        "config": {"reps": reps, "n_tasks": n_tasks, "rates": list(rates),
                   "heuristic": "ELARE"},
        "simulate_batch": results,
        "run_sweep_3heuristics_s": round(sweep_s, 4),
    }


def perf_federation_scaling(*, site_counts=(1, 2, 8, 32), reps: int = 2,
                            n_tasks: int = 150, rates=(3.0,)) -> dict:
    """Compile/warm wall clock of the batched engine vs site count F.

    Per F, AOT-splits the batched simulator: ``trace_s`` (jaxpr trace +
    lowering), ``compile_s`` (XLA codegen), then a warm run of the
    compiled executable. The masked-vmap site loop (plus the
    block-diagonal reshape fast path for the uniform ``paper_xF`` fleets)
    keeps both flat in F — wider arrays, same program. The derived
    ``compile_ratio_f32_vs_f2`` (on trace+compile, the end-to-end cost of
    a fresh jit) is the ISSUE acceptance metric (<= 1.2, asserted
    wall-clock by ``tests/test_compile_flatness.py``). The F=1 row runs
    first and doubles as the jit/XLA init warmup, so later rows aren't
    credited for one-time setup the first row paid.

    Measured AOT (``jit(...).lower(flat).compile()``) rather than
    cold-minus-warm ``simulate_batch`` calls: first-run dispatch overhead
    pollutes the subtraction by several hundred ms at the large-F end.
    """
    import jax

    from repro import scenarios
    from repro.core import dispatch, engine, policy
    from repro.datapipe import synthetic

    rows = []
    for f_sites in site_counts:
        fleet = "paper" if f_sites == 1 else f"paper_x{f_sites}"
        system = scenarios.get_fleet(fleet).build()
        stacked = synthetic.trace_stack(
            jax.random.PRNGKey(0), tuple(rates), reps, n_tasks, system.eet
        )
        flat = jax.tree.map(
            lambda x: x.reshape((len(rates) * reps,) + x.shape[2:]), stacked
        )
        sim = engine.make_simulator(
            policy.get("ELARE"), system.as_jax(),
            queue_size=system.queue_size,
            fairness_factor=float(system.fairness_factor),
            dispatcher=(dispatch.resolve("round_robin")
                        if f_sites > 1 else None),
            site_of_machine=system.sites,
        )
        trace_s = compile_s = float("inf")
        for rep in range(2):
            # min-of-2 against scheduler noise; the second repeat trims a
            # task so its HLO differs, dodging the in-process executable
            # cache (an identical program would "compile" in ~0s).
            fr = (flat if rep == 0 else
                  jax.tree.map(lambda x: x[:, :-1] if x.ndim > 1 else x,
                               flat))
            t0 = time.perf_counter()
            lowered = jax.jit(jax.vmap(sim)).lower(fr)
            t1 = time.perf_counter()
            compiled = lowered.compile()
            t2 = time.perf_counter()
            trace_s = min(trace_s, t1 - t0)
            compile_s = min(compile_s, t2 - t1)
        jax.block_until_ready(compiled(fr))  # first run: alloc + dispatch
        t0w = time.perf_counter()
        jax.block_until_ready(compiled(fr))
        warm_s = time.perf_counter() - t0w
        rows.append({
            "n_sites": f_sites,
            "n_machines": system.n_machines,
            "trace_s": round(trace_s, 4),
            "compile_s": round(compile_s, 4),
            "warm_s": round(warm_s, 4),
        })
    by_f = {r["n_sites"]: r for r in rows}

    def total(r):
        return r["trace_s"] + r["compile_s"]

    ratio = (total(by_f[32]) / total(by_f[2])
             if 2 in by_f and 32 in by_f else None)
    return {
        "bench": "federation_scaling",
        "config": {"reps": reps, "n_tasks": n_tasks, "rates": list(rates),
                   "heuristic": "ELARE", "dispatcher": "round_robin"},
        "sites": rows,
        "compile_ratio_f32_vs_f2":
            None if ratio is None else round(ratio, 3),
    }


def perf_tiered_sweep(*, reps: int = 4, n_tasks: int = 300,
                      rates=(2.0, 4.0)) -> dict:
    """Warm/cold wall clock of the tiered edge-cloud network path.

    Same shape as :func:`perf_vmapped_sweep` but on the ``tiered_x4``
    fleet with the ``tiered`` network model and the ``tier_aware``
    dispatcher — the full per-link ready-time/energy machinery inside the
    single jit. Its warm row is gated against ``benchmarks/BENCH_1.json``
    like every other configuration.
    """
    import jax

    from repro import scenarios
    from repro.core import engine
    from repro.datapipe import synthetic

    system = scenarios.get_fleet("tiered_x4").build()
    stacked = synthetic.trace_stack(
        jax.random.PRNGKey(0), tuple(rates), reps, n_tasks, system.eet
    )
    flat = jax.tree.map(
        lambda x: x.reshape((len(rates) * reps,) + x.shape[2:]), stacked
    )
    t0 = time.perf_counter()
    out = engine.simulate_batch(flat, system, "FELARE",
                                dispatcher="tier_aware", network="tiered")
    jax.block_until_ready(out)
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = engine.simulate_batch(flat, system, "FELARE",
                                dispatcher="tier_aware", network="tiered")
    jax.block_until_ready(out)
    warm_s = time.perf_counter() - t0
    return {
        "bench": "tiered_sweep",
        "config": {"reps": reps, "n_tasks": n_tasks, "rates": list(rates),
                   "heuristic": "FELARE", "fleet": "tiered_x4",
                   "dispatcher": "tier_aware", "network": "tiered"},
        "cold_s": round(cold_s, 4),
        "warm_s": round(warm_s, 4),
        "compile_s": round(cold_s - warm_s, 4),
    }


def _fused_map_pair(n_tasks, n_machines, *, interpret, seed=0,
                    heuristic="FELARE", n_types=4, queue_slots=2):
    """Jitted lax/fused select closures + their random raw inputs.

    Both closures rebuild the SchedContext from the same raw arrays, so
    timing them head-to-head isolates the map-decision math — Eq. 1/2
    grids, nomination, phase-2 keys, drops, the FELARE eviction stats —
    which is exactly what the fused kernel replaces.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import policy
    from repro.core.policy.context import MachineView, SchedContext
    from repro.core.types import SystemArrays

    r = np.random.default_rng(seed)
    n, m, s, q = n_tasks, n_machines, n_types, queue_slots
    raw = dict(
        now=jnp.float32(25.0),
        pending=jnp.asarray(r.integers(0, 2, n).astype(bool)),
        task_type=jnp.asarray(r.integers(0, s, n).astype(np.int32)),
        deadline=jnp.asarray(r.uniform(0, 120, n).astype(np.float32)),
        avail_base=jnp.asarray(r.uniform(0, 60, m).astype(np.float32)),
        queue=jnp.asarray(
            np.where(np.arange(q)[None, :] < r.integers(0, q + 1, m)[:, None],
                     r.integers(0, n, (m, q)), -1).astype(np.int32)),
        eet=jnp.asarray(r.uniform(0.5, 20, (s, m)).astype(np.float32)),
        p_dyn=jnp.asarray(r.uniform(1, 10, m).astype(np.float32)),
        p_idle=jnp.asarray(r.uniform(0.1, 1, m).astype(np.float32)),
        suffered=jnp.asarray(r.integers(0, 2, s).astype(bool)),
    )
    raw["qlen"] = (raw["queue"] >= 0).sum(axis=1).astype(jnp.int32)

    def make(pol):
        def f(now, pending, task_type, deadline, avail_base, queue, qlen,
              eet, p_dyn, p_idle, suffered):
            ctx = SchedContext(
                now=now, pending=pending, task_type=task_type,
                deadline=deadline,
                view=MachineView(avail_base, queue, qlen),
                sysarr=SystemArrays(eet=eet, p_dyn=p_dyn, p_idle=p_idle),
                suffered=suffered)
            act = pol.select(ctx)
            return act.assign, act.drop, act.queue_drop
        return jax.jit(f)

    order = ("now", "pending", "task_type", "deadline", "avail_base",
             "queue", "qlen", "eet", "p_dyn", "p_idle", "suffered")
    args = tuple(raw[k] for k in order)
    lax_fn = make(policy.get(heuristic))
    fused_fn = make(policy.with_pallas_map(heuristic, interpret=interpret))
    return lax_fn, fused_fn, args


def perf_fused_map(*, shapes=((100, 8), (1000, 64), (10000, 512))) -> dict:
    """Lax-vs-fused warm wall clock of the map decision over (N x M).

    Per shape, jits the full FELARE ``select`` (context rebuild + decision)
    both ways on identical random inputs, asserts output parity, then
    times warm calls. On CPU the fused path runs the Pallas kernels in
    interpret mode — parity is still asserted but the timing comparison
    would measure the interpreter, so rows carry ``status: "skipped"``
    and no speedup is claimed (the 1.5x gate only reads ``"ok"`` rows).
    """
    import time as _time

    import jax
    import numpy as np

    from repro.kernels import pallas_backend

    interpret = pallas_backend.default_interpret()
    mode = "interpret" if interpret else "compiled"
    rows = []
    for n, m in shapes:
        lax_fn, fused_fn, args = _fused_map_pair(n, m, interpret=interpret)
        out_lax = jax.block_until_ready(lax_fn(*args))
        out_fused = jax.block_until_ready(fused_fn(*args))
        parity = all(np.array_equal(np.asarray(a), np.asarray(b))
                     for a, b in zip(out_lax, out_fused))
        row = {"n_tasks": n, "n_machines": m, "mode": mode,
               "parity": bool(parity)}
        if interpret:
            row["status"] = "skipped"
        else:
            reps = max(3, min(100, int(2e6 / (n * m))))
            timed = {}
            for tag, fn in (("lax", lax_fn), ("fused", fused_fn)):
                jax.block_until_ready(fn(*args))
                t0 = _time.perf_counter()
                for _ in range(reps):
                    out = fn(*args)
                jax.block_until_ready(out)
                timed[tag] = (_time.perf_counter() - t0) / reps
            row.update({
                "status": "ok", "reps": reps,
                "lax_warm_s": round(timed["lax"], 6),
                "fused_warm_s": round(timed["fused"], 6),
                "speedup": round(timed["lax"] / timed["fused"], 3),
            })
        rows.append(row)
    return {
        "bench": "fused_map",
        "config": {"heuristic": "FELARE", "mode": mode},
        "shapes": rows,
        "parity_all": all(r["parity"] for r in rows),
    }


def fused_parity_smoke() -> bool:
    """Quick lax-vs-fused parity check (the CI pre-gate smoke).

    Select-level parity at two shapes plus a dispatcher balance-scan
    parity row; returns False on any mismatch. Runs in interpret mode on
    CPU so CI exercises the exact kernel bodies the compiled path runs.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.dispatch.base import DispatchContext, sequential_balance
    from repro.kernels import map_fused, pallas_backend

    interpret = pallas_backend.default_interpret()
    ok = True
    for n, m in ((100, 8), (130, 129)):
        lax_fn, fused_fn, args = _fused_map_pair(n, m, interpret=interpret,
                                                 seed=n)
        out_lax = jax.block_until_ready(lax_fn(*args))
        out_fused = jax.block_until_ready(fused_fn(*args))
        good = all(np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(out_lax, out_fused))
        print(f"  select parity N={n} M={m}: {'ok' if good else 'MISMATCH'}")
        ok = ok and good

    r = np.random.default_rng(7)
    n, m, f, s = 90, 12, 3, 4
    site = np.sort(np.r_[np.arange(f), r.integers(0, f, m - f)])
    ctx = DispatchContext(
        now=jnp.float32(10.0),
        unassigned=jnp.asarray(r.integers(0, 2, n).astype(bool)),
        task_type=jnp.asarray(r.integers(0, s, n).astype(np.int32)),
        deadline=jnp.asarray(r.uniform(0, 120, n).astype(np.float32)),
        qlen=jnp.asarray(r.integers(0, 3, m).astype(np.int32)),
        running=jnp.asarray(r.integers(0, 2, m).astype(bool)),
        completed=jnp.asarray(r.integers(0, 20, s).astype(np.int32)),
        arrived=jnp.asarray(r.integers(20, 40, s).astype(np.int32)),
        eet=jnp.asarray(r.uniform(0.5, 20, (s, m)).astype(np.float32)),
        site_of_machine=site,
        n_sites=f,
        fairness_factor=1.0,
        alive=None,
    )
    target = jnp.asarray(r.integers(0, 2, n).astype(bool))
    home = jnp.asarray(r.integers(0, f, n).astype(np.int32))
    want = np.asarray(sequential_balance(ctx, target, home))
    got = np.asarray(sequential_balance(
        ctx, target, home,
        lambda l0, un, tgt, hm: map_fused.balance_scan(
            l0, un, tgt, hm, interpret=interpret)))
    good = np.array_equal(want, got)
    print(f"  balance parity N={n} F={f}: {'ok' if good else 'MISMATCH'}")
    return ok and good


def write_perf_artifact(outdir, baseline=None,
                        allow_new_rows=False) -> pathlib.Path:
    """Run the perf benches and write the next ``BENCH_<n>.json`` in outdir.

    With ``baseline`` (a prior BENCH_*.json, e.g. the checked-in
    ``benchmarks/BENCH_1.json``), compares warm times per configuration
    and exits nonzero when any exceeds ``WARM_TOLERANCE`` x its baseline
    — the blocking CI perf gate.
    """
    outdir = pathlib.Path(outdir)
    outdir.mkdir(parents=True, exist_ok=True)
    seen = [int(m.group(1)) for p in outdir.glob("BENCH_*.json")
            if (m := re.fullmatch(r"BENCH_(\d+)\.json", p.name))]
    path = outdir / f"BENCH_{max(seen, default=-1) + 1}.json"
    payload = perf_vmapped_sweep()
    payload["federation_scaling"] = perf_federation_scaling()
    payload["tiered_sweep"] = perf_tiered_sweep()
    payload["fused_map"] = perf_fused_map()
    path.write_text(json.dumps(payload, indent=2))
    print(json.dumps(payload, indent=2))
    print(f"wrote {path}")
    if not payload["fused_map"]["parity_all"]:
        print("FAIL: fused map kernel disagrees with the lax path")
        raise SystemExit(1)
    if baseline and not compare_to_baseline(payload, baseline,
                                            allow_new_rows=allow_new_rows):
        raise SystemExit(1)
    return path


#: Blocking warm-time regression tolerance vs the checked-in baseline.
WARM_TOLERANCE = 1.5


def compare_to_baseline(payload: dict, baseline,
                        allow_new_rows: bool = False) -> bool:
    """Compare warm times of ``payload`` vs a baseline BENCH JSON.

    Returns False (the CI-blocking verdict) when any matched
    configuration — observer rows of the vmapped sweep, per-F rows of the
    federation scaling bench, timed ``fused_map`` rows — regresses past
    ``WARM_TOLERANCE`` x its baseline warm time, or when a payload row
    has no baseline counterpart: a silently unmatched row is an ungated
    benchmark, so new rows fail loudly until either the baseline is
    refreshed or ``allow_new_rows`` opts them in (the ``--allow-new-rows``
    flag, for the PR that introduces a bench). A missing baseline file
    passes (first run on a fresh checkout).
    """
    baseline = pathlib.Path(baseline)
    if not baseline.exists():
        print(f"perf baseline {baseline} not found; skipping comparison")
        return True
    base = json.loads(baseline.read_text())
    ok = True
    new_rows = []

    def check(tag, warm, ref):
        nonlocal ok
        ref_warm = ref.get("warm_s") if ref else None
        if not ref_warm:
            new_rows.append(tag)
            return
        ratio = warm / ref_warm
        bad = ratio > WARM_TOLERANCE
        ok = ok and not bad
        print(f"  {tag:40s} {warm:.3f}s vs {ref_warm:.3f}s "
              f"({ratio:.2f}x){' REGRESSION' if bad else ''}")

    base_by_obs = {tuple(r["observers"]): r
                   for r in base.get("simulate_batch", ())}
    print(f"\nwarm-time vs baseline {baseline} "
          f"(blocking at {WARM_TOLERANCE}x):")
    for row in payload["simulate_batch"]:
        check("observers=" + (",".join(row["observers"]) or "off"),
              row["warm_s"], base_by_obs.get(tuple(row["observers"])))
    fed = payload.get("federation_scaling", {}).get("sites", ())
    base_by_f = {r["n_sites"]: r
                 for r in base.get("federation_scaling", {})
                             .get("sites", ())}
    for row in fed:
        check(f"federation F={row['n_sites']}", row["warm_s"],
              base_by_f.get(row["n_sites"]))
    tiered = payload.get("tiered_sweep")
    if tiered:
        check("tiered_x4 network=tiered", tiered["warm_s"],
              base.get("tiered_sweep"))
    base_by_nm = {(r["n_tasks"], r["n_machines"]): r
                  for r in base.get("fused_map", {}).get("shapes", ())
                  if r.get("status") == "ok"}
    for row in payload.get("fused_map", {}).get("shapes", ()):
        if row.get("status") != "ok":
            continue  # interpret-mode parity-only rows carry no timing
        key = (row["n_tasks"], row["n_machines"])
        check(f"fused_map N={key[0]} M={key[1]}", row["fused_warm_s"],
              base_by_nm.get(key))
    if new_rows and not allow_new_rows:
        ok = False
        for tag in new_rows:
            print(f"  {tag:40s} NO BASELINE ROW")
        print("FAIL: benchmark rows missing from the baseline — refresh "
              "the checked-in BENCH json or pass --allow-new-rows")
    if not ok:
        print(f"FAIL: perf gate vs {WARM_TOLERANCE}x baseline")
    return ok


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--full", action="store_true",
                    help="paper-scale (30 traces x 2000 tasks)")
    ap.add_argument("--perf-out", default=None, metavar="DIR",
                    help="run only the engine perf benchmark and append a "
                         "BENCH_<n>.json artifact under DIR")
    ap.add_argument("--perf-baseline", default=None, metavar="PATH",
                    help="with --perf-out: compare warm times against this "
                         "prior BENCH_<n>.json (e.g. the checked-in "
                         "benchmarks/BENCH_1.json) and exit nonzero past "
                         f"{WARM_TOLERANCE}x (the blocking CI gate)")
    ap.add_argument("--allow-new-rows", action="store_true",
                    help="with --perf-baseline: tolerate payload rows with "
                         "no baseline counterpart (for the PR introducing a "
                         "bench) instead of failing loudly")
    ap.add_argument("--fused-parity-smoke", action="store_true",
                    help="run only the fused-vs-lax kernel parity smoke "
                         "(the CI step ahead of the blocking perf gate) and "
                         "exit nonzero on mismatch")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.fused_parity_smoke:
        print("fused-vs-lax parity smoke:")
        if not fused_parity_smoke():
            raise SystemExit(1)
        return

    if args.perf_out:
        write_perf_artifact(args.perf_out, baseline=args.perf_baseline,
                            allow_new_rows=args.allow_new_rows)
        return

    from benchmarks import ablations, paper_figures, roofline_report

    benches = dict(paper_figures.ALL)
    benches.update(ablations.ALL)
    benches["roofline_map_stage"] = roofline_report.map_stage

    print("name,us_per_call,derived")
    blocks = []
    for name, fn in benches.items():
        if args.only and args.only not in name:
            continue
        t0 = time.perf_counter()
        rows, derived = fn(full=args.full)
        us = (time.perf_counter() - t0) * 1e6
        print(f"{name},{us:.0f},{json.dumps(derived, default=float)}", flush=True)
        blocks.append((name, rows, derived))

    for name, rows, derived in blocks:
        print(f"\n=== {name} ===")
        if rows:
            cols = list(rows[0].keys())
            print(" | ".join(f"{c:>12s}" for c in cols))
            for r in rows:
                print(" | ".join(f"{str(r.get(c, '')):>12s}" for c in cols))
        print(f"derived: {json.dumps(derived, default=float)}")

    n_fail = sum(1 for _, _, d in blocks if d.get("pass") is False)
    print(f"\n{len(blocks)} benchmarks; {n_fail} failed claims")


if __name__ == "__main__":
    main()
