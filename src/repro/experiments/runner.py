"""Batched Monte-Carlo sweep execution.

One sweep = one trace stack + one jitted computation. The trace stack is
the full (rates x reps) grid from ``Scenario.stack`` (every heuristic sees
identical traces — the paper's paired-comparison design; the scenario
resolves through the :mod:`repro.scenarios` registry). The jitted
computation contains one vmapped ``lax.while_loop`` simulator per
heuristic over the flattened grid, so the whole experiment is a single XLA
program and a single dispatch:

    Metrics leaves come back with shape (H, R, K, ...) for H heuristics,
    R rates, K replicates — and so does every leaf of the observer aux
    when the spec attaches engine observers (:mod:`repro.core.observe`):
    telemetry rides inside the same jitted program, never a second pass.

The jitted program is cached on what it closes over statically (see
:func:`simulate_sweep`), so a repeated sweep with equal static inputs
re-runs the compiled program without tracing or lowering it again;
:func:`sweep_cache_info` counts the hits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine, policy
from repro.core.types import SystemSpec, Trace
from repro.experiments.results import SweepResult
from repro.experiments.spec import SweepSpec

# Trace-time observability: one (heuristic, scenario label, dispatcher
# label, dynamics label, network label) entry is appended each time a
# per-heuristic simulator body is *traced* (not dispatched). Tests read
# this to pin the single-jit contract — every (policy, dispatcher,
# dynamics, network, scenario) tuple of a sweep must trace exactly once
# inside one XLA program, and a cached program (a repeated sweep) traces
# nothing. Bounded to the most recent entries so long-lived processes
# don't accumulate.
_TRACE_LOG: list = []
_TRACE_LOG_MAX = 256

# Host spans on the profiler's clock (a no-op unless a profiler runs):
# ``sweep.build`` (input resolution, the program cache's key and lookup,
# and simulator construction on a miss, up to the jit call),
# ``sweep.trace.<NAME>`` (one per heuristic body JAX traces), and in
# ``run_sweep`` ``sweep.stack`` and ``sweep.reduce``. Each
# heuristic's device loop runs under ``jax.named_scope("sweep.<NAME>")``.
_annotate = jax.profiler.TraceAnnotation

# Jitted sweep programs kept by ``_sweep_program``, least recently used
# dropped first.
_SWEEP_CACHE_MAX = 32


def _select_fns(names, use_pallas: bool, use_pallas_map: bool = False):
    """Resolve policy names through the registry, with the Pallas toggles.

    When ``use_pallas`` is set, every policy whose nominator has a fused
    Phase-I hook (built-ins: ELARE/FELARE) is swapped onto the Pallas
    ``phase1_map`` kernel nominator; other policies are unaffected.
    ``use_pallas_map`` instead fuses the whole map decision
    (``policy.with_pallas_map``); applied after the phase1 toggle, it
    wins wherever both could apply (the fused kernel subsumes phase1).
    """
    pols = [policy.get(name) for name in names]
    if use_pallas:
        pols = [policy.with_pallas_phase1(p) for p in pols]
    if use_pallas_map:
        pols = [policy.with_pallas_map(p) for p in pols]
    return pols


def _resolve_dispatcher(dispatcher, use_pallas_map: bool = False):
    """Resolve the dispatcher, with the fused balance scan when the map
    kernels are on (``dispatch.with_pallas_balance``; a no-op for
    dispatchers that never run the scan)."""
    from repro.core import dispatch as dispatch_mod

    disp = dispatch_mod.resolve(dispatcher)
    if use_pallas_map:
        disp = dispatch_mod.with_pallas_balance(disp)
    return disp


class _SystemKey:
    """A system compared by value, for the sweep program's cache key.

    Two keys are equal when the systems' ``eet``, ``p_dyn`` and
    ``p_idle`` agree in shape, dtype and bytes, and their queue size,
    fairness factor, sites and tiers agree: the program closes over
    those as constants. ``system`` is the first such system seen, the
    one a cache miss builds from.
    """

    __slots__ = ("system", "_value")

    def __init__(self, system):
        self.system = system
        arrays = tuple(
            (a.shape, a.dtype.str, a.tobytes())
            for a in map(np.asarray, (system.eet, system.p_dyn,
                                      system.p_idle)))
        self._value = (arrays, system.queue_size,
                       float(system.fairness_factor), system.sites,
                       getattr(system, "tiers", None))

    def __hash__(self):
        return hash(self._value)

    def __eq__(self, other):
        return (isinstance(other, _SystemKey)
                and self._value == other._value)


@functools.lru_cache(maxsize=_SWEEP_CACHE_MAX)
def _sweep_program(system_key, heuristic_names, pols, obs, disp, dyn, net,
                   max_steps, use_pallas_phase1, use_pallas_map, mesh,
                   labels):
    """The jitted sweep over every heuristic, built once per static key.

    ``use_pallas_phase1``/``use_pallas_map`` are already applied to
    ``pols`` and ``disp``; they are keys only. ``labels`` is the
    ``(scenario, dispatcher, dynamics, network)`` annotation of
    ``_TRACE_LOG``. With a ``mesh`` the program is the jitted
    ``shard_map`` of the sweep over its batch axis.
    """
    del use_pallas_phase1, use_pallas_map
    system = system_key.system
    sysarr = system.as_jax()
    sims = [
        engine.make_simulator(
            fn, sysarr, queue_size=system.queue_size,
            fairness_factor=float(system.fairness_factor),
            max_steps=max_steps, observers=obs,
            dispatcher=disp, site_of_machine=system.sites,
            dynamics=dyn, network=net,
            tier_of_site=getattr(system, "tiers", None),
        )
        for fn in pols
    ]

    def run_all(tr):
        per_h = []
        for name, sim in zip(heuristic_names, sims):
            _TRACE_LOG.append((name, *labels))  # trace-time
            with (_annotate(f"sweep.trace.{name}"),
                  jax.named_scope(f"sweep.{name}")):
                per_h.append(jax.vmap(sim)(tr))
        return jax.tree.map(lambda *xs: jnp.stack(xs), *per_h)

    if mesh is None:
        return jax.jit(run_all)
    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding

    # check_vma=False: the engine's while_loop carry starts from
    # constants (now=0, empty queues) that become varying over the
    # grid axis after one step, and the loop requires the input and
    # output carry types to match. Marking the initial carry varying
    # would thread this mesh's axis name into the engine. Each device
    # simulates its own slice with no collective, so the check has
    # nothing to verify here.
    return jax.jit(jax.shard_map(
        run_all, mesh=mesh,
        in_specs=P(sharding.SWEEP_AXIS),
        out_specs=P(None, sharding.SWEEP_AXIS),
        check_vma=False,
    ))


def sweep_cache_info():
    """``(hits, misses, maxsize, currsize)`` of the sweep program cache.

    A hit is a ``simulate_sweep`` call that reused a jitted program (no
    simulator built, nothing traced); a miss built one.
    """
    return _sweep_program.cache_info()


def clear_sweep_cache() -> None:
    """Drop every cached sweep program and zero the hit/miss counts."""
    _sweep_program.cache_clear()


def simulate_sweep(traces: Trace, system: SystemSpec, heuristic_names,
                   *, use_pallas_phase1: bool = False,
                   use_pallas_map: bool = False,
                   max_steps=None, trace_label: str = "",
                   observers=(), dispatcher=None, dynamics=None,
                   network=None, shard: bool = False):
    """Simulate a flat batch of traces under every heuristic, in one jit.

    The jitted program is cached (up to 32, least recently used dropped)
    on what it closes over statically: the resolved policies with
    ``heuristic_names``, the resolved observers, dispatcher, dynamics and
    network, the system by value (``eet``, ``p_dyn``, ``p_idle`` by
    shape, dtype and bytes; queue size, fairness factor, sites, tiers),
    ``max_steps``, the Pallas toggles, the mesh and the trace-log labels.
    A repeated call with equal static inputs re-runs the compiled program
    without tracing it; JAX's own per-shape cache inside the jit handles a
    new trace shape. Resolved objects, not names, are the key, so
    re-registering a name with ``overwrite=True`` takes effect.
    :func:`sweep_cache_info` counts hits and misses,
    :func:`clear_sweep_cache` empties the cache.

    Args:
      traces: a Trace whose leaves have one flat leading batch dim B
        (e.g. the flattened (R*K) stack from ``Scenario.stack``).
      system: the SystemSpec to simulate; its ``site_of_machine``
        partition (if any) federates the machines into sites.
      heuristic_names: sequence of H heuristic names.
      use_pallas_phase1: route ELARE Phase-I through the Pallas kernel.
      use_pallas_map: fuse the whole map decision into the Pallas
        ``map_fused`` kernel for every policy in its kind space, and the
        dispatcher's balance scan into the fused scan kernel — bit-exact
        with the lax path (``tests/test_map_fused.py``).
      max_steps: optional per-trace event cap (``None`` = engine default).
      trace_label: annotation recorded next to each heuristic in the
        module's trace log (``run_sweep`` passes the scenario name).
      observers: engine observers — registered names or
        :class:`repro.core.observe.Observer` instances. They ride inside
        the same single jit (closed over statically: attaching observers
        adds zero retraces).
      dispatcher: the federation site-selection rule — a registered name
        or :class:`repro.core.dispatch.Dispatcher` instance (``None`` =
        the default ``sticky``; inert on single-site systems). Closed
        over statically like the policies: one trace per
        (policy, dispatcher, dynamics, scenario) tuple.
      dynamics: the machine-failure process — a registered
        :mod:`repro.core.faults` name or
        :class:`repro.core.faults.MachineDynamics` instance
        (``None``/``"none"`` = no failures, bit-exact with pre-faults
        sweeps). Closed over statically like the policies.
      network: the edge-cloud transfer-cost model — a registered
        :mod:`repro.core.network` name or
        :class:`repro.core.network.NetworkModel` instance
        (``None``/``"none"`` = free instantaneous links, bit-exact with
        pre-network sweeps). Closed over statically like the policies.
      shard: split the trace batch across every visible device with
        ``jax.shard_map`` (``repro.distributed.sharding.sweep_mesh``) —
        each device simulates its slice of the batch; the batch is
        padded to the device count and the padding sliced back off, so
        results are *bit-identical* to the unsharded path. With a single
        visible device this falls back to the plain path silently.

    Returns:
      With ``observers=()``: Metrics with leaves of shape (H, B, ...) —
      axis 0 follows ``heuristic_names`` order, axis 1 the trace batch.
      With observers: ``(Metrics, aux)`` where ``aux`` maps observer name
      to its pytree with the same (H, B, ...) leading dims.
    """
    from repro.core import faults as faults_mod
    from repro.core import network as network_mod
    from repro.core import observe

    with _annotate("sweep.build"):
        obs = observe.resolve(observers)
        disp = _resolve_dispatcher(dispatcher, use_pallas_map)
        disp_label = (dispatcher if isinstance(dispatcher, str)
                      else getattr(disp, "kind", type(disp).__name__))
        dyn = faults_mod.resolve(dynamics)
        dyn_label = (dynamics if isinstance(dynamics, str)
                     else getattr(dyn, "kind", type(dyn).__name__))
        net = network_mod.resolve(network)
        net_label = (network if isinstance(network, str)
                     else getattr(net, "kind", type(net).__name__))
        names = tuple(heuristic_names)
        pols = tuple(_select_fns(names, use_pallas_phase1, use_pallas_map))
        mesh = None
        if shard:
            from repro.distributed import sharding

            mesh = sharding.sweep_mesh()
        program = _sweep_program(
            _SystemKey(system), names, pols, obs, disp, dyn, net, max_steps,
            use_pallas_phase1, use_pallas_map, mesh,
            (trace_label, disp_label, dyn_label, net_label))

    if mesh is None:
        out = program(traces)
    else:
        B = traces.arrival.shape[0]
        padded = sharding.pad_batch(traces, mesh.devices.size)
        out = jax.tree.map(lambda x: x[:, :B], program(padded))
    del _TRACE_LOG[:-_TRACE_LOG_MAX]
    return out


def run_sweep(spec: SweepSpec, *, shard: bool = False) -> SweepResult:
    """Execute a full batched Monte-Carlo sweep.

    Resolves the spec's scenario and system through their registries,
    builds the (rates x reps) trace stack under ``PRNGKey(spec.seed)``,
    simulates it under every heuristic in one jitted batch, and wraps the
    raw per-trace Metrics in a :class:`SweepResult` with mean/CI
    reductions.

    Cost scales as H * R * K single-trace simulations of N tasks each;
    the paper-scale grid (5 x 7 x 30 x 2000) runs in one dispatch.
    ``shard=True`` splits the (R*K) trace batch across every visible
    device (``shard_map`` over ``sweep_mesh``) — an execution detail, not
    part of the spec: results are bit-identical to the unsharded sweep
    and the flag is a silent no-op on one device, so a spec remains
    reproducible regardless of the device topology it ran on.
    """
    system = spec.resolve_system()
    scenario = spec.resolve_scenario()
    R, K = len(spec.rates), spec.reps
    with _annotate("sweep.stack"):
        key = jax.random.PRNGKey(spec.seed)
        stacked = scenario.stack(
            key, spec.rates, spec.reps, spec.n_tasks, system.eet,
            cv_run=spec.cv_run,
        )
        flat = jax.tree.map(
            lambda x: x.reshape((R * K,) + x.shape[2:]), stacked
        )
    label = (spec.scenario if isinstance(spec.scenario, str)
             else "<custom scenario>")
    observers = spec.resolve_observers()
    out = simulate_sweep(
        flat, system, spec.heuristics,
        use_pallas_phase1=spec.use_pallas_phase1,
        use_pallas_map=spec.use_pallas_map, max_steps=spec.max_steps,
        trace_label=label, observers=observers, dispatcher=spec.dispatcher,
        dynamics=spec.dynamics, network=spec.network, shard=shard,
    )
    metrics, aux = out if observers else (out, {})
    H = len(spec.heuristics)
    unflatten = lambda x: x.reshape((H, R, K) + x.shape[2:])
    with _annotate("sweep.reduce"):
        metrics = jax.tree.map(unflatten, metrics)
        aux = jax.tree.map(unflatten, aux)
        return SweepResult.from_metrics(spec, system, metrics, aux=aux)
