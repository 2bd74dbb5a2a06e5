"""Core datatypes for the FELARE scheduling system.

Shapes use the paper's notation:
  S = number of task types (ML applications), M = number of machine types,
  N = number of tasks in a workload trace, Q = per-machine local-queue slots.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax.numpy as jnp
import numpy as np

# Task status codes used by both engines.
UNARRIVED = 0   # not yet arrived
PENDING = 1     # in the arriving queue (arrived, unmapped)
QUEUED = 2      # in a machine's local queue
RUNNING = 3     # executing
COMPLETED = 4   # finished on time
MISSED = 5      # started execution but killed at its deadline
CANCELLED = 6   # dropped before being assigned (proactive drop / stale / victim)

STATUS_NAMES = {
    UNARRIVED: "unarrived",
    PENDING: "pending",
    QUEUED: "queued",
    RUNNING: "running",
    COMPLETED: "completed",
    MISSED: "missed",
    CANCELLED: "cancelled",
}

INF = jnp.inf


@dataclasses.dataclass(frozen=True)
class SystemSpec:
    """A heterogeneous edge system: machines + profiling data.

    eet:    (S, M) expected execution time of task type i on machine type j.
    p_dyn:  (M,) dynamic power of each machine.
    p_idle: (M,) idle power of each machine.
    queue_size: local queue slots per machine (bounded, equal across machines).
    fairness_factor: ``f`` in Eq. 3; aggressiveness of the fairness method.
    site_of_machine: optional (M,) partition of the machines into F edge
      *sites* (a federation). ``None`` — the default, and what every spec
      built before the federation layer carries — means one site holding
      every machine, so a flat system is just the degenerate F=1
      federation. Sites must be numbered contiguously ``0..F-1`` and every
      site must own at least one machine. Stored as a tuple of ints so the
      spec stays hashable and ``==``-comparable.
    tier_of_site: optional (F,) edge-cloud tier of each site — device=0,
      edge=1, cloud=2 (higher tiers allowed for deeper hierarchies).
      ``None`` means every site sits on the device tier, so flat and
      pre-network specs are the degenerate single-tier hierarchy. Tasks
      originate on the lowest tier present (see
      :mod:`repro.core.network`). Stored as a tuple of ints for
      hashability.
    """

    eet: np.ndarray
    p_dyn: np.ndarray
    p_idle: np.ndarray
    queue_size: int = 2
    fairness_factor: float = 1.0
    site_of_machine: Optional[Tuple[int, ...]] = None
    tier_of_site: Optional[Tuple[int, ...]] = None

    def __post_init__(self):
        if self.site_of_machine is not None:
            sites = tuple(int(s) for s in np.asarray(self.site_of_machine))
            object.__setattr__(self, "site_of_machine", sites)
            if len(sites) != self.n_machines:
                raise ValueError(
                    f"site_of_machine has {len(sites)} entries for "
                    f"{self.n_machines} machines"
                )
            present = set(sites)
            n_sites = max(sites) + 1
            if min(sites) < 0 or present != set(range(n_sites)):
                raise ValueError(
                    f"sites must be contiguous 0..F-1 with every site "
                    f"non-empty, got {sites}"
                )
        if self.tier_of_site is not None:
            tiers = tuple(int(t) for t in np.asarray(self.tier_of_site))
            object.__setattr__(self, "tier_of_site", tiers)
            if len(tiers) != self.n_sites:
                raise ValueError(
                    f"tier_of_site has {len(tiers)} entries for "
                    f"{self.n_sites} sites"
                )
            if min(tiers) < 0:
                raise ValueError(f"tiers must be >= 0, got {tiers}")

    @property
    def n_task_types(self) -> int:
        return self.eet.shape[0]

    @property
    def n_machines(self) -> int:
        return self.eet.shape[1]

    @property
    def n_sites(self) -> int:
        """Number of federation sites F (1 for the flat single-site system)."""
        if self.site_of_machine is None:
            return 1
        return max(self.site_of_machine) + 1

    @property
    def sites(self) -> Tuple[int, ...]:
        """The (M,) site partition, materialized (all-zeros when unset)."""
        if self.site_of_machine is None:
            return (0,) * self.n_machines
        return self.site_of_machine

    @property
    def tiers(self) -> Tuple[int, ...]:
        """The (F,) site tiers, materialized (all-device when unset)."""
        if self.tier_of_site is None:
            return (0,) * self.n_sites
        return self.tier_of_site

    @property
    def n_tiers(self) -> int:
        """Number of hierarchy levels spanned (``max tier + 1``)."""
        return max(self.tiers) + 1

    def as_jax(self) -> "SystemArrays":
        return SystemArrays(
            eet=jnp.asarray(self.eet, jnp.float32),
            p_dyn=jnp.asarray(self.p_dyn, jnp.float32),
            p_idle=jnp.asarray(self.p_idle, jnp.float32),
            site_of_machine=jnp.asarray(self.sites, jnp.int32),
        )


def site_membership(site_of_machine, n_sites: Optional[int] = None
                    ) -> np.ndarray:
    """(F, M) bool membership grid of a site partition, as a host constant.

    Row ``s`` is the machine mask of site ``s``. Both the engine's masked
    ``vmap`` map stage and the dispatch layer consume this grid as *data*
    (an array fed to vectorized masking), so the site count F shapes only
    array extents — never the traced program — which is what keeps compile
    time flat in F (see ``tests/test_compile_flatness.py``).
    """
    sites = np.asarray(site_of_machine, np.int32)
    F = int(sites.max()) + 1 if n_sites is None else int(n_sites)
    return np.arange(F, dtype=np.int32)[:, None] == sites[None, :]


class SystemArrays(NamedTuple):
    """Device-side mirror of :class:`SystemSpec` for jitted consumers.

    ``site_of_machine`` is the federation partition as an (M,) int32
    array (``None`` on flat systems) — what site-aware policies and
    observers (e.g. the per-site :class:`~repro.core.observe.timeline.
    Timeline`) read inside the trace; the engine's own per-site loop uses
    the *static* tuple instead, since the site count shapes the program.
    """

    eet: jnp.ndarray     # (S, M)
    p_dyn: jnp.ndarray   # (M,)
    p_idle: jnp.ndarray  # (M,)
    site_of_machine: Optional[jnp.ndarray] = None  # (M,) int32 site ids


class Trace(NamedTuple):
    """A workload trace of N dynamically-arriving tasks (arrival-sorted)."""

    arrival: jnp.ndarray    # (N,) float32
    task_type: jnp.ndarray  # (N,) int32
    deadline: jnp.ndarray   # (N,) float32  (Eq. 4)
    exec_actual: jnp.ndarray  # (N, M) float32 Gamma-sampled actual runtimes


class MapAction(NamedTuple):
    """Output of a mapping heuristic at one mapping event."""

    assign: jnp.ndarray      # (M,) int32 task index per machine, -1 = none
    drop: jnp.ndarray        # (N,) bool  proactive drops from the arriving queue
    queue_drop: jnp.ndarray  # (M, Q) bool victims evicted from local queues (FELARE)


class SimState(NamedTuple):
    """The engine's fixed-shape event-loop state (one trace).

    Every field is a JAX array of static shape, so the whole state threads
    through ``lax.while_loop`` and vmaps over trace batches. Observers
    (:mod:`repro.core.observe`) receive this read-only at every event
    stage; their own state rides next to it in :class:`EngineState.aux`.

    The trailing health fields belong to the faults subsystem
    (:mod:`repro.core.faults`): ``alive``/``slowdown`` are the
    per-machine health state a :class:`~repro.core.faults.
    MachineDynamics` evolves at the ``faults`` stage, ``retries`` counts
    each task's orphan re-dispatches, and ``backup`` holds the k-failure
    backup nominations of :func:`~repro.core.faults.with_backup`
    (shape (N, 0) when no backups are in play). With ``dynamics="none"``
    they are constant carries — present in the state, never read by any
    stage — which keeps the default program bit-exact with the
    pre-faults engine.

    The trailing network fields belong to the network subsystem
    (:mod:`repro.core.network`): ``ready`` is each task's arrival time
    at its *dispatched site* (arrival time + link latency; the mapper
    will not place an in-transit task) and ``e_xfer`` accumulates
    transfer energy per destination tier for the ``network`` observer.
    With ``network="none"`` both stay ``None`` — absent pytree leaves,
    so the traced program is structurally identical to the pre-network
    engine.

    ``fair_evicted`` and ``suffered_maps`` accumulate the matching
    :class:`Metrics` counters; they stay ``None`` for a policy without
    fairness, so its loop carries and computes nothing for them.
    """

    now: jnp.ndarray            # ()
    status: jnp.ndarray         # (N,) int32
    site: jnp.ndarray           # (N,) int32 federation site, -1 undispatched
    run_task: jnp.ndarray       # (M,) int32, -1 idle
    run_start: jnp.ndarray      # (M,)
    run_end_act: jnp.ndarray    # (M,) actual completion (inf if idle)
    run_end_exp: jnp.ndarray    # (M,) expected completion (for the mapper)
    run_success: jnp.ndarray    # (M,) bool
    queue: jnp.ndarray          # (M, Q) int32, -1 empty
    qlen: jnp.ndarray           # (M,) int32
    busy_time: jnp.ndarray      # (M,)
    e_dyn: jnp.ndarray          # ()
    e_wasted: jnp.ndarray       # ()
    completed: jnp.ndarray      # (S,) int32
    missed: jnp.ndarray         # (S,) int32
    cancelled: jnp.ndarray      # (S,) int32
    arrived: jnp.ndarray        # (S,) int32
    steps: jnp.ndarray          # () int32
    alive: Optional[jnp.ndarray] = None     # (M,) bool machine health
    slowdown: Optional[jnp.ndarray] = None  # (M,) f32 straggler factors
    retries: Optional[jnp.ndarray] = None   # (N,) int32 orphan re-dispatches
    backup: Optional[jnp.ndarray] = None    # (N, k) int32 backup machines
    ready: Optional[jnp.ndarray] = None     # (N,) f32 ready time at site
    e_xfer: Optional[jnp.ndarray] = None    # (T,) f32 transfer energy by tier
    fair_evicted: Optional[jnp.ndarray] = None   # () int32, fairness only
    suffered_maps: Optional[jnp.ndarray] = None  # () int32, fairness only


class EngineState(NamedTuple):
    """The extensible event-loop carrier: core state + observer aux.

    ``aux`` maps each attached observer's name to its own fixed-shape
    pytree, so extensions carry state through the ``lax.while_loop``
    without touching :class:`SimState` fields. With no observers it is an
    empty dict and the loop is structurally identical to the bare engine.
    """

    sim: SimState
    aux: dict  # observer name -> pytree, fixed structure per simulation


class Metrics(NamedTuple):
    """Aggregate results of one simulated trace.

    ``steps`` is what the run cost rather than what it simulated: the
    event loop's iterations for this trace (int32). Under ``vmap`` the
    loop runs until its slowest trace ends, so a batch's iterations are
    the max over its lanes, and the rest is lanes left idle.

    ``fair_evicted`` and ``suffered_maps`` count the fairness step (Sec.
    V): queued tasks evicted for a suffered task (``cancelled_by_type``
    counts them too), and events whose map stage found at least one type
    suffered (Eq. 3). Both are constant 0 for a policy without fairness.
    """

    completed_by_type: jnp.ndarray  # (S,)
    missed_by_type: jnp.ndarray     # (S,)
    cancelled_by_type: jnp.ndarray  # (S,)
    arrived_by_type: jnp.ndarray    # (S,)
    energy_dynamic: jnp.ndarray     # () total dynamic energy
    energy_wasted: jnp.ndarray      # () dynamic energy spent on missed tasks
    energy_idle: jnp.ndarray        # () idle energy over the makespan
    makespan: jnp.ndarray           # () time of last event
    steps: jnp.ndarray              # () int32 event-loop iterations
    fair_evicted: jnp.ndarray       # () int32 fairness evictions
    suffered_maps: jnp.ndarray      # () int32 events with a suffered type

    @property
    def completion_rate_by_type(self):
        return self.completed_by_type / jnp.maximum(self.arrived_by_type, 1)

    @property
    def collective_completion_rate(self):
        return self.completed_by_type.sum() / jnp.maximum(
            self.arrived_by_type.sum(), 1
        )
