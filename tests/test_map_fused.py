"""Bit-exactness battery for the fused Pallas map/dispatch kernels.

Pins ``kernels/map_fused`` + ``policy.with_pallas_map`` +
``dispatch.with_pallas_balance`` to the lax path, in the style of
``tests/test_siteloop_vmap.py``:

  * select-level — fused ``FusedMapPolicy.select`` equals the lax
    ``select`` leaf for leaf (MapAction: assign/drop/queue_drop) over
    hypothesis-drawn random SchedContexts (arbitrary qfree/pending/
    deadline draws, padded vs exact machine counts), for all 8 built-in
    heuristics and their ``with_fairness`` variants;
  * trace-level — full simulations agree on every metrics leaf and every
    task_log event field, byte for byte, for F in {1, 2, 8} (block-
    reshaped site views) and a non-contiguous partition (masked-vmap
    view), plus metrics/task_log parity against the pure-Python oracle
    for ELARE/FELARE;
  * dispatch — the fused balance scan equals ``sequential_balance``'s
    ``lax.scan`` walk, standalone and through ``with_pallas_balance``;
  * backend selection — ``pallas_backend.default_interpret`` honors the
    ``REPRO_PALLAS_INTERPRET`` override and rejects junk values.

Interpret mode throughout (CPU-exact; the compiled path runs the same
kernel body on TPU/GPU).
"""
import functools

import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings

from repro.core import api, dispatch, engine, faults, policy, pyengine, workload
from repro.core.dispatch.base import DispatchContext, sequential_balance
from repro.core.policy.context import MachineView, SchedContext
from repro.core.policy.fused import FusedMapPolicy
from repro.core.types import SystemArrays, SystemSpec
from repro.kernels import pallas_backend
from repro.scenarios import fleets

SPEC = api.paper_system()
HEURISTICS = ("ELARE", "FELARE", "MM", "MSD", "MMU", "MET", "MCT", "RANDOM")
FLEETS = {1: "paper", 2: "paper_x2", 8: "paper_x8"}


@pytest.fixture(scope="module", autouse=True)
def _release_jit_caches():
    """Drop this module's executables when it finishes.

    The battery compiles hundreds of (policy x shape) programs; left in
    the in-process jit cache they push XLA's CPU compiler into
    segfault territory for later test modules in a one-process run.
    """
    yield
    _select_pair.cache_clear()
    _sim_pair.cache_clear()
    jax.clear_caches()


def _dyadic(x):
    return (np.round(np.asarray(x) * 64) / 64).astype(np.float32)


def _trace(seed, n, rate, eet):
    tr = workload.poisson_trace(jax.random.PRNGKey(seed), n, rate, eet)
    return tr._replace(
        arrival=jnp.asarray(_dyadic(tr.arrival)),
        deadline=jnp.asarray(_dyadic(tr.deadline)),
        exec_actual=jnp.asarray(_dyadic(tr.exec_actual)),
    )


def _leaves_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    return [bool(np.array_equal(np.asarray(x), np.asarray(y)))
            for x, y in zip(la, lb)]


# ----------------------------------------------------------- select level
def _rand_ctx(N, M, S, Q, seed):
    """A random SchedContext with adversarial qfree/pending/deadline draws
    (full queues, stale tasks, empty machines all reachable)."""
    r = np.random.default_rng(seed)
    eet = jnp.asarray(r.uniform(0.5, 20, (S, M)).astype(np.float32))
    sysarr = SystemArrays(
        eet=eet,
        p_dyn=jnp.asarray(r.uniform(1, 10, M).astype(np.float32)),
        p_idle=jnp.asarray(r.uniform(0.1, 1, M).astype(np.float32)),
    )
    queue = np.full((M, Q), -1, np.int32)
    qlen = r.integers(0, Q + 1, M).astype(np.int32)
    for m in range(M):
        queue[m, :qlen[m]] = r.integers(0, N, qlen[m])
    view = MachineView(
        avail_base=jnp.asarray(r.uniform(0, 60, M).astype(np.float32)),
        queue=jnp.asarray(queue),
        qlen=jnp.asarray(qlen),
    )
    return SchedContext(
        now=jnp.float32(r.uniform(0, 50)),
        pending=jnp.asarray(r.integers(0, 2, N).astype(bool)),
        task_type=jnp.asarray(r.integers(0, S, N).astype(np.int32)),
        deadline=jnp.asarray(r.uniform(0, 120, N).astype(np.float32)),
        view=view,
        sysarr=sysarr,
        suffered=jnp.asarray(r.integers(0, 2, S).astype(bool)),
    )


@functools.lru_cache(maxsize=None)
def _select_pair(name: str, fair: bool):
    lax_pol = policy.get(name)
    if fair and not policy.describe(lax_pol).fairness:
        lax_pol = policy.with_fairness(lax_pol)
    fused = policy.with_pallas_map(lax_pol, interpret=True)
    assert isinstance(fused, FusedMapPolicy)
    return lax_pol, fused


def _assert_select_parity(name, fair, N, M, S, Q, seed):
    lax_pol, fused = _select_pair(name, fair)
    ctx = _rand_ctx(N, M, S, Q, seed)
    a, b = lax_pol.select(ctx), fused.select(ctx)
    for field in ("assign", "drop", "queue_drop"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, field)), np.asarray(getattr(b, field)),
            err_msg=f"{name} fair={fair} {field} "
                    f"N={N} M={M} S={S} Q={Q} seed={seed}")


@settings(max_examples=40, deadline=None)
@given(name=st.sampled_from(HEURISTICS),
       seed=st.integers(0, 2**31 - 1),
       dims=st.sampled_from([(50, 4, 4, 2), (130, 9, 5, 3), (64, 128, 4, 2)]))
def test_select_parity_random_contexts(name, seed, dims):
    """Fused == lax bit-for-bit, padded (M=4/9) and exact-lane (M=128)
    machine counts, every built-in heuristic."""
    N, M, S, Q = dims
    _assert_select_parity(name, False, N, M, S, Q, seed)


@settings(max_examples=25, deadline=None)
@given(name=st.sampled_from(("ELARE", "MM", "MSD", "MMU", "MET",
                             "MCT", "RANDOM")),
       seed=st.integers(0, 2**31 - 1))
def test_select_parity_fairness_wrapped(name, seed):
    """The Sec. V wrapper (eviction plan + priority Phase-II) stays
    bit-exact through the fused path, over every base heuristic."""
    _assert_select_parity(name, True, 80, 6, 4, 3, seed)


def test_with_pallas_map_noop_on_unsupported():
    """Policies outside the kernel kind space pass through unchanged."""
    import dataclasses

    @dataclasses.dataclass(frozen=True)
    class WeirdNominator:
        kind = "not_a_kernel_kind"

        def nominate(self, ctx):  # pragma: no cover - never called
            raise NotImplementedError

    weird = policy.TwoPhasePolicy(
        WeirdNominator(), policy.NominationValue(), policy.DropStale())
    assert policy.with_pallas_map(weird, interpret=True) is weird
    opaque = lambda *a: None  # noqa: E731 - opaque callable policy
    assert policy.with_pallas_map(opaque, interpret=True) is opaque
    with pytest.raises(ValueError, match="fused map kernel"):
        FusedMapPolicy(weird, interpret=True)


def test_with_pallas_map_backup_composition():
    """BackupPolicy keeps its k on the outside; the base is fused."""
    bp = faults.with_backup("FELARE", k=2)
    fused = policy.with_pallas_map(bp, interpret=True)
    assert fused.backup_k == 2
    assert isinstance(fused.base, FusedMapPolicy)
    assert fused.describe() == bp.describe()
    ctx = _rand_ctx(40, 5, 4, 2, 11)
    a, b = bp.select(ctx), fused.select(ctx)
    for field in ("assign", "drop", "queue_drop"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, field)), np.asarray(getattr(b, field)))


# ------------------------------------------------------------ trace level
@functools.lru_cache(maxsize=None)
def _sim_pair(fleet_name: str, heuristic: str):
    spec = (SPEC if fleet_name == "paper"
            else fleets.get_fleet(fleet_name).build())
    sysarr = spec.as_jax()
    lax_pol = policy.get(heuristic)
    fused = policy.with_pallas_map(lax_pol, interpret=True)
    kw = dict(queue_size=spec.queue_size,
              fairness_factor=float(spec.fairness_factor),
              site_of_machine=spec.sites)
    return (spec, jax.jit(engine.make_simulator(lax_pol, sysarr, **kw)),
            jax.jit(engine.make_simulator(fused, sysarr, **kw)))


@pytest.mark.parametrize("F", sorted(FLEETS))
@pytest.mark.parametrize("heuristic", ("ELARE", "FELARE", "MM", "RANDOM"))
def test_trace_parity_fleets(F, heuristic):
    """Full-trace metrics leaf equality, F in {1, 2, 8} (flat + the
    block-diagonal reshaped site views)."""
    spec, sim_lax, sim_fused = _sim_pair(FLEETS[F], heuristic)
    for seed in (0, 3):
        tr = _trace(seed, 150, 3.0, spec.eet)
        ok = _leaves_equal(sim_lax(tr), sim_fused(tr))
        assert all(ok), f"F={F} {heuristic} seed={seed}: {ok}"


@pytest.mark.parametrize("heuristic", ("ELARE", "FELARE"))
def test_trace_parity_masked_site_view(heuristic):
    """A non-contiguous partition forces the engine's masked-vmap site
    path (BIG-masked EET columns); the fused kernel must agree there too."""
    base = SPEC
    spec = SystemSpec(
        eet=base.eet, p_dyn=base.p_dyn, p_idle=base.p_idle,
        queue_size=base.queue_size,
        fairness_factor=float(base.fairness_factor),
        site_of_machine=(0, 1, 0, 1),  # interleaved: not block-reshapable
    )
    sysarr = spec.as_jax()
    lax_pol = policy.get(heuristic)
    fused = policy.with_pallas_map(lax_pol, interpret=True)
    kw = dict(queue_size=spec.queue_size,
              fairness_factor=float(spec.fairness_factor),
              site_of_machine=spec.sites)
    sim_lax = jax.jit(engine.make_simulator(lax_pol, sysarr, **kw))
    sim_fused = jax.jit(engine.make_simulator(fused, sysarr, **kw))
    tr = _trace(5, 120, 3.0, spec.eet)
    ok = _leaves_equal(sim_lax(tr), sim_fused(tr))
    assert all(ok), ok


@pytest.mark.parametrize("heuristic", ("ELARE", "FELARE"))
@pytest.mark.parametrize("seed", [0, 5])
def test_oracle_parity_metrics_and_task_log(heuristic, seed):
    """Fused-path full runs match the pure-Python oracle: count metrics
    byte-exact, task_log status/machine byte-exact, event times to f32
    round-off — and the task_log is *byte*-identical to the lax engine's.
    """
    tr = _trace(seed, 100, 3.0, SPEC.eet)
    fused = policy.with_pallas_map(policy.get(heuristic), interpret=True)
    m, aux = engine.simulate(tr, SPEC, fused, observers=("task_log",))
    m_lax, aux_lax = engine.simulate(tr, SPEC, heuristic,
                                     observers=("task_log",))
    # byte parity with the lax engine (metrics + full task log)
    assert all(_leaves_equal(m, m_lax))
    assert all(_leaves_equal(aux["task_log"], aux_lax["task_log"]))
    # oracle parity
    ref = pyengine.simulate(tr, SPEC, heuristic)
    for field in ("completed_by_type", "missed_by_type",
                  "cancelled_by_type", "arrived_by_type"):
        np.testing.assert_array_equal(
            np.asarray(getattr(m, field)), ref[field], err_msg=field)
    log = {k: np.asarray(v) for k, v in aux["task_log"].items()}
    np.testing.assert_array_equal(log["status"], ref["task_log"]["status"])
    np.testing.assert_array_equal(log["machine"],
                                  ref["task_log"]["machine"])
    for field in ("map_time", "start_time", "end_time"):
        np.testing.assert_allclose(
            log[field], ref["task_log"][field], rtol=1e-6, atol=1e-6,
            err_msg=field)


# --------------------------------------------------------------- dispatch
def _rand_dispatch_ctx(N, M, F, S, seed, with_alive=False):
    r = np.random.default_rng(seed)
    site_of_machine = np.sort(r.integers(0, F, M)).astype(np.int64)
    site_of_machine[:F] = np.arange(F)  # every site owns >= 1 machine
    site_of_machine = np.sort(site_of_machine)
    alive = None
    if with_alive:
        alive = jnp.asarray(r.integers(0, 2, M).astype(bool))
    return DispatchContext(
        now=jnp.float32(r.uniform(0, 50)),
        unassigned=jnp.asarray(r.integers(0, 2, N).astype(bool)),
        task_type=jnp.asarray(r.integers(0, S, N).astype(np.int32)),
        deadline=jnp.asarray(r.uniform(0, 120, N).astype(np.float32)),
        qlen=jnp.asarray(r.integers(0, 3, M).astype(np.int32)),
        running=jnp.asarray(r.integers(0, 2, M).astype(bool)),
        completed=jnp.asarray(r.integers(0, 20, S).astype(np.int32)),
        arrived=jnp.asarray(r.integers(20, 40, S).astype(np.int32)),
        eet=jnp.asarray(r.uniform(0.5, 20, (S, M)).astype(np.float32)),
        site_of_machine=site_of_machine,
        n_sites=F,
        fairness_factor=1.0,
        alive=alive,
    )


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       dims=st.sampled_from([(40, 6, 2, 4), (130, 16, 8, 4),
                             (64, 12, 3, 5)]))
def test_balance_scan_parity(seed, dims):
    """Fused balance kernel == the lax.scan walk, via sequential_balance's
    impl hook, dead-site penalties included."""
    import functools as ft

    from repro.kernels.map_fused import balance_scan

    N, M, F, S = dims
    impl = ft.partial(balance_scan, interpret=True)
    r = np.random.default_rng(seed ^ 0x5EED)
    for with_alive in (False, True):
        ctx = _rand_dispatch_ctx(N, M, F, S, seed, with_alive=with_alive)
        target = jnp.asarray(r.integers(0, 2, N).astype(bool))
        home = jnp.asarray(r.integers(0, F, N).astype(np.int32))
        ref = sequential_balance(ctx, target, home)
        got = sequential_balance(ctx, target, home, impl)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


@pytest.mark.parametrize("axis", (0, 1))
def test_min_first_keeps_lowest_index_on_ties(axis):
    """The kernels' argmin is a min plus the lowest index that holds it:
    jnp.argmin's tie-break, which a compiled TPU kernel's own argmin does
    not keep."""
    from repro.kernels.map_fused.kernel import min_first

    x = np.random.default_rng(0).integers(0, 3, (128, 128))
    x = x.astype(np.float32)
    x[:5] = 7.0
    m, i = min_first(jnp.asarray(x), axis=axis)
    np.testing.assert_array_equal(np.asarray(m), x.min(axis, keepdims=True))
    np.testing.assert_array_equal(
        np.asarray(i), np.argmin(x, axis=axis).reshape(m.shape))


@pytest.mark.parametrize("kind", ("least_queued", "fair_spill",
                                  "health_aware"))
def test_with_pallas_balance_dispatcher_parity(kind):
    lax_d = dispatch.get(kind)
    fused_d = dispatch.with_pallas_balance(lax_d, interpret=True)
    assert fused_d.balance_impl is not None
    for seed in (1, 2, 3):
        ctx = _rand_dispatch_ctx(90, 10, 4, 4, seed, with_alive=True)
        np.testing.assert_array_equal(
            np.asarray(lax_d.dispatch(ctx)),
            np.asarray(fused_d.dispatch(ctx)),
            err_msg=f"{kind} seed={seed}")


def test_with_pallas_balance_noop_and_serialization():
    """Scan-less dispatchers pass through; the ephemeral impl never
    serializes, and the JSON form round-trips to the lax default."""
    sticky = dispatch.get("sticky")
    assert dispatch.with_pallas_balance(sticky, interpret=True) is sticky
    fused_d = dispatch.with_pallas_balance("fair_spill", interpret=True)
    d = dispatch.to_json_dict(fused_d)
    assert "balance_impl" not in d
    back = dispatch.from_json_dict(d)
    assert back.balance_impl is None
    assert back.kind == "fair_spill"


# ------------------------------------------------------- backend selection
def test_default_interpret_env_override(monkeypatch):
    monkeypatch.setenv(pallas_backend.ENV_VAR, "1")
    assert pallas_backend.default_interpret() is True
    monkeypatch.setenv(pallas_backend.ENV_VAR, "0")
    assert pallas_backend.default_interpret() is False
    monkeypatch.setenv(pallas_backend.ENV_VAR, "yes")
    with pytest.raises(ValueError, match="REPRO_PALLAS_INTERPRET"):
        pallas_backend.default_interpret()
    monkeypatch.delenv(pallas_backend.ENV_VAR)
    expected = jax.default_backend() not in ("tpu", "gpu", "cuda", "rocm")
    assert pallas_backend.default_interpret() is expected


def test_spec_roundtrips_use_pallas_map():
    from repro.experiments.spec import SweepSpec

    spec = SweepSpec(use_pallas_map=True, n_tasks=10, reps=1,
                     rates=(2.0,), heuristics=("ELARE",))
    d = spec.to_json_dict()
    assert d["use_pallas_map"] is True
    back = SweepSpec.from_json_dict(d)
    assert back.use_pallas_map is True
