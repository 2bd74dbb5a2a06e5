"""The traffic generator: one sweep unit's trace grid, made on the device.

A traffic file (``bench/traffic/<name>.json``) names one piece per role
under ``"generator"``, each a module ``bench/generators/<kind>.py`` with a
``ROLE`` and its sampling function; the other keys of a piece are that
function's parameters. The composition below is the program's
``Scenario.sample_trace``/``stack`` copied: one 3-way key split per trace
(arrivals, types, run times), replicate ``k`` reusing its key at every
rate, the (rates x reps) grid flattened rate-major as ``run_sweep`` does.

Arrivals, deadlines and run times are then rounded to multiples of
``QUANTUM`` (2**-12 s, a quarter of a millisecond). Every event time, a
sum of such multiples below ``TIME_LIMIT`` (2**24 quanta, 4096 s), is
then exact in float32 and float64 alike, so the engine and the plain
reference (``bench/reference.py``) do the same arithmetic. The quantum is
the finest power of two that keeps that true for traces as long as the
paper's slowest (2000 tasks at 2 tasks/s, about 1000 s), so arrivals
rarely share a time that the unrounded traffic would not.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp

HERE = Path(__file__).resolve().parent
QUANTUM = 2.0**-12
TIME_LIMIT = QUANTUM * 2**24
# role -> the function a piece of that role defines
ROLES = {"arrivals": "sample", "mix": "sample", "deadline": "deadlines",
         "runtime": "sample"}


def load_piece(kind: str):
    """The generator module ``bench/generators/<kind>.py``."""
    path = HERE / f"{kind}.py"
    if not path.is_file():
        raise SystemExit(f"no generator {kind!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_generator_{kind}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bind(piece: dict, role: str):
    params = {k: v for k, v in piece.items() if k != "kind"}
    mod = load_piece(piece["kind"])
    if mod.ROLE != role:
        raise SystemExit(f"generator {piece['kind']!r} is a {mod.ROLE} "
                         f"piece, not {role}")
    fn = getattr(mod, ROLES[role])
    return functools.partial(fn, **params) if params else fn


def make_stack(generator: dict):
    """``stack(key, rates, reps, n_tasks, eet)`` -> dict of (R, K, ...)
    arrays, the program's ``Scenario.stack`` for these pieces."""
    arrivals, mix, deadline, runtime = (
        _bind(generator[r], r) for r in ROLES)

    def sample_trace(key, n_tasks, rate, eet):
        k_arr, k_type, k_exec = jax.random.split(key, 3)
        arrival = arrivals(k_arr, n_tasks, rate)
        task_type = mix(k_type, n_tasks, eet.shape[0])
        return dict(arrival=arrival, task_type=task_type,
                    deadline=deadline(arrival, task_type, eet),
                    exec_actual=runtime(k_exec, eet, task_type))

    def stack(key, rates, reps, n_tasks, eet):
        eet = jnp.asarray(eet, jnp.float32)
        rep_keys = jax.random.split(key, reps)
        rates_arr = jnp.asarray(rates, jnp.float32)
        one = lambda rate, k: sample_trace(k, n_tasks, rate, eet)
        over_reps = jax.vmap(one, in_axes=(None, 0))
        return jax.vmap(over_reps, in_axes=(0, None))(rates_arr, rep_keys)

    return stack


def unit_key(seed_lo, seed_hi, unit):
    """The key of unit ``unit``'s traces under a seed split into two
    uint32 words (the seed may not fit 32 signed bits)."""
    key = jax.random.PRNGKey(0)
    for word in (seed_lo, seed_hi, unit):
        key = jax.random.fold_in(key, word)
    return key


def make_unit_generator(traffic: dict, eet):
    """One jitted call ``gen(seed_lo, seed_hi, unit)`` -> flat traces.

    Unit ``u``'s traces are drawn from the seed and ``u``. Leaves have a
    leading batch of rates x reps, rate-major, and times are multiples of
    ``QUANTUM``."""
    stack = make_stack(traffic["generator"])
    rates = tuple(float(r) for r in traffic["rates"])
    reps, n_tasks = int(traffic["reps"]), int(traffic["n_tasks"])
    eet = jnp.asarray(eet, jnp.float32)

    @jax.jit
    def gen(seed_lo, seed_hi, unit):
        g = stack(unit_key(seed_lo, seed_hi, unit), rates, reps, n_tasks,
                  eet)
        g = {k: v.reshape((len(rates) * reps,) + v.shape[2:])
             for k, v in g.items()}
        for k in ("arrival", "deadline", "exec_actual"):
            g[k] = (jnp.round(g[k] / QUANTUM) * QUANTUM).astype(jnp.float32)
        return g

    return gen
