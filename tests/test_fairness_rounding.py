"""Eq. 3 rounded as IEEE float32, in the reference's order, on every backend.

``fairness.suffered_types`` decides whether a task type has suffered from
completion rates (a divide), their mean and standard deviation (a root).
A TPU rounds float32 divides and roots otherwise than IEEE, the CPU's XLA
fuses a multiply into the add that follows it, and either may order a
reduction as it likes; an exact tie such as rates (1/3, 1/7, 1/3, 1/7),
where mean - std equals 1/7, then falls either way. The program builds
quotients, products and the root from integer ops and adds in a written
order. Pinned here against numpy's float32, which is IEEE:

  * ``ratio_f32`` on every pair 0 <= c <= a <= 2048, and beyond;
  * ``mul_f32`` and ``sqrt_f32`` on seeded values and every variance met
    below;
  * ``suffered_types`` against a numpy statement of the reference's
    ``suffered_mask`` (``bench/reference.py``) on every equal-pairs tuple
    with arrivals <= 64 and on 10**5 seeded tuples, at f = 0.5, 1 and 2.

The tuples and the numpy statement are ``chip_smoke.py``'s, which runs the
same comparisons on the TPU.
"""
import importlib.util
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import equations, fairness


def _chip_smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


SMOKE = _chip_smoke()
EQ3_FACTORS, eq3_numpy = SMOKE.EQ3_FACTORS, SMOKE.eq3_numpy
F32 = np.float32
TUPLES = {"equal_pairs": SMOKE.eq3_equal_pairs, "seeded": SMOKE.eq3_seeded}


def _bits(x):
    return np.asarray(x, F32).view(np.int32)


def _reference_mask(c, a, f):
    """``bench/reference.py``'s ``suffered_mask``, word for word, on one
    tuple."""
    R = F32
    cr = [R(R(c[i]) / R(max(a[i], 1))) if a[i] > 0 else R(1.0)
          for i in range(len(c))]
    mu = R(np.mean(np.asarray(cr, np.float32), dtype=np.float32))
    sigma = R(np.std(np.asarray(cr, np.float32), dtype=np.float32))
    eps = max(R(mu - R(R(f) * sigma)), R(0.0))
    return [bool(cr[i] <= eps and a[i] >= 1) for i in range(len(c))]


_program = jax.jit(jax.vmap(fairness.suffered_types, in_axes=(0, 0, None)),
                   static_argnums=2)


def test_the_tie_of_the_chip_is_not_suffered():
    """Counts (1, 1, 3, 1) of (3, 7, 9, 7): mean - std equals 1/7 exactly;
    in IEEE float32 the limit lies below the rate 1/7, so no type is
    suffered, as the reference says."""
    c, a = np.array([1, 1, 3, 1]), np.array([3, 7, 9, 7])
    cr = fairness.completion_rates(c, a)
    eps = equations.fairness_limit(cr, 1.0)
    assert float(eps) == float.fromhex("0x1.249248p-3")
    assert float(cr[1]) == float.fromhex("0x1.24924ap-3")
    assert not np.asarray(fairness.suffered_types(c, a, 1.0)).any()
    assert _reference_mask(c, a, 1.0) == [False] * 4


def test_ratio_equals_numpy_on_every_pair_to_2048():
    n = np.arange(2049)
    c, a = np.meshgrid(n, n, indexing="ij")
    keep = (c <= a) & (a > 0)
    c, a = c[keep].astype(np.int32), a[keep].astype(np.int32)
    got = jax.jit(equations.ratio_f32)(c, a)
    np.testing.assert_array_equal(_bits(got),
                                  _bits(c.astype(F32) / a.astype(F32)))
    rates = jax.jit(fairness.completion_rates)(c, a)
    np.testing.assert_array_equal(_bits(rates), _bits(got))


def test_ratio_beyond_the_counts_of_a_trace():
    rng = np.random.default_rng(3)
    a = rng.integers(1, 2**24, 10**5).astype(np.int32)
    c = rng.integers(0, 2**24, 10**5).astype(np.int32)  # above 1 too
    got = jax.jit(equations.ratio_f32)(c, a)
    np.testing.assert_array_equal(_bits(got),
                                  _bits(c.astype(F32) / a.astype(F32)))


def test_product_equals_numpy():
    rng = np.random.default_rng(24)
    x, y = (np.exp2(rng.uniform(-40.0, 20.0, (2, 10**6)))
            * rng.choice([-1.0, 1.0], (2, 10**6))).astype(F32)
    x[:50], y[25:75] = 0.0, -0.0
    got = jax.jit(equations.mul_f32)(x, y)
    np.testing.assert_array_equal(_bits(got), _bits(x * y))
    # mantissas whose product ends in a tie
    m = rng.integers(2**23, 2**24, (2, 10**5))
    x, y = np.ldexp(m[0], -23).astype(F32), np.ldexp(m[1] | 1, -3).astype(F32)
    np.testing.assert_array_equal(_bits(jax.jit(equations.mul_f32)(x, y)),
                                  _bits(x * y))


def test_out_of_the_normal_range():
    """Overflow reads inf; subnormal operands and results read 0, as a TPU
    flushes them; neither moves the limit of rates."""
    mul, root = jax.jit(equations.mul_f32), jax.jit(equations.sqrt_f32)
    assert float(mul(F32(3e38), F32(10.0))) == np.inf
    assert float(mul(F32(-3e38), F32(10.0))) == -np.inf
    assert float(mul(F32(1e-20), F32(1e-20))) == 0.0  # 1e-40 is subnormal
    assert float(mul(F32(1e-40), F32(1e10))) == 0.0
    assert float(root(F32(1e-40))) == 0.0
    limit = equations.fairness_limit(np.array([0.0, 0.5]), 1.1754944e-38)
    assert float(limit) == 0.25


def test_sqrt_equals_numpy():
    rng = np.random.default_rng(40)
    x = [np.exp2(rng.uniform(-40.0, 0.0, 10**6)).astype(F32),
         F32([0.0, 2.0**-40, 0.25, 1.0])]
    x += [eq3_numpy(*make(), 1.0)[1] for make in TUPLES.values()]
    x = np.concatenate(x)
    got = jax.jit(equations.sqrt_f32)(x)
    np.testing.assert_array_equal(_bits(got), _bits(np.sqrt(x)))


@pytest.mark.parametrize("n", [3, 5, 7, 12])
def test_limit_divides_by_any_type_count(n):
    rng = np.random.default_rng(n)
    cr = rng.random((2000, n)).astype(F32)
    got = jax.jit(jax.vmap(equations.fairness_limit, in_axes=(0, None)))(
        cr, 1.5)
    mu = cr[:, 0]
    for i in range(1, n):  # left to right, as the program adds
        mu = mu + cr[:, i]
    mu = mu / F32(n)
    dd = (cr - mu[:, None]) ** 2
    var = dd[:, 0]
    for i in range(1, n):
        var = var + dd[:, i]
    want = np.maximum(mu - F32(1.5) * np.sqrt(var / F32(n)), F32(0.0))
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("f", EQ3_FACTORS)
@pytest.mark.parametrize("kind", sorted(TUPLES))
def test_suffered_types_equal_ieee_float32(kind, f):
    c, a = TUPLES[kind]()
    want = eq3_numpy(c, a, f)[2]
    got = np.asarray(_program(c, a, f))
    bad = np.flatnonzero((got != want).any(1))
    assert not bad.size, (len(bad), c[bad[:3]], a[bad[:3]])
    assert want.any() and not want.all()
    # the numpy statement is the reference's, tuple by tuple
    for t in np.random.default_rng(7).choice(len(c), 300, replace=False):
        assert _reference_mask(c[t], a[t], f) == list(want[t]), (c[t], a[t])
