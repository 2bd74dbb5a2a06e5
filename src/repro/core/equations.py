"""The paper's closed-form scheduling math (Eqs. 1-4), vectorized.

All functions broadcast over arbitrary leading dims; the canonical use is
(N_tasks, M_machines) grids at a mapping event.

Feasibility note: Eq. 1 of the paper uses strict ``<`` in the first row and
Algorithm 2 tests ``c_ij <= delta_i`` — under the middle row (``c = delta``)
that test is vacuously true, which is a pseudo-code slip. We define a pair
feasible iff ``s + e <= delta`` (the task can fully execute before its
deadline), the only reading consistent with the prose.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def completion_time(start, exec_time, deadline):
    """Eq. 1 — expected completion time of a task mapped at ``start``.

    Three regimes: finishes on time (s+e <= d); killed at its deadline
    mid-execution (s < d < s+e); dropped before starting (s >= d).
    """
    s, e, d = jnp.broadcast_arrays(
        jnp.asarray(start, jnp.float32),
        jnp.asarray(exec_time, jnp.float32),
        jnp.asarray(deadline, jnp.float32),
    )
    on_time = s + e <= d
    started = s < d
    return jnp.where(on_time, s + e, jnp.where(started, d, s))


def feasible(start, exec_time, deadline):
    """A [task, machine] pair is feasible iff it completes by the deadline."""
    return jnp.asarray(start, jnp.float32) + exec_time <= deadline


def expected_energy(start, exec_time, deadline, p_dyn):
    """Eq. 2 — expected dynamic energy of executing the pair.

    Feasible: p_dyn * e.  Killed mid-run: p_dyn * (d - s) — pure waste.
    Never started (s >= d): 0.
    """
    s, e, d, p = jnp.broadcast_arrays(
        jnp.asarray(start, jnp.float32),
        jnp.asarray(exec_time, jnp.float32),
        jnp.asarray(deadline, jnp.float32),
        jnp.asarray(p_dyn, jnp.float32),
    )
    on_time = s + e <= d
    started = s < d
    return jnp.where(on_time, p * e, jnp.where(started, p * (d - s), 0.0))


def fairness_limit(completion_rates, fairness_factor):
    """Eq. 3 — epsilon = mu - f * sigma over per-type completion rates.

    ``f`` large => epsilon -> 0 => fairness disabled. Clamped at 0 so a huge
    ``f`` never produces a negative (meaningless) limit.

    Rounded as IEEE float32 in a written order, on every backend:
    ``mu = (((cr_0 + cr_1) + cr_2) + ...) / S``, the same for the squared
    deviations, then :func:`sqrt_f32`. That is numpy's float32 ``mean`` and
    ``std`` for S < 8 (numpy sums 8 or more terms pairwise). Only f32 add,
    compare and scaling by a power of two are left to the backend.
    ``jnp.mean``/``std`` would leave it the reduction order, the divide and
    the root, which a TPU rounds otherwise than IEEE; a product that feeds
    an add is made in integers too, since the CPU's XLA fuses ``a * b + c``
    into one multiply-add, rounded once.
    """
    cr = jnp.asarray(completion_rates, jnp.float32).reshape(-1)
    n = cr.shape[0]
    mu = _div_count(_sum_in_order(cr), n)
    d = cr - mu
    sigma = sqrt_f32(_div_count(_sum_in_order(mul_f32(d, d)), n))
    return jnp.maximum(mu - mul_f32(fairness_factor, sigma), 0.0)


def _sum_in_order(x):
    """x[0] + x[1] + ... + x[n-1], added left to right."""
    total = x[0]
    for i in range(1, x.shape[0]):
        total = total + x[i]
    return total


def _unrolled(n: int, body, carry):
    """``body(i, carry)`` for i in 0..n-1, traced once and unrolled by
    the compiler into straight-line code."""
    return lax.fori_loop(0, n, body, carry, unroll=True)


def _bits(x):
    return lax.bitcast_convert_type(x, jnp.int32)


def _float(bits):
    return lax.bitcast_convert_type(bits, jnp.float32)


def _subnormal(bits):
    """A float32's bits that read 0 or subnormal: a zero exponent field."""
    return (bits & 0x7F800000) == 0


def ratio_f32(num, den):
    """IEEE float32 ``num / den`` of int32 counts, from integer ops alone.

    For ``0 <= num`` and ``0 < den`` below 2**30. The operands are scaled
    by powers of two so that their quotient lies in [1, 2); a long division
    gives its 24 bits, the remainder rounds them to nearest (ties to even),
    and the exponent and mantissa are put together by bitcast.
    """
    num, den = jnp.broadcast_arrays(jnp.asarray(num, jnp.int32),
                                    jnp.asarray(den, jnp.int32))
    k = lax.clz(num) - lax.clz(den)  # aligns the leading bits
    n = num << jnp.maximum(k, 0)
    d = den << jnp.maximum(-k, 0)
    low = (n < d).astype(jnp.int32)
    r = n << low                      # r / d in [1, 2)
    exp = -(k + low)                  # num / den = r / d * 2**exp

    def digit(_, mr):
        m, r = mr
        bit = (r >= d).astype(jnp.int32)
        return (m << 1) | bit, (r - bit * d) << 1  # r: twice the remainder

    m, r = _unrolled(24, digit, (jnp.zeros_like(r), r))
    up = (r > d) | ((r == d) & ((m & 1) == 1))
    q = _float(((exp + 127) << 23) + (m - (1 << 23)) + up.astype(jnp.int32))
    return jnp.where(num == 0, jnp.float32(0.0), q)


def mul_f32(x, y):
    """IEEE float32 ``x * y`` from integer ops alone: the 48-bit product of
    the mantissas in 12-bit halves, rounded to 24 bits (ties to even).
    Subnormal operands and products flush to zero, as on a TPU."""
    bx = _bits(jnp.asarray(x, jnp.float32))
    by = _bits(jnp.asarray(y, jnp.float32))
    sign = (bx ^ by) & jnp.int32(-2**31)
    mx = (bx & 0x7FFFFF) | 0x800000
    my = (by & 0x7FFFFF) | 0x800000
    xh, xl, yh, yl = mx >> 12, mx & 0xFFF, my >> 12, my & 0xFFF
    mid = xh * yl + xl * yh
    low = xl * yl + ((mid & 0xFFF) << 12)
    top = xh * yh + (mid >> 12) + (low >> 24)  # product >> 24
    low = low & 0xFFFFFF                       # product mod 2**24
    short = top < (1 << 23)                    # product below 2**47
    mant = jnp.where(short, (top << 1) | (low >> 23), top)
    rest = jnp.where(short, (low & 0x7FFFFF) << 1, low)  # out of 2**24
    up = (rest > (1 << 23)) | ((rest == (1 << 23)) & ((mant & 1) == 1))
    field = ((bx >> 23) & 0xFF) + ((by >> 23) & 0xFF) - 126 - short
    q = (jnp.minimum(field, 255) << 23) + (mant - (1 << 23)) + up.astype(
        jnp.int32)
    q = sign | jnp.minimum(q, 0x7F800000)      # past the largest: inf
    zero = (_subnormal(bx) | _subnormal(by) | (field <= 0))
    return jnp.where(zero, _float(sign), _float(q))


def sqrt_f32(x):
    """IEEE float32 square root of a float32 >= 0, from integer ops alone;
    a subnormal flushes to zero, as on a TPU.

    ``x = M * 2**(2p)`` with an integer M in [2**24, 2**26); the digit-by-
    digit root of ``M * 2**24`` gives 25 bits, rounded to 24. A tie cannot
    occur: it would need ``M * 2**24`` to be the square of an odd number.
    """
    bits = _bits(jnp.asarray(x, jnp.float32))
    e = (bits >> 23) - 127
    odd = e & 1
    mant = ((bits & 0x7FFFFF) | 0x800000) << (1 + odd)

    def digit(j, rr):  # bit pair j of mant * 2**24, from the top
        root, rem = rr
        pair = jnp.where(j <= 12, (mant >> jnp.maximum(24 - 2 * j, 0)) & 3, 0)
        rem = (rem << 2) | pair
        trial = (root << 2) | 1
        ge = rem >= trial
        return ((root << 1) | ge.astype(jnp.int32),
                jnp.where(ge, rem - trial, rem))

    zero = jnp.zeros_like(bits)
    root, _ = _unrolled(25, digit, (zero, zero))
    half = (e - 24 - odd) >> 1        # x = mant * 2**(2 * half)
    s = _float(((half + 139) << 23) + ((root + 1) >> 1) - (1 << 23))
    return jnp.where(_subnormal(bits), jnp.float32(0.0), s)


def _div_count(x, n: int):
    """IEEE float32 ``x / n`` for a float32 x >= 0 and a count n: exact
    scaling for a power of two, else the mantissa's :func:`ratio_f32` with
    x's exponent added back (a subnormal x or quotient reads 0)."""
    if n & (n - 1) == 0:
        return x * jnp.float32(1.0 / n)
    bits = _bits(x)
    mant = (bits & 0x7FFFFF) | 0x800000
    q = _bits(ratio_f32(mant, n)) + (((bits >> 23) - 150) << 23)
    return jnp.where(_subnormal(bits) | (q < (1 << 23)), jnp.float32(0.0),
                     _float(q))


def deadlines(arrival, task_type, eet):
    """Eq. 4 — delta_i(k) = arr_k + e_bar_i + e_bar.

    e_bar_i = mean over machines of EET row i; e_bar = mean of e_bar_i.
    """
    eet = jnp.asarray(eet, jnp.float32)
    e_bar_i = eet.mean(axis=1)          # (S,)
    e_bar = e_bar_i.mean()              # ()
    return jnp.asarray(arrival, jnp.float32) + e_bar_i[task_type] + e_bar


def urgency(deadline, exec_time, now):
    """MMU's urgency metric: 1 / (delta - e). Higher = more urgent.

    Negative slack (cannot finish) yields a negative urgency => lowest
    priority, matching the baseline's intent. ``now`` shifts slack to be
    relative to the current mapping event.
    """
    slack = deadline - now - exec_time
    return 1.0 / jnp.where(jnp.abs(slack) < 1e-9, 1e-9, slack)
