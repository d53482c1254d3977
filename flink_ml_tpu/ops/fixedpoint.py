"""Exact sums of float32 values by fixed-point digits.

A float32 accumulator cannot carry a sum of a million continuous values,
and the MXU multiplies bfloat16. What both can do exactly is add small
whole numbers: a value ``w`` with ``|w| <= 1`` is cut into digits, each a
multiple of ``2**(-8 k)`` of at most 256 units, which bfloat16 holds whole;
a one-hot product adds the units of a tile's rows by group in float32
without rounding (they stay under 2**24), and tiles add up in int32 with a
carry. The sum that comes out does not depend on the order of the rows,
the tiles or the shards. Shared by the Pallas kernel
(``pallas_kernels.grouped_moments``) and its XLA twin
(``ops/stats.py``), which so return the same integers.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

#: fixed-point digits of a scaled value ``w`` (``|w| <= 1``) and of its
#: float32 square. Four digits carry either to ``2**-33``: every bit of an
#: element down to ``2**-9`` of its column's scale (the scale is up to four
#: times the column's reach: three digits lost the last bits of elements at
#: the top of the range), and a square to under the float32 product's own
#: rounding for every ``|w| > 2**-4.5``
MOMENTS_DIGITS = (4, 4)
#: a digit's units add up in int32 as ``lo + (hi << MOMENTS_CARRY_BITS)``
#: with ``0 <= lo < 2**20``: a tile adds at most ``2**20`` units (4096 rows
#: of 256), ``hi`` stays under ``2**19`` to ``2**31`` rows, and the ``lo`` of
#: up to 1024 shards add up in int32
MOMENTS_CARRY_BITS = 20
#: rows whose units one float32 sum holds exactly: ``65536 * 256 = 2**24``
MAX_EXACT_ROWS = 1 << 16


def fixed_digits(w, count: int, magic: bool = False):
    """``count`` float32 arrays whose sum is ``w`` to within ``2**-(8 count +
    1)``: the ``k``-th is what is left of ``w`` rounded to the nearest
    multiple of ``2**(-8 k)``, to even on ties. For ``|w| <= 1`` the first
    is at most 256 units of ``2**-8`` and every later one at most 128 of
    its own, so each is whole in bfloat16 and the units of
    ``MAX_EXACT_ROWS`` of them add up exactly in float32.

    ``magic`` rounds by adding ``1.5 * 2**(23 - 8 k)`` and taking it off
    again (the sum rounds a float32 to that multiple, the difference is
    exact): two operations where scale, round and scale back are three, to
    the same digits. It is for a Pallas kernel alone: XLA's simplifier folds
    the two constants into one and the rounding is gone."""
    parts, rest = [], w
    for k in range(1, count + 1):
        if magic:
            c = jnp.float32(1.5 * 2.0 ** (23 - 8 * k))
            part = (rest + c) - c
        else:
            part = jnp.round(rest * jnp.float32(2.0 ** (8 * k))
                             ) * jnp.float32(2.0 ** (-8 * k))
        parts.append(part)
        rest = rest - part
    return parts


def moment_digits(w, magic: bool = False):
    """``[(k, digit)]``: the ``MOMENTS_DIGITS`` digits of ``w``, then those
    of the float32 ``w * w``, each with its 1-based place ``k`` (a unit of
    it is ``2**(-8 k)``)."""
    return (list(enumerate(fixed_digits(w, MOMENTS_DIGITS[0], magic), 1))
            + list(enumerate(fixed_digits(w * w, MOMENTS_DIGITS[1], magic),
                             1)))


def add_units(lo, hi, units):
    """``(lo, hi)`` with ``units`` (int32) added and the carry moved up."""
    lo = lo + units
    carry = lo >> MOMENTS_CARRY_BITS
    return lo - (carry << MOMENTS_CARRY_BITS), hi + carry


def digits_value(lo, hi) -> np.ndarray:
    """The float64 value of ``(count, ...)`` int32 digit sums, ``lo + (hi <<
    MOMENTS_CARRY_BITS)`` units of ``2**(-8 k)`` each, on the host. The
    units are put together in int64 first (four digits: to 2**31 rows):
    exact to 2**21 rows, and to float64's own last place beyond."""
    lo, hi = np.asarray(lo, np.int64), np.asarray(hi, np.int64)
    count = lo.shape[0]
    total = np.zeros(lo.shape[1:], np.int64)
    for k in range(count):
        total += (lo[k] + (hi[k] << MOMENTS_CARRY_BITS)) << (
            8 * (count - 1 - k))
    return total.astype(np.float64) * 2.0 ** (-8 * count)
