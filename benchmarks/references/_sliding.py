"""Shared arithmetic of the two window references: sums over a sliding
range of a sorted column, exact enough to stand as the truth.

A float32 price is split into a part that is a whole multiple of 2**-20
(summed as int64: exact) and a remainder below 2**-20 (summed in float64:
the whole running total stays under 2**-20 * rows, so its rounding is
below 1e-16 * that). ``hi + lo`` is then the window's sum to about one
float64 ulp, whatever the order the program adds in.
"""

from __future__ import annotations

import numpy as np

_SCALE = float(2 ** 20)


def stable_order(key: np.ndarray) -> np.ndarray:
    """Stable argsort of small non-negative integers; numpy sorts 16-bit
    integers by radix, several times faster than it merges 64-bit ones."""
    small = key.max(initial=0) < 2 ** 15
    return np.argsort(key.astype(np.int16) if small else key, kind="stable")


def run_starts(k: np.ndarray) -> np.ndarray:
    """For every position of a sorted array, where its run of equal
    values begins."""
    starts = np.zeros(len(k), np.int64)
    new = np.flatnonzero(k[1:] != k[:-1]) + 1
    starts[new] = new
    return np.maximum.accumulate(starts, out=starts)


def _prefix(x):
    """[0, x0, x0 + x1, ...] in x's own type."""
    c = np.empty(len(x) + 1, x.dtype)
    c[0] = 0
    np.cumsum(x, out=c[1:])
    return c


def tail_sums(values: np.ndarray, first: np.ndarray):
    """sum(values[first[i] : i + 1]) for every i. Floats come back as
    float64 (see above), integers exact as int64."""
    if np.issubdtype(values.dtype, np.integer):
        c = _prefix(values.astype(np.int64, copy=False))
        return c[1:] - c[first]
    v = values.astype(np.float64)
    hi = np.floor(v * _SCALE)
    v -= hi / _SCALE
    chi, clo = _prefix(hi.astype(np.int64)), _prefix(v)
    out = (chi[1:] - chi[first]).astype(np.float64)
    out /= _SCALE
    out += clo[1:] - clo[first]
    return out


def in_precision(total: np.ndarray, count: np.ndarray, dtype: str):
    """total / count as an implementation holding its sums in ``dtype``
    would give it AT BEST: the exact sum rounded once to ``dtype``, the
    division made there. Any real accumulation in ``dtype`` rounds more.
    float64 is the reference itself."""
    if dtype == "float64":
        return total / count
    import ml_dtypes  # ships with jax; numpy has no bfloat16 of its own

    dt = np.dtype(getattr(ml_dtypes, dtype, None) or dtype)
    return (total.astype(dt) / count.astype(dt)).astype(np.float64)
