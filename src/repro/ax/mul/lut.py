"""Compiled lookup tables for approximate multipliers.

The multiplier-side twin of :mod:`repro.ax.lut`.  A multiplier's error
surface is *not* a function of operand low bits alone (the broken-array
vertical break and Mitchell's interpolation touch every bit), so unlike
the adder LUTs these tables cover the full ``2^N x 2^N`` operand domain
— which is why compilation is capped at :data:`MAX_MUL_LUT_BITS`
operand bits (a 10-bit signed MAC table is 4 MiB of int32; an 8-bit one
is 128 KiB of uint16).

Tables are process-cached per *canonical* spec (irrelevant knobs zeroed
via ``effective_*``) and returned read-only, exactly like the adder
tables.

Three table families:

* :func:`compile_mul_lut` — unsigned full products, indexed by
  ``(a << N) | b``; the ``lut`` strategy's gather operand.
* :func:`mul_error_delta_table` — signed ``approx - exact`` deltas over
  the same domain; the raw material for the exact analytics.
* :func:`signed_mul_table` / :func:`tap_tables` — signed
  (sign-magnitude) product tables for the numpy/jax MAC datapaths:
  matmul gathers the 2D table per (a, b) lane pair; conv2d gathers one
  1D per-tap column table per static kernel weight.  The Pallas MAC
  kernels compute the same sign-magnitude products in the kernel body
  (Mosaic lowers only 2-D gathers).
"""

from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

from repro.ax.mul.registry import get_multiplier
from repro.ax.mul.specs import MulSpec
from repro.integrity.digests import record_golden as _record_golden
from repro.obs.caches import register_lru as _register_lru

# Full-domain tables: 4^10 = 1M entries is the largest we compile.
MAX_MUL_LUT_BITS = 10

# Delta tables only feed the host-side exact analytics (never a gather
# strategy), so they extend past the LUT cap to the compose-analytics
# cap: 4^12 int32 = 64 MiB, transient when built via the nocache
# variant.  Keep in sync with repro.ax.analytics.MAX_MUL_COMPOSE_BITS.
MAX_MUL_DELTA_BITS = 12


def mul_lut_supported(spec: MulSpec) -> bool:
    """Whether the ``lut`` strategy can serve ``spec`` (exact kinds use
    the native multiply and are always supported)."""
    if spec.is_exact:
        return True
    return spec.n_bits <= MAX_MUL_LUT_BITS


def _canonical(spec: MulSpec) -> MulSpec:
    """Zero the knobs the kind ignores, so equivalent specs share one
    cached table."""
    return MulSpec(kind=spec.kind, n_bits=spec.n_bits,
                   trunc_bits=spec.effective_trunc_bits,
                   row_bits=spec.effective_row_bits)


def _check_compilable(spec: MulSpec) -> None:
    if spec.n_bits > MAX_MUL_LUT_BITS:
        raise ValueError(
            f"mul LUT limited to n_bits <= {MAX_MUL_LUT_BITS} "
            f"(4^N-entry tables), got n_bits={spec.n_bits}")


def _operand_grids(n_bits: int) -> Tuple[np.ndarray, np.ndarray]:
    """All (a, b) pairs as flat uint64 arrays, row-major in ``a``
    (matching the ``(a << N) | b`` index)."""
    vals = np.arange(1 << n_bits, dtype=np.uint64)
    a = np.repeat(vals, 1 << n_bits)
    b = np.tile(vals, 1 << n_bits)
    return a, b


def _mul_lut_nocache(spec: MulSpec) -> np.ndarray:
    _check_compilable(spec)
    a, b = _operand_grids(spec.n_bits)
    prod = get_multiplier(spec.kind).impl(a, b, spec)
    dtype = np.uint16 if spec.product_bits <= 16 else np.uint32
    table = prod.astype(dtype)
    table.flags.writeable = False
    return table


@functools.lru_cache(maxsize=None)
def _mul_lut_cached(spec: MulSpec) -> np.ndarray:
    from repro.integrity.store import cache_get, cache_put
    table = cache_get("ax.mul.lut.product", spec)
    if table is None:
        table = _mul_lut_nocache(spec)
        cache_put("ax.mul.lut.product", spec, table)
    return _record_golden("ax.mul.lut.product", (spec,), table,
                          functools.partial(_mul_lut_nocache, spec))


_register_lru("ax.mul.lut.product", _mul_lut_cached)


def compile_mul_lut(spec: MulSpec) -> np.ndarray:
    """Unsigned full-product table ``T[(a << N) | b] = approx(a, b)``."""
    return _mul_lut_cached(_canonical(spec))


def mul_error_delta_table_nocache(spec: MulSpec) -> np.ndarray:
    """Signed ``approx(a, b) - a*b`` over the full domain (int32;
    always <= 0 for the builtin kinds, kept signed for plugins)."""
    if spec.n_bits > MAX_MUL_DELTA_BITS:
        raise ValueError(
            f"mul delta table limited to n_bits <= {MAX_MUL_DELTA_BITS} "
            f"(4^N-entry tables), got n_bits={spec.n_bits}")
    a, b = _operand_grids(spec.n_bits)
    approx = get_multiplier(spec.kind).impl(a, b, spec).astype(np.int64)
    delta = (approx - (a * b).astype(np.int64)).astype(np.int32)
    delta.flags.writeable = False
    return delta


@functools.lru_cache(maxsize=None)
def _delta_cached(spec: MulSpec) -> np.ndarray:
    delta = mul_error_delta_table_nocache(spec)
    return _record_golden(
        "ax.mul.lut.delta", (spec,), delta,
        functools.partial(mul_error_delta_table_nocache, spec))


_register_lru("ax.mul.lut.delta", _delta_cached)


def mul_error_delta_table(spec: MulSpec) -> np.ndarray:
    return _delta_cached(_canonical(spec))


def mul_lut_index(a, b, n_bits: int):
    """Gather index for the full-domain tables (container arrays in,
    container indices out)."""
    mask = (1 << n_bits) - 1
    return ((a & mask) << n_bits) | (b & mask)


def lut_mul(a: np.ndarray, b: np.ndarray, spec: MulSpec) -> np.ndarray:
    """Host-side table-strategy multiply (numpy backend)."""
    if spec.is_exact:
        return a * b
    table = compile_mul_lut(spec)
    idx = np.asarray(mul_lut_index(a, b, spec.n_bits)).astype(np.int64)
    return table[idx].astype(np.asarray(a).dtype)


# ------------------------------------------------- signed MAC tables --

def _signed_table_nocache(spec: MulSpec) -> np.ndarray:
    _check_compilable(spec)
    n = spec.n_bits
    patt = np.arange(1 << n, dtype=np.int64)
    signed = np.where(patt >= (1 << (n - 1)), patt - (1 << n), patt)
    mag = np.abs(signed).astype(np.uint64)
    a = np.repeat(mag, 1 << n)
    b = np.tile(mag, 1 << n)
    prod = get_multiplier(spec.kind).impl(a, b, spec).astype(np.int64)
    sgn = np.sign(np.repeat(signed, 1 << n) * np.tile(signed, 1 << n))
    table = (sgn * prod).astype(np.int32)
    table.flags.writeable = False
    return table


@functools.lru_cache(maxsize=None)
def _signed_table_cached(spec: MulSpec) -> np.ndarray:
    from repro.integrity.store import cache_get, cache_put
    table = cache_get("ax.mul.lut.signed", spec)
    if table is None:
        table = _signed_table_nocache(spec)
        cache_put("ax.mul.lut.signed", spec, table)
    return _record_golden("ax.mul.lut.signed", (spec,), table,
                          functools.partial(_signed_table_nocache, spec))


_register_lru("ax.mul.lut.signed", _signed_table_cached)


def signed_mul_table(spec: MulSpec) -> np.ndarray:
    """Sign-magnitude product table for signed MAC datapaths.

    Indexed by ``((a & mask) << N) | (b & mask)`` where a, b are N-bit
    two's-complement lane patterns; the entry is
    ``sign(a)*sign(b)*approx(|a|, |b|)`` as int32.  Note ``|-2^(N-1)| =
    2^(N-1)`` still fits the N-bit unsigned operand domain of the
    implementations.
    """
    return _signed_table_cached(_canonical(spec))


def _tap_tables_nocache(spec: MulSpec,
                        weights: Tuple[int, ...]) -> np.ndarray:
    n = spec.n_bits
    limit = 1 << n
    for w in weights:
        if abs(w) >= limit:
            raise ValueError(
                f"kernel weight {w} exceeds the {n}-bit multiplier "
                f"operand range (|w| < {limit})")
    vals = np.arange(limit, dtype=np.uint64)
    entry = get_multiplier(spec.kind)
    rows = []
    for w in weights:
        prod = entry.impl(vals, np.uint64(abs(w)), spec).astype(np.int64)
        rows.append((prod if w >= 0 else -prod).astype(np.int32))
    table = np.stack(rows)
    table.flags.writeable = False
    return table


@functools.lru_cache(maxsize=None)
def _tap_tables_cached(spec: MulSpec,
                       weights: Tuple[int, ...]) -> np.ndarray:
    table = _tap_tables_nocache(spec, weights)
    return _record_golden(
        "ax.mul.lut.taps", (spec, weights), table,
        functools.partial(_tap_tables_nocache, spec, weights))


_register_lru("ax.mul.lut.taps", _tap_tables_cached)


def tap_tables(spec: MulSpec, weights: Tuple[int, ...]) -> np.ndarray:
    """Per-tap signed product columns for conv2d: ``T[t][v] =
    sign(w_t) * approx(v, |w_t|)`` for input magnitudes ``v``, shaped
    ``(len(weights), 2^N)`` int32.

    One gather per tap replaces the multiplier entirely at runtime on
    the numpy/jax conv datapaths; the Pallas kernel evaluates the same
    entries in-kernel, held to these tables by the cross-backend tests.
    """
    return _tap_tables_cached(_canonical(spec), tuple(int(w)
                                                      for w in weights))
