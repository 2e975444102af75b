"""Execution backends for the approximate-arithmetic engine.

A backend is a named execution target for the registered adders:

- ``"numpy"``      host-side uint64 behavioral simulation (the Table-I
                   error/Monte-Carlo path and the image FFT pipeline).
- ``"jax"``        jitted elementwise emulation on jax arrays (the model
                   integration path: residual adds, reductions).
- ``"pallas"``     Pallas kernels in interpret mode (CPU validation of
                   the fused TPU kernels).
- ``"pallas_tpu"`` Pallas kernels compiled through Mosaic (TPU).

Orthogonal to the backend, every add-shaped primitive takes an execution
*strategy* — how the adder's bit-level function is evaluated:

- ``"reference"``  the registered bit-level oracle (portable operators).
- ``"fused"``      the registered algebraically-fused variant where one
                   exists (bit-identical, fewer vector ops; kinds
                   without one fall back to the reference form).
- ``"lut"``        the compiled ``2^m x 2^m`` low-part table
                   (:mod:`repro.ax.lut`): one gather + one exact high
                   add.  numpy and jax backends only: a VMEM table
                   gather has no Mosaic lowering, so the Pallas
                   backends refuse it.

All strategies and backends are bit-identical for the ops they share —
enforced by the cross-strategy/cross-backend sweeps in
``tests/test_ax.py`` and ``tests/test_lut.py``.

Backends replace the ad-hoc ``interpret: bool`` flags and the pad/reshape
plumbing previously duplicated in ``repro.kernels.ops``: call sites name
a backend (or let :func:`default_backend_name` auto-detect) and the
padding/tiling details live here, once.
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.ax import lut as lut_lib
from repro.ax.mul import lut as mul_lut_lib
from repro.ax.mul.impls import approx_mul
from repro.ax.mul.registry import get_multiplier
from repro.ax.mul.specs import MulSpec
from repro.ax.registry import get_adder
from repro.core.adders import approx_add, approx_add_mod
from repro.core.specs import AdderSpec

TWIDDLE_FRAC = 14

#: Legal execution strategies for the add-shaped primitives.
STRATEGIES = ("reference", "fused", "lut")

#: Placeholder accepted everywhere a strategy is: resolves to the
#: backend's fastest known concrete strategy at engine construction
#: (``Backend.preferred_strategy``) — engines only ever STORE one of
#: :data:`STRATEGIES`.
AUTO_STRATEGY = "auto"


def check_strategy(strategy: str) -> str:
    if strategy not in STRATEGIES and strategy != AUTO_STRATEGY:
        raise ValueError(
            f"unknown strategy {strategy!r}; one of "
            f"{STRATEGIES + (AUTO_STRATEGY,)}")
    return strategy


def resolve_strategy(strategy, fast: bool) -> str:
    """THE mapping from the back-compat ``fast`` flag to a strategy
    name: an explicit ``strategy`` wins, else ``fast`` picks fused.
    Every entry point that still accepts ``fast=`` resolves through
    here, so the alias lives in exactly one place.  (``"auto"`` passes
    through; it becomes concrete once a backend is known —
    ``make_engine``.)"""
    if strategy is None:
        strategy = "fused" if fast else "reference"
    return check_strategy(strategy)


def _require_concrete(strategy: str) -> str:
    """Backend methods take CONCRETE strategies only: the "auto"
    placeholder is resolved by ``make_engine``/``AxEngine.replace``
    (which know the backend); letting it through here would silently
    run the slowest reference path."""
    if strategy == AUTO_STRATEGY:
        raise ValueError(
            "strategy='auto' is resolved at engine construction "
            "(make_engine); Backend methods take one of "
            f"{STRATEGIES} — or call Backend.preferred_strategy(spec)")
    return strategy


def _fast(strategy: str) -> bool:
    """The ``fast`` flag the behavioral models take (lut handled above)."""
    return _require_concrete(strategy) == "fused"


def _use_lut(spec: AdderSpec, strategy: str) -> bool:
    """Whether this (spec, strategy) dispatches through the table (exact
    kinds have no approximate section — the plain add is the fast path)."""
    return _require_concrete(strategy) == "lut" \
        and not get_adder(spec.kind).is_exact


def _use_mul_lut(mul_spec: MulSpec, strategy: str) -> bool:
    """Multiplier-side twin of :func:`_use_lut`: the accurate kind's
    native multiply beats any gather."""
    return _require_concrete(strategy) == "lut" \
        and not get_multiplier(mul_spec.kind).is_exact


class FilterStage(NamedTuple):
    """One separable-filter pass of a :meth:`Backend.filter_chain`:
    replicate-padded taps at ``offsets`` along ``axis``, exact integer
    ``weights``, one weighted approximate accumulation, then an exact
    rounding right-``shift`` (the pass's normalization)."""

    axis: int
    offsets: Tuple[int, ...]
    weights: Tuple[int, ...]
    shift: int = 0


class Backend:
    """Abstract execution engine for approximate-arithmetic primitives.

    All array-valued methods take *container* operands: N-bit unsigned
    patterns stored in a dtype with enough room (uint64 on the host,
    int32/uint32 under jax — matching the hardware's two's-complement
    wraparound when reduced mod 2^N).
    """

    name = "abstract"

    def available(self) -> bool:
        return True

    def preferred_strategy(self, spec: AdderSpec) -> str:
        """The concrete strategy ``strategy="auto"`` resolves to: the
        algebraically-fused form on the XLA/Pallas vector backends (the
        host backend overrides it).  The choice rests on CPU timings
        (BENCH_kernels.json); no chip measurement backs it yet."""
        return "fused"

    def add(self, a, b, spec: AdderSpec, *, strategy: str = "reference"):
        """Elementwise approximate add reduced mod 2^N (container dtype)."""
        raise NotImplementedError

    def add_full(self, a, b, spec: AdderSpec, *, strategy: str = "reference"):
        """Full (N+1)-bit unsigned sum — host-side error analysis only."""
        raise NotImplementedError(
            f"backend {self.name!r} has no full-width add; use the "
            f"'numpy' backend for error analysis")

    def accumulate(self, terms, spec: AdderSpec, *, weights=None,
                   strategy: str = "reference"):
        """Weighted K-term fold through the approximate adder, mod 2^N,
        in ONE dispatch.

        ``terms`` stacks K N-bit container arrays on axis 0; ``weights``
        are K static Python ints applied as *exact* multiplies (mod 2^N —
        the hardware's tap multipliers are not approximated) before the
        K-1 approximate adds.  This is the image-filter / FIR primitive:
        the unfused equivalent is K-1 separate ``add`` dispatches with
        K-2 materialized intermediates."""
        raise NotImplementedError

    def filter_chain(self, q, spec: AdderSpec, stages, *,
                     strategy: str = "reference"):
        """Chained separable-filter passes on SIGNED int containers.

        ``q`` holds signed two's-complement values (int32/int64) of
        ``spec.n_bits`` significant bits; each :class:`FilterStage` taps
        the previous stage's output with replicate padding, folds the
        taps through one weighted approximate accumulation, sign-extends
        and applies the stage's exact rounding shift.  The default
        implementation is one ``accumulate`` dispatch per stage; the
        Pallas backends override it with a multi-stage kernel that keeps
        the tile resident in VMEM across all stages."""
        xp = np if isinstance(q, np.ndarray) else jnp
        mask = (1 << spec.n_bits) - 1
        sign = 1 << (spec.n_bits - 1)
        for st in stages:
            taps = xp.stack(edge_taps(xp, q, st.axis, st.offsets))
            s = self.accumulate(taps & mask, spec, weights=st.weights,
                                strategy=strategy)
            s = (s ^ sign) - sign
            if st.shift:
                s = (s + (1 << (st.shift - 1))) >> st.shift
            q = s
        return q

    def mul(self, a, b, mul_spec: MulSpec, *, strategy: str = "reference"):
        """Elementwise approximate multiply on unsigned N-bit container
        patterns; returns the FULL (2N-bit) product in the container —
        a multiplier's output bus carries every bit, unlike the adder's
        mod-2^N sum."""
        raise NotImplementedError

    def conv2d(self, q, spec: AdderSpec, mul_spec: MulSpec, kernel, *,
               shift: int = 0, strategy: str = "reference"):
        """2D MAC convolution on SIGNED values: per-tap products through
        the approximate multiplier (sign-magnitude, static integer
        kernel weights), tap accumulation through the approximate adder
        mod 2^N, sign extension, then an exact rounding right-``shift``.

        ``q`` holds signed values with ``|q| < 2^mul_spec.n_bits``
        (they index the per-tap product tables); ``kernel`` is a static
        tuple-of-tuples of integer weights with odd dimensions,
        replicate-edge padded.  Row-major tap order — every backend
        folds the taps in the same sequence, which is what makes the
        datapaths bit-identical."""
        raise NotImplementedError

    def matmul(self, a, b, spec: AdderSpec, *, block=(128, 128, 128),
               strategy: str = "reference",
               mul_spec: "MulSpec | None" = None):
        """int8 (M,K) @ int8 (K,N) -> int32.

        With ``mul_spec=None`` (or an exact kind): exact per-K-tile dots
        (the MXU path) and approximate inter-tile accumulation.  With an
        approximate ``mul_spec``: every product runs through the
        approximate multiplier (sign-magnitude), the K tile accumulates
        exactly (int32 wraparound is associative, so in-tile order is
        immaterial), and the inter-tile accumulator stays approximate —
        the full MAC datapath."""
        raise NotImplementedError

    def matmul_form(self, mul_spec: "MulSpec | None") -> dict:
        """The form ``matmul`` takes for ``mul_spec``, as span
        attributes; none where the backend has one form."""
        return {}

    def butterfly(self, a_re, a_im, b_re, b_im, w_re, w_im, spec: AdderSpec,
                  *, inverse: bool = False):
        """One radix-2 FFT butterfly stage (exact Q1.14 twiddle multiplies,
        approximate adds); int32 (rows, half) planes + (half,) twiddles."""
        raise NotImplementedError

    def __repr__(self):  # pragma: no cover - cosmetic
        return f"<ax backend {self.name!r}>"


# ------------------------------------------------------------------ numpy --

def _norm_weights(weights, k: int):
    ws = tuple(weights) if weights is not None else (1,) * k
    if len(ws) != k:
        raise ValueError(f"{len(ws)} weights for {k} stacked terms")
    return ws


def edge_taps(xp, q, axis: int, offsets):
    """Replicate-padded shifted views of a filter tap, as a list: the
    j-th view satisfies ``out[j][..., i] = q[..., i + offsets[j]]``
    along ``axis`` with edges replicated.  THE tap builder of the
    numpy/jax filter chains, and the oracle of the Pallas kernels'
    rotation-built views (:mod:`repro.kernels.stencil`; Mosaic has no
    edge pad).  Works for numpy and jax arrays (``xp`` is the array
    module)."""
    axis = axis % q.ndim
    left = max(-min(offsets), 0)
    right = max(max(offsets), 0)
    pad = [(0, 0)] * q.ndim
    pad[axis] = (left, right)
    p = xp.pad(q, pad, mode="edge")
    n = q.shape[axis]
    idx = [slice(None)] * q.ndim
    views = []
    for o in offsets:
        s = list(idx)
        s[axis] = slice(o + left, o + left + n)
        views.append(p[tuple(s)])
    return views


def conv_taps(xp, q, kh: int, kw: int):
    """Replicate-padded shifted views for a (kh, kw) 2D kernel over the
    trailing (H, W) dims, row-major tap order: view (dy, dx) at output
    (y, x) reads ``q[y + dy - kh//2, x + dx - kw//2]`` (edges
    replicated).  THE 2D tap builder of the numpy/jax conv datapaths,
    and the oracle of the Pallas MAC kernel's views, like
    :func:`edge_taps` for the separable chains."""
    cy, cx = kh // 2, kw // 2
    pad = [(0, 0)] * (q.ndim - 2) + [(cy, kh - 1 - cy),
                                     (cx, kw - 1 - cx)]
    p = xp.pad(q, pad, mode="edge")
    h, w = q.shape[-2], q.shape[-1]
    views = []
    for dy in range(kh):
        for dx in range(kw):
            views.append(p[..., dy:dy + h, dx:dx + w])
    return views


def check_conv_kernel(kernel) -> Tuple[int, int, Tuple[int, ...]]:
    """Validate a static conv kernel: rectangular tuple-of-tuples of
    ints, odd dims.  Returns (kh, kw, row-major flat weights)."""
    kh = len(kernel)
    if kh == 0 or kh % 2 == 0:
        raise ValueError(f"kernel height must be odd and nonzero, got {kh}")
    kw = len(kernel[0])
    if kw == 0 or kw % 2 == 0:
        raise ValueError(f"kernel width must be odd and nonzero, got {kw}")
    if any(len(row) != kw for row in kernel):
        raise ValueError("kernel rows must have equal length")
    return kh, kw, tuple(int(w) for row in kernel for w in row)


class NumpyBackend(Backend):
    """Host behavioral simulation: uint64 containers, vectorized numpy."""

    name = "numpy"

    def preferred_strategy(self, spec: AdderSpec) -> str:
        """One table gather beats numpy's many-op bitwise emulation
        whenever the spec has a compilable LUT (exact kinds and wide
        LSM sections fall back to the fused form)."""
        if not get_adder(spec.kind).is_exact and lut_lib.lut_supported(spec):
            return "lut"
        return "fused"

    def add(self, a, b, spec, *, strategy="reference"):
        a, b = np.asarray(a), np.asarray(b)
        if _use_lut(spec, strategy):
            return lut_lib.lut_add_mod(a, b, spec)
        return approx_add_mod(a, b, spec, fast=_fast(strategy))

    def accumulate(self, terms, spec, *, weights=None, strategy="reference"):
        t = np.asarray(terms)
        ws = _norm_weights(weights, t.shape[0])
        width = 8 * t.dtype.itemsize
        acc = None
        for i, w in enumerate(ws):
            # w mod 2^N is non-negative and fits the container dtype; the
            # container's natural wraparound preserves mod-2^N, so only
            # N < container width needs an explicit mask.
            term = t[i]
            if w != 1:
                term = term * t.dtype.type(w % (1 << spec.n_bits))
                if spec.n_bits < width:
                    term = term & t.dtype.type((1 << spec.n_bits) - 1)
            acc = term if acc is None else self.add(acc, term, spec,
                                                    strategy=strategy)
        return acc

    def add_full(self, a, b, spec, *, strategy="reference"):
        a, b = np.asarray(a), np.asarray(b)
        if _use_lut(spec, strategy):
            return lut_lib.lut_add_full(a, b, spec)
        return approx_add(a, b, spec, fast=_fast(strategy))

    def mul(self, a, b, mul_spec, *, strategy="reference"):
        a, b = np.asarray(a), np.asarray(b)
        if _use_mul_lut(mul_spec, strategy):
            return mul_lut_lib.lut_mul(a, b, mul_spec)
        return approx_mul(a, b, mul_spec, fast=_fast(strategy))

    def conv2d(self, q, spec, mul_spec, kernel, *, shift=0,
               strategy="reference"):
        _require_concrete(strategy)
        q = np.asarray(q)
        kh, kw, weights = check_conv_kernel(kernel)
        tables = mul_lut_lib.tap_tables(mul_spec, weights)
        v = q.astype(np.int64)
        if v.size and int(np.abs(v).max()) >= tables.shape[1]:
            raise ValueError(
                f"conv2d inputs must satisfy |q| < 2^{mul_spec.n_bits} "
                f"(the multiplier operand width); got "
                f"{int(np.abs(v).max())}")
        mask = np.int64((1 << spec.n_bits) - 1)
        signb = np.int64(1 << (spec.n_bits - 1))
        acc = None
        for i, view in enumerate(conv_taps(np, v, kh, kw)):
            p = np.take(tables[i], np.abs(view)).astype(np.int64)
            p = np.where(view < 0, -p, p)
            u = p & mask
            acc = u if acc is None else self.add(acc, u, spec,
                                                 strategy=strategy)
        s = (acc ^ signb) - signb
        if shift:
            s = (s + (1 << (shift - 1))) >> shift
        return s

    def matmul(self, a, b, spec, *, block=(128, 128, 128),
               strategy="reference", mul_spec=None):
        from repro.kernels.ref import ref_approx_matmul
        if _use_lut(spec, strategy):
            raise NotImplementedError(
                "the lut strategy is not implemented for the host matmul "
                "oracle; use the jax backend (all strategies) or "
                "strategy='fused'")
        if mul_spec is not None and not mul_spec.is_exact:
            return self._mac_matmul(np.asarray(a), np.asarray(b), spec,
                                    mul_spec, block[2], strategy)
        return ref_approx_matmul(np.asarray(a), np.asarray(b), spec,
                                 bk=block[2], fast=_fast(strategy))

    def _mac_matmul(self, a, b, spec, mul_spec, bk, strategy):
        """Host MAC oracle: per-element signed-table products, exact
        in-tile sums on int32 wraparound semantics, approximate
        inter-tile folds — the unrolled reference the jax/Pallas MAC
        kernels are tested against.  Output convention matches
        ``ref_approx_matmul``: a single K tile comes back as the raw
        int32 partial; otherwise the last fold's container (masked to
        N bits, so sign-extended int32 only when N = 32)."""
        a64, b64 = a.astype(np.int64), b.astype(np.int64)
        m, k = a64.shape
        n = b64.shape[1]
        table = mul_lut_lib.signed_mul_table(mul_spec)
        w = mul_spec.n_bits
        maskw = np.int64((1 << w) - 1)

        def lanes(x):
            # int32 lane pattern -> uint64 container holding the 32-bit
            # pattern, exactly what the jax fold's bitcast produces.
            return (x.astype(np.int64)
                    & np.int64(0xFFFFFFFF)).astype(np.uint64)

        acc = None
        for t0 in range(0, k, bk):
            part = np.zeros((m, n), dtype=np.int64)
            for kk in range(t0, min(t0 + bk, k)):
                idx = (((a64[:, kk:kk + 1] & maskw) << w)
                       | (b64[kk:kk + 1, :] & maskw))
                part = part + table[idx]
            p32 = (part & np.int64(0xFFFFFFFF)) \
                .astype(np.uint32).astype(np.int32)
            if acc is None:
                acc = p32
            else:
                s = self.add(lanes(acc), lanes(p32), spec,
                             strategy=strategy)
                acc = (s & np.uint64(0xFFFFFFFF)) \
                    .astype(np.uint32).astype(np.int32)
        return acc

    def butterfly(self, a_re, a_im, b_re, b_im, w_re, w_im, spec, *,
                  inverse=False):
        from repro.kernels.ref import ref_butterfly
        return ref_butterfly(a_re, a_im, b_re, b_im, w_re, w_im, spec,
                             inverse=inverse)


# -------------------------------------------------------------------- jax --

def _as_u32(x):
    if jnp.issubdtype(x.dtype, jnp.signedinteger):
        return jax.lax.bitcast_convert_type(x.astype(jnp.int32), jnp.uint32)
    return x


def _like(x, ref_dtype):
    if jnp.issubdtype(ref_dtype, jnp.signedinteger):
        return jax.lax.bitcast_convert_type(x, jnp.int32)
    return x.astype(ref_dtype)


def lut_add_mod_u32(a, b, spec: AdderSpec):
    """THE LUT add on uint32 lanes (jax): one table gather + one exact
    high add, mod 2^N.  The packed uint16 table is a compile-time
    constant of the (spec,)-keyed jit cache, shared with the host
    path's numpy table."""
    table = jnp.asarray(lut_lib.compile_lut(spec))
    m = spec.lsm_bits
    low = jnp.uint32((1 << m) - 1)
    entry = jnp.take(table, (a & low) << m | (b & low)).astype(jnp.uint32)
    s = (((a >> m) + (b >> m)) << m) + entry
    if spec.n_bits < 32:
        s = s & jnp.uint32((1 << spec.n_bits) - 1)
    return s


def _add_mod_u32(a, b, spec: AdderSpec, strategy: str):
    """Strategy dispatch on uint32 container lanes (the jitted jax
    entry points)."""
    if _use_lut(spec, strategy):
        return lut_add_mod_u32(a, b, spec)
    return approx_add_mod(a, b, spec, fast=_fast(strategy))


def _mul_u32(a, b, mul_spec: MulSpec, strategy: str):
    """Multiplier strategy dispatch on uint32 container lanes; the lut
    strategy is one full-product table gather from a jit constant."""
    if _use_mul_lut(mul_spec, strategy):
        table = jnp.asarray(mul_lut_lib.compile_mul_lut(mul_spec))
        n = mul_spec.n_bits
        mask = jnp.uint32((1 << n) - 1)
        return jnp.take(table, ((a & mask) << n) | (b & mask)) \
            .astype(jnp.uint32)
    return approx_mul(a, b, mul_spec, fast=_fast(strategy))


@functools.partial(jax.jit, static_argnames=("mul_spec", "strategy"))
def _jax_mul(a, b, mul_spec: MulSpec, strategy: str):
    p = _mul_u32(_as_u32(a), _as_u32(b), mul_spec, strategy)
    return _like(p, a.dtype)


@functools.partial(jax.jit,
                   static_argnames=("spec", "mul_spec", "kernel", "shift",
                                    "strategy"))
def _jax_conv2d(q, spec: AdderSpec, mul_spec: MulSpec, kernel,
                shift: int, strategy: str):
    """Jitted 2D MAC convolution: the same per-tap product tables and
    the same row-major fold order as the host and Pallas datapaths."""
    kh, kw, weights = check_conv_kernel(kernel)
    tables = jnp.asarray(mul_lut_lib.tap_tables(mul_spec, weights))
    v = q.astype(jnp.int32)
    mask = jnp.uint32((1 << spec.n_bits) - 1)
    sign = jnp.uint32(1 << (spec.n_bits - 1))
    acc = None
    for i, view in enumerate(conv_taps(jnp, v, kh, kw)):
        p = jnp.take(tables[i], jnp.abs(view))
        p = jnp.where(view < 0, -p, p)
        u = jax.lax.bitcast_convert_type(p, jnp.uint32) & mask
        acc = u if acc is None else _add_mod_u32(acc, u, spec, strategy)
    s = jax.lax.bitcast_convert_type((acc ^ sign) - sign, jnp.int32)
    if shift:
        s = (s + (1 << (shift - 1))) >> shift
    return s


@functools.partial(jax.jit,
                   static_argnames=("spec", "mul_spec", "block", "strategy"))
def _jax_mac_matmul(a, b, spec: AdderSpec, mul_spec: MulSpec, block,
                    strategy: str):
    """K-tiled MAC GEMM: signed-table products, exact int32 in-tile
    accumulation (wraparound is associative mod 2^32, so the in-tile
    order cannot affect the container result), approximate inter-tile
    folds — bit-identical to the host oracle and the Pallas kernel.
    Ragged K is zero-padded: zero operands hit table entry 0 (= 0), so
    the padded tile's partial is unchanged."""
    bk = block[2]
    k = a.shape[1]
    a32, b32 = a.astype(jnp.int32), b.astype(jnp.int32)
    n_tiles = -(-k // bk)
    if n_tiles * bk != k:
        pad = n_tiles * bk - k
        a32 = jnp.pad(a32, ((0, 0), (0, pad)))
        b32 = jnp.pad(b32, ((0, pad), (0, 0)))
    table = jnp.asarray(mul_lut_lib.signed_mul_table(mul_spec))
    w = mul_spec.n_bits
    maskw = jnp.int32((1 << w) - 1)
    m, n = a32.shape[0], b32.shape[1]

    def tile_part(i):
        def body(j, acc):
            col = jax.lax.dynamic_slice_in_dim(a32, i * bk + j, 1, axis=1)
            row = jax.lax.dynamic_slice_in_dim(b32, i * bk + j, 1, axis=0)
            idx = ((col & maskw) << w) | (row & maskw)
            return acc + jnp.take(table, idx)

        return jax.lax.fori_loop(0, bk, body,
                                 jnp.zeros((m, n), jnp.int32))

    def outer(i, acc):
        return _jax_add(acc, tile_part(i), spec, strategy)

    acc = tile_part(0)
    if n_tiles > 1:
        acc = jax.lax.fori_loop(1, n_tiles, outer, acc)
    return acc


@functools.partial(jax.jit, static_argnames=("spec", "strategy"))
def _jax_add(a, b, spec: AdderSpec, strategy: str):
    s = _add_mod_u32(_as_u32(a), _as_u32(b), spec, strategy)
    return _like(s, a.dtype)


@functools.partial(jax.jit, static_argnames=("spec", "weights", "strategy"))
def _jax_accumulate(terms, spec: AdderSpec, weights, strategy: str):
    from repro.kernels.accumulate import scale_mod_u32
    acc = None
    for i, w in enumerate(weights):
        term = scale_mod_u32(_as_u32(terms[i]), w, spec.n_bits)
        acc = term if acc is None else _add_mod_u32(acc, term, spec,
                                                    strategy)
    return _like(acc, terms.dtype)


def _mul_q14(x, w):
    """Exact (x * w + half) >> 14 for int32 x and Q1.14 w without int64:
    16-bit limb decomposition (same identity as the Pallas kernel)."""
    half = jnp.int32(1 << (TWIDDLE_FRAC - 1))
    hi = x >> 16
    lo = x & jnp.int32(0xFFFF)
    return (hi * w << (16 - TWIDDLE_FRAC)) + ((lo * w + half) >> TWIDDLE_FRAC)


@functools.partial(jax.jit, static_argnames=("spec", "inverse"))
def _jax_butterfly(a_re, a_im, b_re, b_im, w_re, w_im, spec: AdderSpec,
                   inverse: bool):
    def add(x, y):
        return _jax_add(x, y, spec, "reference")

    rr, ri = _mul_q14(b_re, w_re), _mul_q14(b_re, w_im)
    ir, ii = _mul_q14(b_im, w_re), _mul_q14(b_im, w_im)
    t_re = add(rr, -ii)
    t_im = add(ri, ir)
    top_re, top_im = add(a_re, t_re), add(a_im, t_im)
    bot_re, bot_im = add(a_re, -t_re), add(a_im, -t_im)
    if inverse:
        halve = lambda x: (x + 1) >> 1  # noqa: E731
        return (halve(top_re), halve(top_im), halve(bot_re), halve(bot_im))
    return top_re, top_im, bot_re, bot_im


@functools.partial(jax.jit, static_argnames=("spec", "block", "strategy"))
def _jax_matmul(a, b, spec: AdderSpec, block, strategy: str):
    """K-tiled int8 GEMM with approximate inter-tile accumulation.

    The K loop is a ``lax.fori_loop`` over tiles, so the XLA graph (and
    compile time) stays O(1) in K instead of unrolling one dot per tile.
    A ragged last tile is zero-padded: the pad contributes zeros WITHIN
    that tile's exact dot, so the sequence of approximate adds — and
    therefore the result — is bit-identical to the unrolled short-slice
    form (no extra approximate add of a zero partial is introduced).
    """
    bk = block[2]
    k = a.shape[1]
    a32, b32 = a.astype(jnp.int32), b.astype(jnp.int32)
    n_tiles = -(-k // bk)
    if n_tiles * bk != k:
        pad = n_tiles * bk - k
        a32 = jnp.pad(a32, ((0, 0), (0, pad)))
        b32 = jnp.pad(b32, ((0, pad), (0, 0)))

    def tile_dot(i):
        at = jax.lax.dynamic_slice_in_dim(a32, i * bk, bk, axis=1)
        bt = jax.lax.dynamic_slice_in_dim(b32, i * bk, bk, axis=0)
        return jax.lax.dot(at, bt)

    def body(i, acc):
        return _jax_add(acc, tile_dot(i), spec, strategy)

    acc = tile_dot(0)
    if n_tiles > 1:
        acc = jax.lax.fori_loop(1, n_tiles, body, acc)
    return acc


class JaxBackend(Backend):
    """Jitted elementwise emulation on jax arrays (XLA, any device)."""

    name = "jax"

    def add(self, a, b, spec, *, strategy="reference"):
        return _jax_add(jnp.asarray(a), jnp.asarray(b), spec, strategy)

    def accumulate(self, terms, spec, *, weights=None, strategy="reference"):
        terms = jnp.asarray(terms)
        return _jax_accumulate(terms, spec,
                               _norm_weights(weights, terms.shape[0]),
                               strategy)

    def mul(self, a, b, mul_spec, *, strategy="reference"):
        return _jax_mul(jnp.asarray(a), jnp.asarray(b), mul_spec, strategy)

    def conv2d(self, q, spec, mul_spec, kernel, *, shift=0,
               strategy="reference"):
        kernel = tuple(tuple(int(w) for w in row) for row in kernel)
        return _jax_conv2d(jnp.asarray(q), spec, mul_spec, kernel,
                           shift, strategy)

    def matmul(self, a, b, spec, *, block=(128, 128, 128),
               strategy="reference", mul_spec=None):
        if mul_spec is not None and not mul_spec.is_exact:
            return _jax_mac_matmul(jnp.asarray(a), jnp.asarray(b), spec,
                                   mul_spec, tuple(block), strategy)
        return _jax_matmul(jnp.asarray(a), jnp.asarray(b), spec,
                           tuple(block), strategy)

    def butterfly(self, a_re, a_im, b_re, b_im, w_re, w_im, spec, *,
                  inverse=False):
        w_re = jnp.asarray(w_re)[None, :]
        w_im = jnp.asarray(w_im)[None, :]
        return _jax_butterfly(jnp.asarray(a_re), jnp.asarray(a_im),
                              jnp.asarray(b_re), jnp.asarray(b_im),
                              w_re, w_im, spec, inverse)


# ----------------------------------------------------------------- pallas --

def _pad2(x, bm, bn):
    m, n = x.shape
    pm, pn = (-m) % bm, (-n) % bn
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x, m, n


def _as_tiles(x, size: int, n_cols: int = 256):
    """Flatten an elementwise operand (last ``size`` elements per lead
    dim) to a (rows, n_cols) tile grid with ONE pad — rows kept a
    multiple of the 256-row block above one block."""
    rows = -(-size // n_cols)
    if rows > 256:
        rows = -(-rows // 256) * 256
    pad = [(0, 0)] * (x.ndim - 1) + [(0, rows * n_cols - size)]
    return jnp.pad(x, pad).reshape(x.shape[:-1] + (rows, n_cols))


@functools.partial(jax.jit, static_argnames=("spec", "interpret", "strategy"))
def _pallas_elementwise_add(a, b, spec: AdderSpec, interpret: bool,
                            strategy: str):
    """Tile plumbing for the fused elementwise kernel: flatten to a
    (rows, 256) grid with ONE pad per operand (no intermediate zeros
    buffer), run the kernel, slice back.  The strategy reaches the
    kernel body, which runs the registered reference or fused impl."""
    from repro.kernels.approx_add import approx_add_pallas
    shape = a.shape
    size = int(np.prod(shape)) if shape else 1
    ap = _as_tiles(a.reshape(-1), size)
    bp = _as_tiles(b.reshape(-1), size)
    out = approx_add_pallas(ap, bp, spec, interpret=interpret,
                            fast=_fast(strategy))
    return out.reshape(-1)[:size].reshape(shape)


@functools.partial(jax.jit,
                   static_argnames=("spec", "weights", "interpret",
                                    "strategy"))
def _pallas_accumulate(terms, spec: AdderSpec, weights, interpret: bool,
                       strategy: str):
    """Tile plumbing for the fused K-term kernel: flatten the trailing
    dims to a (rows, 256) grid with ONE pad of the stacked operand, run
    the kernel, slice back."""
    from repro.kernels.accumulate import accumulate_pallas
    k = terms.shape[0]
    shape = terms.shape[1:]
    size = int(np.prod(shape)) if shape else 1
    tp = _as_tiles(terms.reshape(k, -1), size)
    out = accumulate_pallas(tp, spec, weights=weights, interpret=interpret,
                            fast=_fast(strategy))
    return out.reshape(-1)[:size].reshape(shape)


@functools.partial(jax.jit,
                   static_argnames=("spec", "block", "interpret", "fast"))
def _pallas_matmul(a, b, spec: AdderSpec, block, interpret: bool,
                   fast: bool):
    from repro.kernels.approx_matmul import approx_matmul_pallas
    bm, bn, bk = block
    ap, m0, _ = _pad2(a, bm, bk)
    bp, _, n0 = _pad2(b, bk, bn)
    out = approx_matmul_pallas(ap, bp, spec, block=block,
                               interpret=interpret, fast=fast)
    return out[:m0, :n0]


@functools.partial(jax.jit,
                   static_argnames=("mul_spec", "interpret", "strategy"))
def _pallas_elementwise_mul(a, b, mul_spec: MulSpec, interpret: bool,
                            strategy: str):
    """Tile plumbing for the elementwise multiplier kernel — identical
    flatten/pad/slice scheme to :func:`_pallas_elementwise_add`."""
    from repro.kernels.mac import mul_elementwise_pallas
    shape = a.shape
    size = int(np.prod(shape)) if shape else 1
    ap = _as_tiles(a.reshape(-1), size)
    bp = _as_tiles(b.reshape(-1), size)
    out = mul_elementwise_pallas(ap, bp, mul_spec, interpret=interpret,
                                 fast=_fast(strategy))
    return out.reshape(-1)[:size].reshape(shape)


@functools.partial(jax.jit,
                   static_argnames=("spec", "mul_spec", "block",
                                    "interpret", "fast"))
def _pallas_mac_matmul(a, b, spec: AdderSpec, mul_spec: MulSpec, block,
                       interpret: bool, fast: bool):
    """Pad/slice plumbing for the MAC GEMM kernel, with operands in
    int8 on its MXU form (a w <= 8-bit operand keeps its value mod 2^w)
    and int32 on its VPU form.  Zero padding is harmless in every
    dimension: a zero operand's product is 0, so in-tile partials are
    unchanged, and padded M/N lanes are sliced away."""
    from repro.kernels.mac import mac_matmul_pallas, plane_groups
    bm, bn, bk = block
    dtype = jnp.int32 if plane_groups(mul_spec) is None else jnp.int8
    ap, m0, _ = _pad2(a.astype(dtype), bm, bk)
    bp, _, n0 = _pad2(b.astype(dtype), bk, bn)
    out = mac_matmul_pallas(ap, bp, spec, mul_spec, block=block,
                            interpret=interpret, fast=fast)
    return out[:m0, :n0]


class PallasBackend(Backend):
    """Pallas kernels in interpret mode — validates the fused TPU kernel
    bodies on any host."""

    name = "pallas"
    interpret = True

    def _kernel_strategy(self, spec, strategy, what):
        """The Pallas kernels evaluate the registered impls in VMEM; a
        table gather from VMEM has no Mosaic lowering, so there is no
        lut strategy on these backends."""
        if _use_lut(spec, strategy):
            raise NotImplementedError(
                f"the lut strategy is not implemented for {what} on the "
                f"{self.name!r} backend; use strategy='fused' (or the "
                f"numpy/jax backends for lut)")
        return strategy

    def add(self, a, b, spec, *, strategy="reference"):
        self._kernel_strategy(spec, strategy, "add")
        return _pallas_elementwise_add(jnp.asarray(a), jnp.asarray(b), spec,
                                       self.interpret, strategy)

    def accumulate(self, terms, spec, *, weights=None, strategy="reference"):
        terms = jnp.asarray(terms)
        self._kernel_strategy(spec, strategy, "accumulate")
        return _pallas_accumulate(terms, spec,
                                  _norm_weights(weights, terms.shape[0]),
                                  self.interpret, strategy)

    def filter_chain(self, q, spec, stages, *, strategy="reference"):
        from repro.kernels.conv_chain import filter_chain_pallas
        self._kernel_strategy(spec, strategy, "filter_chain")
        return filter_chain_pallas(jnp.asarray(q), spec, tuple(stages),
                                   interpret=self.interpret,
                                   fast=_fast(strategy))

    def mul(self, a, b, mul_spec, *, strategy="reference"):
        if _use_mul_lut(mul_spec, strategy):
            raise NotImplementedError(
                f"the lut strategy (a product table gather) is not "
                f"implemented for mul on the {self.name!r} backend; use "
                f"strategy='fused' (or the numpy/jax backends for lut)")
        return _pallas_elementwise_mul(jnp.asarray(a), jnp.asarray(b),
                                       mul_spec, self.interpret,
                                       _require_concrete(strategy))

    def conv2d(self, q, spec, mul_spec, kernel, *, shift=0,
               strategy="reference"):
        from repro.kernels.mac import conv2d_mac_pallas
        self._kernel_strategy(spec, strategy, "conv2d")
        kernel = tuple(tuple(int(w) for w in row) for row in kernel)
        check_conv_kernel(kernel)
        return conv2d_mac_pallas(jnp.asarray(q), spec, mul_spec, kernel,
                                 shift=shift, interpret=self.interpret,
                                 fast=_fast(strategy))

    def matmul(self, a, b, spec, *, block=(128, 128, 128),
               strategy="reference", mul_spec=None):
        self._kernel_strategy(spec, strategy, "matmul")
        if mul_spec is not None and not mul_spec.is_exact:
            return _pallas_mac_matmul(jnp.asarray(a), jnp.asarray(b),
                                      spec, mul_spec, tuple(block),
                                      self.interpret, _fast(strategy))
        return _pallas_matmul(jnp.asarray(a), jnp.asarray(b), spec,
                              tuple(block), self.interpret,
                              _fast(strategy))

    def matmul_form(self, mul_spec):
        from repro.kernels.mac import mac_form
        if mul_spec is None or mul_spec.is_exact:
            return {}
        return mac_form(mul_spec)

    def butterfly(self, a_re, a_im, b_re, b_im, w_re, w_im, spec, *,
                  inverse=False):
        from repro.kernels.butterfly import butterfly_pallas
        return butterfly_pallas(
            jnp.asarray(a_re), jnp.asarray(a_im), jnp.asarray(b_re),
            jnp.asarray(b_im), jnp.asarray(w_re), jnp.asarray(w_im),
            spec, inverse=inverse, interpret=self.interpret)


class PallasTpuBackend(PallasBackend):
    """Pallas kernels compiled through Mosaic (requires a TPU runtime)."""

    name = "pallas_tpu"
    interpret = False

    def available(self) -> bool:
        return jax.default_backend() == "tpu"


# --------------------------------------------------------------- registry --

_BACKENDS: Dict[str, Backend] = {}


def register_backend(backend: Backend) -> Backend:
    """Register a backend instance under ``backend.name``."""
    if backend.name in _BACKENDS:
        raise ValueError(f"backend {backend.name!r} already registered")
    _BACKENDS[backend.name] = backend
    return backend


def get_backend(backend: Union[str, Backend, None] = None) -> Backend:
    """Resolve a backend by name; ``None`` auto-detects."""
    if backend is None:
        backend = default_backend_name()
    if isinstance(backend, Backend):
        return backend
    try:
        return _BACKENDS[backend]
    except KeyError:
        raise ValueError(
            f"unknown backend {backend!r}; registered: "
            f"{sorted(_BACKENDS)}") from None


def available_backends() -> Dict[str, bool]:
    """name -> availability on this host."""
    return {name: be.available() for name, be in sorted(_BACKENDS.items())}


def default_backend_name() -> str:
    """``pallas_tpu`` when a TPU runtime is attached, else ``jax``."""
    if _BACKENDS["pallas_tpu"].available():
        return "pallas_tpu"
    return "jax"


register_backend(NumpyBackend())
register_backend(JaxBackend())
register_backend(PallasBackend())
register_backend(PallasTpuBackend())
