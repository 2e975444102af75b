"""The spec-first execution handle: one object per (adder, format,
backend) that every approximate-arithmetic call site consumes.

    from repro.ax import make_engine

    ax = make_engine("haloc_axa", fmt=FixedPointFormat(16, 8))
    z = ax.residual_add(x, y)          # float STE path (models)
    s = ax.add_signed(qx, qy)          # fixed-point containers
    c = ax.add(a, b)                   # raw N-bit containers, mod 2^N

Engines are frozen, hashable, and cached: two calls to ``make_engine``
with the same arguments return the same object, so jit caches keyed on
the engine hit across call sites.  The engine replaces the
(spec, fmt, fast, interpret) tuples previously re-derived by numerics,
the image/FFT pipeline, model layers, and benchmarks.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.ax.backends import Backend, check_strategy, get_backend, \
    resolve_strategy
from repro.obs import drift as _drift
from repro.obs import trace as _obs
from repro.obs.caches import register_lru as _register_lru
from repro.ax.lut import lut_supported
from repro.ax.mul import (
    MAX_MUL_LUT_BITS,
    MacSpec,
    MulSpec,
    default_mul_spec,
    get_multiplier,
    mul_lut_supported,
)
from repro.ax.registry import get_adder
from repro.core.specs import AdderSpec
from repro.resilience.faults import FaultSpec, apply_fault, validate_fault
from repro.numerics.fixed_point import (
    FixedPointFormat,
    container_to_signed,
    dequantize,
    quantize,
    signed_to_container,
)


@dataclasses.dataclass(frozen=True)
class AxEngine:
    """Approximate-arithmetic execution handle.

    Attributes:
      spec: the adder (validated against the adder registry).
      fmt: fixed-point format for the signed/float entry points; ``None``
        for raw-container use (e.g. the 32-bit image FFT, which manages
        its own Q-format).
      backend: resolved execution backend.
      strategy: how the adder's bit-level function is evaluated —
        ``"reference"`` (the registered oracle), ``"fused"`` (the
        algebraically-fused variant where registered), or ``"lut"`` (the
        compiled low-part table).  All bit-identical.
      mul_spec: the approximate multiplier, or ``None`` for an
        adder-only engine.  With a multiplier the engine is a MAC
        engine: ``mul``/``mul_signed`` run the multiplier alone, and
        ``conv2d``/``matmul`` route every product through it (with the
        adder on the accumulations).
      fault: an injected hardware fault
        (:class:`repro.resilience.faults.FaultSpec`) applied to every
        ``add``/``accumulate``/``filter_chain`` output bus, or ``None``
        for the healthy datapath.  Portable masks — the faulted
        datapath is bit-identical across backends, same as the healthy
        one.
    """

    spec: AdderSpec
    fmt: Optional[FixedPointFormat]
    backend: Backend
    strategy: str = "reference"
    mul_spec: Optional[MulSpec] = None
    fault: Optional[FaultSpec] = None

    @property
    def fast(self) -> bool:
        """Back-compat view of the old boolean knob."""
        return self.strategy == "fused"

    # ------------------------------------------------------ raw containers

    def add(self, a, b):
        """Elementwise approximate add mod 2^N on N-bit containers."""
        if _obs._ENABLED:
            with _obs.span("ax:add", kind=self.spec.kind,
                           backend=self.backend.name):
                out = self._faulted(self.backend.add(
                    a, b, self.spec, strategy=self.strategy))
            # A faulted datapath's error is no longer a function of the
            # spec's delta table, so capture measures the actual output.
            _drift.capture_add(self.spec, a, b,
                               out=out if self.fault is not None else None)
            return out
        return self._faulted(self.backend.add(a, b, self.spec,
                                              strategy=self.strategy))

    def add_full(self, a, b):
        """Full (N+1)-bit unsigned sum (host error analysis; numpy)."""
        return self.backend.add_full(a, b, self.spec,
                                     strategy=self.strategy)

    def accumulate(self, terms, weights=None):
        """Weighted fold of K stacked container terms mod 2^N in one
        backend dispatch (one fused kernel on the Pallas backends, not
        K-1 sequential ``add`` calls).  ``weights`` are K static ints,
        multiplied exactly before the K-1 approximate adds."""
        if _obs._ENABLED:
            out = self._faulted(self.backend.accumulate(
                terms, self.spec, weights=weights,
                strategy=self.strategy))
            _drift.capture_accumulate(self.spec, terms, weights, out)
            return out
        return self._faulted(self.backend.accumulate(
            terms, self.spec, weights=weights, strategy=self.strategy))

    def filter_chain(self, q, stages):
        """Chained separable-filter passes on signed containers: each
        :class:`FilterStage` taps the previous stage's output (replicate
        padding), folds the taps through one weighted approximate
        accumulation and applies its exact rounding shift.  One
        multi-stage VMEM-resident kernel on the Pallas backends; one
        ``accumulate`` dispatch per stage elsewhere."""
        self._require_fmt("filter_chain")
        if _obs._ENABLED:
            out = self._faulted(self.backend.filter_chain(
                q, self.spec, tuple(stages), strategy=self.strategy),
                signed=True)
            _drift.capture_filter_chain(self.spec, q, tuple(stages), out)
            return out
        return self._faulted(self.backend.filter_chain(
            q, self.spec, tuple(stages), strategy=self.strategy),
            signed=True)

    # --------------------------------------------------------- multipliers

    def mul(self, a, b):
        """Elementwise approximate multiply on unsigned N-bit container
        operands (N = ``mul_spec.n_bits``); returns the full approximate
        product (up to 2N+1 bits for logarithmic kinds)."""
        ms = self._require_mul("mul")
        return self.backend.mul(a, b, ms, strategy=self.strategy)

    def mul_signed(self, qa, qb):
        """Sign-magnitude signed multiply on signed integer arrays with
        ``|q| <= 2^(N-1)``: ``sign(qa)*sign(qb)*approx(|qa|, |qb|)`` —
        the product convention of the MAC datapaths."""
        ms = self._require_mul("mul_signed")
        xp = np if isinstance(qa, np.ndarray) else jnp
        p = self.backend.mul(xp.abs(qa), xp.abs(qb), ms,
                             strategy=self.strategy)
        return xp.where((qa < 0) != (qb < 0), -p, p)

    def conv2d(self, q, kernel, shift: int = 0):
        """2D MAC convolution on signed containers: every tap product
        runs the approximate multiplier, the tap sums run the
        approximate adder (row-major fold, replicate-edge padding), and
        ``shift`` applies an exact rounding right-shift (the kernel's
        normalization).  ``kernel`` is a tuple-of-tuples of static
        integer weights with odd dimensions."""
        self._require_fmt("conv2d")
        ms = self._require_mul("conv2d")
        with _obs.span("ax:conv2d", kind=self.spec.kind, mul=ms.kind,
                       backend=self.backend.name) if _obs._ENABLED \
                else _obs._NOOP:
            return self.backend.conv2d(q, self.spec, ms, kernel,
                                       shift=shift, strategy=self.strategy)

    # --------------------------------------------------------- fixed point

    def add_signed(self, qx, qy):
        """Two's-complement fixed-point add (signed int32 containers)."""
        fmt = self._require_fmt("add_signed")
        a = signed_to_container(qx, fmt)
        b = signed_to_container(qy, fmt)
        return container_to_signed(self.add(a, b), fmt)

    def accumulate_signed(self, qs, weights=None, shift: int = 0):
        """Signed fixed-point weighted accumulation: ``sum_i w_i * q_i``
        with exact tap multiplies, approximate adds, and an exact final
        rounding right-shift (the filter's normalization stage).

        ``qs`` stacks K signed int32 containers on axis 0.  The true
        weighted sum must fit the N-bit two's-complement range (headroom
        is the caller's filter design, exactly as in the hardware)."""
        fmt = self._require_fmt("accumulate_signed")
        u = signed_to_container(qs, fmt)
        s = container_to_signed(self.accumulate(u, weights), fmt)
        if shift:
            s = (s + (1 << (shift - 1))) >> shift
        return s

    def scaled_add(self, qx, qy, wx: int = 1, wy: int = 1, shift: int = 0):
        """Two-term weighted fixed-point add, ``(wx*qx + wy*qy) >> shift``
        with a single approximate add (alpha-blend / unsharp-mask tap)."""
        xp = np if isinstance(qx, np.ndarray) else jnp
        return self.accumulate_signed(xp.stack([qx, qy]), (wx, wy),
                                      shift=shift)

    def sum(self, q, axis: int = -1):
        """Log-depth tree reduction with approximate partial sums (the
        accumulator of a MAC array built from these adders)."""
        self._require_fmt("sum")
        q = jnp.moveaxis(q, axis, -1)
        n = q.shape[-1]
        pow2 = 1 << (n - 1).bit_length()
        if pow2 != n:
            pad = [(0, 0)] * (q.ndim - 1) + [(0, pow2 - n)]
            q = jnp.pad(q, pad)
        while q.shape[-1] > 1:
            half = q.shape[-1] // 2
            q = self.add_signed(q[..., :half], q[..., half:])
        return q[..., 0]

    # --------------------------------------------------------- float entry

    def residual_add(self, x, y):
        """Float-in/float-out residual-stream add: quantize -> approximate
        add -> dequantize, with a straight-through estimator (gradient of
        an exact add) so the op is trainable."""
        if get_adder(self.spec.kind).is_exact:
            return x + y
        self._require_fmt("residual_add")
        return _ste_residual_add(self, x, y)

    # ----------------------------------------------------------- compound

    def matmul(self, a, b, block=(128, 128, 128)):
        """int8 GEMM with approximate inter-K-tile accumulation.  On a
        MAC engine (``mul_spec`` set) every product additionally runs
        the approximate multiplier."""
        with _obs.span("ax:matmul", kind=self.spec.kind,
                       backend=self.backend.name,
                       **self.backend.matmul_form(self.mul_spec)):
            return self.backend.matmul(a, b, self.spec, block=block,
                                       strategy=self.strategy,
                                       mul_spec=self.mul_spec)

    def butterfly(self, a_re, a_im, b_re, b_im, w_re, w_im,
                  inverse: bool = False):
        """One radix-2 FFT butterfly stage through the approximate adder."""
        return self.backend.butterfly(a_re, a_im, b_re, b_im, w_re, w_im,
                                      self.spec, inverse=inverse)

    # -------------------------------------------------------------- misc

    def replace(self, **kw) -> "AxEngine":
        """A new engine with some fields swapped (``backend`` may be a
        name string; ``fast`` maps onto ``strategy``; ``mul`` accepts a
        :class:`MulSpec`, a kind name, or ``None`` like
        :func:`make_engine`)."""
        if "backend" in kw:
            kw["backend"] = get_backend(kw["backend"])
        if "mul" in kw:
            kw["mul_spec"] = _normalize_mul(kw.pop("mul"))
        if "fast" in kw:
            kw["strategy"] = resolve_strategy(kw.get("strategy"),
                                              kw.pop("fast"))
        if "strategy" in kw:
            check_strategy(kw["strategy"])
            if kw["strategy"] == "auto":
                kw["strategy"] = kw.get("backend", self.backend) \
                    .preferred_strategy(kw.get("spec", self.spec))
        return dataclasses.replace(self, **kw)

    def _faulted(self, out, signed: bool = False):
        """Apply the installed fault to an adder output bus (identity
        on healthy engines — one ``is None`` test on the hot path)."""
        if self.fault is None:
            return out
        return apply_fault(out, self.fault, self.spec.n_bits,
                           signed=signed)

    def _require_fmt(self, what: str) -> FixedPointFormat:
        if self.fmt is None:
            raise ValueError(
                f"AxEngine.{what} needs a fixed-point format; pass "
                f"fmt=FixedPointFormat(...) to make_engine")
        return self.fmt

    def _require_mul(self, what: str) -> MulSpec:
        if self.mul_spec is None:
            raise ValueError(
                f"AxEngine.{what} needs a multiplier; pass mul=... (a "
                f"MulSpec or kind name) or a MacSpec to make_engine")
        return self.mul_spec


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ste_residual_add(engine: AxEngine, x, y):
    qx, qy = quantize(x, engine.fmt), quantize(y, engine.fmt)
    return dequantize(engine.add_signed(qx, qy), engine.fmt, x.dtype)


def _ste_fwd(engine, x, y):
    return _ste_residual_add(engine, x, y), None


def _ste_bwd(engine, _res, g):
    # Straight-through: d(approx_add)/dx ~= d(x+y)/dx = 1.
    return g, g


_ste_residual_add.defvjp(_ste_fwd, _ste_bwd)


def _default_spec(kind: str, n_bits: int) -> AdderSpec:
    """Scale the paper's 32-bit (m=10, k=5) partition to an ``n_bits``
    datapath: m = n/2, k = m/2 (the paper's own Fig-4 example is exactly
    the N=16/m=8/k=4 instance of this rule)."""
    try:
        entry = get_adder(kind)
    except KeyError:
        raise ValueError(f"unknown adder kind {kind!r}") from None
    if entry.is_exact:
        return AdderSpec(kind=kind, n_bits=n_bits)
    if n_bits == 32:
        m, k = 10, 5
    else:
        m = max(2, n_bits // 2)
        k = m // 2
    return AdderSpec(kind=kind, n_bits=n_bits, lsm_bits=m,
                     const_bits=k if entry.const_section else 0)


def _normalize_mul(mul: Union[MulSpec, str, None]) -> Optional[MulSpec]:
    """``mul=`` coercion: a spec passes through, a kind name gets the
    kind's default knobs at 8 operand bits (the image-processing width),
    ``None`` means adder-only."""
    if mul is None or isinstance(mul, MulSpec):
        return mul
    if isinstance(mul, str):
        try:
            get_multiplier(mul)
        except KeyError:
            raise ValueError(f"unknown multiplier kind {mul!r}") from None
        return default_mul_spec(mul, n_bits=8)
    raise TypeError(f"mul must be a MulSpec, kind name or None; "
                    f"got {type(mul).__name__}")


@functools.lru_cache(maxsize=None)
def _make_engine_cached(spec: AdderSpec, fmt: Optional[FixedPointFormat],
                        backend: Backend, strategy: str,
                        mul_spec: Optional[MulSpec],
                        fault: Optional[FaultSpec] = None) -> AxEngine:
    return AxEngine(spec=spec, fmt=fmt, backend=backend, strategy=strategy,
                    mul_spec=mul_spec, fault=fault)


_register_lru("ax.engine", _make_engine_cached)


def make_engine(spec: Union[AdderSpec, MacSpec, str],
                fmt: Optional[FixedPointFormat] = None,
                backend: Union[str, Backend, None] = None,
                fast: bool = False,
                strategy: Optional[str] = None,
                mul: Union[MulSpec, str, None] = None,
                fault: Optional[FaultSpec] = None,
                integrity: bool = False) -> AxEngine:
    """Build (or fetch the cached) execution engine.

    Args:
      spec: an :class:`AdderSpec`, a :class:`MacSpec` (bundling adder
        and multiplier; then ``mul`` must be left ``None``), or a
        registered adder kind name — a bare name gets the paper's (m, k)
        partition scaled to the format width (N=32 when no ``fmt`` is
        given).
      fmt: fixed-point format for the signed/float entry points.  Must
        match ``spec.n_bits`` for non-exact adders.  ``None`` restricts
        the engine to the raw-container ops.
      backend: backend name (``"numpy" | "jax" | "pallas" | "pallas_tpu"``),
        a :class:`Backend` instance, or ``None`` to auto-detect.
      fast: back-compat alias for ``strategy="fused"``.
      strategy: ``"reference" | "fused" | "lut"`` execution strategy
        (all bit-identical), or ``"auto"`` to take the backend's
        fastest known one (fused on the jax/Pallas backends, lut on
        numpy where the spec has a compilable table).  ``None`` derives
        it from ``fast``.
      mul: optional approximate multiplier — a :class:`MulSpec`, a
        registered multiplier kind name (default knobs at 8 bits), or
        ``None`` for an adder-only engine.  With a multiplier the
        engine exposes ``mul``/``mul_signed``/``conv2d`` and its
        ``matmul`` becomes a full approximate MAC.
      fault: optional injected hardware fault
        (:class:`repro.resilience.faults.FaultSpec`) — validated
        against the adder width (out-of-range bit positions and
        malformed rates raise ``ValueError`` here instead of silently
        wrapping in the mod-2^N arithmetic) and applied to every adder
        output bus.
      integrity: verify-on-load — before the engine is returned, every
        shared LUT it will gather from is compiled (or touched) and
        re-hashed against its golden digest, repairing in place on
        mismatch (:func:`repro.integrity.scrub.verify_engine_tables`);
        an unrepairable table raises ``IOError`` instead of serving.
        Default ``False``: the check is entirely skipped (zero cost).
    """
    strategy = resolve_strategy(strategy, fast)
    if isinstance(spec, MacSpec):
        if mul is not None:
            raise ValueError("pass either a MacSpec or mul=..., not both")
        spec, mul = spec.adder, spec.mul
    if isinstance(spec, str):
        spec = _default_spec(spec, fmt.n_bits if fmt is not None else 32)
    mul_spec = _normalize_mul(mul)
    if (fmt is not None and not get_adder(spec.kind).is_exact
            and spec.n_bits != fmt.n_bits):
        raise ValueError(
            f"adder width N={spec.n_bits} must match fixed-point "
            f"container n_bits={fmt.n_bits}")
    if strategy == "lut" and not lut_supported(spec):
        raise ValueError(
            f"no compilable LUT for {spec.short_name} (lsm_bits too "
            f"wide); use strategy='reference' or 'fused'")
    if (strategy == "lut" and mul_spec is not None
            and not mul_lut_supported(mul_spec)):
        raise ValueError(
            f"no compilable LUT for {mul_spec.short_name} (n_bits > "
            f"{MAX_MUL_LUT_BITS}); use strategy='reference' or 'fused'")
    validate_fault(fault, spec.n_bits, what=f"{spec.kind} adder bus")
    resolved = get_backend(backend)
    if strategy == "auto":
        strategy = resolved.preferred_strategy(spec)
    if integrity:
        from repro.integrity.scrub import verify_engine_tables
        verify_engine_tables(spec, mul_spec)
    return _make_engine_cached(spec, fmt, resolved, strategy, mul_spec,
                               fault)
