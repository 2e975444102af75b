"""Compiled image-processing pipelines: many operators, ONE dispatch.

Running a multi-stage pipeline operator-by-operator through the corpus
workloads costs one jit dispatch and one full host<->device round-trip
of the intermediate image per stage.  :func:`compile_pipeline` chains
the registered operators into a single jitted callable instead: the
intermediate images never leave the device, XLA fuses the per-stage
quantize/dequantize seams, and on the Pallas backends the separable
stages inside each operator already run as one VMEM-resident
multi-pass kernel (``repro.kernels.conv_chain``).

Two requantization modes select what flows BETWEEN stages:

- ``requant="stage"`` (default): each stage dequantizes, rounds and
  saturates to uint8 exactly as the standalone operators do — the
  compiled plan is bit-identical to running its stages individually
  (the PR-3 behavior; the speedup is pure dispatch/transfer/fusion).
- ``requant="fused"``: the chain runs END-TO-END in the fixed-point
  integer domain through the operators' raw Q-forms
  (:class:`repro.imgproc.ops.QForm`): ONE exact quantize at entry, one
  round/clip at exit, and at each inter-stage seam the float32
  dequantize → round → saturate → requantize round-trip collapses to
  three integer ops (rounding shift, clamp, exact rescale into the
  next stage's declared scale) — the datapath the paper's hardware
  would actually run, with stage-mode rounding semantics preserved.
  Bit-identical to stage mode for chains whose q-forms are all
  ``exact`` (every stock pipeline); chains through ``box_blur`` may
  differ by one integer-vs-float /9 rounding LSB, so the mode is
  PSNR-gated rather than declared bit-identical —
  :func:`fused_psnr_gate` scores both modes against the ideal float
  reference, and the acceptance bound (within 0.1 dB of stage requant
  for every Table-1 kind) is enforced by ``tests/test_tiles.py`` and
  recorded by ``benchmarks/bench_imgproc``.

    from repro.imgproc import compile_pipeline

    pipe = compile_pipeline(("gaussian_blur", "sharpen", "downsample2x"),
                            kind="haloc_axa", backend="jax",
                            requant="fused")
    out = pipe(batch)            # one jitted call, uint8 in -> uint8 out

Plans are cached: the same (stages, engine, requant) request returns
the same compiled object, so warm calls hit the XLA cache.
:data:`PIPELINES` names the corpus's stock pipelines (registered as
workloads alongside the single operators by ``repro.imgproc.workloads``).
Every compiled plan also exposes its single-image ``chain`` callable
and per-stage (halo, down) geometry, which is what the halo-aware tile
streamer (:mod:`repro.imgproc.tiles`) consumes to run the plan over
megapixel images in bounded memory.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Sequence, \
    Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.imgproc import ops as ops_lib
from repro.obs import metrics as _metrics
from repro.obs import trace as _obs
from repro.obs.caches import register_lru as _register_lru

#: One stage: an operator name, optionally with fixed keyword arguments.
StageSpec = Union[str, Tuple[str, Dict[str, Any]]]

#: Legal inter-stage requantization modes.
REQUANT_MODES = ("stage", "fused")

#: Stock multi-stage pipelines swept by the corpus (registered as
#: workloads): a denoise->enhance->shrink chain and an edge pipeline.
PIPELINES: Dict[str, Tuple[StageSpec, ...]] = {
    "pipe_blur_sharpen_down": ("gaussian_blur", "sharpen", "downsample2x"),
    "pipe_blur_sobel": ("gaussian_blur", "sobel"),
}


def check_requant(requant: str) -> str:
    if requant not in REQUANT_MODES:
        raise ValueError(
            f"unknown requant mode {requant!r}; one of {REQUANT_MODES}")
    return requant


def _norm_stages(stages: Sequence[StageSpec]):
    """Hashable ((name, ((kw, val), ...)), ...) form; validates ops."""
    norm = []
    for st in stages:
        name, kw = (st, {}) if isinstance(st, str) else st
        op = ops_lib.get_operator(name)
        if op.n_inputs != 1:
            raise ValueError(
                f"pipelines chain unary operators; {name!r} takes "
                f"{op.n_inputs} images")
        norm.append((name, tuple(sorted(kw.items()))))
    if not norm:
        raise ValueError("empty pipeline")
    return tuple(norm)


@dataclasses.dataclass(frozen=True)
class CompiledPipeline:
    """A chain of operators compiled to one callable.

    Attributes:
      stages: normalized (name, kwargs-items) tuples, in order.
      engine: the shared base image engine (each stage re-derives its
        own fractional split from it, exactly as standalone ops do).
      requant: inter-stage requantization mode ("stage" | "fused").
      fn: the compiled callable — ``uint8 (B, H, W) -> uint8 batch``
        (jit(vmap(chain)) on the jax-family backends, a plain host loop
        on the numpy engine).
      chain: the UNJITTED single-image chain ``uint8 (H, W) -> uint8``
        (leading batch dims also accepted) — the tile streamer maps
        this over halo-padded regions.
      halos: per-stage receptive-field radius, in that stage's input
        pixels (from each operator's :class:`~repro.imgproc.ops.QForm`).
      downs: per-stage integer output downscale factor.
    """

    stages: Tuple[Tuple[str, Tuple], ...]
    engine: Any
    requant: str
    fn: Callable = dataclasses.field(compare=False)
    chain: Callable = dataclasses.field(compare=False)
    halos: Tuple[int, ...] = ()
    downs: Tuple[int, ...] = ()

    def __call__(self, imgs):
        if _obs.live():
            with _obs.span("plan:call", stages=self.stage_names,
                           requant=self.requant,
                           backend=self.engine.backend.name):
                out = self.fn(imgs)
            _metrics.counter("plan.pixels_in").inc(
                int(np.prod(np.shape(imgs))))
            return out
        return self.fn(imgs)

    @property
    def stage_names(self) -> Tuple[str, ...]:
        return tuple(name for name, _ in self.stages)

    @property
    def total_down(self) -> int:
        """The chain's overall integer downscale factor per axis."""
        d = 1
        for di in self.downs:
            d *= di
        return d

    @property
    def receptive_halo(self) -> int:
        """The chain's receptive-field radius in INPUT pixels: stage
        halos scaled by the downsampling accumulated before them."""
        h, scale = 0, 1
        for hi, di in zip(self.halos, self.downs):
            h += hi * scale
            scale *= di
        return h

    def out_size(self, in_size: int) -> int:
        """Output extent along one spatial axis for ``in_size`` input
        pixels (filters preserve extent; each 2x stage floors)."""
        for d in self.downs:
            in_size //= d
        return in_size


def _stage_scope(ax) -> Callable:
    """What each stage runs under, given its name.  On the traced
    backends a ``jax.named_scope("stage:<name>")``: every HLO operation
    the stage emits, fusions and kernels included, carries the scope in
    its metadata, so the device trace names the stage of each
    operation.  On the numpy host engine an ``obs.span``, which is what
    gives drift capture its stage attribution."""
    if ax.backend.name == "numpy":
        return lambda name: _obs.span(f"stage:{name}")
    return lambda name: jax.named_scope(f"stage:{name}")


def _stage_chain(stages, ax) -> Callable:
    """requant="stage": the standalone operators back to back — each
    stage's own quantize/round/saturate runs, so the chain is
    bit-identical to per-stage workload calls."""
    scope = _stage_scope(ax)

    def chain(img):
        x = img
        for name, kw_items in stages:
            with scope(name):
                x = ops_lib.get_operator(name).fn(x, ax, **dict(kw_items))
        return x

    return chain


def _fused_chain(stages, ax) -> Callable:
    """requant="fused": chain the operators' raw Q-forms — the whole
    pipeline runs in the int32 fixed-point domain.

    One exact quantize at entry (``uint8 << frac``); at each inter-stage
    seam the float32 dequantize/round/saturate/requantize round-trip of
    stage mode collapses to three integer ops (rounding shift to the
    gray grid, clamp, exact shift to the next stage's declared scale);
    one round/clip to uint8 at exit.  Keeping the gray-grid rounding at
    seams preserves stage-mode SEMANTICS: for chains whose q-forms are
    all ``exact`` (every stock pipeline) the fused chain is bit-identical
    to stage mode, and chains through ``box_blur`` differ by at most the
    one integer-vs-float /9 rounding LSB — which is what keeps the
    fused path inside the 0.1 dB PSNR gate.  (A fully requant-free
    variant that carries fractional precision across seams was measured
    2–3 dB off stage mode on sharpen-amplified chains — the per-stage
    approximate adds see a different low-bit operand distribution — and
    is exactly what the PSNR gate exists to reject.)"""
    qforms = [ops_lib.get_operator(name).qform for name, _ in stages]
    scope = _stage_scope(ax)

    def chain(img):
        q = jnp.asarray(img, jnp.int32) << qforms[0].in_frac
        for i, ((name, kw_items), qf) in enumerate(zip(stages, qforms)):
            with scope(name):
                q = qf.fn(q, ax, **dict(kw_items))
            f = qf.out_frac
            if i + 1 < len(qforms):
                # The integer seam: round half up to whole gray levels,
                # saturate, and rescale exactly into the next stage's
                # Q format — 3 integer ops where stage mode pays a
                # float32 round-trip, with identical arithmetic.
                if f:
                    q = (q + (1 << (f - 1))) >> f
                q = jnp.clip(q, 0, 255) << qforms[i + 1].in_frac
        return ops_lib._finish_q(q, f)

    return chain


@functools.lru_cache(maxsize=None)
def _compile_cached(stages, kind, backend_name, strategy, n_bits,
                    requant, fault=None) -> CompiledPipeline:
    with _obs.span("plan:compile", kind=str(kind), backend=backend_name,
                   requant=requant,
                   stages=tuple(n for n, _ in stages)) \
            if _obs._ENABLED else _obs._NOOP:
        return _compile_uncached(stages, kind, backend_name, strategy,
                                 n_bits, requant, fault)


_register_lru("imgproc.plan.compiled", _compile_cached)


def _compile_uncached(stages, kind, backend_name, strategy, n_bits,
                      requant, fault=None) -> CompiledPipeline:
    ax = ops_lib.make_image_engine(kind, backend=backend_name,
                                   strategy=strategy, n_bits=n_bits,
                                   fault=fault)
    qforms = [ops_lib.get_operator(name).qform for name, _ in stages]
    if requant == "fused":
        missing = [name for (name, _), qf in zip(stages, qforms)
                   if qf is None]
        if missing:
            raise ValueError(
                f"requant='fused' chains raw Q-forms, but {missing} "
                f"registered no QForm; use requant='stage'")
        chain = _fused_chain(stages, ax)
    else:
        chain = _stage_chain(stages, ax)

    if ax.backend.name == "numpy":
        # Host engine: not traceable, but operators take leading batch
        # dims natively — the chain runs as-is on the whole batch.
        fn = lambda imgs: np.asarray(chain(np.asarray(imgs)))  # noqa: E731
    else:
        fn = jax.jit(jax.vmap(chain))
    geom = all(qf is not None for qf in qforms)
    return CompiledPipeline(
        stages=stages, engine=ax, requant=requant, fn=fn, chain=chain,
        halos=tuple(qf.halo for qf in qforms) if geom else (),
        downs=tuple(qf.down for qf in qforms) if geom else ())


def compile_pipeline(stages: Sequence[StageSpec],
                     kind="haloc_axa",
                     backend: Optional[str] = None,
                     fast: bool = False,
                     strategy: Optional[str] = None,
                     n_bits: int = ops_lib.IMAGE_N_BITS,
                     requant: str = "stage",
                     fault=None) -> CompiledPipeline:
    """Compile ``stages`` (operator names, or (name, kwargs) pairs) into
    one callable over a batch of uint8 images.

    The result is cached by (stages, kind, backend, strategy, n_bits,
    requant, fault): repeated requests return the same object and warm
    calls hit the XLA jit cache.  ``requant="stage"`` is bit-identical
    to running the stages individually; ``requant="fused"`` chains the
    raw Q-forms with no intermediate uint8 round-trips (PSNR-gated, see
    the module docstring).

    ``kind`` is a registered kind name or a full
    :class:`~repro.core.specs.AdderSpec` — the explicit-spec form is
    what lets the degradation ladder (:mod:`repro.resilience.degrade`)
    compile fallback plans at arbitrary Pareto-frontier (m, k) points.
    ``fault`` injects a hardware defect
    (:class:`repro.resilience.faults.FaultSpec`) into every adder of
    the plan; bit positions and rates are validated here (via
    ``make_engine``) before anything compiles."""
    from repro.ax.backends import resolve_strategy
    strategy = resolve_strategy(strategy, fast)
    check_requant(requant)
    ax = ops_lib.make_image_engine(kind, backend=backend, strategy=strategy,
                                   n_bits=n_bits, fault=fault)
    # The engine's RESOLVED strategy keys the cache, so "auto" and its
    # concrete spelling share one plan (and one XLA compilation).
    return _compile_cached(_norm_stages(stages), kind, ax.backend.name,
                           ax.strategy, ax.spec.n_bits, requant, fault)


def run_pipeline(stages: Sequence[StageSpec], imgs, *,
                 kind="haloc_axa", backend: Optional[str] = None,
                 fast: bool = False, strategy: Optional[str] = None,
                 requant: str = "stage", fault=None):
    """One-shot convenience: compile (or fetch) the plan and run it."""
    pipe = compile_pipeline(stages, kind=kind, backend=backend, fast=fast,
                            strategy=strategy, requant=requant,
                            fault=fault)
    if pipe.engine.backend.name == "numpy":
        return pipe(imgs)
    return np.asarray(pipe(jnp.asarray(np.asarray(imgs))))


class GateResult(NamedTuple):
    """One :func:`fused_psnr_gate` measurement.  PSNRs are clamped at
    99 dB so a lossless cell compares as 99.0, not inf (inf - inf is
    nan and would FAIL the bound it should trivially pass)."""

    psnr_stage: float
    psnr_fused: float
    bit_identical: bool

    @property
    def delta_db(self) -> float:
        return self.psnr_fused - self.psnr_stage

    def admissible(self, bound_db: float = 0.1) -> bool:
        return abs(self.delta_db) <= bound_db


def fused_psnr_gate(stages: Sequence[StageSpec], imgs, *,
                    kind: str = "haloc_axa",
                    backend: Optional[str] = None,
                    strategy: Optional[str] = None,
                    tile: Optional[Tuple[int, int]] = None) -> GateResult:
    """THE quality gate on the fused-requant fast path: both requant
    modes scored against the ideal float reference on ``imgs`` (the
    tests and the megapixel benchmark both consume this one
    implementation).

    The fused side runs tiled when ``tile`` is given — the exact
    fast-path configuration the acceptance bar measures; the stage side
    is always the untiled PR-3 plan.  The fused path is admissible when
    the PSNRs are within 0.1 dB (:meth:`GateResult.admissible`);
    ``bit_identical`` reports the stronger property the built-in
    operators actually achieve."""
    from repro.image.quality import psnr
    imgs = np.asarray(imgs)
    ref = imgs.astype(np.float64)
    for st in _norm_stages(stages):
        name, kw_items = st
        ref = ops_lib.get_operator(name).reference(ref, **dict(kw_items))

    def score(got):
        return float(np.mean([min(psnr(r, o), 99.0)
                              for r, o in zip(ref, got)]))

    out_stage = run_pipeline(stages, imgs, kind=kind, backend=backend,
                             strategy=strategy, requant="stage")
    if tile is None:
        out_fused = run_pipeline(stages, imgs, kind=kind, backend=backend,
                                 strategy=strategy, requant="fused")
    else:
        from repro.imgproc.tiles import run_tiled
        out_fused = run_tiled(
            compile_pipeline(stages, kind=kind, backend=backend,
                             strategy=strategy, requant="fused"),
            imgs, tile=tile)
    return GateResult(psnr_stage=score(out_stage),
                      psnr_fused=score(out_fused),
                      bit_identical=bool(np.array_equal(out_stage,
                                                        out_fused)))
