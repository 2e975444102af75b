"""Batched approximate image operators on the ``repro.ax`` engines.

Each operator is the fixed-point dataflow an image-processing ASIC built
from the paper's adders would run: pixels are quantized to a Q16.f
format (the N=16 datapath is the paper's own Fig-4 instance of the
(m, k) partition rule: m=8, k=4), filter taps are applied as *exact*
integer multiplies, and **every addition** — the accumulation loop of
the separable filters, the blend, the gradient-magnitude merge — routes
through one :class:`~repro.ax.engine.AxEngine` dispatch via the fused
multi-operand :meth:`~repro.ax.engine.AxEngine.accumulate_signed` /
:meth:`~repro.ax.engine.AxEngine.scaled_add` /
:meth:`~repro.ax.engine.AxEngine.filter_chain` primitives (a single
Pallas tile kernel per separable CHAIN on the Pallas backends — the
tile stays VMEM-resident across consecutive passes).

Per-operator fractional widths are chosen so the true weighted sum of
every accumulation stays inside the 16-bit two's-complement range
(headroom analysis in each docstring) — exactly the filter designer's
job in the hardware.

Operators accept ``(..., H, W)`` arrays in [0, 255] (uint8 or float);
leading batch dims are free, and each operator is a pure jax function
of its image arguments, so ``jax.vmap`` / ``jax.jit`` compose.  Ideal
float references live in :mod:`repro.imgproc.reference`; the corpus
runner (:mod:`repro.imgproc.corpus`) scores every registered adder kind
against them.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple, Union

import jax.numpy as jnp
from jax import lax

from repro.ax.backends import FilterStage
from repro.ax.engine import AxEngine, make_engine
from repro.core.specs import AdderSpec
from repro.imgproc import reference
from repro.numerics.fixed_point import FixedPointFormat, quantize

#: Default image datapath width: the paper's N=16 (m=8, k=4) instance.
IMAGE_N_BITS = 16

_F_ADD = 6     # Q16.6: |a + b| <= 510        -> 510 * 64  = 32640 < 2^15
_F_SEP = 3     # Q16.3: 3x3 box sum <= 2295   -> 2295 * 8  = 18360 < 2^15
_F_SOBEL = 2   # Q16.2: |smoothed diff| <= 2040 -> 2040 * 4 * 2 = 16320
_F_DOWN = 4    # Q16.4: 2x2 sum <= 1020       -> 1020 * 16 = 16320 < 2^15
_F_BRIGHT = 2  # Q16.2: coarse split so the LSM error is not sub-LSB
_ALPHA_BITS = 6


def make_image_engine(kind: Union[str, AdderSpec] = "haloc_axa",
                      backend=None, fast: bool = False,
                      n_bits: int = IMAGE_N_BITS,
                      strategy: Optional[str] = None,
                      fault=None) -> AxEngine:
    """Engine for the image datapath.

    A bare kind name gets the paper's scaled partition at ``n_bits``
    (m = n/2, k = m/2 — the Fig-4 example at N=16).  The format's
    fractional split is re-derived per operator, so only the width
    matters here.  ``strategy`` picks the adder evaluation path
    (reference / fused / lut, all bit-identical); ``fast`` is the
    back-compat alias for ``strategy="fused"``.  ``fault`` injects a
    hardware defect (:class:`repro.resilience.faults.FaultSpec`) into
    every adder output bus — validated against the datapath width."""
    if isinstance(kind, AdderSpec):
        n_bits = kind.n_bits
    if not (2 <= n_bits <= 30):
        raise ValueError(
            f"the imgproc datapath runs in int32 fixed-point containers "
            f"and needs n_bits <= 30; got N={n_bits}.  (The N=32 paper "
            f"spec belongs to the FFT pipeline; the image operators use "
            f"the paper's Fig-4 N=16 instance by default.)")
    return make_engine(kind, fmt=FixedPointFormat(n_bits, 0),
                       backend=backend, fast=fast, strategy=strategy,
                       fault=fault)


def _with_frac(ax: AxEngine, frac_bits: int) -> AxEngine:
    """The cached engine with the operator's Q-format split (the
    injected fault, when present, rides along — each operator's
    re-derived engine runs the same defective hardware)."""
    return make_engine(ax.spec,
                       fmt=FixedPointFormat(ax.spec.n_bits, frac_bits),
                       backend=ax.backend, strategy=ax.strategy,
                       fault=ax.fault)


def _q(img, fmt: FixedPointFormat):
    return quantize(jnp.asarray(img, jnp.float32), fmt)




# ----------------------------------------------------------- registry --

@dataclasses.dataclass(frozen=True)
class QForm:
    """The raw Q-domain form of an operator: ``fn(q, ax, **kw) -> q_out``.

    The scale/headroom contract the plan compiler chains on:

    - input: signed int32 containers at ``in_frac`` fractional bits
      holding pixel values in [0, 255] (so ``q <= 255 << in_frac``, the
      headroom every operator's accumulation analysis assumes);
    - output: signed int32 containers at ``out_frac`` fractional bits,
      NOT yet saturated — the caller (the fused-requant chain) clamps to
      ``[0, 255 << out_frac]`` between stages and rounds/clips to uint8
      exactly once at pipeline exit.

    ``halo`` is the spatial receptive-field radius in input pixels and
    ``down`` the integer output downscale factor — the geometry the tile
    streamer (:mod:`repro.imgproc.tiles`) sizes overlaps from.

    ``exact`` records whether the float operator is EXACTLY
    quantize -> fn -> round/clip.  True for every built-in operator
    (normalizations are power-of-two rounding shifts, and box_blur's /9
    carries :data:`_BOX_NORM_BITS` guard bits so its integer quotient
    can never round differently from the float division); custom
    operators whose q-form only approximates their float path should
    register ``exact=False`` — the fused-requant PSNR gate
    (:func:`repro.imgproc.plan.fused_psnr_gate`) is what admits them.
    """

    fn: Callable
    in_frac: int
    out_frac: int
    halo: int = 0
    down: int = 1
    exact: bool = True


@dataclasses.dataclass(frozen=True)
class ImageOp:
    """One registered operator: the approximate implementation paired
    with its ideal float reference (``n_inputs`` images each) and, when
    available, its raw Q-domain form (:class:`QForm`) for requant-free
    pipeline chaining."""

    name: str
    fn: Callable
    reference: Callable
    n_inputs: int = 1
    qform: Optional[QForm] = None


OPERATORS: Dict[str, ImageOp] = {}


def register_operator(name: str, reference_fn: Callable, n_inputs: int = 1,
                      qform: Optional[QForm] = None):
    """Decorator pairing an approximate operator with its reference
    (and optionally its raw Q-domain form)."""

    def deco(fn: Callable) -> Callable:
        if name in OPERATORS:
            raise ValueError(f"operator {name!r} already registered")
        OPERATORS[name] = ImageOp(name, fn, reference_fn, n_inputs, qform)
        return fn

    return deco


def get_operator(name: str) -> ImageOp:
    try:
        return OPERATORS[name]
    except KeyError:
        raise KeyError(f"unknown operator {name!r}; registered: "
                       f"{sorted(OPERATORS)}") from None


def operator_names() -> Tuple[str, ...]:
    return tuple(sorted(OPERATORS))


# ---------------------------------------------------------- operators --
#
# Each operator is written as a raw Q-domain core (the QForm, integer
# in -> integer out) plus a float wrapper (quantize -> core ->
# ``_finish_q``) — the wrapper is the standalone operator the corpus
# and the stage-requant pipelines run; the core is what integer-domain
# ("fused"-requant) pipelines chain directly.  Every wrapper is
# bit-identical to the pre-QForm float operators: the normalizations
# are power-of-two rounding shifts, sobel's /4 is absorbed into its
# declared output scale, and box_blur's /9 rounds in integer with
# enough guard bits that the float path can never differ.

def _finish_q(v, frac_bits: int):
    """Round half up from ``frac_bits`` and saturate to uint8 — the
    integer form of ``_finish(dequantize(v, fmt))``, exact whenever the
    Q value fits int32 (floor((v + half) >> f) == floor(v/2^f + 0.5))."""
    if frac_bits:
        v = (v + (1 << (frac_bits - 1))) >> frac_bits
    return jnp.clip(v, 0, 255).astype(jnp.uint8)


#: Extra fractional bits carried by box_blur's integer /9 quotient.  At
#: Q16.3 the 9x box sum v <= 18360; emitting round(v * 2^7 / 9) at
#: 3 + 7 = 10 fractional bits keeps the quotient's rounding error below
#: 2^-11 gray while the true value v/72 is never closer than 1/144 to a
#: half-gray boundary without landing on it exactly (2v and 144t + 72
#: are both integers), so the later round-to-gray can never flip — the
#: integer form is bit-identical to the float32 /9.0 normalization.
_BOX_NORM_BITS = 7


def _box_blur_q(q, ax: AxEngine):
    """Headroom: 9 * 255 * 2^3 = 18360 < 2^15, so both passes accumulate
    unnormalized; the /9 normalization is one exact rounded integer
    division at the end (see :data:`_BOX_NORM_BITS`), v * 128 < 2^22."""
    e = _with_frac(ax, _F_SEP)
    v = e.filter_chain(q, (FilterStage(-1, (-1, 0, 1), (1, 1, 1)),
                           FilterStage(-2, (-1, 0, 1), (1, 1, 1))))
    return ((v << _BOX_NORM_BITS) + 4) // 9  # round(v * 2^7 / 9), v >= 0


@register_operator("box_blur", reference.box_blur,
                   qform=QForm(_box_blur_q, _F_SEP,
                               _F_SEP + _BOX_NORM_BITS, halo=1))
def box_blur(img, ax: AxEngine):
    """3x3 box blur, separable: ONE two-stage filter chain (a single
    VMEM-resident multi-pass kernel on the Pallas backends)."""
    e = _with_frac(ax, _F_SEP)
    return _finish_q(_box_blur_q(_q(img, e.fmt), ax),
                     _F_SEP + _BOX_NORM_BITS)


def _gauss3(e: AxEngine, q):
    """Separable 3x3 binomial core: two (1, 2, 1)/4 weighted passes with
    exact rounding shifts as ONE filter chain — shared by gaussian_blur
    and the blur inside sharpen's unsharp mask."""
    return e.filter_chain(q, (FilterStage(-1, (-1, 0, 1), (1, 2, 1), 2),
                              FilterStage(-2, (-1, 0, 1), (1, 2, 1), 2)))


def _gaussian_blur_q(q, ax: AxEngine):
    return _gauss3(_with_frac(ax, _F_SEP), q)


@register_operator("gaussian_blur", reference.gaussian_blur,
                   qform=QForm(_gaussian_blur_q, _F_SEP, _F_SEP, halo=1))
def gaussian_blur(img, ax: AxEngine):
    """3x3 binomial (Gaussian) blur: separable (1, 2, 1)/4 passes, each
    one fused weighted accumulation with an exact rounding shift."""
    e = _with_frac(ax, _F_SEP)
    return _finish_q(_gaussian_blur_q(_q(img, e.fmt), ax), _F_SEP)


def _sharpen_q(q, ax: AxEngine, amount: int = 1):
    """Unsharp mask core: ``(1 + amount) * img - amount * blur`` as one
    weighted approximate pair-add on top of the Gaussian pyramid."""
    if not 0 <= amount <= 15:
        # (1 + amount) * 255 * 2^_F_SEP must stay below 2^15
        raise ValueError(f"amount must be in [0, 15] (Q16.{_F_SEP} "
                         f"headroom); got {amount}")
    e = _with_frac(ax, _F_SEP)
    return e.scaled_add(q, _gauss3(e, q), 1 + amount, -amount)


@register_operator("sharpen", reference.sharpen,
                   qform=QForm(_sharpen_q, _F_SEP, _F_SEP, halo=1))
def sharpen(img, ax: AxEngine, amount: int = 1):
    """Unsharp mask: ``(1 + amount) * img - amount * blur`` as one
    weighted approximate pair-add on top of the Gaussian pyramid."""
    e = _with_frac(ax, _F_SEP)
    return _finish_q(_sharpen_q(_q(img, e.fmt), ax, amount), _F_SEP)


def _sobel_q(q, ax: AxEngine):
    """Sobel core.  The |Gx| + |Gy| magnitude carries the 4x gradient
    gain, so its Q-form output is declared at ``_F_SOBEL + 2`` fractional
    bits — the /4 normalization is absorbed into the scale contract
    instead of rounding early."""
    e = _with_frac(ax, _F_SOBEL)
    gx = e.filter_chain(q, (FilterStage(-2, (-1, 0, 1), (1, 2, 1)),
                            FilterStage(-1, (1, -1), (1, -1))))
    gy = e.filter_chain(q, (FilterStage(-1, (-1, 0, 1), (1, 2, 1)),
                            FilterStage(-2, (1, -1), (1, -1))))
    return e.scaled_add(jnp.abs(gx), jnp.abs(gy))


@register_operator("sobel", reference.sobel,
                   qform=QForm(_sobel_q, _F_SOBEL, _F_SOBEL + 2, halo=1))
def sobel(img, ax: AxEngine):
    """Sobel edge magnitude |Gx| + |Gy| (the L1 merge is itself an
    approximate add), each gradient one smooth(1,2,1) x diff(+1,-1)
    two-stage filter chain."""
    e = _with_frac(ax, _F_SOBEL)
    return _finish_q(_sobel_q(_q(img, e.fmt), ax), _F_SOBEL + 2)


def _img_add_q(qa, qb, ax: AxEngine):
    return _with_frac(ax, _F_ADD).scaled_add(qa, qb)


@register_operator("add", reference.img_add, n_inputs=2,
                   qform=QForm(_img_add_q, _F_ADD, _F_ADD))
def img_add(a, b, ax: AxEngine):
    """Saturating image add (exposure stacking): one approximate add
    per pixel.  Exact for the accurate kind (510 * 2^6 fits Q16.6)."""
    e = _with_frac(ax, _F_ADD)
    return _finish_q(_img_add_q(_q(a, e.fmt), _q(b, e.fmt), ax), _F_ADD)


def _blend_q(qa, qb, ax: AxEngine, alpha: float = 0.5):
    if not 0.0 <= alpha <= 1.0:
        raise ValueError(f"alpha must be in [0, 1] (the weighted sum "
                         f"must fit the 16-bit datapath); got {alpha}")
    e = _with_frac(ax, 0)
    wa = int(round(alpha * (1 << _ALPHA_BITS)))
    return e.scaled_add(qa, qb, wa, (1 << _ALPHA_BITS) - wa,
                        shift=_ALPHA_BITS)


@register_operator("blend", reference.blend, n_inputs=2,
                   qform=QForm(_blend_q, 0, 0))
def blend(a, b, ax: AxEngine, alpha: float = 0.5):
    """Alpha blend with a 6-bit quantized alpha: one weighted
    approximate pair-add, then an exact rounding shift.  At alpha = 0.5
    the accurate kind is bit-identical to the float reference."""
    e = _with_frac(ax, 0)
    return _finish_q(_blend_q(_q(a, e.fmt), _q(b, e.fmt), ax, alpha), 0)


def _brightness_q(q, ax: AxEngine, delta: float = 37.0):
    """Runs at Q16.2 (not Q16.6): with 6 fractional bits the m=8 LSM
    error stays below half a gray level and every kind rounds lossless;
    the coarser split keeps the adder families distinguishable."""
    if not -255.0 <= delta <= 255.0:
        raise ValueError(f"delta must be in [-255, 255]; got {delta}")
    e = _with_frac(ax, _F_BRIGHT)
    qd = jnp.full_like(q, int(round(delta * e.fmt.scale)))
    return e.scaled_add(q, qd)


@register_operator("brightness", reference.brightness,
                   qform=QForm(_brightness_q, _F_BRIGHT, _F_BRIGHT))
def brightness(img, ax: AxEngine, delta: float = 37.0):
    """Brightness adjust: one approximate add of a constant plane
    (Q16.2 so the LSM error is not sub-LSB)."""
    e = _with_frac(ax, _F_BRIGHT)
    return _finish_q(_brightness_q(_q(img, e.fmt), ax, delta), _F_BRIGHT)


def phases2x(q):
    """The four 2x2 phase planes of ``q[..., :h, :w]`` (``h``, ``w`` the
    even crop), as ``q[..., i::2, j::2]`` for (i, j) = (0, 0), (0, 1),
    (1, 0), (1, 1), in that order.

    No strided indexing: JAX lowers a strided index with a non-zero
    start to a ``gather``, one point gather per output pixel on a TPU.
    Each row phase is a strided ``lax.slice`` over rows; its columns are
    split by a reshape to (w/2, 2) pairs, which a TPU deinterleaves far
    faster than it runs a slice strided across lanes."""
    lead = q.shape[:-2]
    h = q.shape[-2] & ~1
    w = q.shape[-1] & ~1
    ones = (1,) * len(lead)
    planes = []
    for i in (0, 1):
        rows = lax.slice(q, (0,) * len(lead) + (i, 0), lead + (h, w),
                         ones + (2, 1))
        pairs = rows.reshape(lead + (h // 2, w // 2, 2))
        planes += [pairs[..., 0], pairs[..., 1]]
    return tuple(planes)


def _downsample2x_q(q, ax: AxEngine):
    """2x box core: the four phase planes of each 2x2 quad are one fused
    4-term accumulation with an exact /4 rounding shift."""
    e = _with_frac(ax, _F_DOWN)
    return e.accumulate_signed(jnp.stack(phases2x(q)), shift=2)


@register_operator("downsample2x", reference.downsample2x,
                   qform=QForm(_downsample2x_q, _F_DOWN, _F_DOWN, down=2))
def downsample2x(img, ax: AxEngine):
    """2x box downsampling: the four phase planes of each 2x2 quad are
    one fused 4-term accumulation with an exact /4 rounding shift."""
    e = _with_frac(ax, _F_DOWN)
    return _finish_q(_downsample2x_q(_q(img, e.fmt), ax), _F_DOWN)
