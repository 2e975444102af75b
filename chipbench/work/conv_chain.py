"""Stencil work of one image-pipeline call, from its logical shape.

Each filter stage of the pipeline is a 3x3 separable filter: two 3-tap
passes, so six weighted taps folded by the adder per pixel.  The stage
reads each value once and writes each result once, at the adder's N-bit
width.  The count is the algorithm's, whatever number of ``conv_chain``
calls the program splits or fuses the stages into; work that another
kernel does (the downsample, the sharpen's blend) is not counted.
"""

from __future__ import annotations

#: Taps per pixel of each filter stage (two separable 3-tap passes).
FILTER_TAPS = {"gaussian_blur": 6, "sharpen": 6}


def work(shape, cfg):
    """(operations, bytes) of the filter stages of one call on a
    (B, H, W) batch under the configuration ``cfg``."""
    b, h, w = shape
    width = -(-cfg["adder"]["n_bits"] // 8)
    ops = nbytes = 0
    for name in cfg["pipeline"]:
        if name in FILTER_TAPS:
            ops += b * h * w * FILTER_TAPS[name]
            nbytes += 2 * b * h * w * width
        if name == "downsample2x":
            h, w = h // 2, w // 2
    return ops, nbytes
