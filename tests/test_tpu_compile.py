"""Compile the main path's Pallas kernels for a TPU v5e at benchmark
sizes, on a host without one.

Interpret mode (the ``"pallas"`` backend every other test runs) accepts
kernels the chip's compiler refuses: edge-mode pads, 1-D gathers,
``dynamic_slice`` on values, blocks beyond the scoped VMEM.  Each case
here lowers one kernel (or a whole compiled pipeline) against a
described, unattached v5e chip and asserts that the compiled program
really contains a Mosaic kernel (``tpu_custom_call``), so that neither
an XLA-only path nor the interpreter can pass.

The topology is described inside a module fixture, never at import, so
every test worker collects the same tests; where no v5e can be
described, the fixture skips.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.ax import get_backend
from repro.ax.backends import FilterStage
from repro.ax.mul.specs import MulSpec
from repro.core.specs import AdderSpec, paper_spec

SPEC16 = AdderSpec(kind="haloc_axa", n_bits=16, lsm_bits=8, const_bits=4)
SPEC32 = paper_spec("haloc_axa")
TPU = get_backend("pallas_tpu")
KERNEL3 = ((1, 2, 1), (2, 4, 2), (1, 2, 1))
MEGA = (4, 1024, 1024)
MEGA_TILE = (256, 256)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _pipeline(name, tiled):
    from repro.imgproc.plan import PIPELINES, compile_pipeline
    from repro.imgproc.tiles import compile_tiled
    pipe = compile_pipeline(PIPELINES[name], backend="pallas_tpu",
                            requant="fused", strategy="auto")
    fn = compile_tiled(pipe, MEGA, tile=MEGA_TILE).raw if tiled else pipe.fn
    return fn, [(MEGA, jnp.uint8)]


def _filter_chain():
    stages = (FilterStage(-1, (-1, 0, 1), (1, 2, 1), 2),
              FilterStage(-2, (-1, 0, 1), (1, 2, 1), 2))
    return (lambda q: TPU.filter_chain(q, SPEC16, stages,
                                       strategy="fused"),
            [(MEGA, jnp.int32)])


def _accumulate():
    return (lambda t: TPU.accumulate(t, SPEC16, weights=(1, 2, 1) * 3,
                                     strategy="fused"),
            [((9, 1024, 1024), jnp.int32)])


def _add():
    return (lambda a, b: TPU.add(a, b, SPEC32, strategy="fused"),
            [((1024, 1024), jnp.int32)] * 2)


def _matmul():
    return (lambda a, b: TPU.matmul(a, b, SPEC32, strategy="fused"),
            [((256, 256), jnp.int8)] * 2)


def _mac_matmul(mul):
    return (lambda a, b: TPU.matmul(a, b, SPEC32, strategy="fused",
                                    mul_spec=mul),
            [((256, 256), jnp.int8)] * 2)


def _conv2d():
    mul = MulSpec("truncated", 8, 4)
    return (lambda q: TPU.conv2d(q, SPEC16, mul, KERNEL3, shift=4,
                                 strategy="fused"),
            [((4, 256, 256), jnp.int32)])


def _butterfly():
    plane = ((512, 256), jnp.int32)
    tw = ((256,), jnp.int32)
    return (lambda *xs: TPU.butterfly(*xs, SPEC32),
            [plane] * 4 + [tw] * 2)


CASES = {
    "filter_chain_4x1024": _filter_chain,
    "pipe_blur_sharpen_down": lambda: _pipeline("pipe_blur_sharpen_down",
                                                False),
    "pipe_blur_sharpen_down_tiled": lambda: _pipeline(
        "pipe_blur_sharpen_down", True),
    "pipe_blur_sobel": lambda: _pipeline("pipe_blur_sobel", False),
    "pipe_blur_sobel_tiled": lambda: _pipeline("pipe_blur_sobel", True),
    "accumulate_k9_1024": _accumulate,
    "fused_add_1024": _add,
    "approx_matmul_int8_256": _matmul,
    "mac_matmul_truncated_256": lambda: _mac_matmul(
        MulSpec("truncated", 8, 4)),
    "mac_matmul_mitchell_256": lambda: _mac_matmul(MulSpec("mitchell", 8)),
    "conv2d_mac_4x256": _conv2d,
    "butterfly_512x256": _butterfly,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiles_for_v5e(case, one_chip):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("case", ["pipe_blur_sharpen_down",
                                  "pipe_blur_sharpen_down_tiled"])
def test_downsample_compiles_without_gathers(case, one_chip):
    """The downsample's 2x2 phases are strided slices: a strided index
    with a non-zero start would lower to a point gather per output
    pixel, which dominated the chip's time for this pipeline."""
    import re
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert not re.search(r"\bgather\(", text)
    assert re.search(r"\bslice\(", text)


@pytest.mark.parametrize("case,kernel", [("accumulate_k9_1024", "accumulate"),
                                         ("fused_add_1024", "approx_add")])
def test_kernel_is_named_in_the_compiled_module(case, kernel, one_chip):
    """The ``pallas_call`` carries its ``name``: XLA names the Mosaic
    custom call after it, and so does the device trace."""
    import re
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert re.search(rf"%{kernel}(\.\d+)? = .*tpu_custom_call", text)
