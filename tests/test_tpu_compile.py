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


def _mac_matmul_conv2():
    """conv2_x's GEMM tile rows with the GEMM cell's specs: int8
    (1024, 576) @ (576, 64), truncated n8t4 products."""
    mul = MulSpec("truncated", 8, 4)
    return (lambda a, b: TPU.matmul(a, b, SPEC32, strategy="fused",
                                    mul_spec=mul),
            [((1024, 576), jnp.int8), ((576, 64), jnp.int8)])


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
    "mac_matmul_truncated_conv2": _mac_matmul_conv2,
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


@pytest.mark.parametrize("case,kernel", [
    ("accumulate_k9_1024", "accumulate"),
    ("fused_add_1024", "approx_add"),
    ("mac_matmul_truncated_256", "mac_matmul"),
])
def test_kernel_is_named_in_the_compiled_module(case, kernel, one_chip):
    """The ``pallas_call`` carries its ``name``: XLA names the Mosaic
    custom call after it, and so does the device trace."""
    import re
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert re.search(rf"%{kernel}(\.\d+)? = .*tpu_custom_call", text)


def _dsv2_decode(one_chip):
    """The DeepSeek-V2 cell's decode step at its published widths and
    shapes (batch 64 over 16384 cache slots), compiled for one v5e."""
    import json
    import os

    from chipbench.system import deepseek_v2
    from repro.models import transformer as T
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "deepseek-v2-ep20-haloc16.json")) as f:
        cfg = json.load(f)
    system = deepseek_v2.build(cfg, "pallas_tpu")
    model = system.model

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(lambda: T.init_params(
        jax.random.key(0), model, dtype=jnp.bfloat16)))
    cache = on_chip(jax.eval_shape(lambda: T.init_cache(
        model, 64, 16384, jnp.bfloat16)))
    tokens = jax.ShapeDtypeStruct((64, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((64,), jnp.int32, sharding=one_chip)
    step = system.decode.__wrapped__
    return step.lower(params, tokens, pos, cache).compile()


@pytest.fixture(scope="module")
def dsv2_decode(one_chip):
    return _dsv2_decode(one_chip)


#: One layer's latent cache in the DeepSeek-V2 cell: 64 rows of 16384
#: slots of 576 bf16 values.
DSV2_LAYER_CACHE = 64 * 16384 * (512 + 64) * 2


def test_dsv2_decode_scopes_its_attention_and_experts(dsv2_decode):
    """MLA decode and the expert layer run under ``mla:decode`` and
    ``moe:experts``, which the compiled module's operations carry in
    their metadata; the residual adds are the Pallas adder kernel."""
    import re
    text = dsv2_decode.as_text()
    scopes = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        scopes.update(re.findall(r"mla:decode|moe:experts", op_name))
    assert scopes == {"mla:decode", "moe:experts"}
    assert re.search(r"%approx_add[.\d]* = .*tpu_custom_call", text)


def test_dsv2_decode_updates_its_cache_in_place(dsv2_decode):
    """The cache is donated: the output aliases all 6 GB of it, and the
    step's temporaries stay far below one layer's cache (1.2 GB), so no
    layer is copied."""
    mem = dsv2_decode.memory_analysis()
    assert mem.alias_size_in_bytes == 5 * DSV2_LAYER_CACHE
    assert mem.temp_size_in_bytes < 0.5e9


def test_dsv2_decode_copies_a_layer_when_mla_slices_its_cache(one_chip,
                                                               monkeypatch):
    """Why MLA is in ``transformer.IN_PLACE_DECODE``: when its layer is
    sliced out of the stacked cache and set back, as the other mixers'
    are, XLA copies the layer; the temporaries outgrow it."""
    from repro.models import transformer as T
    monkeypatch.setattr(T, "IN_PLACE_DECODE", frozenset())
    mem = _dsv2_decode(one_chip).memory_analysis()
    assert mem.temp_size_in_bytes > DSV2_LAYER_CACHE
