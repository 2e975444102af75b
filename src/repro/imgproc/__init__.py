"""``repro.imgproc`` — the batched approximate image-processing workload
subsystem.

The paper's headline demonstration is deployment of the adder for image
processing; this package is that demonstration at workload breadth: a
library of jit/vmap-batched image operators whose every addition routes
through a :mod:`repro.ax` engine (fused multi-operand accumulation and
multi-stage ``filter_chain`` passes — one VMEM-resident Pallas kernel
per separable chain, not K elementwise dispatches), a plan compiler
(:mod:`repro.imgproc.plan`) that chains operators into ONE jitted
pipeline dispatch — in the stage-requant mode of PR 3 or end-to-end in
the fixed-point integer domain (``requant="fused"``, each operator's
raw :class:`~repro.imgproc.ops.QForm`) — a halo-aware tile streamer
(:mod:`repro.imgproc.tiles`) that runs any plan over megapixel images
in bounded memory, bit-identical to untiled execution, a workload
registry that hosts the operators, the stock pipelines and the
FFT->IFFT reconstruction formerly one-off in ``repro.image.pipeline``,
and a corpus runner that sweeps {adder kinds} x {workloads} x {image
batch} into PSNR/SSIM/throughput tables, plus a pipelined dispatch/fetch
loop (``run_streaming``) that keeps up to ``depth`` batches in flight
for steady-state megapixel throughput.  Retries, deadlines and
per-request isolation belong to :mod:`repro.serving`.

    from repro.imgproc import make_image_engine, box_blur, run_corpus

    ax = make_image_engine("haloc_axa", backend="jax")
    out = box_blur(img, ax)                   # every add is approximate
    rows = run_corpus()                       # the full breadth sweep
"""

from __future__ import annotations

from repro.imgproc.corpus import (  # noqa: F401
    CorpusResult,
    format_table,
    run_corpus,
    run_streaming,
    synthetic_batch,
)
from repro.imgproc.ops import (  # noqa: F401
    IMAGE_N_BITS,
    OPERATORS,
    ImageOp,
    QForm,
    blend,
    box_blur,
    brightness,
    downsample2x,
    gaussian_blur,
    get_operator,
    img_add,
    make_image_engine,
    operator_names,
    register_operator,
    sharpen,
    sobel,
)
from repro.imgproc.plan import (  # noqa: F401
    PIPELINES,
    REQUANT_MODES,
    CompiledPipeline,
    compile_pipeline,
    fused_psnr_gate,
    run_pipeline,
)
from repro.imgproc.tiles import (  # noqa: F401
    compile_tiled,
    run_tiled,
)
from repro.imgproc.workloads import (  # noqa: F401
    WORKLOADS,
    Workload,
    get_workload,
    register_workload,
    workload_names,
)

__all__ = [
    "CompiledPipeline", "CorpusResult", "IMAGE_N_BITS", "ImageOp",
    "OPERATORS", "PIPELINES", "QForm", "REQUANT_MODES", "WORKLOADS",
    "Workload", "blend", "box_blur", "brightness", "compile_pipeline",
    "compile_tiled", "downsample2x", "format_table", "fused_psnr_gate",
    "gaussian_blur", "get_operator", "get_workload", "img_add",
    "make_image_engine", "operator_names", "register_operator",
    "register_workload", "run_corpus", "run_pipeline", "run_streaming",
    "run_tiled", "sharpen", "sobel", "synthetic_batch", "workload_names",
]
