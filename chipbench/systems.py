"""The system under test, built from a configuration through the entry
points a user calls.  This is the only module that imports the
program, apart from the stream loop's calls into ``run_streaming``."""

from __future__ import annotations


def image_pipeline(cfg: dict, backend: str):
    """``compile_pipeline`` of the configured stages: uint8 (B, H, W)
    in, the pipeline's uint8 batch out."""
    from repro.core.specs import AdderSpec
    from repro.imgproc import compile_pipeline
    return compile_pipeline(tuple(cfg["pipeline"]),
                            kind=AdderSpec(**cfg["adder"]),
                            backend=backend, requant=cfg["requant"],
                            strategy=cfg["strategy"])


def mac_gemm(cfg: dict, backend: str):
    """``AxEngine.matmul`` of the configured MAC engine: int8 (M, K) and
    int8 (K, N) in, int32 (M, N) out."""
    from repro.ax import make_engine
    from repro.ax.mul import MacSpec, MulSpec
    from repro.core.specs import AdderSpec
    engine = make_engine(MacSpec(AdderSpec(**cfg["adder"]),
                                 MulSpec(**cfg["multiplier"])),
                         backend=backend, strategy=cfg["strategy"])
    block = tuple(cfg["block"])
    return lambda a, b: engine.matmul(a, b, block=block)


SYSTEMS = {"image_pipeline": image_pipeline, "mac_gemm": mac_gemm}


def build(cfg: dict, backend: str):
    system = SYSTEMS[cfg["system"]](cfg, backend)
    actual = getattr(getattr(system, "engine", None), "backend", None)
    if actual is not None and actual.name != backend:
        raise RuntimeError(f"{cfg['name']} resolved backend "
                           f"{actual.name!r}, not {backend!r}")
    return system
