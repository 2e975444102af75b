"""The ``mac_gemm`` system: the GEMM of a configured MAC engine."""

from __future__ import annotations


def build(cfg: dict, backend: str):
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
