"""The ``image_pipeline`` system: a configured image pipeline."""

from __future__ import annotations


def build(cfg: dict, backend: str):
    """``compile_pipeline`` of the configured stages: uint8 (B, H, W)
    in, the pipeline's uint8 batch out."""
    from repro.core.specs import AdderSpec
    from repro.imgproc import compile_pipeline
    return compile_pipeline(tuple(cfg["pipeline"]),
                            kind=AdderSpec(**cfg["adder"]),
                            backend=backend, requant=cfg["requant"],
                            strategy=cfg["strategy"])
