"""A toy decoder: one jitted step maps each sequence's last token to
its next, ``(token * multiplier + increment) % vocab``."""

from __future__ import annotations


def build(cfg: dict, backend: str):
    import jax
    s = cfg["step"]

    @jax.jit
    def step(tokens):
        return (tokens * s["multiplier"] + s["increment"]) % s["vocab"]

    return step
