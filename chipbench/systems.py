"""The system under test, built from a configuration through the entry
points a user calls.  A configuration's ``system`` key names the file
``system/<system>.py`` whose ``build(cfg, backend)`` makes it; those
files are the only ones that import the program, apart from the loops'
calls into the program's own drivers (``run_streaming``)."""

from __future__ import annotations


def build(make, cfg: dict, backend: str):
    """``make(cfg, backend)``, refused where the system's engine
    resolved another backend than ``backend``."""
    system = make(cfg, backend)
    actual = getattr(getattr(system, "engine", None), "backend", None)
    if actual is not None and actual.name != backend:
        raise RuntimeError(f"{cfg['name']} resolved backend "
                           f"{actual.name!r}, not {backend!r}")
    return system
