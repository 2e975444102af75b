"""Decode steps completed in the window (a program counter)."""


def read(r):
    return r.counters.get("steps")
