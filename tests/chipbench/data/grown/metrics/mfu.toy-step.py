"""Percent of the window that the chip needs at least for the steps'
work at its int8 peak."""

from chipbench import readings


def read(r):
    return readings.step_share(r, "toy_step", "int8_ops")
