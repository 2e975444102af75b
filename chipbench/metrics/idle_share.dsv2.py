"""Percent of the measured window in which no operation ran on the
device (the profiler trace's busy union against the window)."""

from chipbench import readings


def read(r):
    return readings.idle_share(r)
