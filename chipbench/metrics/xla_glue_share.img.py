"""Percent of the device's busy time spent in XLA operations that are
not Pallas kernels: the plan's fused requantization and the backend's
flatten, pad and slice around the kernels."""

from chipbench import readings


def read(r):
    return readings.glue_share(r)
