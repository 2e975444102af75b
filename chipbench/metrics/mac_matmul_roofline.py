"""Percent of its roofline that the ``mac_matmul`` Pallas kernel reached: the
least time the chip needs for the algorithm's work of every call
(``work/mac_matmul.py`` against ``peaks.py``) over the summed device time of
the kernel's operations in the trace."""

from chipbench import readings


def read(r):
    return readings.roofline(r, "mac_matmul")
