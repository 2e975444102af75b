"""Percent of its roofline that the ``conv_chain`` Pallas kernel reached: the
least time the chip needs for the algorithm's work of every call
(``work/conv_chain.py`` against ``peaks.py``) over the summed device time of
the kernel's operations in the trace."""

from chipbench import readings


def read(r):
    return readings.roofline(r, "conv_chain")
