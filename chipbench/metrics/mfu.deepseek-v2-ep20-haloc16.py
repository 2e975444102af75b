"""Percent of the window that the chip needs at least for the decode
steps' logical work (``work/deepseek_v2_decode.py``: bf16 operations at
the bf16 peak, bytes at HBM bandwidth, whichever binds)."""

from chipbench import readings


def read(r):
    return readings.step_share(r, "deepseek_v2_decode", "bf16_flops")
