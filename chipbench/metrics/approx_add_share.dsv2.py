"""Percent of the device's busy time in the window spent in the
``approx_add`` Pallas kernel: the HALOC-AxA residual adds of the decode
steps.  (No roofline share: at this shape XLA keeps the kernel's
operands and result in VMEM, so HBM bandwidth does not bound it.)"""

from chipbench import trace as trace_lib


def read(r):
    lo, hi = r.window
    busy = trace_lib.busy_ns(r.ops, r.window)
    spent = sum(max(0, min(o.end, hi) - max(o.start, lo)) for o in r.ops
                if o.kernel == "approx_add")
    if busy <= 0 or spent <= 0:
        return None
    return 100.0 * spent / busy
