"""Work of one GEMM call, from its logical shape.

An (M, K) @ (K, N) GEMM performs 2 M N K operations (a multiply and an
add per term), reads M K + K N int8 operands and writes M N int32
results.
"""

from __future__ import annotations


def work(shape, cfg):
    """(operations, bytes) of one (M, K, N) GEMM."""
    m, k, n = shape
    return 2 * m * n * k, m * k + k * n + 4 * m * n
