"""Least time for the protected sites' plain GEMM work over the summed entangled_matmul kernel time, from the trace, %."""
from bench import readers


def read(rec):
    return readers.kernel_roofline_pct(rec)
