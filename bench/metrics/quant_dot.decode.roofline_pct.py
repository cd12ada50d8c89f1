"""Kernels: the fused rotate->quantize->GEMM kernel's share of its
roofline inside the decode program (%): each call's least time, the
larger of its operations over the matmul peak and its bytes over HBM
bandwidth (``work.counts.quant_dot`` at the decode rows, the slots),
summed over the kernel's events in the window, over their summed device
time. A cell whose path bypasses the kernel reads nothing."""
from bench.metrics_util import kernel_roofline_pct


def read(run):
    return kernel_roofline_pct(run, "quant_dot", "decode")
