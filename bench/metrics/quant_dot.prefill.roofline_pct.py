"""Kernels: the fused rotate->quantize->GEMM kernel's share of its
roofline inside the prefill program (%), rows = the prefill bucket;
as ``quant_dot.decode.roofline_pct`` otherwise."""
from bench.metrics_util import kernel_roofline_pct


def read(run):
    return kernel_roofline_pct(run, "quant_dot", "prefill")
