"""Kernels: the Hadamard transform kernel's share of its roofline inside
the prefill program (%). On a non-power-of-two down-projection this is
the grouped transform I_g (x) H_p of the prefill bucket's rows (p the
largest power-of-two divisor); each call's least time is its bytes (in
and out) over HBM bandwidth or its n log2 n additions over the peak,
whichever is larger."""
from bench.metrics_util import kernel_roofline_pct


def read(run):
    return kernel_roofline_pct(run, "hadamard", "prefill")
