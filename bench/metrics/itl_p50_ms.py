"""Median gap between consecutive output tokens of a request, over every
gap that ends in the window, stalls from admissions included (ms)."""
from bench.harness import percentile


def read(run):
    v = percentile(run.token_gaps_s(), 50)
    return None if v is None else v * 1e3
