"""p95 of the gaps between consecutive output tokens of a request, over
every gap that ends in the window (ms)."""
from bench.harness import percentile


def read(run):
    v = percentile(run.token_gaps_s(), 95)
    return None if v is None else v * 1e3
