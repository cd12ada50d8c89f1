"""Model step, prefill: median host time of one admission (batch-1
prefill + insert, blocked on its token) that starts in the window (ms)."""
from bench.harness import percentile


def read(run):
    v = percentile([b - a for a, b, _ in run.admits()], 50)
    return None if v is None else v * 1e3
