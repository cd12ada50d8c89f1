"""Model step, decode: median host time of one decode step over all
slots, blocked on its tokens, that starts in the window (ms)."""
from bench.harness import percentile


def read(run):
    v = percentile([b - a for a, b, _ in run.decodes()], 50)
    return None if v is None else v * 1e3
