"""Scheduler and engine host loop: p90 over requests due in the window
of the wait from the due time to the start of the admission (ms)."""
from bench.harness import percentile


def read(run):
    v = percentile(run.queue_waits_s(), 90)
    return None if v is None else v * 1e3
